"""Serving metrics: throughput, TTFT, queue depth, slot occupancy,
compile counter — a thin facade over an observability MetricsRegistry.

Every number lives in a per-engine paddle_tpu.observability registry
(counters / gauges / fixed-bucket histograms), so one accounting point
feeds BOTH the stable ``snapshot()`` dict the bench artifacts pin AND
Prometheus text exposition (``prometheus_text()``, served over HTTP by
``ServingEngine.serve_metrics()``). The legacy attribute surface
(``metrics.compiles += 1`` etc.) is preserved via properties so the
engine's hot path reads exactly as before.

Latency series are BOUNDED: TTFT / request latency / queue wait each
record into a fixed-bucket histogram (Prometheus view, exact avg)
plus a fixed-size uniform reservoir (exact p50/p90/p99 over a sampled
window) — replacing the unbounded Python lists that leaked memory
under sustained traffic. ``snapshot()["latency_percentiles"]`` carries
the percentiles.

Timed sections route through paddle_tpu.profiler.record_scope, so
every span is simultaneously (a) accrued here for snapshot(), (b)
annotated into the XLA trace when an XPlane capture is live, and (c)
recorded into the host-span ring buffer for the chrome://tracing
timeline — one scope, three sinks.
"""
import time

import numpy as np

from .. import profiler as _profiler
from ..observability import (CacheObservatory, MetricsRegistry,
                             ProgramPerf, Reservoir, SLOTracker,
                             TenantLedger, WindowedReservoir)

# serving latencies are sub-ms (CPU smoke) to tens of seconds (deep
# queues on big models) — the default time buckets cover that span
_PCTS = ((50, "p50_ms"), (90, "p90_ms"), (99, "p99_ms"))


def _counter_property(attr):
    def get(self):
        v = getattr(self, attr).value
        return int(v) if float(v).is_integer() else v

    def set_(self, value):
        getattr(self, attr).set_to(value)

    return property(get, set_)


class ServingMetrics:
    """Engine-scoped metrics facade. ``registry`` defaults to a fresh
    MetricsRegistry per engine (pass a shared one to aggregate several
    engines into a single /metrics endpoint).

    ``slo_ttft_ms`` / ``slo_tpot_ms`` / ``slo_window_s`` configure the
    attached observability.SLOTracker (``metrics.slo``): per-request
    SLO verdicts, goodput tokens, and sliding-window p50/p90/p99
    gauges — ``snapshot()["slo"]`` carries its report. Device cost
    telemetry lands in gauges: per-decode-step flops/bytes (from the
    decode executable's cost_analysis), an estimated-MFU pull gauge
    (decode flops over busy wall time against the device's peak
    FLOP/s, 0 when the peak is unknown), and HBM in-use/free pull
    gauges where the backend reports memory_stats.
    """

    RESERVOIR_SIZE = 1024

    PREFIX_WINDOW_S = 60.0

    def __init__(self, registry=None, slo_ttft_ms=None,
                 slo_tpot_ms=None, slo_window_s=60.0, perf=True,
                 cache=True, cache_sample_rate=0.125, max_tenants=32):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        r = self.registry
        self.slo = SLOTracker(r, slo_ttft_ms=slo_ttft_ms,
                              slo_tpot_ms=slo_tpot_ms,
                              window_s=slo_window_s)
        # tenant observatory (observability.tenant): bounded per-tenant
        # attribution accrued at the SAME record_* sites as the global
        # counters (conservation by construction). max_tenants=0
        # disables it (the report keeps its schema shape).
        self.tenants = TenantLedger(r, max_tenants=max_tenants)
        # per-program perf attribution (observability.perf): the
        # engine records measured dispatch/sync wall per AOT-table key
        # through this; snapshot()["perf"] / /debug/perf report it
        self.perf = ProgramPerf(r, enabled=perf)
        # cache observatory (observability.cache): MRC estimation,
        # prefix heat, savings attribution, churn telemetry. Reports
        # the disabled shape until the engine attaches a paged pool.
        self.cache = CacheObservatory(r, enabled=cache,
                                      sample_rate=cache_sample_rate)
        self.cache.bind_cost_source(
            self.perf, lambda: self._c_prefill_tokens.value)
        self._peak_flops = None
        self._g_decode_flops = r.gauge(
            "serving_decode_flops_per_step",
            "cost_analysis flops of ONE pooled decode dispatch")
        self._g_decode_bytes = r.gauge(
            "serving_decode_bytes_per_step",
            "cost_analysis bytes accessed by ONE pooled decode "
            "dispatch")
        self._g_mfu = r.gauge(
            "serving_estimated_mfu",
            "estimated model-flops utilization: decode flops issued "
            "over busy wall time against device peak FLOP/s (0 when "
            "peak or cost_analysis is unavailable)")
        self._g_mfu.set_function(self.estimated_mfu)
        self._c_compiles = r.counter(
            "serving_compiles_total", "XLA executables built (ever)")
        self._c_prefills = r.counter(
            "serving_prefill_dispatches_total",
            "prefill dispatches (one per group)")
        self._c_prefill_requests = r.counter(
            "serving_prefill_requests_total",
            "requests prefilled (sum of group sizes)")
        self._c_decode_steps = r.counter(
            "serving_decode_steps_total", "pooled decode dispatches")
        self._c_tokens = r.counter(
            "serving_tokens_generated_total", "tokens emitted")
        self._c_spec_masked = r.counter(
            "serving_speculative_masked_total",
            "pipelined tokens discarded at harvest (request stopped "
            "while its next step was in flight)")
        self._c_admitted = r.counter(
            "serving_requests_admitted_total", "requests admitted")
        self._c_completed = r.counter(
            "serving_requests_completed_total", "requests completed")
        self._g_queue_depth = r.gauge(
            "serving_queue_depth", "queued requests (per engine step)")
        self._g_occupancy = r.gauge(
            "serving_slot_occupancy", "live slots / num_slots")
        self._c_groups = r.counter(
            "serving_prefill_groups_total",
            "prefill dispatches by group size",
            labelnames=("group_size",))
        self._c_span = r.counter(
            "serving_span_seconds_total",
            "wall seconds accrued per engine scope",
            labelnames=("span",))
        self._span_kids = {}    # span name -> its _c_span child
        # "received" is where the caller handed the request over: the
        # gateway's entry, before its lock (== arrival without one)
        self._h_ttft = r.histogram(
            "serving_ttft_seconds", "received -> first token")
        self._h_latency = r.histogram(
            "serving_request_latency_seconds", "received -> done")
        self._h_queue_wait = r.histogram(
            "serving_queue_wait_seconds", "arrival -> slot admission")
        self._h_submit_wait = r.histogram(
            "serving_submit_wait_seconds",
            "gateway entry -> its lock taken (received -> arrival)")
        # prefix-cache economy: admissions that reused a
        # cached prefix vs not, tokens served FROM cache (never
        # prefill-computed) vs tokens the prefill actually computed
        self._c_prefix_hits = r.counter(
            "serving_prefix_cache_hits_total",
            "admissions that reused a cached prompt prefix")
        self._c_prefix_misses = r.counter(
            "serving_prefix_cache_misses_total",
            "admissions with no reusable cached prefix")
        self._c_prefix_cached_tokens = r.counter(
            "serving_prefix_cached_tokens_total",
            "prompt tokens served from the prefix cache instead of "
            "being prefill-computed")
        self._c_prefill_tokens = r.counter(
            "serving_prefill_tokens_computed_total",
            "prompt tokens actually computed by prefill dispatches "
            "(excludes prefix-cache hits and bucket padding)")
        # sliding-window prefix-cache effectiveness (a router reading
        # lifetime counters sees the historical average, not what the
        # cache is doing NOW): per-admission hit indicator + cached
        # token counts over the last PREFIX_WINDOW_S seconds
        self._w_prefix_hits = WindowedReservoir(
            window_s=self.PREFIX_WINDOW_S, capacity=4096)
        self._w_prefix_cached = WindowedReservoir(
            window_s=self.PREFIX_WINDOW_S, capacity=4096)
        r.gauge(
            "serving_prefix_cache_windowed_hit_rate",
            "prefix-cache hit rate over the sliding window "
            "(admissions with a cached prefix / admissions; 0 when "
            "the window is empty)"
        ).set_function(self.windowed_prefix_hit_rate)
        r.gauge(
            "serving_prefix_cached_tokens_per_sec",
            "prompt tokens served from the prefix cache per second, "
            "sliding window"
        ).set_function(self.windowed_cached_tokens_per_sec)
        # scheduling-subsystem accounting (serving.sched): load-shed /
        # deferred admissions and chunked-prefill dispatches, plus a
        # scheduler_policy info label on the serving family so a
        # Prometheus query can slice any serving metric by the policy
        # that produced it
        self._c_shed = r.counter(
            "serving_requests_shed_total",
            "requests dropped by the admission policy before serving "
            "(by reason)", labelnames=("reason",))
        self._c_deprioritized = r.counter(
            "serving_requests_deprioritized_total",
            "requests moved behind still-SLO-viable queue members by "
            "the admission policy")
        self._c_chunks = r.counter(
            "serving_prefill_chunks_total",
            "chunked-prefill dispatches (one per chunk)")
        self._c_chunked_reqs = r.counter(
            "serving_chunked_requests_total",
            "requests whose prefill ran chunk-by-chunk")
        # which arm of the paged prefill program a dispatch ran: the
        # run over its own keys alone (start 0), or also the walk over
        # the cached prefix below it (a radix hit, a later chunk)
        self._c_prefill_arm = r.counter(
            "serving_prefill_arm_dispatches_total",
            "prefill and chunk dispatches by what they attended: "
            "'own' keys only, or a cached 'prefix' as well",
            labelnames=("arm",))
        self._c_prefix_read = r.counter(
            "serving_prefill_prefix_tokens_read_total",
            "cached positions below their start that prefill and "
            "chunk dispatches attended")
        self._g_policy = r.gauge(
            "serving_scheduler_policy",
            "active scheduling policy (the labeled policy reads 1)",
            labelnames=("scheduler_policy",))
        # resilience accounting (serving.resilience): dispatch
        # failures by seam, retry absorptions, deadline timeouts,
        # aborts, caught callback errors, quarantined slots, injected
        # chaos faults by site, and supervisor recoveries
        self._c_dispatch_failures = r.counter(
            "serving_dispatch_failures_total",
            "dispatch attempts that raised (rolled back, then retried "
            "or escalated)", labelnames=("kind",))
        self._c_retries = r.counter(
            "serving_dispatch_retries_total",
            "failed dispatches absorbed by the bounded-retry budget")
        self._c_timeouts = r.counter(
            "serving_requests_timed_out_total",
            "requests retired at their deadline_ms (SLO-judged as "
            "violations)")
        self._c_aborted = r.counter(
            "serving_requests_aborted_total",
            "requests retired unfinished (engine close with in-flight "
            "work, or dispatch retry budget exhausted)")
        self._c_callback_errors = r.counter(
            "serving_callback_errors_total",
            "user on_token callbacks that raised (caught and counted; "
            "the step loop kept streaming)")
        self._c_quarantine = r.counter(
            "serving_slots_quarantined_total",
            "slots excluded from admission after repeated same-slot "
            "dispatch failures")
        self._c_faults = r.counter(
            "serving_faults_injected_total",
            "chaos-harness fault injections by site",
            labelnames=("site",))
        self._c_restarts = r.counter(
            "supervisor_restarts_total",
            "in-process supervisor recoveries (AOT tables rebuilt, "
            "pools reset, in-flight requests replayed)")
        # speculative-decoding economy (serving.spec): what the
        # drafter shipped, what the verify program kept, and how many
        # tokens each verify dispatch actually yielded
        self._c_spec_drafted = r.counter(
            "serving_spec_drafted_tokens_total",
            "draft tokens shipped to verify dispatches")
        self._c_spec_accepted = r.counter(
            "serving_spec_accepted_tokens_total",
            "draft tokens accepted (longest-accepted-prefix)")
        self._c_spec_rejected = r.counter(
            "serving_spec_rejected_tokens_total",
            "draft tokens rejected at verify (including drafts masked "
            "with a retired request)")
        self._c_spec_emitted = r.counter(
            "serving_spec_emitted_tokens_total",
            "tokens emitted by verify dispatches (accepted drafts "
            "plus the bonus token, after stop masking)")
        self._c_spec_verify_steps = r.counter(
            "serving_spec_verify_steps_total",
            "k-token verify dispatches")
        self._c_spec_slot_steps = r.counter(
            "serving_spec_slot_steps_total",
            "per-slot verify legs harvested (one slot in one verify "
            "dispatch; a plain decode leg emits exactly 1 token, so "
            "emitted/slot_steps is the per-slot amortization factor)")
        self._c_spec_fallback_steps = r.counter(
            "serving_spec_fallback_steps_total",
            "decode-capable steps on a speculative engine dispatched "
            "on the plain decode program (no slot drafted)")
        self._spec_info = {"enabled": False, "k": None}
        self._resilience_fn = None
        self._sched_info = {"policy": "fifo", "prefill_chunk": None,
                            "prefill_token_budget": None}
        self._prefix_pool_stats = None
        self._health_fn = None
        self._identity = None
        self._trace_fn = None
        # plain-int mirror of the labeled shed counter: the health
        # tick reads a shed total on EVERY engine step, and iterating
        # the labeled series per step is measurable overhead there
        self.shed_count = 0
        self._res = {
            "ttft": Reservoir(self.RESERVOIR_SIZE),
            "request_latency": Reservoir(self.RESERVOIR_SIZE),
            "queue_wait": Reservoir(self.RESERVOIR_SIZE),
        }
        self.kv_donation = {"enabled": False, "effective": False}
        self._moe = None   # set_moe_counters
        self._loop = None  # set_loop_counters
        self._g_cache_entries = None   # enable_entry_cache
        self._g_cache_live_bytes = None    # enable_ring_cache
        self._t_first_work = None
        self._t_last_work = None

    # ------------------------------------------- legacy attribute facade
    compiles = _counter_property("_c_compiles")
    prefills = _counter_property("_c_prefills")
    prefill_requests = _counter_property("_c_prefill_requests")
    decode_steps = _counter_property("_c_decode_steps")
    tokens_generated = _counter_property("_c_tokens")
    speculative_masked = _counter_property("_c_spec_masked")
    requests_admitted = _counter_property("_c_admitted")
    requests_completed = _counter_property("_c_completed")
    spec_drafted = _counter_property("_c_spec_drafted")
    spec_accepted = _counter_property("_c_spec_accepted")
    spec_rejected = _counter_property("_c_spec_rejected")
    spec_tokens_emitted = _counter_property("_c_spec_emitted")
    spec_verify_steps = _counter_property("_c_spec_verify_steps")
    spec_slot_steps = _counter_property("_c_spec_slot_steps")
    spec_fallback_steps = _counter_property("_c_spec_fallback_steps")

    @property
    def queue_depth(self):
        return int(self._g_queue_depth.value)

    @queue_depth.setter
    def queue_depth(self, value):
        self._g_queue_depth.set(value)

    @property
    def slot_occupancy(self):
        return self._g_occupancy.value

    @slot_occupancy.setter
    def slot_occupancy(self, value):
        self._g_occupancy.set(value)

    @property
    def prefill_group_hist(self):
        """group size G -> dispatch count (read-only view of the
        labeled counter; mutate via record_prefill_group)."""
        fam = self._c_groups
        return {int(labels[0]): int(child.value)
                for labels, child in fam.series()}

    @property
    def span_s(self):
        """section name -> accumulated seconds (read-only view)."""
        return {labels[0]: child.value
                for labels, child in self._c_span.series()}

    @property
    def ttft_s(self):
        """BOUNDED reservoir view of per-request TTFT seconds (the
        unbounded list this replaced leaked under sustained traffic);
        exact totals live in the serving_ttft_seconds histogram."""
        return list(self._res["ttft"].samples())

    @property
    def request_latency_s(self):
        return list(self._res["request_latency"].samples())

    # ------------------------------------------------------- accounting
    def span(self, name, t0=None):
        """Context manager: XPlane annotation + chrome host span +
        registry accrual (profiler.host_scope's three sinks: the step
        loop only dispatches compiled programs, nothing is staged under
        its spans) + this engine's own span counter."""
        return _profiler.host_scope(name, sink=self._accrue, t0=t0)

    def record_span(self, name, t0, dt):
        """A span timed by hand, into the same sinks but the XPlane
        annotation (profiler.record_span); returns the HostSpan."""
        return _profiler.record_span(name, t0, dt, self._accrue)

    def _accrue(self, name, dt):
        kid = self._span_kids.get(name)
        if kid is None:
            kid = self._span_kids[name] = self._c_span.labels(name)
        kid.inc(dt)
        now = time.perf_counter()
        if self._t_first_work is None:
            self._t_first_work = now - dt
        self._t_last_work = now

    def record_prefill_group(self, group_size):
        self._c_groups.labels(str(int(group_size))).inc()

    def record_prefix_reuse(self, cached_tokens, computed_tokens,
                            tenant=None):
        """One paged admission's prefix economy: ``cached_tokens``
        came straight from the radix-matched blocks (a hit when > 0),
        ``computed_tokens`` is the uncached tail the prefill actually
        ran. The cached/computed split is what keeps engine.cost_model
        honest — cached spans must not be credited as prefill compute.
        Returns the estimated TTFT ms this admission saved (None until
        the cache observatory's perf join has prefill measurements) so
        the engine can stamp it onto the flight-recorder detail."""
        if cached_tokens > 0:
            self._c_prefix_hits.inc()
        else:
            self._c_prefix_misses.inc()
        if cached_tokens:
            self._c_prefix_cached_tokens.inc(int(cached_tokens))
        if computed_tokens:
            self._c_prefill_tokens.inc(int(computed_tokens))
        self._w_prefix_hits.add(1.0 if cached_tokens > 0 else 0.0)
        self._w_prefix_cached.add(float(cached_tokens or 0))
        saved_ms = self.cache.note_reuse(int(cached_tokens or 0))
        if cached_tokens:
            self.tenants.note_cache_savings(tenant, int(cached_tokens),
                                            saved_ms)
        return saved_ms

    def record_prefill_tokens(self, computed_tokens):
        """Legacy-pool prefill accounting: every prompt token is
        computed (no cache to hit)."""
        if computed_tokens:
            self._c_prefill_tokens.inc(int(computed_tokens))

    def set_kv_bytes_per_token(self, nbytes):
        """Gauge ``serving_kv_bytes_per_token``: the useful bytes one
        cached token takes over all layers, from the model's cache
        spec."""
        self.registry.gauge(
            "serving_kv_bytes_per_token",
            "bytes one cached token takes over all layers (cache spec)"
        ).set(float(nbytes))

    def set_state_bytes_per_slot(self, nbytes):
        """Gauge ``serving_state_bytes_per_slot``: the useful bytes of
        per-slot state (recurrent layers) one slot takes over all
        layers, from the model's cache spec; 0 for a model that keeps
        none."""
        self.registry.gauge(
            "serving_state_bytes_per_slot",
            "bytes of per-slot state one slot takes over all layers "
            "(cache spec)").set(float(nbytes))

    def enable_entry_cache(self):
        """A cache whose ENTRIES are not positions (``CacheSpec.window``:
        a finished window is compacted, ``serving_kv_bytes_per_token``
        is then the bytes of one entry): what the live slots hold, from
        the step loop's own counts, and how often a window was compacted
        and what that gave back."""
        r = self.registry
        self._g_cache_entries = r.gauge(
            "serving_cache_entries_live",
            "cache entries the decoding slots hold (summaries of "
            "finished windows + positions of the current one)")
        self._g_cache_positions = r.gauge(
            "serving_cache_positions_live",
            "positions the decoding slots have reached (what a cache "
            "of one entry a position would hold)")
        self._c_compactions = r.counter(
            "serving_cache_compactions_total",
            "windows compacted into their summaries (by the compaction "
            "program after a decode step, or by the prefill run that "
            "filled them)")
        self._c_blocks_released = r.counter(
            "serving_cache_blocks_released_total",
            "blocks a live slot gave back to the pool at a compaction")

    def set_cache_live(self, entries, positions):
        self._g_cache_entries.set(float(entries))
        self._g_cache_positions.set(float(positions))

    def record_compaction(self, blocks_released):
        self._c_compactions.inc()
        if blocks_released:
            self._c_blocks_released.inc(int(blocks_released))

    def entry_cache_report(self):
        """The four numbers above, or None for a model whose entries
        are positions."""
        if self._g_cache_entries is None:
            return None
        return {"entries_live": int(self._g_cache_entries.value),
                "positions_live": int(self._g_cache_positions.value),
                "compactions": int(self._c_compactions.value),
                "blocks_released": int(self._c_blocks_released.value)}

    def enable_ring_cache(self, bytes_per_token, bytes_per_slot,
                          dense_bytes_per_token):
        """A cache in which some layers keep a RING a slot and not an
        entry a position (``CacheSpec.ring``): what the decoding slots'
        positions hold, and what the same positions would hold if every
        layer kept each of them, from the step loop's own counts."""
        r = self.registry
        self._ring_bytes = (int(bytes_per_token), int(bytes_per_slot),
                            int(dense_bytes_per_token))
        self._g_cache_live_bytes = r.gauge(
            "serving_cache_live_bytes",
            "bytes the decoding slots hold: their positions in the "
            "layers that keep every one + their rings in the others")
        self._g_cache_dense_bytes = r.gauge(
            "serving_cache_full_equiv_bytes",
            "bytes the decoding slots' positions would hold if every "
            "layer kept each of them")

    def set_ring_cache_live(self, positions, slots):
        per_token, per_slot, dense = self._ring_bytes
        self._g_cache_live_bytes.set(
            float(positions * per_token + slots * per_slot))
        self._g_cache_dense_bytes.set(float(positions * dense))

    def ring_cache_report(self):
        """The two gauges above, or None for a model without rings."""
        if self._g_cache_live_bytes is None:
            return None
        return {"cache_live_bytes": int(self._g_cache_live_bytes.value),
                "cache_full_equiv_bytes":
                    int(self._g_cache_dense_bytes.value)}

    def set_moe_counters(self, read_fn, layers, first, count):
        """Expert-routing counters that the decode program keeps ON THE
        DEVICE (``CacheSpec.state``): ``read_fn()`` fetches the array
        ``[len(layers), count + 2]`` (tokens per held expert, distinct
        experts hit, steps) and is only called when somebody looks (a
        snapshot or a scrape), never by the step loop."""
        r = self.registry
        tokens = r.counter(
            "serving_moe_expert_tokens_total",
            "decode tokens routed to each held expert",
            labelnames=("layer", "expert"))
        tokens.max_label_values = len(layers) * count + 1
        hit = r.counter(
            "serving_moe_experts_hit_total",
            "distinct held experts hit, summed over decode steps",
            labelnames=("layer",))
        steps = r.counter(
            "serving_moe_layer_steps_total",
            "expert-layer executions of the decode program")
        self._moe = {"read": read_fn, "layers": list(layers),
                     "first": int(first), "count": int(count)}

        def moe_collect():
            arr = self.moe_counts()
            for i, layer in enumerate(layers):
                for e in range(count):
                    tokens.labels(layer, first + e).set_to(
                        float(arr[i, e]))
                hit.labels(layer).set_to(float(arr[i, count]))
            steps.set_to(float(arr[:, count + 1].sum()))
        r.add_collect_hook(moe_collect)

    def moe_counts(self):
        """The device's routing counters as a host array (None for a
        model without expert layers)."""
        if self._moe is None:
            return None
        return np.asarray(self._moe["read"]())

    def moe_report(self):
        """The counters by layer and expert (None for a model without
        expert layers)."""
        if self._moe is None:
            return None
        arr = self.moe_counts()
        m = self._moe
        return {"layers": m["layers"], "first_expert": m["first"],
                "held_experts": m["count"],
                "expert_tokens": arr[:, :m["count"]].tolist(),
                "experts_hit": arr[:, m["count"]].tolist(),
                "layer_steps": arr[:, m["count"] + 1].tolist()}

    def set_loop_counters(self, read_fn, passes, cache_passes):
        """Counters of a LOOPED model (one stack of layers run
        ``passes`` times) that the decode program keeps ON THE DEVICE
        (``CacheSpec.state``): ``read_fn()`` fetches ``(counts [passes
        + 1]`` int: decoded tokens by the pass they were read from, then
        the passes run for them; ``mass [passes]`` float: the exit
        distribution's summed mass a pass``)`` and is only called when
        somebody looks, never by the step loop. The gauge
        ``serving_cache_passes`` says how many cache layers a weight
        layer has."""
        r = self.registry
        run = r.counter(
            "serving_loop_passes_total",
            "passes of the layer stack run, summed over decoded tokens")
        exits = r.counter(
            "serving_loop_exit_pass",
            "decoded tokens by the pass they were read from",
            labelnames=("pass",))
        mass = r.counter(
            "serving_loop_gate_mass",
            "the exit distribution's mass a pass, summed over decoded "
            "tokens (what a threshold under 1 would let leave)",
            labelnames=("pass",))
        r.gauge("serving_cache_passes",
                "cache layers a weight layer has (a looped model keeps "
                "keys and values a pass)").set(float(cache_passes))
        self._loop = {"read": read_fn, "passes": int(passes),
                      "cache_passes": int(cache_passes)}

        def loop_collect():
            rep = self.loop_report()
            run.set_to(float(rep["passes_run"]))
            for i in range(passes):
                exits.labels(i).set_to(float(rep["exit_pass"][i]))
                mass.labels(i).set_to(float(rep["gate_mass"][i]))
        r.add_collect_hook(loop_collect)

    def loop_report(self):
        """The loop's counters (None for a model that runs its layers
        once)."""
        if self._loop is None:
            return None
        counts, mass = self._loop["read"]()
        n = self._loop["passes"]
        return {"passes": n, "cache_passes": self._loop["cache_passes"],
                "exit_pass": [int(c) for c in counts[:n]],
                "passes_run": int(counts[n]),
                "gate_mass": [float(m) for m in mass]}

    def set_prefix_pool(self, stats_fn):
        """Attach the paged pool's ``stats()`` as the pull source for
        snapshot()["prefix_cache"]["pool"]."""
        self._prefix_pool_stats = stats_fn

    def windowed_prefix_hit_rate(self):
        vals = self._w_prefix_hits.values()
        return sum(vals) / len(vals) if vals else 0.0

    def windowed_cached_tokens_per_sec(self):
        return sum(self._w_prefix_cached.values()) \
            / self.PREFIX_WINDOW_S

    def prefix_cache_report(self):
        hits = int(self._c_prefix_hits.value)
        misses = int(self._c_prefix_misses.value)
        cached = int(self._c_prefix_cached_tokens.value)
        computed = int(self._c_prefill_tokens.value)
        total = hits + misses
        w_admissions = self._w_prefix_hits.count()
        return {
            "hits": hits,
            "misses": misses,
            "hit_rate": round(hits / total, 4) if total else None,
            "cached_tokens": cached,
            "computed_tokens": computed,
            "cached_fraction": round(cached / (cached + computed), 4)
            if (cached + computed) else None,
            "windowed": {
                "window_s": self.PREFIX_WINDOW_S,
                "admissions": w_admissions,
                "hit_rate": round(self.windowed_prefix_hit_rate(), 4)
                if w_admissions else None,
                "cached_tokens_per_s": round(
                    self.windowed_cached_tokens_per_sec(), 3),
            },
            "pool": self._prefix_pool_stats()
            if self._prefix_pool_stats is not None else None,
        }

    def set_identity(self, identity, version=None, jax_version=None):
        """Stamp this engine's replica identity
        (observability.fleet.ReplicaIdentity) into the registry:
        ``serving_uptime_seconds`` (a pull gauge — uptime moving
        BACKWARDS between two fleet scrapes means the process
        bounced) and the ``paddle_tpu_build_info{replica, version,
        jax_version}`` info gauge (value 1, Prometheus ``*_info``
        convention) every fleet view uses to tell replicas and
        versions apart."""
        self._identity = identity
        self.registry.gauge(
            "serving_uptime_seconds",
            "seconds since this engine replica was constructed "
            "(restart detection: uptime going backwards between "
            "scrapes means the process bounced)"
        ).set_function(identity.uptime_s)
        self.registry.gauge(
            "paddle_tpu_build_info",
            "replica identity + build info (value is always 1; the "
            "labels are the payload)",
            labelnames=("replica", "version", "jax_version"),
        ).labels(identity.replica_id, str(version or "unknown"),
                 str(jax_version or "unknown")).set(1)

    def identity_report(self):
        """The ``snapshot()["replica"]`` section (also stamped into
        ``/debug/state`` and incident bundles): same key shape with
        None values before ``set_identity`` wires a real identity."""
        if self._identity is None:
            return {"replica_id": None, "uptime_s": None,
                    "started_at": None}
        return self._identity.report()

    def set_trace(self, snapshot_fn):
        """Attach the trace recorder's ``snapshot()`` as the pull
        source for ``snapshot()["trace"]`` (the recorder keeps its
        shape when tracing is disabled, so the schema contract holds
        either way)."""
        self._trace_fn = snapshot_fn

    def trace_report(self):
        """The ``snapshot()["trace"]`` section
        (observability.trace.TRACE_SNAPSHOT_KEYS pins the key set;
        engines without a recorder report the disabled shape)."""
        if self._trace_fn is not None:
            return self._trace_fn()
        return {"enabled": False, "spans_recorded": 0,
                "spans_dropped": 0, "ring_occupancy": 0,
                "ring_capacity": 0}

    def set_health(self, summary_fn):
        """Attach the health monitor's ``summary()`` as the pull
        source for ``snapshot()["health"]`` (engines built with
        health=False report the disabled shape instead — same keys,
        so the snapshot schema contract holds either way)."""
        self._health_fn = summary_fn

    def health_report(self):
        if self._health_fn is not None:
            return self._health_fn()
        from ..observability.health import disabled_health_summary
        return disabled_health_summary()

    def set_scheduler_info(self, policy_name, prefill_chunk,
                           prefill_token_budget):
        """Stamp the engine's scheduling configuration: the
        ``scheduler_policy`` info label (value 1) and the static
        fields of ``snapshot()["scheduler"]``."""
        self._sched_info = {
            "policy": str(policy_name),
            "prefill_chunk": prefill_chunk,
            "prefill_token_budget": prefill_token_budget,
        }
        self._g_policy.labels(str(policy_name)).set(1)

    def record_shed(self, reason, tenant=None):
        """One request dropped by the admission policy: counted by
        reason here AND judged by the SLO tracker (a shed request is a
        violated request with zero goodput tokens — shedding must
        never inflate attainment)."""
        self._c_shed.labels(str(reason)).inc()
        self.shed_count += 1
        self.slo.observe_shed(str(reason))
        self.tenants.note_shed(tenant, str(reason))

    def record_deprioritized(self):
        self._c_deprioritized.inc()

    def record_prefill_chunk(self, computed_tokens):
        """One chunked-prefill dispatch: the chunk counter plus the
        real computed-token accounting (chunk overlap recompute tokens
        included — they ARE prefill compute)."""
        self._c_chunks.inc()
        if computed_tokens:
            self._c_prefill_tokens.inc(int(computed_tokens))

    def record_chunked_request(self):
        self._c_chunked_reqs.inc()

    def record_prefill_arm(self, start):
        """One prefill or chunk dispatch that stuck, by the cached
        positions below its run (``start``)."""
        self._c_prefill_arm.labels("prefix" if start else "own").inc()
        if start:
            self._c_prefix_read.inc(int(start))

    def _prefill_arm(self, arm):
        return int(self._c_prefill_arm.labels(arm).value)

    def scheduler_report(self):
        """The ``snapshot()["scheduler"]`` section: policy identity,
        chunking configuration, and the shed / deferred / chunk
        decision counters."""
        shed = {labels[0]: int(child.value)
                for labels, child in self._c_shed.series()}
        return dict(
            self._sched_info,
            shed=shed,
            shed_total=sum(shed.values()),
            deprioritized=int(self._c_deprioritized.value),
            prefill_chunks=int(self._c_chunks.value),
            chunked_requests=int(self._c_chunked_reqs.value),
        )

    # ------------------------------------------------------- resilience
    def record_dispatch_failure(self, kind):
        self._c_dispatch_failures.labels(str(kind)).inc()

    def record_retry(self):
        self._c_retries.inc()

    def record_timeout(self, tenant=None):
        """One request retired at its deadline: counted here AND
        SLO-judged as a violation (dimension "deadline", zero goodput)
        — a timed-out answer is worth nothing to its caller, so
        timeouts must never inflate attainment."""
        self._c_timeouts.inc()
        self.slo.observe_shed("deadline")
        self.tenants.note_timeout(tenant)

    def record_abort(self, tenant=None):
        self._c_aborted.inc()
        self.tenants.note_abort(tenant)

    def record_callback_error(self):
        self._c_callback_errors.inc()

    def record_quarantine(self):
        self._c_quarantine.inc()

    def record_fault(self, site):
        self._c_faults.labels(str(site)).inc()

    def record_restart(self):
        self._c_restarts.inc()

    def set_resilience(self, state_fn):
        """Attach the engine's live resilience state (quarantined
        slots, draining flag, supervisor + chaos reports) as the pull
        source for ``snapshot()["resilience"]``."""
        self._resilience_fn = state_fn

    def resilience_report(self):
        """The ``snapshot()["resilience"]`` section: failure/retry/
        timeout/abort counters plus the engine's live quarantine,
        supervisor and chaos state."""
        fails = {labels[0]: int(child.value) for labels, child
                 in self._c_dispatch_failures.series()}
        faults = {labels[0]: int(child.value) for labels, child
                  in self._c_faults.series()}
        state = self._resilience_fn() if self._resilience_fn is not None \
            else {"quarantined_slots": [], "draining": False,
                  "supervisor": {"enabled": False},
                  "chaos": {"enabled": False}}
        return dict({
            "dispatch_failures": fails,
            "dispatch_failures_total": sum(fails.values()),
            "dispatch_retries": int(self._c_retries.value),
            "requests_timed_out": int(self._c_timeouts.value),
            "requests_aborted": int(self._c_aborted.value),
            "callback_errors": int(self._c_callback_errors.value),
            "slots_quarantined_total": int(self._c_quarantine.value),
            "faults_injected": faults,
            "supervisor_restarts": int(self._c_restarts.value),
        }, **state)

    def record_admission(self, request):
        """Queue-wait accounting at slot-claim time (the scheduler
        stamps request.t_admitted in admit())."""
        wait = 0.0
        if request.t_admitted is not None:
            wait = request.t_admitted - request.t_arrival
            self._h_queue_wait.observe(wait)
            self._res["queue_wait"].add(wait)
        self.tenants.note_admission(
            getattr(request, "tenant_id", None), len(request.prompt),
            wait)

    def record_submit_wait(self, seconds):
        """The gateway's wait for its own lock, per submission."""
        self._h_submit_wait.observe(seconds)

    def record_first_token(self, request):
        request.t_first_token = time.perf_counter()
        ttft = request.t_first_token - request.t_received
        self._h_ttft.observe(ttft)
        self._res["ttft"].add(ttft)
        self.tenants.note_first_token(
            getattr(request, "tenant_id", None), ttft)

    def record_completion(self, request):
        """Completion accounting + the request's SLO verdict; returns
        the violated dimensions (empty list = SLO attained) so the
        engine can stamp them onto the flight-recorder retirement."""
        self._c_completed.inc()
        latency = request.t_done - request.t_received
        self._h_latency.observe(latency)
        self._res["request_latency"].add(latency)
        ttft = (None if request.t_first_token is None
                else request.t_first_token - request.t_received)
        violations = self.slo.observe_request(ttft, latency,
                                              len(request.generated))
        # the tenant ledger receives the engine's OWN verdict — never
        # a re-judgment — so per-tenant attainment/goodput sums match
        # the global SLO counters bit-exactly
        self.tenants.note_completion(
            getattr(request, "tenant_id", None),
            len(request.generated), violations)
        return violations

    # ---------------------------------------------------- cost model
    def set_decode_cost(self, flops=None, bytes_accessed=None):
        """Per-decode-dispatch device cost from the compiled decode
        executable's cost_analysis (the engine calls this when the
        decode program is built)."""
        if flops is not None:
            self._g_decode_flops.set(flops)
        if bytes_accessed is not None:
            self._g_decode_bytes.set(bytes_accessed)

    def set_peak_flops(self, peak_flops):
        """Device peak FLOP/s the MFU estimate is computed against
        (None = unknown -> the gauge reads 0)."""
        self._peak_flops = None if not peak_flops else float(peak_flops)

    def estimated_mfu(self):
        """Rough MFU: decode_steps * flops_per_decode over the busy
        wall window, against peak FLOP/s. An ESTIMATE — prefill flops
        are excluded and the busy window includes host time — but it
        trends correctly and costs nothing to keep on."""
        peak = self._peak_flops
        flops = self._g_decode_flops.value
        if not peak or not flops or self._t_first_work is None \
                or self._t_last_work is None:
            return 0.0
        busy = self._t_last_work - self._t_first_work
        if busy <= 0:
            return 0.0
        return self.decode_steps * flops / (busy * peak)

    def enable_device_memory(self, stats_fn):
        """Register HBM pull gauges backed by ``stats_fn()`` (a
        callable returning observability.device_memory_stats()-shaped
        dicts). Only called on backends that actually report — CPU
        serves no HBM gauges rather than zeros."""

        def field(name):
            stats = stats_fn()
            v = (stats or {}).get(name)
            return 0.0 if v is None else float(v)

        self.registry.gauge(
            "serving_hbm_bytes_in_use", "device memory in use (bytes)"
        ).set_function(lambda: field("bytes_in_use"))
        self.registry.gauge(
            "serving_hbm_bytes_free",
            "device memory headroom: bytes_limit - bytes_in_use"
        ).set_function(lambda: field("bytes_free"))

    # --------------------------------------------------------- derived
    def tokens_per_sec(self):
        """Generated tokens over the busy window (first to last timed
        span) — the serving throughput headline."""
        if self._t_first_work is None or self._t_last_work is None:
            return 0.0
        dt = self._t_last_work - self._t_first_work
        return self.tokens_generated / dt if dt > 0 else 0.0

    def dispatch_sync_split(self):
        """(dispatch_s, sync_s): wall time spent ISSUING device work vs
        BLOCKED on device->host reads. The pipelined hot path's whole
        point is pushing time out of sync and letting it overlap the
        dispatch column."""
        spans = self.span_s
        dispatch = sum(v for k, v in spans.items()
                       if k.endswith("_dispatch"))
        return dispatch, spans.get("serving/sync", 0.0)

    def latency_percentiles(self):
        """{"ttft": {...}, "request_latency": {...}, "queue_wait":
        {...}} — count + p50/p90/p99 in ms from the bounded
        reservoirs (None when the series is empty)."""
        out = {}
        for name, res in self._res.items():
            entry = {"count": res.seen}
            for q, key in _PCTS:
                p = res.percentile(q)
                entry[key] = None if p is None else round(p * 1000.0, 3)
            out[name] = entry
        return out

    def cache_report(self):
        """The ``snapshot()["cache"]`` / ``/debug/cache`` body: MRC,
        heat digest, savings attribution and churn telemetry from the
        cache observatory (the disabled shape until a paged pool is
        attached — same key set, the snapshot schema contract holds
        either way)."""
        return self.cache.report()

    def set_spec(self, enabled, k):
        """Engine wiring: record whether speculative decoding is on
        (and its draft width) so perf_report's ``spec`` section can
        tell "off" apart from "on but nothing drafted yet"."""
        self._spec_info = {"enabled": bool(enabled),
                           "k": int(k) if enabled else None}

    def spec_report(self):
        """The ``perf["spec"]`` section: speculation economy from the
        live counters (observability.perf.PERF_SPEC_KEYS pins the key
        set; the disabled shape keeps it when speculation is off)."""
        drafted = self.spec_drafted
        slot_steps = self.spec_slot_steps
        return {
            "enabled": self._spec_info["enabled"],
            "k": self._spec_info["k"],
            "drafted_tokens": drafted,
            "accepted_tokens": self.spec_accepted,
            "rejected_tokens": self.spec_rejected,
            "emitted_tokens": self.spec_tokens_emitted,
            "verify_steps": self.spec_verify_steps,
            "slot_steps": slot_steps,
            "fallback_steps": self.spec_fallback_steps,
            "acceptance_rate":
                round(self.spec_accepted / drafted, 4) if drafted
                else None,
            # tokens one slot yields from one verify leg: a plain
            # decode leg is exactly 1.0, so this IS the per-slot
            # HBM-read amortization factor
            "effective_tokens_per_dispatch":
                round(self.spec_tokens_emitted / slot_steps, 4)
                if slot_steps else None,
        }

    def perf_report(self):
        """The ``snapshot()["perf"]`` / ``/debug/perf`` body:
        per-program measured time + roofline fractions, with the
        accrued ``serving/step`` span seconds as the attribution
        denominator — plus the speculation economy under ``spec``
        (the one perf section fed by engine counters rather than
        dispatch timing, so it lives here, not in ProgramPerf)."""
        report = self.perf.report(
            step_total_s=self.span_s.get("serving/step"))
        report["spec"] = self.spec_report()
        return report

    def tenant_report(self):
        """The ``snapshot()["tenants"]`` / ``/debug/tenants`` body:
        per-tenant attribution rows plus the overflow accounting (see
        observability.tenant.TENANT_KEYS / TENANT_ENTRY_KEYS)."""
        return self.tenants.report()

    def prometheus_text(self):
        """This engine's registry in Prometheus text exposition format
        (also served over HTTP by ServingEngine.serve_metrics())."""
        return self.registry.prometheus_text()

    def snapshot(self):
        """The stable dict the bench artifacts embed. Schema is a
        CONTRACT (tests/test_observability.py pins the key set): keys
        only get added, never renamed/removed within a PR sequence."""
        n_ttft = self._h_ttft.count
        dispatch_s, sync_s = self.dispatch_sync_split()
        return {
            "tokens_generated": self.tokens_generated,
            "tokens_per_sec": round(self.tokens_per_sec(), 2),
            "ttft_avg_ms": round(
                self._h_ttft.sum / n_ttft * 1000.0, 3) if n_ttft else None,
            "queue_depth": self.queue_depth,
            "slot_occupancy": round(self.slot_occupancy, 4),
            "prefills": self.prefills,
            "prefill_requests": self.prefill_requests,
            # prefill + chunk dispatches by the program's arm
            "prefills_without_prefix": self._prefill_arm("own"),
            "prefills_with_prefix": self._prefill_arm("prefix"),
            "prefill_prefix_tokens_read": int(self._c_prefix_read.value),
            "prefill_groups": {str(k): v for k, v in
                               sorted(self.prefill_group_hist.items())},
            "decode_steps": self.decode_steps,
            "speculative_masked": self.speculative_masked,
            "kv_donation": dict(self.kv_donation),
            "compiles": self.compiles,
            "requests_admitted": self.requests_admitted,
            "requests_completed": self.requests_completed,
            "dispatch_s": round(dispatch_s, 4),
            "sync_s": round(sync_s, 4),
            "span_s": {k: round(v, 4) for k, v in self.span_s.items()},
            "latency_percentiles": self.latency_percentiles(),
            "slo": self.slo.report(),
            "prefix_cache": self.prefix_cache_report(),
            "scheduler": self.scheduler_report(),
            "health": self.health_report(),
            "resilience": self.resilience_report(),
            "perf": self.perf_report(),
            "cache": self.cache_report(),
            "replica": self.identity_report(),
            "trace": self.trace_report(),
            "tenants": self.tenant_report(),
            # only a model with expert layers adds its section
            **({"moe": self.moe_report()} if self._moe else {}),
            # only a looped model
            **({"loop": self.loop_report()} if self._loop else {}),
            # only a model whose cache entries are not positions
            **({"cache_entries": self.entry_cache_report()}
               if self._g_cache_entries is not None else {}),
            **({"cache_rings": self.ring_cache_report()}
               if self._g_cache_live_bytes is not None else {}),
        }
