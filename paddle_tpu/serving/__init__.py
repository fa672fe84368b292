"""Continuous-batching inference serving (the Orca/vLLM pattern,
TPU-native).

``generate()`` is batch-synchronous: every request in a batch waits for
the slowest, and every new (batch, prompt_len, new_tokens) signature
compiles a fresh XLA executable. This package turns the same decode
math into a multi-tenant server:

  * **paged static-shape KV cache** (paged.PagedKVPool, the
    engine's only cache) — one ``[layers, num_blocks, heads,
    block_size, head_dim]`` pair behind a fixed-shape block table;
    finished sequences free their slot and blocks and waiting
    requests claim them mid-flight, so the jitted decode step keeps
    ONE shape forever, and a radix index over frozen prompt blocks
    turns a shared prompt prefix into a cache hit (see
    ``serving.paged``). The pooled kc/vc (and the position vector)
    are DONATED into every serving executable, so on TPU/GPU the
    cache updates in place instead of double-buffering ~2x its
    footprint per call;
  * **bucketed tail prefill** — each admission prefills only the
    uncached tail of its prompt, padded to a small geometric bucket
    set, so prompt-length AND queue-depth variety costs at most
    ``len(buckets)`` prefill compiles;
  * **one-step-deep async decode pipeline** — step N's tokens are read
    back only after step N+1's decode is dispatched (token/position
    state chains device-side), so host bookkeeping overlaps device
    compute; a just-stopped request's speculative in-flight token is
    masked at harvest, keeping exact greedy generate() parity
    (``async_depth=0`` restores the synchronous schedule);
  * **step scheduler** (scheduler.StepScheduler) — FIFO queue,
    prefix-aware admission on free slots, per-slot EOS/max-token
    stops, streaming token callbacks;
  * **metrics** (metrics.ServingMetrics) — a thin facade over a
    paddle_tpu.observability MetricsRegistry: tokens/sec, TTFT /
    request-latency / queue-wait percentiles (bounded histograms +
    fixed-size reservoirs — no unbounded lists under sustained
    traffic), queue depth, slot occupancy, prefill-group histogram,
    KV-donation status, dispatch-vs-sync wall split and an exact
    compile counter. Every timed section uses the ONE-SCOPE-THREE-
    SINKS discipline (paddle_tpu.profiler.host_scope, the span for
    code under which nothing is staged): the same ``serving/*`` scope
    is (1) annotated into the XLA trace for live XPlane captures, (2)
    recorded into the bounded host-span ring buffer — dump the
    engine-step anatomy as a chrome://tracing / Perfetto timeline via
    ``observability.default_recorder().dump_chrome_trace(path)`` —
    and (3) accrued into the registry for the snapshot()/Prometheus
    numbers. The spans, outermost first. On a caller's thread:
    ``serving/submit_wait`` (EngineGateway.submit()/prefill() waiting
    for the gateway's lock; ring args carry the rid; also the
    histogram ``serving_submit_wait_seconds``). On the gateway's
    driver thread: ``serving/drive`` (one iteration that stepped,
    from before the lock is asked for until step() returned) holding
    ``serving/drive_lock_wait``, ``serving/step`` and, after it,
    ``serving/health_tick`` (step-ledger row + detectors; with
    ``serving/health_audit`` inside it every ``health_audit_every``
    steps). Inside ``serving/step``: ``serving/retirement`` →
    ``serving/triage`` → ``serving/admit`` →
    ``serving/prefill_dispatch`` / ``serving/chunk_dispatch`` →
    ``serving/draft`` (speculative) → ``serving/decode_dispatch`` →
    ``serving/harvest`` holding one ``serving/sync`` (the only
    device→host wait) per harvested dispatch and at most one
    ``serving/on_token`` (the time its callbacks took, added up);
    ``serving/compile`` wherever a
    program is first built; ``serving/kv_export``,
    ``serving/kv_import`` and ``serving/supervisor_restart`` on their
    own paths. ``serving/drive_lock_wait`` and ``serving/on_token``
    are written from stamps once they are over (ring and registry,
    no XPlane annotation); ``serving/drive``'s annotation opens when
    the lock is held. Per request, beside the spans: ``Request.t_received``
    (the gateway's entry, before its lock; TTFT, latency and the SLO
    verdicts count from it), ``t_arrival`` (enqueued; queue wait and
    deadlines count from it), ``t_admitted``,
    ``t_prefill_dispatched`` with ``prefill_tokens_dispatched`` (the
    padded tokens the device computed for it), ``t_first_token``,
    ``t_done``. Scrape with ``server = engine.serve_metrics()`` then
    ``GET http://127.0.0.1:<port>/metrics`` (Prometheus text) or
    ``/metrics.json`` (the snapshot schema); the handle's ``close()``
    stops the server (idempotent; ``engine.close()`` closes every
    handle the engine handed out);
  * **request flight recorder** (``engine.flight``, an
    observability.FlightRecorder) — every request gets a lifecycle
    trace (enqueued → admitted(slot, bucket, group) → prefill
    dispatched → first token → per-decode-window progress →
    retired(reason, SLO verdict)) emitted into the host chrome trace
    as FLOW events, so Perfetto draws one arrow chain per request
    across the engine step spans. Completed traces park in a bounded
    keep-last-N ring (``trace_keep``); read one back with
    ``engine.request_trace(rid)`` or all of them from the
    ``/debug/requests`` endpoint (``/debug/state`` serves the live
    queue/slot/pipeline/watchdog picture);
  * **SLO & goodput accounting** (``metrics.slo``, an
    observability.SLOTracker) — ``ServingConfig(slo_ttft_ms=...,
    slo_tpot_ms=...)`` sets time-to-first-token / time-per-output-
    token targets; per-request attainment and per-dimension violation
    counters, goodput tokens (from requests that met their SLOs) vs
    total, and sliding-window p50/p90/p99 TTFT/TPOT/latency gauges
    (``slo_window_s``, default 60 s) computed AT SCRAPE TIME, so
    /metrics reflects current traffic — all in ``snapshot()["slo"]``;
  * **device cost telemetry** — every AOT build's
    ``cost_analysis()`` (flops, bytes) and ``memory_stats()`` ride on
    its watchdog compile record (graceful None on backends that don't
    report); per-decode-step flops/bytes, estimated-MFU (vs the
    device-kind peak-FLOP/s table, ``peak_flops=`` /
    ``$PADDLE_TPU_PEAK_FLOPS`` override) and HBM in-use/free pull
    gauges; ``engine.cost_model()`` is the artifact-ready summary;
  * **scheduling subsystem** (serving.sched, PR 7 — all default-off):
    chunked prefill (``prefill_chunk=`` — long prompts prefill in
    fixed-width chunks co-scheduled with decode steps under a
    per-step token budget; a chunk is a tail prefill at the
    chunk-width bucket, exact parity with whole-prompt prefill),
    SLO-feedback
    admission (``policy="slo_feedback"`` — sheds/defers queued
    requests whose TTFT SLO is already lost against live delivered
    latency; counted, SLO-judged, flight-evented), and per-slot
    sampling (``sampling=True`` — temperature/top-k/top-p per slot in
    the one compiled decode, greedy slots bit-exact with generate());
  * **health observatory** (``engine.health``, an
    observability.health.HealthMonitor; ON by default,
    ``PADDLE_HEALTH=0`` / ``health=False`` opts out) — every step
    appends a structured row to a bounded step ledger
    (wall/dispatch/sync seconds, queue + slot state, token/shed
    deltas, paged block economy, compile flags; ``/debug/ledger``)
    and runs pluggable online anomaly detectors over it (step-time
    spike, queue stall, goodput collapse, KV-block leak via the
    periodic ``health_audit_every`` pool conservation audit, steady-
    state compile). Firings count in
    ``serving_anomalies_total{detector}``, drop ``health/<detector>``
    marker spans into the chrome timeline, and (with
    ``incident_dir=`` set) capture debounced black-box incident
    bundles — ledger tail, metrics snapshot, request traces, span
    tail — with keep-last-N rotation (``tools/incident_report.py``
    renders them). ``/debug/health`` returns ``{healthy, detectors,
    last_incident}``: the per-replica readiness signal a scale-out
    router polls;
  * **resilience** (serving.resilience, PR 9) — a deterministic,
    seeded fault-injection harness (``chaos=FaultPlan(seed)`` /
    ``PADDLE_CHAOS``, off by default) at the engine's real seams
    (dispatches, transfers, pool exhaustion, compile storms, poisoned
    callbacks; identical seed => identical fault log AND token
    streams), plus the hardening it forces: per-request deadlines
    (``add_request(..., deadline_ms=)``, timeout retirement
    SLO-judged), bounded leak-free dispatch retry
    (``max_dispatch_retries=``), slot quarantine
    (``quarantine_after=``), guarded ``on_token`` callbacks, graceful
    ``drain()`` and explicit-abort ``close()`` — and a self-healing
    supervisor that turns wedge verdicts (queue stall, KV-block leak,
    repeated dispatch failure) into an in-process restart: rebuilt
    AOT tables, fresh pools, in-flight requests replayed bit-exact;
    ``/debug/health`` reports ``{degraded, draining, restarts}``
    truthfully throughout (``snapshot()["resilience"]`` carries the
    counters; ``tools/chaos_sweep.py`` is the CI fault matrix);
  * **fleet router** (serving.router, PR 14 — ROADMAP direction #2's
    request path) — the client-facing front-end over N replicas:
    ``EngineGateway`` gives every engine a ``POST /v1/generate`` wire
    surface (and an in-process transport for tests/benches), and
    ``Router`` dispatches over the fleet with load+prefix-affinity
    placement fed by the PR-11 poller verdicts and PR-13
    ``cache.heat_top`` fingerprints, per-replica circuit breakers,
    bounded retry/failover with deterministic jittered backoff, a
    prompt+tokens-so-far journal for bit-exact greedy continuation
    after replica death, remaining-deadline propagation, and optional
    p99-derived first-wins hedging (OFF by default). Explicit shed
    verdicts, ``/router/state`` on its own registry (rendered by
    ``tools/fleet_top.py --router``), and a kill-a-replica drill
    (``tools/router_drill.py``) that proves 100% completion + parity
    + zero leaks where a no-failover baseline loses in-flight work;
  * **self-drafting speculative decoding** (serving.spec, PR 16 —
    default-off: ``speculative=True`` / ``PADDLE_SPEC_DECODE=1``) —
    an n-gram/prompt-lookup drafter over each slot's own context (no
    second model; bounded, incremental, radix-aware: shared prompts
    share draft statistics) proposes up to ``spec_k`` tokens per
    slot, and ONE extra AOT program
    (``paged_spec_verify``) verifies all k+1
    positions in a single fixed-shape dispatch — amortizing the
    HBM-bound parameter + KV read plain decode pays per token.
    Greedy streams stay bit-exact with ``generate()`` by construction
    (per-query causal masking + longest-accepted-prefix harvest);
    per-request EWMA acceptance below ``spec_min_accept`` falls that
    request back to plain decode, and a step where nobody drafts
    dispatches the plain decode program (both flavors warm at the
    first decode, so the steady state never compiles).
    ``snapshot()["perf"]["spec"]`` carries the economy (acceptance
    rate, effective tokens per slot-dispatch, drafted / accepted /
    rejected counters); the flight recorder logs ``draft_accepted`` /
    ``draft_rejected`` per verify; greedy-only (speculation x
    sampling is rejected at config time);
  * **disaggregated prefill/decode** (serving.kv_wire + the
    ``role="prefill"|"decode"|"monolithic"`` config, PR 17 — ROADMAP
    direction #1) — dedicated prefill replicas compute KV and stream
    it to decode replicas as digest-checked paged blocks:
    ``export_kv(rid)`` serializes ``[heads, block_size, head_dim]``
    tiles + the block-table row (a held-export parks the source
    blocks until the payload is handed off), ``import_kv(payload)``
    validates everything up front (corruption raises ``KVWireError``
    before the pool is touched) and binds the blocks via
    ``PagedKVPool.rebind`` + block-table splice, resuming at the first
    decode step with no prefill recompute;
    ``warmup_kv_handoff()`` pre-builds the import path so BOTH tiers
    keep the zero-compile steady state. Role is routing posture, not
    capability — every engine can still serve anything, so router
    failover replays on any survivor (``router_drill.py --kill
    prefill`` proves bit-exact journal replay after prefill-replica
    SIGKILL). The router runs the two-hop 1P+ND flow with
    deterministic affinity tie-break, two-hop deadline propagation
    and a congestion fallback to monolithic dispatch;
  * zero-recompile steady state BY CONSTRUCTION — and ATTRIBUTED
    (engine.ServingEngine): all device work runs ahead-of-time
    compiled executables, the whole-lifetime compiled-program
    inventory is bounded by ``len(buckets) + 1`` (+ the chunk-width
    bucket when chunked, + verify when speculative, + the two wire
    programs when warmed), and every build is logged in a compile watchdog
    (``engine.watchdog``) with its abstract-shape signature and
    dispatch call-site. After ``engine.declare_warmup()`` any further
    compile is flagged in ``watchdog.report()`` — or raised
    immediately with ``ServingConfig(watchdog_mode="raise")`` — so a
    production recompile is an attributed alarm, not a silent counter
    drift.

Tuning knobs
------------
``num_slots``   decode batch width and cache pool size. Throughput
                rises with concurrency until the pooled cache
                (``PagedKVPool.nbytes()``) or the decode step's matmul
                width saturates the chip; 8-32 is a sensible range.
``max_len``     per-slot capacity (prompt + generated), default the
                model's max_seq_len. The cache is num_slots*max_len
                tokens — size it to the traffic's real tail, not the
                model maximum.
``buckets`` / ``bucket_min``
                prefill pad lengths, default geometric doubling
                ``[bucket_min, 2x, ..., max_len]``. More buckets = less
                pad waste per prefill but more compiles; the doubling
                set bounds pad waste at <2x and compiles at
                O(log(max_len/bucket_min)).
``block_size`` / ``num_blocks``
                paging granularity (prefix sharing happens at block
                multiples; default 16) and the physical pool size
                (default: every slot fully backed + the trash block).
``async_depth`` 1 (default) = one-step-deep decode pipelining; 0 =
                fully synchronous per-step host reads (can win on
                churn-heavy tiny-model CPU workloads where every step
                prefills); k > 1 = up to k steps' results unread, so
                the device holds k steps of queued work while the
                host is away (tokens surface k steps late; refused
                with ``speculative``).
``donate_buffers``
                None (default) = donate kc/vc/pos where the backend
                aliases donated buffers (TPU/GPU); True/False forces.
``watchdog_mode``
                "flag" (default) records post-warmup compiles in
                ``engine.watchdog.report()``; "raise" turns them into
                CompileAfterWarmupError at the offending dispatch.
``slo_ttft_ms`` / ``slo_tpot_ms`` / ``slo_window_s``
                SLO targets (None = untargeted) and the sliding-
                percentile window for the goodput/attainment
                accounting above.
``prefill_chunk`` / ``prefill_token_budget``
                chunked prefill (serving.sched): prompts longer than
                ``prefill_chunk`` prefill in fixed-width chunks
                interleaved with decode steps, at most
                ``prefill_token_budget`` chunk tokens per step
                (default: one chunk). None (default) = whole-prompt
                prefill; ``PADDLE_PREFILL_CHUNK`` sets an env default.
``policy``      admission policy: "fifo" (default), "slo_feedback"
                (shed queued requests whose TTFT SLO is already
                lost, judged against live delivered latency), or a
                serving.sched.SchedulingPolicy instance;
                ``PADDLE_SCHED_POLICY`` sets an env default.
``sampling``    True threads per-slot temperature / top-k / top-p
                (``add_request(..., temperature=, top_k=, top_p=,
                seed=)``) through the one compiled decode/prefill
                executable; False (default) keeps the greedy-only
                signatures and rejects sampled requests.
``health``      True (default; env gate ``PADDLE_HEALTH=0``) runs the
                health observatory: per-step ledger + online anomaly
                detectors + ``/debug/health`` / ``/debug/ledger``.
``health_audit_every``
                steps between periodic paged-pool conservation audits
                (default 64; cost visible as a
                ``serving/health_audit`` host span).
``health_ledger_keep`` / ``health_detectors``
                ledger ring size (default 512) and per-detector
                threshold overrides, e.g.
                ``{"queue_stall": {"stall_steps": 8}}``.
``incident_dir`` / ``incident_keep`` / ``health_debounce_s``
                where detector firings dump black-box incident
                bundles (None (default) = no disk writes; env
                ``PADDLE_INCIDENT_DIR``), how many bundles the
                directory keeps (default 16), and the per-detector
                capture debounce (default 60 s).
``chaos``       arm the fault-injection harness: a
                ``resilience.FaultPlan``, an int seed (default
                rates), or a ``{seed, faults}`` dict; None (default)
                consults ``PADDLE_CHAOS`` (``<seed>`` or
                ``<seed>:<rate>``), False forces off. Deterministic
                per seed; fires counted in
                ``serving_faults_injected_total{site}``.
``max_dispatch_retries``
                failed prefill/chunk/decode dispatches (and harvest
                transfers) absorbed per request/step before the
                request retires ``"error"`` (0 = default = the raise-
                through prior behavior). Rollback is leak-free;
                decode failures past the budget escalate to the
                supervisor.
``retry_backoff_s``
                base of the exponential admission backoff after an
                absorbed dispatch failure (0 = retry next step).
``quarantine_after``
                same-slot dispatch failures before the slot is
                excluded from admission (default 3; never the last
                admissible slot; reset by a supervisor restart).
``supervisor`` / ``supervisor_max_restarts`` / ``supervisor_cooldown_s``
                the self-healing supervisor (None = on whenever the
                health observatory is on): consumes queue_stall /
                kv_block_leak verdicts + repeated dispatch failure,
                performs an in-process restart (rebuilt AOT tables,
                fresh pools, bit-exact greedy replay of in-flight
                requests), reports ``{degraded, draining, restarts}``
                on ``/debug/health``; max_restarts bounds the
                crash-loop, cooldown_s debounces same-episode
                verdicts.
``completed_keep`` / ``trace_keep`` / ``trace_decode_window``
                retention bounds: completed Request objects kept by
                the scheduler (default 4096), completed RequestTraces
                kept by the flight recorder (default 256), and the
                token granularity of mid-decode trace events.
``peak_flops``  device peak FLOP/s for the estimated-MFU gauge
                (default: device_kind table / $PADDLE_TPU_PEAK_FLOPS;
                unknown -> the gauge reads 0).
``replica_id``  this engine's identity in a fleet (default:
                ``$PADDLE_REPLICA_ID``, else a stable host:pid id).
                Stamped into ``snapshot()["replica"]``,
                ``/debug/state``, ``/debug/health``, incident bundles,
                and the ``paddle_tpu_build_info`` /
                ``serving_uptime_seconds`` exposition — what
                ``observability.fleet.FleetPoller`` and the /fleet/*
                surface key replicas by.
``speculative`` / ``spec_k`` / ``spec_min_accept``
                self-drafting speculative decoding (serving.spec):
                None (default) consults ``PADDLE_SPEC_DECODE``;
                ``spec_k`` (default 4, must be >= 1) is the draft
                width — each verify dispatch runs ``spec_k + 1``
                positions per slot and emits 1..spec_k+1 tokens;
                ``spec_min_accept`` (default 0.35) is the per-request
                EWMA acceptance floor below which the request falls
                back to plain decode. Greedy-only: combining with
                ``sampling=True`` raises at config time.
``max_tenants`` per-tenant attribution cardinality cap (default 32;
                0 disables the tenant ledger, same report shape).
                ``add_request(..., tenant_id=)`` / the ``tenant_id``
                POST field attributes a request (unset = trace-baggage
                tenant, else ``"default"``); ids past the cap fold
                into ``~other`` with counters conserved. Surfaces:
                ``snapshot()["tenants"]``, ``/debug/tenants``,
                ``serving_tenant_*_total{tenant=}``, the fleet's
                ``/fleet/tenants`` + ``tools/tenant_report.py``.
``eos_id``      default stop token (per-request override on
                add_request).

Correctness contracts live in tests/test_serving.py and
tests/test_paged_serving.py; the measured cells are
``benchmarks/`` (``BENCHMARK.json``).
"""
from .engine import (  # noqa: F401
    ServingConfig, ServingEngine, default_buckets,
)
from .metrics import ServingMetrics  # noqa: F401
from .paged import PagedKVPool, RadixPrefixIndex  # noqa: F401
from .resilience import (  # noqa: F401
    EngineSupervisor, FaultInjector, FaultPlan, FaultSpec,
    InjectedFault,
)
from .router import (  # noqa: F401
    CircuitBreaker, EngineGateway, HTTPTransport, InProcessTransport,
    RequestJournal, Router, RouterConfig, TransportError,
    TransportRefused,
)
from .sched import (  # noqa: F401
    ChunkPlan, FIFOPolicy, SchedulingPolicy, SLOFeedbackPolicy,
    SlotSampler, plan_chunks,
)
from .scheduler import Request, StepScheduler  # noqa: F401
from .spec import NGramDrafter, SpecDecoder  # noqa: F401
