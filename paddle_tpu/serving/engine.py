"""Continuous-batching inference engine.

One engine step = (dispatch of ONE pooled decode step) + (harvest of
the PREVIOUS step's dispatched results) + (admission + bucketed
tail prefill of newly admitted requests). All device work goes
through ahead-of-time compiled executables
(jax.jit(...).lower(...).compile()), so steady state is zero-recompile
BY CONSTRUCTION: an executable either exists in the table (cache hit,
no jit dispatch at all) or is built exactly once and counted in
``metrics.compiles`` — a shape drifting from its compiled signature is
a hard error at the call, never a silent recompile.

Two hot-path properties keep the device saturated between scheduler
ticks:

  * **donated KV buffers** — prefill/decode executables are built with
    the pooled kc/vc (and the position vector) donated, so on donating
    backends (TPU/GPU) the cache updates in place instead of
    double-buffering ~2x its footprint per call (CPU ignores donation;
    ``metrics.kv_donation`` reports both facts, and beside them what
    the compiled decode program really aliases and holds as
    temporaries against the pool's bytes);
  * **one-step-deep async decode pipelining** — step N's token values
    are read back only AFTER step N+1's decode has been dispatched
    (tokens and write positions chain device-side through the
    executables), so host bookkeeping overlaps device compute via JAX
    async dispatch. Retirement is therefore deferred one step and the
    speculative extra token a just-stopped request's in-flight step
    produced is masked at harvest — greedy parity with ``generate()``
    is exact. Max-token stops are PREDICTABLE at dispatch time, so
    those slots prerelease before the next decode goes out and pay no
    retirement lag at all; only EOS stops (unknowable until the token
    value is read) cost one masked speculative token.
    ``async_depth=0`` restores the fully synchronous schedule — on
    CPU's serial device queue it can win on churn-heavy tiny-model
    workloads (every step prefilling), while the pipeline pays off
    when decode dominates the step. ``async_depth=k`` keeps up to k
    steps' results unread: the device then holds k steps of queued
    work, so a host that is away for less than that (a collector's
    pause, a frozen machine) stalls nothing; a token surfaces k steps
    after its dispatch and an EOS stop masks k tokens. Speculative
    decoding drafts from harvested tokens and is refused beyond 1.

Compiled program inventory for a whole serving lifetime:
  * one decode step at the fixed pooled-cache shape,
  * at most ``len(buckets)`` prefill programs (prompt tails pad up to
    a small geometric bucket set; with chunked prefill enabled,
    ``prefill_chunk=``, a chunk IS a tail prefill at the chunk-width
    bucket, so chunking adds at most that one bucket),
  * one verify program when speculative, and
  * the two KV wire programs (export/import) once
    ``warmup_kv_handoff`` has warmed them,
so prompt-length AND queue-depth variety is O(buckets) compiles — the
generate() LRU problem this engine exists to delete.

Scheduling (serving.sched, all default-off): long prompts can prefill
in fixed-width chunks interleaved with decode steps under a per-step
token budget (no more one-4k-prefill-stalls-63-decoders), an
SLO-feedback admission policy can shed/defer queued requests whose
TTFT target is already unrecoverable (goodput under overload), and
per-slot sampling threads temperature/top-k/top-p through the one
compiled decode.
"""
import collections
import os
import time
import warnings
import weakref

import numpy as np

from ..analysis import threads as _lockpatrol
from ..observability import (CompileWatchdog, FlightRecorder,
                             abstract_signature, device_memory_stats,
                             executable_cost, executable_memory)
from ..observability.watchdog import note_program, programs_report
from .metrics import ServingMetrics
from .paged.pool import TRASH_BLOCK, PagedKVPool
from .scheduler import QUEUED, RUNNING, Request, StepScheduler

# published per-chip peak FLOP/s (bf16) by PJRT device_kind prefix —
# the denominator of the estimated-MFU gauge. ServingConfig(peak_flops=)
# or the PADDLE_TPU_PEAK_FLOPS env var cover kinds the table does not
# know; an unknown TPU kind is an error, and on the CPU the MFU gauge
# reads 0 (unknown, never a made-up number).
_PEAK_FLOPS_BY_KIND = (
    ("tpu v6", 918e12),
    ("tpu v5p", 459e12),
    ("tpu v5 lite", 197e12),
    ("tpu v5e", 197e12),
    ("tpu v4", 275e12),
    ("tpu v3", 123e12),
    ("tpu v2", 46e12),
)


def _weak_method(method, default):
    """Wrap a bound engine method as a weakly-referencing callable
    (``default()`` once the engine is gone). Pull callbacks handed to
    long-lived collaborators (metrics registry, health monitor) must
    not strongly reference the engine: every such back-edge turns a
    dead engine into cyclic garbage whose gen-2 collection pause lands
    inside some LIVE engine's timed step."""
    ref = weakref.WeakMethod(method)

    def call():
        m = ref()
        return default() if m is None else m()
    return call


def _peak_flops_for(device_kind):
    from ..observability.perf.roofline import peak_for
    return peak_for(device_kind, _PEAK_FLOPS_BY_KIND,
                    "PADDLE_TPU_PEAK_FLOPS")

# kc/vc/pos are donated into every serving executable; backends without
# donation support (CPU) warn once per compiled program — expected, not
# actionable (see ROADMAP "Cache-buffer donation").
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


def _kv_export_fn(kc, vc, idx):
    """The ``("kv_export",)`` program: one slot's blocks as tiles."""
    return kc[:, idx], vc[:, idx]


def _kv_import_fn(kc, vc, idx, ktiles, vtiles, toks, pos, slot,
                  first_tok, plen):
    """The ``("kv_import",)`` program: scatter received tiles into
    freshly bound blocks and splice the slot's token/position lanes."""
    # unused idx lanes point at the trash block — the scatter
    # scribbles garbage no reader sees, exactly the released-slot
    # stale-write discipline
    kc = kc.at[:, idx].set(ktiles)
    vc = vc.at[:, idx].set(vtiles)
    # toks/pos are RETURNED, not donated: a pending decode harvest
    # still reads the pre-import token array
    toks = toks.at[slot].set(first_tok)
    pos = pos.at[slot].set(plen)
    return toks, pos, kc, vc


def default_buckets(cache_len, bucket_min=32):
    """Geometric prefill bucket set: bucket_min, 2x, 4x, ... capped at
    cache_len (the per-slot capacity) which is always included so any
    admissible prompt has a bucket."""
    if bucket_min < 1:
        raise ValueError(f"bucket_min must be >= 1, got {bucket_min}")
    buckets = []
    b = int(bucket_min)
    while b < cache_len:
        buckets.append(b)
        b *= 2
    buckets.append(int(cache_len))
    return buckets


class ServingConfig:
    """Knobs (see package docstring): num_slots sizes the decode batch
    and the pooled cache; max_len is the per-slot capacity (default:
    the model's max_seq_len); buckets/bucket_min shape the prefill
    compile set; async_depth selects the
    decode pipeline depth (1 = read step N's tokens after dispatching
    step N+1, k = after dispatching step N+k, 0 = synchronous);
    eos_id is the default stop token."""

    def __init__(self, num_slots=8, max_len=None, buckets=None,
                 bucket_min=32, eos_id=None, async_depth=1,
                 donate_buffers=None,
                 watchdog_mode="flag", slo_ttft_ms=None,
                 slo_tpot_ms=None, slo_window_s=60.0,
                 completed_keep=4096, trace_keep=256,
                 trace_decode_window=32, peak_flops=None,
                 paged=None, block_size=16, num_blocks=None,
                 prefill_chunk=None, prefill_token_budget=None,
                 policy=None, sampling=False, health=None,
                 health_audit_every=64, health_ledger_keep=512,
                 health_detectors=None, incident_dir=None,
                 incident_keep=16, health_debounce_s=60.0,
                 chaos=None, max_dispatch_retries=0,
                 retry_backoff_s=0.0, quarantine_after=3,
                 supervisor=None, supervisor_max_restarts=8,
                 supervisor_cooldown_s=1.0, perf=None,
                 cache_observatory=None, cache_sample_rate=0.125,
                 replica_id=None, speculative=None, spec_k=4,
                 spec_min_accept=0.35, role="monolithic",
                 trace_spans=None, trace_span_keep=4096,
                 max_tenants=32):
        self.num_slots = int(num_slots)
        self.max_len = max_len
        self.buckets = buckets
        self.bucket_min = int(bucket_min)
        self.eos_id = eos_id
        self.async_depth = int(async_depth)
        if self.async_depth < 0:
            raise ValueError(
                f"async_depth must be 0 (synchronous) or the number "
                f"of steps kept in flight (1 = one-step-deep "
                f"pipeline), got {async_depth}")
        # None = auto: donate kc/vc/pos where the backend aliases
        # donated buffers (TPU/GPU). On CPU donation never aliases but
        # JAX still enforces the input invalidation AND charges ~40us
        # of buffer bookkeeping per dispatch — pure loss, so auto
        # turns it off there. Force True to exercise the donation
        # discipline (rebind correctness) on any backend.
        self.donate_buffers = donate_buffers
        # compile-watchdog behavior once declare_warmup() has been
        # called: "flag" records steady-state compiles in the report,
        # "raise" hard-fails at the offending compile (tests/canaries)
        self.watchdog_mode = watchdog_mode
        # SLO targets (ms): time-to-first-token and time-per-output-
        # token. None = no target (every request trivially attains;
        # the sliding windows still run). slo_window_s sets the
        # sliding-percentile window the /metrics gauges report over.
        self.slo_ttft_ms = slo_ttft_ms
        self.slo_tpot_ms = slo_tpot_ms
        self.slo_window_s = float(slo_window_s)
        # retention bounds for a serve-forever process: completed
        # Request objects kept by the scheduler, completed
        # RequestTrace records kept by the flight recorder, and the
        # token granularity of mid-decode trace progress events
        self.completed_keep = completed_keep
        self.trace_keep = int(trace_keep)
        self.trace_decode_window = int(trace_decode_window)
        # device peak FLOP/s override for the estimated-MFU gauge
        # (default: a device_kind table, then $PADDLE_TPU_PEAK_FLOPS)
        self.peak_flops = peak_flops
        # the paged KV pool + radix prefix cache (serving.paged) is the
        # engine's only cache. `paged` is still accepted because the
        # benchmark's configuration files pass `"paged": true` (ROADMAP
        # D2b): None and True mean the same thing and nothing reads it.
        if paged is not None and not paged:
            raise ValueError(
                "paged=False: the slot-contiguous KV pool was removed "
                "(PR 30); the paged pool is the engine's only cache. "
                "Drop the argument.")
        # block_size is the paging granularity (prefix sharing happens
        # at block multiples); num_blocks sizes the physical pool
        # (default: every slot fully backed + the trash block —
        # sharing stretches the same bytes further).
        self.block_size = int(block_size)
        self.num_blocks = num_blocks
        # chunked prefill (serving.sched): prompts longer than
        # prefill_chunk split into fixed-width chunks interleaved with
        # decode steps under prefill_token_budget chunk tokens per
        # step (default: one chunk per step), so a long prompt never
        # monopolizes the step loop. None = off (whole-prompt prefill,
        # prior behavior); the PADDLE_PREFILL_CHUNK env var sets a
        # default width.
        if prefill_chunk is None:
            env = os.environ.get("PADDLE_PREFILL_CHUNK")
            if env:
                prefill_chunk = int(env)
        self.prefill_chunk = None if prefill_chunk is None \
            else int(prefill_chunk)
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if prefill_token_budget is not None:
            if self.prefill_chunk is None:
                raise ValueError(
                    "prefill_token_budget requires chunked prefill "
                    "(set prefill_chunk); without chunking the budget "
                    "would silently never apply")
            prefill_token_budget = int(prefill_token_budget)
            if prefill_token_budget < self.prefill_chunk:
                raise ValueError(
                    f"prefill_token_budget {prefill_token_budget} "
                    f"cannot be smaller than prefill_chunk "
                    f"{self.prefill_chunk} (no chunk could ever "
                    f"dispatch)")
        else:
            prefill_token_budget = self.prefill_chunk
        self.prefill_token_budget = prefill_token_budget
        # admission policy: "fifo" (default) | "slo_feedback" | a
        # serving.sched.SchedulingPolicy instance; the env var mirrors
        # the other ops gates
        if policy is None:
            policy = os.environ.get("PADDLE_SCHED_POLICY") or None
        self.policy = policy
        # per-slot sampling threaded through the compiled decode/
        # prefill programs; greedy stays the default (and the only
        # mode whose signatures match prior PRs bit-for-bit)
        self.sampling = bool(sampling)
        # health observatory (observability.health): per-step ledger +
        # online anomaly detectors, ON by default (continuous
        # self-monitoring is the point; PADDLE_HEALTH=0 opts out).
        # Incident-bundle capture engages only when incident_dir is
        # set (or $PADDLE_INCIDENT_DIR) — detectors/counters/debug
        # endpoints run either way, disk writes are opt-in.
        if health is None:
            health = os.environ.get("PADDLE_HEALTH", "1") != "0"
        self.health = bool(health)
        self.health_audit_every = int(health_audit_every)
        if self.health_audit_every < 1:
            raise ValueError(
                f"health_audit_every must be >= 1, got "
                f"{health_audit_every}")
        self.health_ledger_keep = int(health_ledger_keep)
        # per-detector threshold overrides, e.g.
        # {"queue_stall": {"stall_steps": 8}} (tests tighten this way)
        self.health_detectors = health_detectors
        if incident_dir is None:
            incident_dir = os.environ.get("PADDLE_INCIDENT_DIR") or None
        self.incident_dir = incident_dir
        self.incident_keep = int(incident_keep)
        self.health_debounce_s = float(health_debounce_s)
        # resilience (serving.resilience): chaos arms the seeded
        # fault-injection harness (None = the PADDLE_CHAOS env gate,
        # default off); max_dispatch_retries bounds how many times a
        # failed dispatch is rolled back and retried before the
        # request retires with reason "error" (0 = prior behavior:
        # the exception propagates); retry_backoff_s is the base of
        # the exponential admission backoff between retries;
        # quarantine_after excludes a slot from admission after that
        # many same-slot dispatch failures; supervisor=None enables
        # the self-healing supervisor whenever the health observatory
        # is on (True/False forces).
        self.chaos = chaos
        self.max_dispatch_retries = int(max_dispatch_retries)
        if self.max_dispatch_retries < 0:
            raise ValueError(
                f"max_dispatch_retries must be >= 0, got "
                f"{max_dispatch_retries}")
        self.retry_backoff_s = float(retry_backoff_s)
        self.quarantine_after = int(quarantine_after)
        if self.quarantine_after < 1:
            raise ValueError(
                f"quarantine_after must be >= 1, got {quarantine_after}")
        self.supervisor = supervisor
        self.supervisor_max_restarts = int(supervisor_max_restarts)
        self.supervisor_cooldown_s = float(supervisor_cooldown_s)
        # performance observatory (observability.perf): per-program
        # dispatch/sync attribution + roofline fractions, ON by
        # default (two perf_counter reads and one histogram observe
        # per dispatch); PADDLE_PERF=0 opts out, True/False forces.
        if perf is None:
            perf = os.environ.get("PADDLE_PERF", "1") != "0"
        self.perf = bool(perf)
        # cache observatory (observability.cache): reuse-distance/MRC
        # sampling, prefix heat, savings attribution and churn
        # telemetry over the paged pool, ON by default (a few dict/int
        # ops per admission); PADDLE_CACHE_OBS=0 opts out, True/False
        # forces.
        if cache_observatory is None:
            cache_observatory = os.environ.get(
                "PADDLE_CACHE_OBS", "1") != "0"
        self.cache_observatory = bool(cache_observatory)
        self.cache_sample_rate = float(cache_sample_rate)
        # replica identity (observability.fleet): the id a fleet view
        # knows this engine by — stamped into snapshot()/debug routes/
        # incident bundles and the paddle_tpu_build_info exposition.
        # None = $PADDLE_REPLICA_ID (the k8s/pod-name case), else a
        # stable host:pid-derived id at engine construction.
        if replica_id is None:
            replica_id = os.environ.get("PADDLE_REPLICA_ID") or None
        self.replica_id = replica_id
        # self-drafting speculative decoding (serving.spec): None =
        # the PADDLE_SPEC_DECODE env gate (default off — plain
        # one-token decode stays the measured fallback). spec_k is
        # the draft width: the verify program runs [slots, spec_k + 1] positions per dispatch and
        # emits 1..spec_k+1 tokens. spec_min_accept is the per-request
        # EWMA acceptance floor below which a request falls back to
        # plain decode (its slot stops drafting). Greedy-only: the
        # acceptance rule compares drafts against argmax, which is
        # exact for greedy but would bias sampled streams, so
        # speculation x sampling is rejected outright.
        if speculative is None:
            speculative = os.environ.get("PADDLE_SPEC_DECODE", "0") == "1"
        self.speculative = bool(speculative)
        self.spec_k = int(spec_k)
        self.spec_min_accept = float(spec_min_accept)
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if not 0.0 <= self.spec_min_accept <= 1.0:
            raise ValueError(
                f"spec_min_accept must be in [0, 1], got "
                f"{spec_min_accept}")
        if self.speculative and self.sampling:
            raise ValueError(
                "speculative decoding is greedy-only (draft acceptance "
                "compares against argmax); drop sampling=True or "
                "speculative=True")
        if self.speculative and self.async_depth > 1:
            raise ValueError(
                "speculative decoding drafts from each request's last "
                "HARVESTED token, so it keeps at most one step in "
                f"flight; drop speculative=True or async_depth="
                f"{self.async_depth}")
        # replica role in a disaggregated fleet (None = env override):
        # "monolithic" (default) serves prefill+decode like every
        # prior PR; "prefill" replicas compute KV for admitted
        # requests and export it over the wire (serving.kv_wire);
        # "decode" replicas import streamed KV and own the decode
        # span. The role is ROUTING POSTURE, not capability — every
        # role keeps the full engine (failover replays a dead prefill
        # tier's work on whoever survives).
        if role is None:
            role = os.environ.get("PADDLE_SERVING_ROLE") \
                or "monolithic"
        role = str(role)
        if role not in ("prefill", "decode", "monolithic"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'monolithic', "
                f"got {role!r}")
        self.role = role
        # distributed request tracing (observability.trace): per-hop
        # wall-anchored spans into a bounded ring served at
        # /debug/traces, ON by default (a handful of dict appends per
        # request lifetime); PADDLE_TRACE_SPANS=0 opts out,
        # True/False forces. The disabled recorder keeps its full surface (scrapes answer,
        # snapshot shape identical). trace_span_keep bounds the ring.
        if trace_spans is None:
            trace_spans = os.environ.get(
                "PADDLE_TRACE_SPANS", "1") != "0"
        self.trace_spans = bool(trace_spans)
        self.trace_span_keep = int(trace_span_keep)
        if self.trace_span_keep < 1:
            raise ValueError(
                f"trace_span_keep must be >= 1, got {trace_span_keep}")
        # tenant observatory (observability.tenant): per-tenant
        # attribution ledger cardinality bound — at most max_tenants
        # live tenant ids per engine, every further unique id folds
        # into "~other" with an overflow counter. 0 disables the
        # ledger entirely (snapshot()["tenants"] keeps its shape).
        self.max_tenants = int(max_tenants)
        if self.max_tenants < 0:
            raise ValueError(
                f"max_tenants must be >= 0, got {max_tenants}")


class ServingEngine:
    """Continuous-batching engine over a GPTForCausalLM.

    Weights are snapshotted at construction (export_decode_params);
    greedy decoding only — sampling is a ROADMAP open item. Typical
    use::

        eng = ServingEngine(model, num_slots=8)
        reqs = [eng.add_request(p, max_new_tokens=64) for p in prompts]
        eng.run()                 # or eng.step() in a service loop
        reqs[0].output_ids        # prompt + generated, as generate()
    """

    def __init__(self, model, config=None, **kwargs):
        if config is None:
            config = ServingConfig(**kwargs)
        elif kwargs:
            raise TypeError("pass either config= or knob kwargs, not both")
        self.config = config
        cfg = model.cfg
        cache_len = int(config.max_len or cfg.max_seq_len)
        if cache_len > cfg.max_seq_len:
            raise ValueError(
                f"max_len {cache_len} exceeds the model's position "
                f"table max_seq_len {cfg.max_seq_len}")
        self.cache_len = cache_len
        self.params = model.export_decode_params()
        self.sampling = bool(config.sampling)
        # a model that has programs for only some of the engine's
        # options refuses the others here, by name
        check = getattr(model, "check_serving_config", None)
        if check is not None:
            check(config)
        # the pool holds what the model's CACHE SPEC says a token owns
        # (a GPT: K and V as the qkv projection computes them, in the
        # weights' dtype, so a bf16 model gets a bf16 pool; latent
        # attention: one latent and one rotary key, no head axis). A
        # model without the method is a GPT.
        if hasattr(model, "cache_spec"):
            self.cache_spec = model.cache_spec()
        else:
            from .paged.cache_spec import kv_pair_spec
            self.cache_spec = kv_pair_spec(
                cfg.num_layers, cfg.num_heads,
                cfg.hidden_size // cfg.num_heads,
                self.params["stacked"]["qkv_w"].dtype)
        kv_dtype = self.cache_spec.arrays[0].dtype
        # entries that are not positions (CacheSpec.window): a prompt
        # is prefilled a WINDOW at a time (a run that fills its window
        # compacts it), so the chunk is the window and no bucket is
        # wider; the step loop keeps every live slot's position (from
        # its own counts, never read back) to hand out and take back
        # blocks and to dispatch a finished window's compaction
        self._window = self.cache_spec.window
        # rings beside the blocks (CacheSpec.ring): the same count of
        # positions feeds the gauges of what the rings save
        self._ring = self.cache_spec.ring
        self._counts_positions = self._window is not None \
            or self._ring is not None
        self._hpos = {}   # slot -> the position its next step writes
        widest = cache_len
        self.chunk_len = config.prefill_chunk
        self.prefill_token_budget = config.prefill_token_budget
        if self._window is not None:
            W = self._window[0]
            if self.chunk_len not in (None, W):
                raise ValueError(
                    f"prefill_chunk {self.chunk_len}: this model's "
                    f"cache is compacted a window of {W} positions at "
                    f"a time and its prompts are prefilled by windows")
            widest = min(W, cache_len)
            self.chunk_len = W
            self.prefill_token_budget = config.prefill_token_budget or W
        buckets = config.buckets or default_buckets(widest,
                                                    config.bucket_min)
        if max(buckets) > widest:
            raise ValueError(
                "prefill buckets cannot exceed max_len" if widest
                == cache_len else f"prefill buckets cannot exceed the "
                f"cache window ({widest} positions)")
        if self.chunk_len is not None and self.chunk_len > cache_len \
                and self._window is None:
            raise ValueError(
                f"prefill_chunk {self.chunk_len} exceeds the per-slot "
                f"capacity {cache_len}")
        # the GPT's (k, v) pair, one entry a position, nothing carried
        # beside it: what kv_wire, the speculative verify programs and
        # the analytic decode model are written for (a looped model
        # keeps a pair a PASS of every layer, and its counters)
        self._kv_pair = [a.name for a in self.cache_spec.arrays] \
            == ["k", "v"] and self._window is None \
            and not self.cache_spec.state
        self.pool = self._new_pool()
        # the decode-attention path, resolved ONCE at build time
        # from what is observable and nothing else: a (k, v) pool
        # whose shapes, dtype and backend the Pallas kernel takes
        # (ops.paged_attention.kernel_viable) gets the kernel, the
        # rest (the CPU, untileable shapes) the XLA gather. A
        # trace-time branch inside the one compiled decode program,
        # so signatures, AOT keys and the zero-steady-state-compile
        # contract are the same on either path. A model with
        # another cache (latent attention) brings its own programs
        # and kernels and is never handed this choice.
        sizes = (config.num_slots, self.pool.block_size,
                 self.pool.num_blocks, self.pool.blocks_per_slot)
        attn_kernel = False
        if self._kv_pair:
            from ..ops.paged_attention import kernel_viable
            attn_kernel = bool(kernel_viable(
                cfg.num_heads, cfg.hidden_size // cfg.num_heads,
                self.pool.block_size, kv_dtype))
            self._prefill_fn, self._decode_fn = \
                model.build_paged_serving_fns(
                    *sizes, sampling=self.sampling,
                    attn_kernel=attn_kernel)
        else:
            self._prefill_fn, self._decode_fn = \
                model.build_paged_serving_fns(
                    *sizes, sampling=self.sampling)
        self._compact_fn = model.build_paged_compact_fn(*sizes) \
            if self._window is not None else None
        # the attention path the decode program actually runs — what
        # the roofline prices (observability.perf.roofline.LAYOUTS);
        # ``paged_attn`` reads it
        self.decode_layout = "paged_pallas" if attn_kernel \
            else "paged_xla"
        # disaggregated-serving role + KV wire programs (serving.
        # kv_wire): export gathers one slot's prompt blocks into
        # [layers, blocks_per_slot, ...] tiles (a bounded per-slot
        # read, NEVER a full-pool device_get), import scatters
        # received tiles into freshly bound blocks and splices the
        # slot's token/position lanes — both fixed-shape, so each
        # compiles exactly once (warmup_kv_handoff) and the steady
        # state stays zero-recompile across any number of handoffs.
        self.role = config.role
        self._held_exports = {}   # rid -> retired Request holding KV
        # speculative decoding (serving.spec): ONE extra verify program
        # + the host-side drafter/acceptance gate. The
        # plain decode program stays built either way — it is the
        # per-step fallback whenever no slot drafts, so BOTH programs
        # warm at the first decode-capable dispatch (zero steady-state
        # compiles regardless of which one a later step needs).
        self.speculative = bool(config.speculative)
        self.spec_k = int(config.spec_k)
        if self.speculative:
            if self.spec_k + 1 > cache_len:
                raise ValueError(
                    f"spec_k + 1 ({self.spec_k + 1}) exceeds the "
                    f"per-slot cache capacity {cache_len}")
            from .spec import SpecDecoder
            self._verify_fn = model.build_paged_spec_verify_fn(
                config.num_slots, self.pool.block_size,
                self.pool.num_blocks, self.pool.blocks_per_slot,
                self.spec_k)
            self._verify_key = ("paged_spec_verify",)
            self._spec = SpecDecoder(config.num_slots, self.spec_k,
                                     config.spec_min_accept)
        else:
            self._verify_fn = None
            self._verify_key = None
            self._spec = None
        from .sched import ChunkPlan, SlotSampler, resolve_policy
        self._ChunkPlan = ChunkPlan
        self._sampler = SlotSampler(config.num_slots) \
            if self.sampling else None
        self._chunk_q = []        # ChunkPlans awaiting chunk dispatch
        self._prefilling = set()  # slots parked mid-chunked-prefill
        self._policy = resolve_policy(config.policy,
                                      config.slo_ttft_ms)
        self.flight = FlightRecorder(
            keep_last=config.trace_keep,
            decode_window=config.trace_decode_window)
        self.scheduler = StepScheduler(
            buckets, cache_len, completed_keep=config.completed_keep,
            flight=self.flight, policy=self._policy,
            # a prompt beyond the chunk is prefilled by chunks of the
            # chunk's bucket and needs no bucket of its own
            chunked_beyond=self.chunk_len)
        self.metrics = ServingMetrics(
            slo_ttft_ms=config.slo_ttft_ms,
            slo_tpot_ms=config.slo_tpot_ms,
            slo_window_s=config.slo_window_s,
            perf=config.perf,
            cache=config.cache_observatory,
            cache_sample_rate=config.cache_sample_rate,
            max_tenants=config.max_tenants)
        self._perf_on = config.perf
        self.metrics.set_spec(self.speculative, self.spec_k)

        # scrape-time per-tenant queue depth: a read-only walk of the
        # live admission queue (no accrual — reports only)
        def _tenant_queue_depths(sch=self.scheduler):
            depths = {}
            for r in sch.queue:
                t = getattr(r, "tenant_id", None) or "default"
                depths[t] = depths.get(t, 0) + 1
            return depths
        self.metrics.tenants.set_queue_probe(_tenant_queue_depths)
        # replica identity: who this engine is in a fleet of
        # lookalikes — uptime + build-info gauges in the exposition,
        # and a "replica" section on snapshot()/debug/state/incidents
        import jax as _jax
        from ..observability.fleet import ReplicaIdentity
        from ..version import full_version as _pt_version
        self.identity = ReplicaIdentity(config.replica_id)
        self.replica_id = self.identity.replica_id
        self.metrics.set_identity(self.identity, version=_pt_version,
                                  jax_version=_jax.__version__)
        # distributed tracing: this replica's per-hop span ring
        # (observability.trace), keyed by the TraceContext each
        # request carries — served at /debug/traces, summarized in
        # snapshot()["trace"], embedded in incident bundles
        from ..observability.trace import TraceContext, TraceRecorder
        self._TraceContext = TraceContext
        self.trace = TraceRecorder(self.replica_id,
                                   capacity=config.trace_span_keep,
                                   enabled=config.trace_spans)
        self.metrics.set_trace(self.trace.snapshot)
        self.metrics.set_scheduler_info(
            self._policy.name, self.chunk_len,
            self.prefill_token_budget)
        self.watchdog = CompileWatchdog(mode=config.watchdog_mode)
        self._exec = {}  # (kind, bucket?) -> XLA executable
        self._t_last_compile = float("-inf")  # SLO-feedback taint mark
        self._metric_servers = []
        # resilience: chaos harness + retry/quarantine/drain state
        # (the supervisor attaches after the health observatory below)
        from .resilience import resolve_chaos
        self.chaos = resolve_chaos(config.chaos)
        if self.chaos is not None:
            from ..observability import default_recorder as _rec
            self.chaos.bind(on_fire=self.metrics.record_fault,
                            recorder=_rec())
        self.max_dispatch_retries = config.max_dispatch_retries
        self.retry_backoff_s = config.retry_backoff_s
        self._retry_at = 0.0        # admission backoff gate
        self._decode_fail_streak = 0
        self._slot_failures = {}    # slot -> consecutive failures
        self._draining = False
        self._closed = False
        self._deadlines_armed = False
        self._restart_epoch = 0     # bumped by supervisor restarts
        self.metrics.set_resilience(_weak_method(
            self._resilience_state,
            lambda: {"quarantined_slots": [], "draining": False,
                     "supervisor": {"enabled": False},
                     "chaos": {"enabled": False}}))
        # health observatory: per-step ledger + anomaly detectors +
        # (when an incident_dir is configured) black-box bundle capture
        self._step_id = 0
        self._hprev = None      # previous step's cumulative counters
        self._hspan_kids = None  # cached span children (tick fast path)
        self._slo_on = (config.slo_ttft_ms is not None
                        or config.slo_tpot_ms is not None)
        if config.health:
            from ..observability import default_recorder
            from ..observability.health import (HealthMonitor,
                                                IncidentRecorder)
            incidents = None
            if config.incident_dir:
                incidents = IncidentRecorder(
                    config.incident_dir,
                    keep_last=config.incident_keep,
                    debounce_s=config.health_debounce_s)
            rec = default_recorder()

            def _spans_tail(rec=rec):
                return [{"name": s.name, "t0": round(s.t0, 6),
                         "dur": round(s.dur, 6), "tid": s.tid}
                        for s in rec.spans()[-120:]]

            def _incident_traces(trace=self.trace,
                                 flight=self.flight):
                # assembled traces of requests ACTIVE at incident
                # time: the cross-replica spans this replica holds
                # for them (a fleet collector joins the rest by
                # trace_id)
                from ..observability.trace import TraceAssembler
                tids = sorted({t.trace_id for t in flight.active()
                               if t.trace_id is not None})
                asm = TraceAssembler()
                asm.add_recorder(trace)
                out = []
                for tid in tids:
                    at = asm.assemble(tid)
                    if at is not None:
                        out.append(at.as_dict())
                return out

            context = {
                "metrics": self.metrics.snapshot,
                "watchdog": self.watchdog.report,
                "requests": self.flight.debug_requests,
                "spans_tail": _spans_tail,
                "traces": _incident_traces,
                # replica attribution: a bundle collected off one
                # member of a fleet must name which member wrote it
                "replica": self.metrics.identity_report,
                # who was on the box when it went down: top tenants
                # by token share (the noisy-neighbor suspect list)
                "tenants": self.metrics.tenants.top,
            }
            if self.chaos is not None:
                # a chaos-found incident must be replayable from its
                # bundle alone: embed the plan (seed) + fault history
                context["chaos"] = self.chaos.report
            self.health = HealthMonitor(
                self.metrics.registry,
                ledger_keep=config.health_ledger_keep,
                detector_config=config.health_detectors,
                incidents=incidents,
                context=context)
            self.health.attach_resilience(_weak_method(
                self._health_resilience,
                lambda: {"degraded": False, "draining": False,
                         "restarts": 0}))
            self.health.attach_identity(self.metrics.identity_report)
            self.metrics.set_health(self.health.summary)
        else:
            self.health = None
        # self-healing supervisor: default ON alongside the health
        # observatory (its restart triggers are the observatory's
        # wedge verdicts); explicit True works without it too (the
        # dispatch-failure escalation path needs no detectors)
        sup_on = config.supervisor if config.supervisor is not None \
            else (self.health is not None)
        if sup_on:
            from .resilience import EngineSupervisor
            self.supervisor = EngineSupervisor(
                self, max_restarts=config.supervisor_max_restarts,
                cooldown_s=config.supervisor_cooldown_s)
        else:
            self.supervisor = None

        import jax
        import jax.numpy as jnp
        # rolling device state: last token and next write position per
        # slot. Prefill/decode scatter their results in, so step N+1's
        # inputs never depend on step N's values reaching the host.
        self._toks = jnp.zeros((config.num_slots,), jnp.int32)
        self._pos = jnp.zeros((config.num_slots,), jnp.int32)
        # what the decode program carries beside the cache (the cache
        # spec's ``state``: a GPT has none): returned new each step and
        # never donated, so whoever reads it holds a live array
        self._state = tuple(jnp.zeros(shape, dt) for _, shape, dt
                            in self.cache_spec.state)
        # dispatched, not-yet-read device results, oldest first, and
        # how many of them each step still in flight dispatched
        self._pending = []
        self._pending_steps = collections.deque()
        # first callback's start / summed callback seconds of the
        # harvest in progress (its serving/on_token span)
        self._on_token_t0, self._on_token_s = None, 0.0
        effective = jax.devices()[0].platform != "cpu"
        self._donate = (effective if config.donate_buffers is None
                        else bool(config.donate_buffers))
        self.metrics.kv_donation = {
            "enabled": self._donate,
            # in-place aliasing actually happens (donation is enforced
            # but never aliases on CPU)
            "effective": self._donate and effective,
        }
        # device cost telemetry: peak FLOP/s for the MFU estimate, and
        # HBM pull gauges where the backend reports memory_stats (CPU
        # doesn't — the gauges simply aren't registered there)
        dev = jax.devices()[0]
        self._device = dev
        peak = config.peak_flops or _peak_flops_for(dev.device_kind)
        self.metrics.set_peak_flops(peak)
        if device_memory_stats(dev) is not None:
            self.metrics.enable_device_memory(
                lambda: device_memory_stats(dev))
        self.metrics.set_prefix_pool(self.pool.stats)
        self.metrics.cache.attach_pool(self.pool)
        self.metrics.set_kv_bytes_per_token(
            self.cache_spec.bytes_per_token)
        self.metrics.set_state_bytes_per_slot(
            self.cache_spec.bytes_per_slot)
        if self._window is not None:
            self.metrics.enable_entry_cache()
        if self._ring is not None:
            self.metrics.enable_ring_cache(
                self.cache_spec.bytes_per_token,
                self.cache_spec.bytes_per_slot,
                self.cache_spec.dense_bytes_per_token)
        moe = getattr(model, "moe_counter_layout", None)
        if moe is not None:
            self.metrics.set_moe_counters(
                lambda: np.asarray(self._state[0]), **moe())
        loop = getattr(model, "loop_counter_layout", None)
        if loop is not None:
            self.metrics.set_loop_counters(
                lambda: tuple(np.asarray(a) for a in self._state),
                **loop())
        if self._perf_on and self._kv_pair:
            # price the per-program roofline (the CPU has no peaks:
            # device_peak/device_hbm=false and None fractions in the
            # report; an unknown TPU kind raised above) and attach
            # the analytic decode-step HBM model: the fixed-shape
            # pooled decode reads the WHOLE cache_len layout every
            # step, so kv_len is the per-slot capacity, not the live
            # lengths — exactly the over-read the model prices
            from ..observability import hbm_bps_for
            from ..observability.perf import build_decode_model
            P = self.metrics.perf
            P.set_device(dev.platform, dev.device_kind,
                         peak_flops=peak,
                         hbm_bps=hbm_bps_for(dev.device_kind))
            leaves = jax.tree_util.tree_leaves(self.params)
            n_params = sum(int(np.prod(l.shape)) for l in leaves)
            P.set_decode_model(build_decode_model(
                batch=config.num_slots, kv_len=cache_len,
                num_layers=cfg.num_layers, num_heads=cfg.num_heads,
                head_dim=cfg.hidden_size // cfg.num_heads,
                n_params=n_params,
                param_bytes=leaves[0].dtype.itemsize if leaves else 4,
                kv_bytes=self.pool.kc.dtype.itemsize,
                layout=self.decode_layout,
                peak_flops=P.peak_flops,
                hbm_bps=P.hbm_bps))

    # ---------------------------------------------------------- requests

    def add_request(self, prompt, max_new_tokens, eos_id=None,
                    on_token=None, temperature=0.0, top_k=0,
                    top_p=1.0, seed=None, deadline_ms=None,
                    hold_kv=False, trace=None, tenant_id=None,
                    t_received=None):
        """Enqueue a prompt; returns the Request handle immediately.
        Tokens stream through on_token(request, token) as steps run
        (with async_depth=1 a token surfaces one engine step after the
        decode that produced it was dispatched).

        ``temperature`` / ``top_k`` / ``top_p`` / ``seed`` select
        per-slot sampling for THIS request (the engine must be built
        with ``sampling=True`` — greedy engines reject sampled
        requests rather than silently argmaxing them); the defaults
        are greedy, matching ``generate(temperature=0.0)`` exactly.

        ``deadline_ms`` bounds the request end to end: past
        ``t_arrival + deadline_ms`` the engine retires it (queued or
        mid-decode) with stop reason "deadline", counted in
        ``serving_requests_timed_out_total`` and SLO-judged as a
        violation. None (default) = no deadline.

        ``hold_kv=True`` parks the request's slot —
        blocks still live — when it retires instead of releasing it,
        so ``export_kv(rid)`` can serialize the prompt's KV blocks
        for a disaggregated handoff; the export (or abort/close)
        releases the slot. The prefill tier submits its work this way
        with ``max_new_tokens=1``.

        ``trace`` is the propagated distributed-trace context
        (TraceContext, traceparent string, or its dict form from the
        gateway wire). Whatever arrives is COERCED — None on a direct
        add_request, or malformed input from a corrupted header,
        mints a locally-rooted context rather than raising — so every
        request carries a usable trace id.

        ``tenant_id`` attributes the request in the tenant observatory
        (tokens, SLO verdict, queue wait, cache savings — see
        observability.tenant). None falls back to the ``"tenant"``
        trace-baggage entry (the router stamps it at admission, so a
        decode-tier import or failover replay keeps the original
        tenant), then to ``"default"``. The resolved id is written
        back into the baggage so every downstream hop inherits it.

        ``t_received`` (perf_counter) is when the caller's side took
        the request over, where that was before this call: the gateway
        stamps it before it waits for its lock. TTFT, latency and the
        SLO verdicts count from it; None = now."""
        if self._draining or self._closed:
            raise RuntimeError(
                "engine is draining/closed: no new requests (drain() "
                "finishes already-submitted work, close() aborts it)")
        if hold_kv:
            self._require_kv_pair("hold_kv")
        ctx = self._TraceContext.coerce(trace)
        if tenant_id is None:
            tenant_id = ctx.baggage.get("tenant")
        req = Request(prompt, max_new_tokens,
                      eos_id=self.config.eos_id if eos_id is None
                      else eos_id,
                      on_token=on_token, temperature=temperature,
                      top_k=top_k, top_p=top_p, seed=seed,
                      deadline_ms=deadline_ms, hold_kv=hold_kv,
                      tenant_id=tenant_id, t_received=t_received)
        if ctx.baggage.get("tenant") != req.tenant_id:
            # write the resolved tenant back into the baggage (same
            # trace/span ids — this is annotation, not a new hop) so
            # export_kv()/failover journals carry it downstream
            ctx = self._TraceContext(
                ctx.trace_id, ctx.span_id,
                baggage={**ctx.baggage, "tenant": req.tenant_id},
                minted_local=ctx.minted_local)
        req.trace = ctx
        if req.sampled and not self.sampling:
            raise ValueError(
                "sampled request on a greedy engine: build the engine "
                "with ServingConfig(sampling=True) to serve "
                "temperature/top-k/top-p traffic")
        if req.deadline_ms is not None:
            self._deadlines_armed = True
        return self.scheduler.submit(req)

    @property
    def pending(self):
        return self.scheduler.pending or bool(self._pending)

    # ------------------------------------------------------- compilation

    @property
    def paged_attn(self):
        """Whether the decode program's attention is the Pallas paged
        kernel (``decode_layout`` says which path by name)."""
        return self.decode_layout == "paged_pallas"

    def _new_pool(self):
        """A fresh pool of this engine's sizes (construction, and the
        supervisor's restart)."""
        config = self.config
        return PagedKVPool(
            config.num_slots, max_len=self.cache_len,
            block_size=config.block_size,
            num_blocks=config.num_blocks, spec=self.cache_spec)

    def _compiled(self, key, fn, args, donate=()):
        """AOT compile-once table. The ONLY place executables are
        built; metrics.compiles is therefore an exact compile counter
        for the whole engine, and every build is logged in the compile
        watchdog with its abstract-shape signature and the dispatch
        call-site that triggered it (skip=1 walks past this helper) —
        after declare_warmup() a build here is a flagged/raised
        steady-state violation. ``donate`` argnums are recorded in the
        lowered program (in-place cache updates on TPU/GPU)."""
        if self.chaos is not None and key in self._exec \
                and self.chaos.fires("compile_storm", key=str(key)):
            # compile storm: the cached executable evaporates and the
            # very next dispatch pays a rebuild — watchdog-attributed,
            # a steady-state violation when warmed (by design: this
            # fault exists to prove the alarm fires)
            del self._exec[key]
        ex = self._exec.get(key)
        if ex is None:
            import jax
            event = self.watchdog.record(key, abstract_signature(args),
                                         skip=1)
            if not self._donate:
                donate = ()
            with self.metrics.span("serving/compile"):
                ex = jax.jit(fn, donate_argnums=donate) \
                    .lower(*args).compile()
            self._exec[key] = ex
            self.metrics.compiles += 1
            # compile-taint watermark for the SLO-feedback loop: any
            # first token whose admission predates this stamp paid
            # compile time and is excluded from the service EWMA (a
            # seconds-scale compile fed into a milliseconds-scale
            # estimate would shed every fresh arrival on sight)
            self._t_last_compile = time.perf_counter()
            # device cost telemetry rides on the compile record:
            # flops/bytes from cost_analysis plus the memory picture
            # at build time (both best-effort None on non-reporting
            # backends — CPU has no memory_stats)
            cost = executable_cost(ex)
            # what the program's instructions belong to (op_name: the
            # device_scope names), for the process-wide table the
            # readers of a trace join op times to: the text alone, the
            # parse waits for the first request, and the table never
            # holds the executable
            note_program(key, text=ex.as_text(),
                         signature=event["signature"],
                         owner=self.watchdog.id)
            self.watchdog.annotate(
                event["seq"], cost=cost,
                memory=device_memory_stats(self._device))
            if key == ("decode",):
                if cost:
                    self.metrics.set_decode_cost(
                        cost.get("flops"), cost.get("bytes_accessed"))
                mem = executable_memory(ex)
                if mem:
                    # is the KV pool held once or twice while decode
                    # runs: aliased >= pool and temporaries well under
                    # it mean the donated pool is updated in place
                    self.metrics.kv_donation.update(
                        decode_alias_bytes=mem["alias_bytes"],
                        decode_temp_bytes=mem["temp_bytes"],
                        pool_bytes=int(self.pool.nbytes()))
            if cost:
                # the same cost_analysis prices this program's
                # roofline floor in snapshot()["perf"] (no-op with
                # perf off)
                self.metrics.perf.bind_cost(key, cost)
        return ex

    def programs_report(self):
        """``GET /debug/programs``: what each program this engine built
        is made of (``observability.watchdog.programs_report`` joined
        to this engine's compile records). Seconds-free; device time by
        scope needs a capture (``benchmarks/tools/scope_report.py``)."""
        return programs_report(self.watchdog.events(),
                               owner=self.watchdog.id)

    def _timed_call(self, key, ex, args):
        """Dispatch one compiled executable, attributing its measured
        wall seconds to its program key (the perf observatory's
        dispatch leg; harvest attributes the sync leg). With perf off
        this is a bare call — no clock reads."""
        if _lockpatrol._armed:
            # Any patrolled lock held here is the PR-9 pause class: a
            # dispatch stall propagates to every waiter on that lock.
            _lockpatrol.note_blocking("aot_dispatch", str(key))
        if not self._perf_on:
            return ex(*args)
        t0 = time.perf_counter()
        out = ex(*args)
        self.metrics.perf.record_dispatch(
            key, time.perf_counter() - t0)
        return out

    def declare_warmup(self):
        """Declare warmup complete: the compiled-executable inventory
        is final, and any further compile is an attributed steady-state
        violation (flagged in ``watchdog.report()``, or raised when
        the engine was built with watchdog_mode="raise"). Also resets
        the admission policy's service-latency estimate: warmup
        first tokens paid compile time, which would otherwise poison
        the SLO-feedback EWMA into shedding the whole steady-state
        queue."""
        self.watchdog.declare_warmup_complete()
        self._policy.reset_service()

    def serve_metrics(self, port=0, addr="127.0.0.1",
                      post_routes=None):
        """Expose this engine's metrics registry over HTTP: GET
        /metrics (Prometheus text), /metrics.json (the snapshot
        schema), /debug (the route index — every mounted path, so the
        surface is discoverable without reading source),
        /debug/requests (flight-recorder traces; ``?tenant=<id>``
        filters to one tenant's requests), /debug/traces
        (this replica's distributed-trace span ring — the surface
        tools/trace_report.py assembles fleet-wide), /debug/state (live
        engine state), /debug/perf (per-program attribution +
        roofline fractions), /debug/programs (per compiled program its
        signature, cost, memory and the count of its instructions per
        device_scope: which layer an op of a capture belongs to),
        /debug/cache (MRC, prefix heat, savings
        attribution, churn), /debug/tenants (the per-tenant
        attribution ledger) and — with the health observatory on —
        /debug/health ({healthy, detectors, last_incident}: the
        per-replica router signal) and /debug/ledger (the per-step
        ring). ``post_routes`` mounts POST handlers alongside (the
        router's EngineGateway mounts ``POST /v1/generate`` this way —
        see start_metrics_server for the body-parsing contract).
        Returns a MetricsServerHandle — ``handle.port`` is the
        bound port, ``handle.close()`` stops it (idempotent); every
        handle is also closed by ``engine.close()`` so the server
        thread shuts down with the engine."""
        from ..observability import start_metrics_server

        def _debug_requests(params):
            return self.flight.debug_requests(
                tenant=params.get("tenant"))
        _debug_requests.accepts_query = True
        routes = {
            "/debug/requests": _debug_requests,
            "/debug/state": self.debug_state,
            "/debug/perf": self.metrics.perf_report,
            "/debug/programs": self.programs_report,
            "/debug/cache": self.metrics.cache_report,
            "/debug/traces": self.trace.debug_traces,
            "/debug/tenants": self.metrics.tenant_report,
        }
        if self.health is not None:
            routes["/debug/health"] = self.health.report
            routes["/debug/ledger"] = self.health.debug_ledger
        handle = start_metrics_server(
            self.metrics.registry, port=port, addr=addr,
            extra_routes=routes, post_routes=post_routes)
        self._metric_servers.append(handle)
        return handle

    def start_draining(self):
        """Flip the drain flag WITHOUT stepping: new ``add_request``
        calls raise immediately and ``/debug/health`` reports
        ``draining: true``, while whoever owns the step loop (e.g. a
        router EngineGateway driver thread) keeps stepping the
        already-submitted work to completion. ``drain()`` is the
        synchronous flavor that also runs the steps and closes."""
        self._draining = True

    # ------------------------------------------- disaggregated handoff

    def _require_kv_pair(self, what):
        """``serving.kv_wire`` carries a GPT's (k, v) pair: a model
        with another cache is refused the hand-off by name."""
        if not self._kv_pair:
            raise NotImplementedError(
                f"{what}: the KV wire carries a (k, v) pair a token; "
                f"this model's cache spec names "
                f"{[a.name for a in self.cache_spec.arrays]}")

    def export_kv(self, rid):
        """Serialize a retired ``hold_kv`` request's prompt KV blocks
        into a wire payload (see serving.kv_wire) and release its
        parked slot. One fixed-shape compiled gather — the
        ``("kv_export",)`` program over a trash-padded
        ``[blocks_per_slot]`` index row — pulls the tiles off the
        pool; everything after the single host read-back is pure numpy,
        so the transfer loop never traces. The slot is released even
        when serialization fails: a prefill tier never leaks blocks."""
        self._require_kv_pair("export_kv")
        req = self._held_exports.pop(rid, None)
        if req is None:
            raise KeyError(
                f"no held KV export for rid {rid}: submit with "
                f"hold_kv=True and let the request retire first")
        from . import kv_wire
        pool = self.pool
        slot = req.slot
        # the kv/export span starts when the KV became READY to ship
        # (first token emitted, blocks parked) — the dwell until the
        # router collects the hop is part of the handoff price the
        # TTFT decomposition must attribute, not an unexplained gap
        t0_exp = self.trace.wall(req.t_first_token) \
            if req.t_first_token is not None else time.time()
        try:
            n = kv_wire.blocks_for_prompt(len(req.prompt),
                                          pool.block_size)
            row = pool._slot_blocks[slot][:n]
            idx = np.full((pool.blocks_per_slot,),
                          TRASH_BLOCK, np.int32)
            idx[:n] = row
            args = (pool.kc, pool.vc, idx)
            ex = self._compiled(("kv_export",), _kv_export_fn,
                                args)
            with self.metrics.span("serving/kv_export"):
                k_dev, v_dev = self._timed_call(("kv_export",), ex,
                                                args)
                # the ONLY device read on this path: 2 * n_blocks
                # tiles, never a full pool
                k = np.asarray(k_dev)[:, :n]
                v = np.asarray(v_dev)[:, :n]
            payload = kv_wire.serialize_handoff(
                k, v, req.prompt, req.generated[0],
                trace=req.trace.as_dict()
                if req.trace is not None else None)
        finally:
            if req.slot is not None:
                pool.release(req.slot)
                req.slot = None
        self.trace.record(req.trace, "kv/export", t0_exp,
                          time.time() - t0_exp,
                          {"rid": req.rid, "blocks": n})
        self.flight.kv_exported(req, n,
                                kv_wire.payload_wire_bytes(payload))
        return payload

    def import_kv(self, payload, max_new_tokens, eos_id=None,
                  on_token=None, deadline_ms=None):
        """Bind a streamed KV handoff into this engine's pool and
        resume the stream at the FIRST DECODE STEP — no recompute:
        the prompt's K/V arrives on the wire, the prefill program
        never runs here. ``max_new_tokens`` counts ALL new tokens
        including the already-produced first one (so it matches what
        the client asked the fleet for); the remaining
        ``max_new_tokens - 1`` decode normally.

        The payload is fully verified (structure + per-frame digests
        + shape/dtype against this pool) BEFORE any pool mutation — a
        corrupt frame raises KVWireError and the pool is bit-identical
        to never having seen it. The splice itself is the one
        fixed-shape compiled ``("kv_import",)`` scatter (kc/vc donated;
        toks/pos returned as copies — a pending decode harvest still
        reads the pre-import token array). commit_prefix() then shares
        the imported prompt's full blocks through the radix index, so
        later local admissions hit them and the fleet heat map sees
        this replica as the prefix's owner. Returns the live Request."""
        self._require_kv_pair("import_kv")
        if self._draining or self._closed:
            raise RuntimeError(
                "engine is draining/closed: no new requests (drain() "
                "finishes already-submitted work, close() aborts it)")
        from . import kv_wire
        t0_imp = time.time()
        handoff = kv_wire.deserialize_handoff(payload)
        pool, sch = self.pool, self.scheduler
        layers, _, heads, bs, hd = pool.kc.shape
        if handoff.block_size != pool.block_size:
            raise kv_wire.KVWireError(
                f"block_size drift: payload {handoff.block_size}, "
                f"pool {pool.block_size}")
        if (handoff.k.shape[0] != layers
                or handoff.k.shape[2:] != (heads, bs, hd)):
            raise kv_wire.KVWireError(
                f"tile shape drift: payload {handoff.k.shape}, pool "
                f"tiles [{layers}, ., {heads}, {bs}, {hd}]")
        if handoff.k.dtype != pool.kc.dtype:
            raise kv_wire.KVWireError(
                f"tile dtype drift: payload {handoff.k.dtype}, pool "
                f"{pool.kc.dtype}")
        req = Request(handoff.prompt, max_new_tokens,
                      eos_id=self.config.eos_id if eos_id is None
                      else eos_id,
                      on_token=on_token, deadline_ms=deadline_ms)
        # join the prefill tier's trace: whatever rode the wire is
        # coerced (a corrupted/absent trace field mints a local root
        # — the tiles already verified clean, the import proceeds).
        # The tenant id rides the baggage, so attribution survives
        # the tier hop without any kv_wire format change.
        req.trace = self._TraceContext.coerce(handoff.trace)
        tenant = req.trace.baggage.get("tenant")
        if tenant:
            req.tenant_id = str(tenant)
        req.imported = True
        ids = req.prompt
        alloc = pool.acquire(req.rid, ids, req.cache_tokens, 0)
        if alloc is None:
            raise RuntimeError(
                "kv import refused: pool at capacity (the router "
                "retries another decode replica)")
        slot = alloc.slot
        n = handoff.n_blocks
        bps = pool.blocks_per_slot
        idx = np.full((bps,), TRASH_BLOCK, np.int32)
        idx[:n] = pool._slot_blocks[slot][:n]
        ktiles = np.zeros((layers, bps, heads, bs, hd),
                          pool.kc.dtype)
        vtiles = np.zeros_like(ktiles)
        ktiles[:, :n] = handoff.k
        vtiles[:, :n] = handoff.v
        args = (pool.kc, pool.vc, idx, ktiles, vtiles, self._toks,
                self._pos, np.int32(slot),
                np.int32(handoff.first_token), np.int32(len(ids)))
        try:
            ex = self._compiled(("kv_import",), _kv_import_fn,
                                args, donate=(0, 1))
            with self.metrics.span("serving/kv_import"):
                toks, pos, kc, vc = self._timed_call(
                    ("kv_import",), ex, args)
        except BaseException:
            pool.release(slot)
            raise
        pool.rebind(kc, vc)
        self._toks, self._pos = toks, pos
        pool.commit_prefix(slot, ids)
        if self._sampler is not None:
            self._sampler.set_slot(slot, req)
        now = time.perf_counter()
        req.state = RUNNING
        req.slot = slot
        req.generated = [int(handoff.first_token)]
        # admission and first token both already happened, fleet-wise:
        # stamp rather than observe (TTFT was paid on the prefill
        # tier; the router's handoff histogram prices this hop)
        req.t_admitted = now
        req.t_first_token = now
        sch.active[slot] = req
        self.metrics.record_admission(req)
        self.metrics.requests_admitted += 1
        self.flight.enqueued(req)
        self.flight.kv_imported(req, n, handoff.wire_bytes)
        # kv/import covers deserialization + verification + the
        # splice; decode/queue starts here (import done -> first
        # decode dispatch, stamped in the dispatch loop)
        self.trace.record(req.trace, "kv/import", t0_imp,
                          time.time() - t0_imp,
                          {"rid": req.rid, "blocks": n,
                           "wire_bytes": handoff.wire_bytes})
        reason = sch.stop_reason(req, req.generated[0])
        if reason is not None:
            # max_new_tokens=1 (or first==eos): nothing left to
            # decode — retire immediately, never leaving a saturated
            # request for prerelease to orphan
            sch.finish(req, pool)
            violations = self.metrics.record_completion(req)
            self.flight.retired(req, reason,
                                slo_violations=list(violations))
            if self.supervisor is not None:
                self.supervisor.note_completion(req.rid)
        return req

    def warmup_kv_handoff(self):
        """Compile the ``("kv_export",)`` / ``("kv_import",)``
        programs while the engine is idle, so a steady-state handoff
        is dispatch-only — call during warmup (before
        ``declare_warmup``) on any replica that may export or import.
        The warmup import splices zero tiles through the trash block
        and scribbles slot 0's toks/pos, both dead state on an idle
        engine; the donated kc/vc are rebound exactly like a real
        import."""
        pool = self.pool
        layers, _, heads, bs, hd = pool.kc.shape
        bps = pool.blocks_per_slot
        idx = np.full((bps,), TRASH_BLOCK, np.int32)
        args = (pool.kc, pool.vc, idx)
        ex = self._compiled(("kv_export",), _kv_export_fn, args)
        k_dev, v_dev = ex(*args)
        np.asarray(k_dev), np.asarray(v_dev)
        tile = np.zeros((layers, bps, heads, bs, hd), pool.kc.dtype)
        args = (pool.kc, pool.vc, idx, tile, tile, self._toks,
                self._pos, np.int32(0), np.int32(0), np.int32(0))
        ex = self._compiled(("kv_import",), _kv_import_fn, args,
                            donate=(0, 1))
        toks, pos, kc, vc = ex(*args)
        pool.rebind(kc, vc)
        self._toks, self._pos = toks, pos
        # these builds land BETWEEN steps: resync the health row's
        # compile baseline, or the first post-warmup step would charge
        # them as steady-state compiles and trip the health detector
        if self._hprev is not None:
            row = list(self._hprev)
            row[7] = self.metrics._c_compiles._default()._value
            self._hprev = tuple(row)

    def drain(self):
        """Graceful drain: stop accepting NEW requests (add_request
        raises), finish every already-submitted request — queued and
        in-flight — then close. ``/debug/health`` reports
        ``draining: true`` for the duration, so a router stops
        routing to this replica while it finishes its commitments.
        Returns the completed requests (submission order)."""
        self.start_draining()
        while self.step():
            pass
        done = sorted(self.scheduler.completed, key=lambda r: r.rid)
        self.close()
        return done

    def close(self):
        """Shut down the engine: any still-in-flight work is retired
        with an explicit ``aborted`` stop reason (slot/block
        conservation audited by tests — nothing leaks, nothing is
        silently abandoned; use ``drain()`` to finish it instead),
        then the metrics/debug HTTP servers stop. Idempotent; the
        engine is also a context manager."""
        if not self._closed and (self.scheduler.pending
                                 or self._pending or self._chunk_q
                                 or self._held_exports):
            self._abort_inflight()
        self._closed = True
        servers, self._metric_servers = self._metric_servers, []
        for handle in servers:
            handle.close()

    def _abort_inflight(self):
        """Retire every request the engine still owes tokens —
        queued, active, mid-chunk, or pending harvest — with reason
        "aborted" (zero further tokens, slots/blocks released, flight
        traces closed). The close()-with-work-in-flight path."""
        sch = self.scheduler
        owed = {}
        for r in sch.queue:
            owed[r.rid] = r
        for r in sch.active.values():
            owed[r.rid] = r
        for plan in self._chunk_q:
            owed.setdefault(plan.req.rid, plan.req)
        for entry in self._pending:
            coll = entry[2]
            rs = coll.values() if isinstance(coll, dict) \
                else [r for r, _ in coll]
            for r in rs:
                if r.state == RUNNING:   # prereleased finals included
                    owed.setdefault(r.rid, r)
        self._pending = []
        self._pending_steps.clear()
        self._chunk_q = []
        self._prefilling.clear()
        # parked exports are already DONE — just give their blocks back
        held, self._held_exports = self._held_exports, {}
        for r in sorted(held.values(), key=lambda r: r.rid):
            if r.slot is not None:
                self.pool.release(r.slot)
                r.slot = None
        for r in sorted(owed.values(), key=lambda r: r.rid):
            r.inflight = 0
            sch.abort(r, self.pool)
            self.metrics.record_abort(r.tenant_id)
            self.flight.retired(r, "aborted")
            if self.supervisor is not None:
                self.supervisor.note_completion(r.rid)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------- observability

    def request_trace(self, rid):
        """The flight-recorder RequestTrace for request ``rid`` —
        completed (kept in the bounded ring) or still in flight; None
        when unknown/evicted."""
        return self.flight.trace(rid)

    def debug_state(self):
        """The ``/debug/state`` JSON body: live queue/slot/pipeline
        state plus the compile + flight summaries — the first page to
        look at when a serve loop misbehaves."""
        sch = self.scheduler
        wd = self.watchdog.report()
        return {
            "replica": self.metrics.identity_report(),
            "queue_depth": len(sch.queue),
            "queued_rids": [r.rid for r in sch.queue],
            "active_slots": {str(slot): req.rid
                             for slot, req in sorted(sch.active.items())},
            "slot_occupancy": self.pool.occupancy,
            "inflight_harvests": len(self._pending),
            "completed_kept": len(sch.completed),
            "compiles": self.metrics.compiles,
            "watchdog": {k: wd[k] for k in
                         ("warmed", "mode", "compiles_total",
                          "steady_state_compiles")},
            "kv_donation": dict(self.metrics.kv_donation),
            "flight": self.flight.state(),
            "slo": self.metrics.slo.report(),
            "paged_attn": self.decode_layout == "paged_pallas",
            "role": self.role,
            "held_exports": len(self._held_exports),
            "decode_layout": self.decode_layout,
            "speculative": self.speculative,
            "spec_k": self.spec_k,
            "prefix_cache": self.metrics.prefix_cache_report(),
            "cache": self.metrics.cache_report(),
            "scheduler": dict(
                self.metrics.scheduler_report(),
                chunked_inflight=len(self._chunk_q)),
            "health": self.metrics.health_report(),
            "resilience": self.metrics.resilience_report(),
            "tenants": self.metrics.tenant_report(),
        }

    def lint(self, passes=None, min_donation_bytes=1 << 20,
             program="decode"):
        """Static-analysis findings over this engine's hot path (see
        paddle_tpu.analysis.lint_jaxpr): the chosen executable's jaxpr
        runs through the ``f64-upcast`` / ``host-callback`` / ``donation``
        passes, and the engine's compile watchdog feeds
        ``dynamic-shape-risk``. ``program`` picks the jaxpr:
        "decode" (default), "spec_verify" (the speculative k-token
        verify program) or "kv_import" (the disaggregation
        block-splice program). A chunk of a chunked prefill IS a
        prefill dispatch, so there is no chunk program to lint. The
        donation metadata mirrors the real AOT build: kc/vc/pos
        donated iff ``self._donate``
        (``metrics.kv_donation["enabled"]``), aliasing iff the backend
        aliases donated buffers (``kv_donation["effective"]`` on) — so
        the ``donation`` pass cross-checks
        ``snapshot()["kv_donation"]`` by construction: a non-aliasing
        (CPU) backend lints clean, an aliasing backend lints clean
        exactly when the big cache buffers are donated."""
        import jax
        from ..analysis import lint as lint_mod
        if program == "spec_verify":
            if self._verify_fn is None:
                raise ValueError(
                    "no verify program on this engine "
                    "(ServingConfig(speculative=True) builds one)")
            S = self.config.num_slots
            drafts = np.zeros((S, self.spec_k), np.int32)
            dlen = np.zeros((S,), np.int32)
            args, donate = self._verify_dispatch_args(self.pool,
                                                      drafts, dlen)
            donate = donate if self._donate else ()
            fn = self._verify_fn
        elif program == "kv_import":
            bps = self.pool.blocks_per_slot
            layers, _, heads, bs, hd = self.pool.kc.shape
            tile = np.zeros((layers, bps, heads, bs, hd),
                            self.pool.kc.dtype)
            args = (self.pool.kc, self.pool.vc,
                    np.zeros((bps,), np.int32), tile, tile,
                    self._toks, self._pos, np.int32(0), np.int32(0),
                    np.int32(0))
            fn = _kv_import_fn
            donate = (0, 1) if self._donate else ()
        elif program == "decode":
            args, donate = self._decode_dispatch_args(self.pool)
            fn = self._decode_fn
            donate = donate if self._donate else ()
        else:
            raise ValueError(
                f"unknown program {program!r}: expected 'decode', "
                f"'spec_verify' or 'kv_import'")
        closed = jax.make_jaxpr(fn)(*args)
        return lint_mod.lint_jaxpr(
            closed, passes=passes,
            donated_invars=lint_mod.donated_invars_from_argnums(
                args, donate),
            backend_aliases=self._device.platform != "cpu",
            watchdog=self.watchdog,
            min_donation_bytes=min_donation_bytes)

    def cost_model(self):
        """Device cost telemetry as a JSON-safe dict (the bench
        artifact's ``cost_model`` section): per-executable
        cost_analysis from the watchdog compile records, the decode
        per-step flops/bytes, the estimated MFU against the device
        peak, and the current memory picture — every field None-safe
        on backends that don't report."""
        events = self.watchdog.events()
        per_exec = [{"key": e["key"], "signature": e["signature"],
                     "cost": e["cost"]} for e in events]
        costs = [e["cost"] for e in events if e.get("cost")]
        decode_flops = self.metrics._g_decode_flops.value or None
        decode_bytes = self.metrics._g_decode_bytes.value or None
        peak = self.metrics._peak_flops
        mfu = self.metrics.estimated_mfu()
        prefix = self.metrics.prefix_cache_report()
        return {
            "device": {"platform": self._device.platform,
                       "kind": self._device.device_kind},
            "executables": per_exec,
            "executables_with_cost": len(costs),
            "compiled_flops_total": sum(
                c.get("flops", 0.0) for c in costs) or None,
            "decode_flops_per_step": decode_flops,
            "decode_bytes_per_step": decode_bytes,
            "peak_flops": peak,
            # significant figures, not decimal places: toy/CPU probe
            # models run MFU in the 1e-7 range, which a round(_, 6)
            # would collapse to 0.0
            "estimated_mfu": float(f"{mfu:.4g}") if mfu else None,
            "device_memory": device_memory_stats(self._device),
            # prefill compute accounting: prefix-cache hits are SERVED
            # tokens, never prefill flops — only tokens_computed may
            # enter a prefill compute/MFU figure, else the cost model
            # over-credits cached spans (estimated_mfu above is
            # decode-only and unaffected either way)
            "prefill_accounting": {
                "tokens_computed": prefix["computed_tokens"],
                "prefix_cached_tokens": prefix["cached_tokens"],
                "cached_fraction": prefix["cached_fraction"],
            },
        }

    # -------------------------------------------------------------- step

    def _emit(self, req, token):
        """Account one generated token; retire the request on stop.
        The flight recorder sees the first token, every
        trace_decode_window-th token, and the retirement with its
        reason + SLO verdict."""
        first = not req.generated
        req.generated.append(token)
        self.metrics.tokens_generated += 1
        if first:
            self.metrics.record_first_token(req)
            # close the SLO-feedback loop: the policy's shedding
            # threshold tracks the admission->first-token latency the
            # engine is ACTUALLY delivering. Compile-tainted samples
            # (a build happened after this request's admission) are
            # excluded — they measure XLA, not steady-state service,
            # and one seconds-scale sample in a milliseconds-scale
            # EWMA would shed every fresh arrival (including the rest
            # of the warmup sweep) on sight. t_admitted is None only
            # for requests that never went through admit().
            if req.t_admitted is not None \
                    and req.t_admitted > self._t_last_compile:
                self._policy.observe_service(
                    (req.t_first_token - req.t_admitted) * 1000.0)
            # prefill-side TTFT spans: queue (arrival -> admission)
            # and compute (admission -> first token), wall-converted
            # from the request's perf_counter lifecycle stamps.
            # Imported requests never prefill here — their first
            # token predates the import (kv/import covered it).
            if not req.imported and req.t_admitted is not None:
                w = self.trace.wall
                self.trace.record(
                    req.trace, "prefill/queue", w(req.t_arrival),
                    max(0.0, req.t_admitted - req.t_arrival),
                    {"rid": req.rid})
                self.trace.record(
                    req.trace, "prefill/compute", w(req.t_admitted),
                    max(0.0, req.t_first_token - req.t_admitted),
                    {"rid": req.rid})
        elif req.imported and len(req.generated) == 2 \
                and req.t_decode0 is not None:
            # decode-side TTFT spans, closed at the FIRST locally
            # decoded token: queue (import done -> first decode
            # dispatch) and first_step (dispatch -> this emission)
            w = self.trace.wall
            self.trace.record(
                req.trace, "decode/queue", w(req.t_admitted),
                max(0.0, req.t_decode0 - req.t_admitted),
                {"rid": req.rid})
            self.trace.record(
                req.trace, "decode/first_step", w(req.t_decode0),
                max(0.0, time.perf_counter() - req.t_decode0),
                {"rid": req.rid})
        self.flight.token_emitted(req, len(req.generated))
        if req.on_token is not None:
            # a user callback must never take down the step loop: a
            # raise is caught, counted, trace-attributed — and every
            # other slot keeps streaming (the token itself was already
            # emitted and accounted above). The callers' time adds up
            # to the harvest's one serving/on_token span
            t_cb = time.perf_counter()
            if self._on_token_t0 is None:
                self._on_token_t0 = t_cb
            try:
                if self.chaos is not None:
                    self.chaos.maybe_raise("callback",
                                           step=self._step_id + 1)
                req.on_token(req, token)
            except Exception as e:  # noqa: BLE001 - isolation boundary
                self.metrics.record_callback_error()
                self.flight.callback_error(req, e)
            self._on_token_s += time.perf_counter() - t_cb
        reason = self.scheduler.stop_reason(req, token)
        if reason is not None:
            self.scheduler.finish(req, self.pool)
            violations = self.metrics.record_completion(req)
            self.flight.retired(req, reason,
                                slo_violations=list(violations))
            if self.supervisor is not None:
                self.supervisor.note_completion(req.rid)
            if req.hold_kv and req.slot is not None:
                # prefill-tier retirement: the slot (and its blocks)
                # stay live, parked for export_kv(rid)
                self._held_exports[req.rid] = req

    def _harvest(self, pending):
        """Read back dispatched results (at most one step's worth: the
        prefills and the decode of the previous step, in
        dispatch order) and run the host bookkeeping on the token
        values. np.asarray here is the engine's ONLY device->host
        sync; with async_depth=1 the current step's prefill/decode are
        already executing when it blocks, so stop checks, streaming
        callbacks and retirement overlap device compute. The time
        spent in callbacks is charged to ONE ``serving/on_token`` span
        per harvest (none when no token had a callback), so it reads
        apart from the engine's own under ``serving/harvest``."""
        M = self.metrics
        self._on_token_t0, self._on_token_s = None, 0.0
        for entry in pending:
            if self._perf_on:
                t0 = time.perf_counter()
                with M.span("serving/sync"):
                    vals = self._read_back(entry[1])
                # entry[3] is the program key the dispatch leg used —
                # the sync leg lands on the same program, so a step's
                # cost decomposes into named programs end to end
                M.perf.record_sync(entry[3],
                                   time.perf_counter() - t0)
            else:
                with M.span("serving/sync"):
                    vals = self._read_back(entry[1])
            if entry[0] == "prefill":
                for (req, slot), tok in zip(entry[2], vals):
                    req.inflight -= 1
                    self._emit(req, int(tok))
            elif entry[0] == "spec":
                out, acc = vals
                drafted = entry[4]
                for slot, req in entry[2].items():
                    n_draft = drafted.get(slot, 0)
                    if req.state != RUNNING:
                        # retired after dispatch (EOS on a prior
                        # token): the whole candidate block is
                        # speculative — masked, exactly like the
                        # plain-decode case, plus its drafts count as
                        # rejected
                        M.speculative_masked += 1
                        if n_draft:
                            M.spec_drafted += n_draft
                            M.spec_rejected += n_draft
                        continue
                    req.inflight -= 1
                    M.spec_slot_steps += 1
                    n_acc = int(acc[slot])
                    # longest-accepted-prefix harvest: the n_acc
                    # accepted drafts plus the model's bonus token at
                    # out[slot, n_acc]; _emit's stop check runs per
                    # token, so an EOS inside the block retires the
                    # request mid-block and the tail never surfaces
                    emitted = 0
                    for i in range(n_acc + 1):
                        self._emit(req, int(out[slot, i]))
                        emitted += 1
                        if req.state != RUNNING:
                            break
                    M.spec_tokens_emitted += emitted
                    if n_draft:
                        M.spec_drafted += n_draft
                        M.spec_accepted += n_acc
                        M.spec_rejected += n_draft - n_acc
                        self._spec.observe(req.rid, n_draft, n_acc)
                        if n_acc:
                            self.flight.draft_accepted(req, n_acc,
                                                       n_draft)
                        if n_draft > n_acc:
                            self.flight.draft_rejected(
                                req, n_draft - n_acc, n_draft)
            else:
                for slot, req in entry[2].items():
                    if req.state != RUNNING:
                        # the request hit an (unpredictable) EOS stop
                        # after this decode was dispatched: the extra
                        # token is speculative — masked, preserving
                        # exact greedy parity with generate()
                        M.speculative_masked += 1
                        continue
                    req.inflight -= 1
                    self._emit(req, int(vals[slot]))
        if self._on_token_t0 is not None:
            # the callers' share of this harvest, as one span: it
            # starts with the first callback and lasts as long as all
            # of them together
            M.record_span("serving/on_token", self._on_token_t0,
                          self._on_token_s)

    def _read_back(self, device_vals):
        """One device->host token read, with bounded retry for
        transient transfer failures: the values stay resident on
        device across attempts, so a failed read retries immediately
        and loses nothing. Past the retry budget (or on a hardened=off
        engine) the failure propagates — a persistently dead transfer
        path is the supervisor/operator's problem, not a spin loop."""
        attempt = 0
        while True:
            try:
                if self.chaos is not None:
                    self.chaos.maybe_raise("transfer")
                if isinstance(device_vals, tuple):
                    # spec entries read back (out, accepted) together
                    return tuple(np.asarray(v) for v in device_vals)
                return np.asarray(device_vals)
            except Exception as e:  # noqa: BLE001 - gated below
                self.metrics.record_dispatch_failure("transfer")
                if attempt >= self.max_dispatch_retries \
                        or not self._retryable(e):
                    raise
                attempt += 1
                self.metrics.record_retry()

    def _decode_dispatch_args(self, pool):
        """(args, donate_argnums) for the plain pooled decode program
        — one place, shared by the hot path and the warm-both-flavors
        discipline of the speculative schedule."""
        # the cache spec's arrays, donated; then its state, not
        args = (self.params, self._toks, self._pos,
                pool.device_tables()) + tuple(pool.arrays)
        donate = (2,) + tuple(range(4, len(args)))
        args = args + self._state
        if self.sampling:
            args = args + self._sampler.device_arrays()
        return args, donate

    def _verify_dispatch_args(self, pool, drafts, dlen):
        """(args, donate_argnums) for the k-token verify flavor.
        drafts/dlen are fixed-shape host arrays ([S, k] / [S]); the
        cache and pos donate exactly like plain decode (the two extra
        leading host inputs shift the argnums)."""
        args = (self.params, self._toks, self._pos, drafts, dlen,
                pool.device_tables(), pool.kc, pool.vc)
        return args, (2, 6, 7)

    def step(self):
        """One engine iteration of the pipelined hot path:

        1. prerelease: slots whose request's max-token stop is already
           determined by in-flight tokens free NOW (predictable stops
           pay no retirement lag; EOS stops mask one speculative
           token);
        2. admission + tail prefill dispatch into free slots;
        3. dispatch ONE pooled decode advancing every token-wanting
           slot (freshly prefilled slots included — the device runs
           prefill then decode back to back);
        4. harvest the PREVIOUS step's results — the only host sync,
           overlapped with 2/3's device compute.

        Returns True while work remains. With async_depth=0 every
        dispatch is harvested immediately (the synchronous PR-1
        schedule).

        Each phase runs in its own ``serving/*`` scope nested under
        ``serving/step``, so the step anatomy (retirement → admission
        → prefill → decode dispatch → harvest) is readable in
        the chrome host timeline
        (observability.default_recorder().dump_chrome_trace()) as well
        as the XPlane capture and the span counters.

        With the health observatory on (the default), every step also
        appends one structured row to the step ledger and runs the
        online anomaly detectors over it — the ledger build happens
        AFTER the timed step, so the observatory's own bookkeeping
        never pollutes the wall time it judges; it has its own span,
        ``serving/health_tick``, beside ``serving/step``."""
        if self.health is None:
            more = False
            with self.metrics.span("serving/step"):
                more = self._step_inner()
            # a supervisor restart mid-step re-queued work the stale
            # `more` verdict predates
            return more or self.scheduler.pending or bool(self._pending)
        t0 = time.perf_counter()
        with self.metrics.span("serving/step"):
            more = self._step_inner()
        wall_s = time.perf_counter() - t0
        with self.metrics.span("serving/health_tick"):
            self._health_tick(wall_s)
        return more or self.scheduler.pending or bool(self._pending)

    def _step_inner(self):
        sch, pool, M = self.scheduler, self.pool, self.metrics
        depth = self.config.async_depth
        sync = depth == 0
        epoch = self._restart_epoch

        if self._spec is not None and self._pending:
            # speculative schedule: drafts extend the request's last
            # HARVESTED token, so the previous step's in-flight results
            # are consumed BEFORE proposing. The verify dispatch still
            # overlaps all of this step's host bookkeeping — the
            # pipeline depth is unchanged, only the harvest moves from
            # the tail of the step to its head.
            prev, self._pending = self._pending, []
            self._pending_steps.clear()
            with M.span("serving/harvest"):
                self._harvest(prev)
        held = len(self._pending)

        if self.chaos is not None \
                and self.chaos.fires("step_latency",
                                     step=self._step_id + 1):
            time.sleep(self.chaos.latency_s())
        if self._deadlines_armed:
            self._expire_deadlines()

        with M.span("serving/retirement"):
            # hold_kv requests never prerelease: their blocks must
            # survive retirement for export_kv
            for req in [r for r in sch.active.values()
                        if sch.saturated(r) and not r.hold_kv]:
                sch.prerelease(req, pool)

        self._triage()

        # the exponential-backoff gate: after an absorbed dispatch
        # failure, admission/prefill pauses until the retry moment
        # (decode of already-running slots continues — backoff starves
        # nobody who already holds a slot)
        if time.perf_counter() >= self._retry_at:
            self._paged_prefills(sync)
            if self._chunk_q:
                self._dispatch_chunks(sync)

        # slots parked mid-chunked-prefill decode physically (the
        # pooled dispatch advances every slot) but their parked writes
        # land in always-overwritten-before-visible rows and their
        # tokens are never harvested — excluded here
        snapshot = {slot: req for slot, req in sch.active.items()
                    if not sch.saturated(req)
                    and slot not in self._prefilling}
        if snapshot:
            spec = self._spec
            drafted = None
            if spec is not None:
                with M.span("serving/draft"):
                    drafts, dlen, drafted = spec.propose(snapshot)
                if not drafted:
                    # nobody drafted this step — dispatch the plain
                    # decode program outright (per-slot fallbacks with
                    # dlen=0 still ride the verify program whenever at
                    # least one slot drafts)
                    drafted = None
            use_spec = drafted is not None
            t_dec = time.perf_counter()
            for req in snapshot.values():
                req.inflight += 1
                if req.t_decode0 is None:
                    # first decode dispatch carrying this request —
                    # the decode/queue -> decode/first_step boundary
                    # for an imported request's trace
                    req.t_decode0 = t_dec
            if self._window is not None:
                self._grow_for_decode(snapshot)
            args, donate = self._decode_dispatch_args(pool)
            if spec is not None:
                v_args, v_donate = self._verify_dispatch_args(
                    pool, drafts, dlen)
            key = self._verify_key if use_spec else ("decode",)
            ok = False
            try:
                if self.chaos is not None:
                    self.chaos.maybe_raise("decode_dispatch",
                                           step=self._step_id + 1)
                ex = self._compiled(("decode",), self._decode_fn, args,
                                    donate=donate)
                if self._window is not None \
                        and ("compact",) not in self._exec:
                    # warm with decode: a window's first end must not
                    # compile in steady state
                    c_args, c_donate = self._compact_args(
                        next(iter(snapshot)), 0)
                    self._compiled(("compact",), self._compact_fn,
                                   c_args, donate=c_donate)
                if spec is not None:
                    # BOTH flavors warm up-front regardless of which
                    # one this step needs: a later acceptance-collapse
                    # fallback (plain decode) or first n-gram hit
                    # (verify) must never compile in steady state
                    ex_v = self._compiled(self._verify_key,
                                          self._verify_fn, v_args,
                                          donate=v_donate)
                if use_spec:
                    with M.span("serving/decode_dispatch"):
                        out, acc, nxt, self._pos, *arrs = \
                            self._timed_call(key, ex_v, v_args)
                else:
                    with M.span("serving/decode_dispatch"):
                        nxt, self._pos, *rest = self._timed_call(
                            ("decode",), ex, args)
                    # the cache arrays first, then the carried state
                    n = len(rest) - len(self._state)
                    arrs, self._state = rest[:n], tuple(rest[n:])
                ok = True
            except BaseException as e:
                # the dispatch never ran (chaos injects BEFORE the
                # call; a compile error dies before donation), so the
                # device state is intact — undo the inflight marks and
                # either absorb (retry next step / supervisor restart)
                # or propagate
                for req in snapshot.values():
                    req.inflight -= 1
                if not self._absorb_decode_failure(e):
                    raise
            if ok:
                pool.rebind(*arrs)
                self._toks = nxt
                if self._window is not None:
                    self._after_decode(snapshot)
                elif self._ring is not None:
                    self._count_ring_cache(snapshot)
                M.decode_steps += 1
                self._decode_fail_streak = 0
                if use_spec:
                    M.spec_verify_steps += 1
                    entry = ("spec", (out, acc), snapshot, key, drafted)
                else:
                    if spec is not None:
                        M.spec_fallback_steps += 1
                    entry = ("decode", nxt, snapshot, ("decode",))
                if sync:
                    self._harvest([entry])
                else:
                    self._pending.append(entry)

        if epoch == self._restart_epoch:
            # at most `depth` steps' results stay in flight (this
            # step's among them), so the device holds that many steps
            # of queued work while the host is away; a step that
            # dispatched nothing holds nothing back
            steps = self._pending_steps
            steps.append(len(self._pending) - held)
            keep = depth if steps[-1] else 0
            while len(steps) > keep:
                steps.popleft()
            n = len(self._pending) - sum(steps)
            prev = self._pending[:n]
            del self._pending[:n]
            with M.span("serving/harvest"):
                self._harvest(prev)
        # else: a supervisor restart happened this step — everything
        # in flight belonged to the pre-restart schedule and went with
        # it; its requests were re-queued with inflight reset, and
        # greedy replay regenerates every unread token bit-exactly

        M.queue_depth = len(sch.queue)
        M.slot_occupancy = self.pool.occupancy
        return sch.pending or bool(self._pending)

    def _health_tick(self, wall_s):
        """Author one step-ledger row (counter deltas against the
        previous tick) and feed the health monitor. The periodic
        paged-pool conservation audit runs here every
        ``health_audit_every`` steps under its own
        ``serving/health_audit`` host span, so the observatory's own
        overhead is visible in traces — and excluded from the step
        wall time the spike detector judges."""
        M = self.metrics
        self._step_id += 1
        step = self._step_id
        conservation_ok = conservation_error = None
        if step % self.config.health_audit_every == 0:
            with M.span("serving/health_audit"):
                audit = self.pool.audit()
            conservation_ok = audit["ok"]
            conservation_error = audit["error"]
        # per-tick fast path: cache the counter/span CHILDREN once and
        # read their values directly — the general family-property
        # reads (dispatch_sync_split, facade properties) re-resolve
        # labels and series per call, and this path runs on EVERY
        # engine step. Deltas are computed tuple-wise: one allocation,
        # no intermediate dicts (GC pressure IS step-time overhead).
        k = self._hspan_kids
        if k is None:
            k = self._hspan_kids = (
                M._c_tokens._default(),
                M._c_admitted._default(),
                M._c_completed._default(),
                M.slo._c_goodput._default(),
                M._c_prefill_tokens._default(),
                M._c_chunks._default(),
                M._c_deprioritized._default(),
                M._c_compiles._default(),
                M._c_span.labels("serving/prefill_dispatch"),
                M._c_span.labels("serving/decode_dispatch"),
                M._c_span.labels("serving/chunk_dispatch"),
                M._c_span.labels("serving/sync"),
                M._c_prefix_hits._default(),
                M._c_prefix_misses._default(),
            )
        # raw child-slot reads (not the .value property): counters are
        # plain floats behind __slots__, and 14 property hops per step
        # are real money on a sub-ms step
        pool = self.pool
        cur = (k[0]._value, k[1]._value, k[2]._value, k[3]._value,
               k[4]._value, k[5]._value, k[6]._value, k[7]._value,
               k[8]._value + k[9]._value + k[10]._value, k[11]._value,
               M.shed_count,
               # cache-pressure facts (plain attr reads)
               pool.index.thrash_count, pool.evictable_blocks)
        prev = self._hprev
        self._hprev = cur
        if prev is None:
            prev = (0,) * len(cur)
        new_compiles = int(cur[7] - prev[7])
        hits = int(k[12]._value)
        misses = int(k[13]._value)
        queue = self.scheduler.queue
        fired = self.health.observe({
            "step": step,
            "t": time.time(),
            "wall_s": wall_s,
            "dispatch_s": cur[8] - prev[8],
            "sync_s": cur[9] - prev[9],
            "queue_depth": len(queue),
            "queue_age_s": time.perf_counter() - queue[0].t_arrival
            if queue else 0.0,
            # parked KV exports still OWN their slot and blocks (the
            # handoff isn't done until export_kv streams them) — count
            # them occupied or the kv_block_leak detector reads a
            # mid-handoff prefill tier as a leak and the supervisor
            # wipes the pool out from under the export
            "occupied_slots": (len(self.scheduler.active)
                               + len(self._held_exports)),
            "held_exports": len(self._held_exports),
            "chunked_inflight": len(self._chunk_q),
            "admitted": int(cur[1] - prev[1]),
            "tokens": int(cur[0] - prev[0]),
            "completed": int(cur[2] - prev[2]),
            "goodput_tokens": int(cur[3] - prev[3]),
            "prefill_tokens": int(cur[4] - prev[4]),
            "prefill_chunks": int(cur[5] - prev[5]),
            "shed": int(cur[10] - prev[10]),
            "deprioritized": int(cur[6] - prev[6]),
            "new_compiles": new_compiles,
            # a post-warmup build is a steady-state violation; the
            # steady_state_compile detector turns it into an anomaly
            "steady_compiles": new_compiles if self.watchdog.warmed
            else 0,
            "slo_on": self._slo_on,
            "prefix_hit_rate": round(hits / (hits + misses), 4)
            if (hits + misses) else None,
            "pool_free_blocks": pool.free_blocks,
            "pool_evictable_blocks": pool.evictable_blocks,
            "pool_live_blocks": pool.live_blocks,
            # per-step cache-pressure deltas (PR 13): thrash deltas
            # are clamped at 0 because a supervisor pool swap resets
            # the radix counter mid-stream; the evictable delta is
            # signed (pinning legitimately shrinks the supply)
            "cache_thrash": max(0, int(cur[11] - prev[11])),
            "pool_evictable_delta": int(cur[12] - prev[12]),
            "conservation_ok": conservation_ok,
            "conservation_error": conservation_error,
        })
        if fired and self.supervisor is not None:
            # the observatory's wedge verdicts are the supervisor's
            # restart triggers — this is PR 8's loop, closed
            self.supervisor.consider(fired)

    def _triage(self):
        """Apply the admission policy to the queue (scheduler does the
        queue surgery and request state; this engine layer emits the
        counters + flight events the decisions owe the observability
        contract: every shed/deferred request is counted, SLO-judged,
        and trace-attributed with its headroom at decision time)."""
        sch, M = self.scheduler, self.metrics
        with M.span("serving/triage"):
            shed, deprioritized = sch.triage()
        for req, headroom in deprioritized:
            M.record_deprioritized()
            self.flight.deprioritized(req, headroom)
        for req, headroom in shed:
            M.record_shed(req.shed_reason, req.tenant_id)
            self.flight.shed(req, req.shed_reason, headroom)

    def _paged_prefills(self, sync):
        """Prefix-aware admission + tail-only prefill over the paged
        pool: each admission pins its longest cached prefix (radix
        lookup, block refcounts) and dispatches ONE [1, bucket] prefill
        covering just the uncached tail — shared system prompts cost
        their K/V once. The full prompt's frozen blocks are committed
        to the radix index only AFTER the dispatch succeeded, so a
        failed dispatch rolls back (slot + blocks released, request
        requeued) without poisoning the cache."""
        sch, pool, M = self.scheduler, self.pool, self.metrics
        while True:
            if self.chaos is not None \
                    and self.chaos.fires("block_exhaustion",
                                         step=self._step_id + 1):
                break       # simulated dry pool: admission waits
            with M.span("serving/admit"):
                admission = sch.admit_paged(pool, self.chunk_len)
            if admission is None:
                break
            req, alloc, bucket, chunked = admission
            if self._sampler is not None:
                self._sampler.set_slot(alloc.slot, req)
            if chunked:
                # long uncached tail: slot + blocks are claimed, the
                # prefill itself runs chunk by chunk under the per-
                # step budget (_dispatch_chunks); commit-to-index
                # still waits for the FINAL chunk's dispatch success
                self._register_chunked(req, alloc)
                continue
            ids = req.prefill_ids   # prompt (+ replayed tokens)
            start = alloc.prefix_tokens
            tail = len(ids) - start
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :tail] = ids[start:]
            if self._window is not None:
                pool.grow(alloc.slot, self.cache_spec.entries(len(ids)))
            args = (self.params, tokens, np.int32(tail),
                    np.int32(start), np.int32(alloc.slot),
                    np.int32(1), pool.table_row(alloc.slot),
                    self._toks, self._pos) + tuple(pool.arrays)
            donate = tuple(range(8, len(args)))
            if self.sampling:
                args = args + self._samp_scalars(req)
            req.inflight += 1
            try:
                if self.chaos is not None:
                    self.chaos.maybe_raise("prefill_dispatch",
                                           step=self._step_id + 1)
                ex = self._compiled(("paged_prefill", bucket),
                                    self._prefill_fn, args,
                                    donate=donate)
                t_disp = time.perf_counter()
                with M.span("serving/prefill_dispatch"):
                    if start:
                        self.flight.prefix_hit(
                            req, start, tail,
                            saved_ms=M.cache.estimate_saved_ms(start))
                    self.flight.prefill_dispatched(req, bucket, 1)
                    first, self._toks, self._pos, *arrs = \
                        self._timed_call(("paged_prefill", bucket),
                                         ex, args)
            except BaseException as e:
                req.inflight -= 1
                sch.rollback_admission([req], pool)
                if self._absorb_dispatch_failure(
                        e, "prefill", [(req, alloc.slot)]):
                    return   # rolled back; the retry runs next step
                raise
            pool.rebind(*arrs)
            pool.commit_prefix(alloc.slot, ids)
            if self._counts_positions:
                self._prefilled(alloc.slot, start, tail)
            M.record_admission(req)
            self._stamp_prefill(req, t_disp, bucket)
            M.requests_admitted += 1
            M.prefills += 1
            M.record_prefill_arm(start)
            M.prefill_requests += 1
            M.record_prefill_group(1)
            M.record_prefix_reuse(start, tail, req.tenant_id)
            entry = ("prefill", first, [(req, alloc.slot)],
                     ("paged_prefill", bucket))
            if sync:
                self._harvest([entry])
            else:
                self._pending.append(entry)

    # ---------------------------------------------- chunked prefill

    @staticmethod
    def _stamp_prefill(req, t_dispatched, padded_tokens):
        """A prefill dispatch that stuck: when the request's first one
        went out, and the padded tokens the device computes for it
        (its bucket; the chunk width for every chunk)."""
        if req.t_prefill_dispatched is None:
            req.t_prefill_dispatched = t_dispatched
        req.prefill_tokens_dispatched += int(padded_tokens)

    @staticmethod
    def _samp_scalars(req):
        """Per-dispatch sampling scalars for singleton prefills (the
        chunk and paged-tail programs)."""
        from .sched import request_sampling_params
        seed, temp, topk, topp = request_sampling_params(req)
        return (np.int32(seed), np.float32(temp), np.int32(topk),
                np.float32(topp))

    def _register_chunked(self, req, alloc):
        """Queue a freshly admitted long prompt for chunk-by-chunk
        prefill and park its slot out of decode harvest."""
        # a slot with recurrent state cannot recompute rows it passed
        self._chunk_q.append(self._ChunkPlan(
            req, alloc.slot, alloc.prefix_tokens, self.chunk_len,
            tile=bool(self.cache_spec.slot_arrays)
            or self._window is not None))
        self._prefilling.add(alloc.slot)

    def _dispatch_chunks(self, sync):
        """Advance chunked prefills: dispatch chunks FIFO across the
        queued plans until the per-step token budget runs out. Every
        dispatch is the tail-prefill program at the chunk-width bucket
        (traced start/len/slot/final — any prompt-length mix, zero
        steady-state compiles). Interior chunks park the slot (no
        token emitted, decode ignores it); the FINAL chunk emits the
        first token, restores the slot to the decode set, and lands
        the deferred admission accounting — so a dispatch failure
        anywhere rolls the request back to the queue uncounted, the
        PR-6 rollback discipline."""
        sch, pool, M = self.scheduler, self.pool, self.metrics
        budget = self.prefill_token_budget
        C = self.chunk_len
        while self._chunk_q and budget > 0:
            plan = self._chunk_q[0]
            req = plan.req
            start, clen, final = plan.peek()
            if clen > budget:
                break           # FIFO: never skip ahead past the head
            tokens = np.zeros((1, C), np.int32)
            tokens[0, :clen] = plan.ids[start:start + clen]
            if self._window is not None:
                pool.grow(plan.slot,
                          self.cache_spec.entries(start + clen))
            args = (self.params, tokens, np.int32(clen),
                    np.int32(start), np.int32(plan.slot),
                    np.int32(1 if final else 0),
                    pool.table_row(plan.slot), self._toks,
                    self._pos) + tuple(pool.arrays)
            key, fn, donate = ("paged_prefill", C), \
                self._prefill_fn, tuple(range(8, len(args)))
            if self.sampling:
                args = args + self._samp_scalars(req)
            if final:
                req.inflight += 1
            try:
                if self.chaos is not None:
                    self.chaos.maybe_raise("chunk_dispatch",
                                           step=self._step_id + 1,
                                           chunk=plan.next)
                ex = self._compiled(key, fn, args, donate=donate)
                t_disp = time.perf_counter()
                with M.span("serving/chunk_dispatch"):
                    if plan.next == 0 and plan.start0:
                        self.flight.prefix_hit(
                            req, plan.start0,
                            len(plan.ids) - plan.start0,
                            saved_ms=M.cache.estimate_saved_ms(
                                plan.start0))
                    self.flight.prefill_chunk(req, plan.next, start,
                                              clen, final)
                    if final:
                        self.flight.prefill_dispatched(req, C, 1)
                    first, self._toks, self._pos, *arrs = \
                        self._timed_call(key, ex, args)
            except BaseException as e:
                if final:
                    req.inflight -= 1
                self._chunk_q.remove(plan)
                self._prefilling.discard(plan.slot)
                sch.rollback_admission([req], pool)
                if self._absorb_dispatch_failure(
                        e, "chunk", [(req, plan.slot)]):
                    return   # rolled back (all chunk progress voided;
                raise        # the retry re-plans from the queue)
            pool.rebind(*arrs)
            if self._counts_positions:
                self._prefilled(plan.slot, start, clen, final)
            M.record_prefill_chunk(clen)
            M.record_prefill_arm(start)
            self._stamp_prefill(req, t_disp, C)
            budget -= clen
            plan.advance()
            if final:
                self._chunk_q.pop(0)
                self._prefilling.discard(plan.slot)
                pool.commit_prefix(plan.slot, plan.ids)
                M.record_prefix_reuse(plan.start0, 0, req.tenant_id)
                M.record_admission(req)
                M.requests_admitted += 1
                M.prefill_requests += 1
                M.record_chunked_request()
                entry = ("prefill", first, [(req, plan.slot)], key)
                if sync:
                    self._harvest([entry])
                else:
                    self._pending.append(entry)

    # ------------------------------- entries that are not positions

    def _prefilled(self, slot, start, length, final=True):
        """A prefill run of ``length`` positions from window boundary
        ``start`` went out for ``slot``: a run that filled its window
        left it compacted; the final run sets where decode goes on."""
        if self._window is not None and length == self._window[0]:
            self.metrics.record_compaction(0)
        if final:
            self._hpos[slot] = start + length

    def _grow_for_decode(self, snapshot):
        """Before a decode dispatch: every slot it advances has the
        block its next entry falls in."""
        pool, entries = self.pool, self.cache_spec.entries
        for slot in snapshot:
            pool.grow(slot, entries(self._hpos[slot]) + 1)

    def _compact_args(self, slot, window):
        pool = self.pool
        args = (self.params, np.int32(window), pool.table_row(slot)) \
            + tuple(pool.arrays)
        return args, tuple(range(3, len(args)))

    def _after_decode(self, snapshot):
        """After a decode dispatch: every slot it advanced is one
        position on. One whose window that step FILLED is compacted now,
        before the next step goes out (``paged_compact``, one dispatch a
        slot, nothing read back), and the blocks behind its summaries go
        back to the pool. The gauges count what the live slots hold."""
        pool, spec, M = self.pool, self.cache_spec, self.metrics
        W = self._window[0]
        hpos = self._hpos
        entries = positions = 0
        for slot in snapshot:
            t = hpos[slot] = hpos[slot] + 1
            if t % W == 0:
                args, donate = self._compact_args(slot, t // W - 1)
                ex = self._compiled(("compact",), self._compact_fn, args,
                                    donate=donate)
                with M.span("serving/compact_dispatch"):
                    arrs = self._timed_call(("compact",), ex, args)
                pool.rebind(*arrs)
                M.record_compaction(pool.shrink(slot, spec.entries(t)))
            positions += t
            entries += spec.entries(t)
        M.set_cache_live(entries, positions)

    def _count_ring_cache(self, snapshot):
        """After a decode dispatch of a model with rings beside its
        blocks: every slot it advanced is one position on; the gauges
        count what those positions hold and what they would."""
        hpos = self._hpos
        positions = 0
        for slot in snapshot:
            t = hpos[slot] = hpos[slot] + 1
            positions += t
        self.metrics.set_ring_cache_live(positions, len(snapshot))

    # ------------------------------------------------------ resilience

    def _retryable(self, exc):
        """Whether a failed dispatch/transfer may be absorbed by the
        bounded-retry machinery: the engine must be hardened
        (max_dispatch_retries > 0) and the failure an ordinary
        Exception (KeyboardInterrupt & friends always propagate).
        Unhardened engines keep the PR-6 behavior bit-for-bit: roll
        back, then raise."""
        return self.max_dispatch_retries > 0 \
            and isinstance(exc, Exception)

    def _absorb_dispatch_failure(self, exc, kind, pairs):
        """Account a rolled-back prefill/chunk dispatch failure and
        decide its fate: True = absorbed (requests are back in the
        queue; retry next step, minus any whose budget ran out — those
        retire with reason "error"), False = caller re-raises. Also
        drives slot quarantine: the slot(s) the failed dispatch wrote
        through accumulate failure counts, and a slot that keeps
        failing is excluded from admission so one bad lane cannot eat
        every retry budget in the queue."""
        M = self.metrics
        M.record_dispatch_failure(kind)
        for req, slot in pairs:
            req.dispatch_failures += 1
            self.flight.dispatch_failed(req, kind, exc)
            self._slot_failures[slot] = \
                self._slot_failures.get(slot, 0) + 1
        if not self._retryable(exc):
            return False
        for req, slot in pairs:
            self._maybe_quarantine(slot)
            if req.dispatch_failures > self.max_dispatch_retries:
                self._abort_request(req, "error")
            else:
                M.record_retry()
        if self.retry_backoff_s > 0:
            worst = max(r.dispatch_failures for r, _ in pairs)
            self._retry_at = time.perf_counter() \
                + self.retry_backoff_s * (2 ** (worst - 1))
        return True

    def _absorb_decode_failure(self, exc):
        """The pooled decode dispatch failed. It advances EVERY slot,
        so the failure is not attributable to one request: the engine
        retries the whole step up to the budget, then escalates to
        the supervisor (repeated dispatch failure IS the wedge the
        in-process restart exists for). False = re-raise."""
        M = self.metrics
        M.record_dispatch_failure("decode")
        self._decode_fail_streak += 1
        if not self._retryable(exc):
            return False
        if self._decode_fail_streak <= self.max_dispatch_retries:
            M.record_retry()
            if self.retry_backoff_s > 0:
                self._retry_at = time.perf_counter() \
                    + self.retry_backoff_s \
                    * (2 ** (self._decode_fail_streak - 1))
            return True
        if self.supervisor is not None and self.supervisor.trigger(
                "dispatch_failure",
                {"detector": "dispatch_failure",
                 "streak": self._decode_fail_streak,
                 "error": f"{type(exc).__name__}: {exc}"[:200]}):
            return True
        return False

    def _maybe_quarantine(self, slot):
        """Quarantine ``slot`` once its failure count reaches the
        threshold — unless it is the last admissible slot (a fully
        quarantined pool would deadlock the queue; the supervisor's
        pool rebuild is the reset path)."""
        if self._slot_failures.get(slot, 0) < self.config.quarantine_after:
            return
        pool = self.pool
        if slot in pool.quarantined:
            return
        admissible = pool.num_slots - len(pool.quarantined)
        if admissible <= 1:
            return
        pool.quarantine(slot)
        self.metrics.record_quarantine()
        self._slot_failures.pop(slot, None)

    def _abort_request(self, req, reason):
        """Retire a request that exhausted its retry budget (it is
        already rolled back into the queue): counted, flight-closed,
        zero further tokens."""
        self.scheduler.abort(req, self.pool)
        self.metrics.record_abort(req.tenant_id)
        self.flight.retired(req, reason)
        if self.supervisor is not None:
            self.supervisor.note_completion(req.rid)

    def _expire_deadlines(self):
        """Retire requests past their ``deadline_ms`` (queued or
        actively decoding): timeout-counted, SLO-judged as violations,
        flight-retired with reason "deadline"."""
        now = time.perf_counter()
        expired_q, expired_a = self.scheduler.expire_deadlines(
            self.pool, prefilling=self._prefilling, now=now)
        for req in expired_q + expired_a:
            if req.hold_kv and req.slot is not None:
                # a dead-on-deadline handoff holds nothing: nobody
                # will export it, so the parked slot goes back now
                self.pool.release(req.slot)
                req.slot = None
            self.metrics.record_timeout(req.tenant_id)
            over = (now - req.t_arrival) * 1000.0 - req.deadline_ms
            self.flight.deadline_exceeded(req, over)
            self.flight.retired(req, "deadline",
                                slo_violations=["deadline"])
            if self.supervisor is not None:
                self.supervisor.note_completion(req.rid)

    def _supervisor_restart(self, reason):
        """In-process recovery (called ONLY by the supervisor): drop
        every piece of suspect state — in-flight device results, the
        pool's bookkeeping, the AOT executable table, per-slot failure
        tallies — and re-queue every request still owed tokens for a
        re-prefill of its prompt + already-emitted tokens. Greedy
        decoding makes the replay continuation bit-exact; the
        (rebuilt-empty) radix index re-warms as replays
        commit, so sibling requests sharing a prefix soften each
        other's recompute. Returns the re-queued requests; the whole
        recovery runs under a ``serving/supervisor_restart`` span and
        increments ``supervisor_restarts_total``."""
        M = self.metrics
        with M.span("serving/supervisor_restart"):
            sch = self.scheduler
            owed = {}
            for r in sch.active.values():
                owed[r.rid] = r
            for plan in self._chunk_q:
                owed.setdefault(plan.req.rid, plan.req)
            for entry in self._pending:
                coll = entry[2]
                rs = coll.values() if isinstance(coll, dict) \
                    else [r for r, _ in coll]
                for r in rs:
                    if r.state == RUNNING:  # prereleased finals too
                        owed.setdefault(r.rid, r)
            replayed = sorted(owed.values(), key=lambda r: r.rid)
            # unread device results are DISCARDED, not harvested: the
            # tokens they carry were never surfaced, and the greedy
            # replay regenerates them bit-exactly from clean state
            self._pending = []
            self._pending_steps.clear()
            self._chunk_q = []
            self._prefilling.clear()
            self._hpos.clear()
            sch.active.clear()
            # parked exports die with the pool: their blocks live in
            # the arrays being replaced, so there is nothing to stream
            # — the router re-drives the prefill on a healthy replica
            for r in self._held_exports.values():
                r.slot = None
            self._held_exports.clear()
            self.pool = self._new_pool()
            M.set_prefix_pool(self.pool.stats)
            M.cache.attach_pool(self.pool)
            import jax.numpy as jnp
            self._toks = jnp.zeros((self.config.num_slots,), jnp.int32)
            self._pos = jnp.zeros((self.config.num_slots,), jnp.int32)
            # rebuild the AOT table from scratch; the rebuild compiles
            # land under a reopened warmup (the supervisor re-declares
            # once the replay drains), so "zero steady-state compiles
            # outside supervisor restarts" stays a checkable invariant
            self._exec = {}
            self.watchdog.reopen_warmup()
            if self._spec is not None:
                # slot bindings and draft indices describe the
                # pre-restart schedule; replay re-syncs them from each
                # request's journaled prompt + generated tokens (and
                # parity never depends on draft content, so the
                # rebuilt drafter proposing differently is harmless)
                self._spec.reset()
            self._slot_failures.clear()
            self._decode_fail_streak = 0
            self._retry_at = 0.0
            self._restart_epoch += 1
            for req in reversed(replayed):
                req.slot = None
                req.state = QUEUED
                req.t_admitted = None
                req.inflight = 0
                req.dispatch_failures = 0
                sch.queue.appendleft(req)
                self.flight.requeued(req, reason)
            M.record_restart()
        return replayed

    def _resilience_state(self):
        """The live half of ``snapshot()["resilience"]``."""
        sup = self.supervisor
        return {
            "quarantined_slots": list(self.pool.quarantined),
            "draining": self._draining,
            "supervisor": sup.report() if sup is not None
            else {"enabled": False},
            "chaos": self.chaos.report() if self.chaos is not None
            else {"enabled": False},
        }

    def _health_resilience(self):
        """The replica-posture facts ``/debug/health`` folds in."""
        sup = self.supervisor
        return {
            "degraded": sup.degraded if sup is not None else False,
            "draining": self._draining,
            "restarts": sup.restarts if sup is not None else 0,
        }

    def run(self):
        """Drain the queue: step until every submitted request is done.
        Returns the completed requests in SUBMISSION order (sorted by
        rid — the scheduler's own completed list is finish-ordered)."""
        while self.step():
            pass
        return sorted(self.scheduler.completed, key=lambda r: r.rid)
