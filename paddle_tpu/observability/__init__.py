"""Unified observability layer: metrics registry, host-span chrome
tracing, compile watchdog.

The reference stack treats observability as a platform subsystem
(profiler.h RecordEvent/EnableProfiler, the CUPTI DeviceTracer
timeline, tools/timeline.py). This package is its operational,
TPU-native generalization, built around ONE instrumentation point:
``paddle_tpu.profiler.record_scope(name)`` feeds three sinks at once —

  1. the **XLA trace**  (TraceAnnotation + named_scope: op metadata in
     a live XPlane capture, as before);
  2. the **host timeline** (tracing.HostSpanRecorder: a bounded ring
     buffer dumpable as chrome://tracing / Perfetto JSON, no capture
     session needed);
  3. the **dashboard** (registry.default_registry(): per-scope
     seconds + call counters, scrapeable as Prometheus text).

``profiler.host_scope(name)`` is the same three sinks without the
named_scope, for sections under which nothing is staged. The serving
engine and its gateway, the DataLoader, to_static and the hapi training
loop all instrument through the two, so `serving/*`, `io/*`, `jit/*`,
`hapi/*` and `optimizer/*` scopes land in all three views. The third pillar, watchdog.CompileWatchdog, turns the
serving engine's exact compile counter into an ATTRIBUTED invariant:
every compile logs its key + abstract-shape signature + call-site,
and any compile after ``declare_warmup_complete()`` is flagged (or
raised) with that attribution.

Quick start::

    from paddle_tpu import observability as obs

    reg = obs.MetricsRegistry()
    reqs = reg.counter("requests_total", "requests served")
    reqs.inc()
    print(reg.prometheus_text())          # scrape format
    server = obs.start_metrics_server(reg)  # GET /metrics, /metrics.json

    obs.default_recorder().dump_chrome_trace("host_trace.json")
    # -> open in chrome://tracing or ui.perfetto.dev

PR 4 adds the REQUEST-level layer on top: flight.FlightRecorder gives
every serving request a lifecycle trace (enqueued -> admitted ->
prefill -> first token -> retired) flow-linked across engine step
spans in the chrome trace; slo.SLOTracker accounts SLO attainment,
goodput tokens, and sliding-window (registry.WindowedReservoir)
p50/p90/p99; watchdog compile records carry device cost telemetry
(executable_cost / device_memory_stats — graceful None on backends
that don't report). start_metrics_server() now returns a cleanly
stoppable MetricsServerHandle and mounts engine debug endpoints
(/debug/requests, /debug/state) via extra_routes.

PR 8 closes the loop with the health observatory (health/): a per-step
ledger of structured engine-state rows, pluggable online anomaly
detectors (step-time spike, queue stall, goodput collapse, KV-block
leak, steady-state compile) counted in
``serving_anomalies_total{detector}``, and debounced black-box
incident bundles on disk — rolled up at ``/debug/health`` (the
per-replica router signal) and ``/debug/ledger``.

PR 10 adds the performance observatory (perf/): per-program
device-time attribution (every AOT dispatch's measured dispatch/sync
wall accumulated per program key — ``snapshot()["perf"]``,
``/debug/perf``), a decode-step roofline model joined with
``executable_cost`` into ``serving_roofline_fraction{program}``, and
the cross-run perf ledger + ``tools/perf_diff.py`` regression gate.

PR 13 adds the cache observatory (cache/): SHARDS-style sampled
reuse-distance / miss-ratio-curve estimation over the paged KV block
economy ("what would hit-rate be at 2x capacity" — the ROADMAP-#5
spill-tier sizing tool), the top-K hot-prefix heat digest (the
ROADMAP-#2 router affinity signal), per-request cache-savings
attribution (cached tokens x measured per-token prefill cost ->
estimated TTFT ms saved), and eviction-churn telemetry (block
lifetimes + the radix thrash counter feeding the ``cache_thrash``
detector) — rolled up at ``snapshot()["cache"]`` / ``/debug/cache``
and merged exactly into the fleet view.

PR 11 adds the fleet observatory (fleet/): replica identity
(``replica_id`` / ``serving_uptime_seconds`` /
``paddle_tpu_build_info`` on every engine), a resilient
multi-replica scrape poller (per-replica timeout, backoff, staleness,
eviction/readmission ``up|stale|down`` verdicts), federated rollups
whose counters sum and fixed-bucket histograms merge bucket-wise
(fleet percentiles from merged buckets, never averaged percentiles),
``scope="fleet"`` detectors (replica_flap / fleet_goodput_collapse /
load_skew), and a FleetServer exposing ``/fleet/health`` /
``/fleet/state`` / ``/fleet/metrics`` — the surface the ROADMAP
direction-#2 router consumes.

PR 39 adds device time in the program's own layer names. The models name
their parts with ``profiler.device_scope`` (a ``jax.named_scope`` whose
stack the tape keeps, so a compiled step's backward ops read
``bwd/block/mlp``), and ``watchdog.program_scopes()`` keeps, for every
program the engine's AOT table or a ``to_static`` step built, which
scope each instruction belongs to (text and parsed pairs only: no
Tensor, no buffer, and no executable once its engine or step is gone).
An operator gets device time by layer from a capture in three steps:
``GET /debug/programs`` on the engine's metrics server shows each
program's signature, cost, memory and instructions per scope; a cell
run through ``benchmarks/tools/scope_report.py --workload <cell>`` keeps
the trace with its table beside it; the same tool on those two files
prints device self time by program and scope, no chip needed.
"""
from .cache import (  # noqa: F401
    CACHE_KEYS, CacheObservatory, ReuseDistanceSampler,
    disabled_cache_report, exact_mrc, merge_heat_digests,
    merge_mrc_points, top_prefix_digest,
)
from .fleet import (  # noqa: F401
    FleetPoller, FleetServer, ReplicaIdentity, default_replica_id,
)
from .flight import (  # noqa: F401
    FlightRecorder, RequestTrace,
)
from .health import (  # noqa: F401
    HealthMonitor, IncidentRecorder, LEDGER_ROW_KEYS, StepLedger,
    build_detectors, detector_names, disabled_health_summary,
    register_detector, unregister_detector,
)
from .perf import (  # noqa: F401
    PERF_KEYS, PERF_PROGRAM_KEYS, PERF_SPEC_KEYS, ProgramPerf,
    disabled_perf_report, disabled_spec_report, format_program_key,
    hbm_bps_for,
)
from .registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, MetricsServerHandle,
    Reservoir, WindowedReservoir, DEFAULT_TIME_BUCKETS,
    default_registry, merge_histogram_snapshots,
    percentile_from_buckets, prometheus_text_from_snapshots,
    start_metrics_server,
)
from .slo import SLOTracker  # noqa: F401
from .tenant import (  # noqa: F401
    TENANT_ENTRY_KEYS, TENANT_KEYS, TenantLedger,
    disabled_tenant_report,
)
from .tracing import (  # noqa: F401
    FlowEvent, HostSpan, HostSpanRecorder, default_recorder, span_timer,
)
from .watchdog import (  # noqa: F401
    CompileAfterWarmupError, CompileWatchdog, abstract_signature,
    device_memory_stats, executable_cost, executable_memory,
    program_scopes, watch_jax_lowering,
)
