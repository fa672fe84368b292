"""Unified observability layer (paddle_tpu.observability): metrics
registry + Prometheus exposition, bounded host-span chrome tracing,
and the compile watchdog — including the serving-engine integration
(snapshot schema contract, zero steady-state recompiles as an
ATTRIBUTED invariant, induced shape drift flagged with its call-site).

Acceptance criteria pinned here: the emitted chrome trace is valid
JSON with nesting spans and stable pid/tids; Prometheus text parses
(TYPE/HELP lines, label escaping); every engine compile is attributed.
"""
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler as prof_mod
from paddle_tpu.observability import (
    CompileAfterWarmupError, CompileWatchdog, HostSpanRecorder,
    MetricsRegistry, Reservoir, abstract_signature, start_metrics_server,
    watch_jax_lowering,
)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig


def _model(seed=7):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _drive(eng, rs, specs):
    for n, k in specs:
        eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                        max_new_tokens=k)
    eng.run()


# --------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)            # counters are monotone
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec(2)
    assert g.value == 5
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    assert h.count == 3 and h.sum == pytest.approx(2.55)
    # re-registration returns the same family; kind mismatch raises
    assert reg.counter("reqs_total") is c
    with pytest.raises(ValueError):
        reg.gauge("reqs_total")


def test_labeled_families_and_snapshot_stability():
    reg = MetricsRegistry()
    c = reg.counter("rpc_total", "calls", labelnames=("route", "code"))
    c.labels("generate", "200").inc(3)
    c.labels(route="health", code="500").inc()
    with pytest.raises(ValueError):
        c.inc()              # labeled family needs .labels(...)
    with pytest.raises(ValueError):
        c.labels("only-one")
    snap = reg.snapshot()
    assert snap["rpc_total"]["type"] == "counter"
    assert snap["rpc_total"]["values"]["route=generate,code=200"] == 3
    # snapshot is stable JSON: serializable and key-sorted reproducible
    assert json.loads(reg.snapshot_json()) == json.loads(
        reg.snapshot_json())


def test_label_cardinality_guard_folds_flood():
    """PR-19 registry hardening: a label flood costs O(cap) series —
    past ``max_label_values`` distinct tuples, new values fold into
    the shared ``~other`` series and the fold is counted in the
    lazily-registered ``metrics_label_overflow_total{family}``."""
    reg = MetricsRegistry(max_label_values=4)
    c = reg.counter("flood_total", "flood", labelnames=("who",))
    for i in range(100):
        c.labels(f"tenant-{i}").inc()
    snap = reg.snapshot()
    series = snap["flood_total"]["values"]
    assert len(series) == 5                # 4 distinct + ~other
    assert series["who=~other"] == 96      # every fold lands there
    assert sum(series.values()) == 100     # nothing dropped
    over = snap["metrics_label_overflow_total"]["values"]
    assert over["family=flood_total"] == 96
    # a tuple minted BEFORE the cap keeps accruing to its own series
    c.labels("tenant-2").inc(9)
    assert reg.snapshot()["flood_total"]["values"]["who=tenant-2"] == 10
    # two-label families fold EVERY position (one aggregate series)
    g = reg.gauge("depth", "d", labelnames=("a", "b"))
    for i in range(10):
        g.labels(str(i), str(i)).set(1)
    assert "a=~other,b=~other" in reg.snapshot()["depth"]["values"]
    # max_label_values=0 disables the guard entirely
    free = MetricsRegistry(max_label_values=0)
    f = free.counter("free_total", "f", labelnames=("who",))
    for i in range(300):
        f.labels(f"t{i}").inc()
    assert len(free.snapshot()["free_total"]["values"]) == 300
    assert "metrics_label_overflow_total" not in free.snapshot()


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n_total")
    h = reg.histogram("v_seconds", buckets=(0.5,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})? (\S+)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prometheus(text):
    """Minimal format-0.0.4 parser: returns (types, samples)."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
        elif line.startswith("# HELP "):
            assert line.split(" ", 3)[2]  # named help line
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"unparseable sample line: {line!r}"
            labels = dict(
                (k, v) for k, v in _LABEL_RE.findall(m.group(3) or ""))
            samples.append((m.group(1), labels, float(m.group(4))))
    return types, samples


def test_prometheus_text_parses_with_label_escaping():
    reg = MetricsRegistry()
    c = reg.counter("odd_total", "weird labels", labelnames=("k",))
    nasty = 'a"b\\c\nd'
    c.labels(nasty).inc(2)
    reg.gauge("g", "a gauge").set(1.5)
    reg.histogram("h_seconds", "hist", buckets=(0.01, 1.0)).observe(0.5)
    types, samples = _parse_prometheus(reg.prometheus_text())
    assert types == {"odd_total": "counter", "g": "gauge",
                     "h_seconds": "histogram"}
    # the escaped label value round-trips through the parser
    (name, labels, value), = [s for s in samples if s[0] == "odd_total"]
    unescaped = (labels["k"].replace("\\\\", "\0").replace('\\"', '"')
                 .replace("\\n", "\n").replace("\0", "\\"))
    assert unescaped == nasty and value == 2
    # histogram exposition: cumulative le buckets ending at +Inf, with
    # the _sum/_count pair
    hb = [(s[1]["le"], s[2]) for s in samples if s[0] == "h_seconds_bucket"]
    assert [b for b, _ in hb] == ["0.01", "1", "+Inf"]
    assert [c for _, c in hb] == [0.0, 1.0, 1.0]  # cumulative
    assert ("h_seconds_count", {}, 1.0) in samples
    # every sample belongs to a TYPEd family
    for name, _, _ in samples:
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in types or base in types


def test_poisoned_gauge_callback_does_not_kill_scrape():
    """A set_function callback that raises at scrape time must not
    take down the whole exposition: the series exports NaN, every
    other metric still scrapes, and the failure is counted in
    metrics_scrape_errors_total{metric} (registered lazily — a clean
    registry exposes no error family)."""
    import math

    reg = MetricsRegistry()
    reg.counter("fine_total", "healthy neighbor").inc(3)
    g = reg.gauge("poisoned", "always raises")
    g.set_function(lambda: 1 / 0)
    # clean registries never grew the error family (lazy registration)
    assert reg.get("metrics_scrape_errors_total") is None
    text = reg.prometheus_text()             # does not raise
    types, samples = _parse_prometheus(text)
    by_name = {name: value for name, labels, value in samples}
    assert by_name["fine_total"] == 3        # neighbors survive
    assert math.isnan(by_name["poisoned"])   # canonical NaN spelling
    # the failure was counted (the family registers lazily mid-scrape,
    # so it rides along from the NEXT exposition onward)
    assert reg.get("metrics_scrape_errors_total") \
        .labels("poisoned").value == 1
    _, samples = _parse_prometheus(reg.prometheus_text())
    errs = [(labels, v) for name, labels, v in samples
            if name == "metrics_scrape_errors_total"]
    assert errs == [({"metric": "poisoned"}, 1.0)]
    # snapshot() is the second exposition surface: same survival, and
    # the counter keeps counting per failed scrape
    snap = reg.snapshot()
    assert snap["fine_total"]["values"][""] == 3
    assert math.isnan(snap["poisoned"]["values"][""])
    assert reg.get("metrics_scrape_errors_total") \
        .labels("poisoned").value == 3       # one per failed scrape
    # a labeled pull gauge attributes the error to its family name
    fam = reg.gauge("labeled_pull", "per-series pulls",
                    labelnames=("which",))
    fam.labels("bad").set_function(lambda: {}["missing"])
    fam.labels("good").set_function(lambda: 7.0)
    _, samples = _parse_prometheus(reg.prometheus_text())
    vals = {tuple(sorted(lb.items())): v for name, lb, v in samples
            if name == "labeled_pull"}
    assert vals[(("which", "good"),)] == 7.0
    assert math.isnan(vals[(("which", "bad"),)])
    assert reg.get("metrics_scrape_errors_total") \
        .labels("labeled_pull").value == 1


def test_metric_name_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad-name")
    with pytest.raises(ValueError):
        reg.counter("9starts_with_digit")
    with pytest.raises(ValueError):
        reg.counter("ok_total", labelnames=("bad-label",))


def test_reservoir_bounded_and_percentiles():
    res = Reservoir(capacity=100)
    for v in range(10000):
        res.add(float(v))
    assert len(res.samples()) == 100       # bounded under 100x overflow
    assert res.seen == 10000
    # uniform sample of 0..9999: median lands near 5000
    assert 2500 < res.percentile(50) < 7500
    assert res.percentile(0) >= 0 and res.percentile(100) <= 9999
    empty = Reservoir(4)
    assert empty.percentile(50) is None


def test_http_metrics_endpoint():
    reg = MetricsRegistry()
    reg.counter("served_total", "hits").inc(5)
    server = start_metrics_server(reg, port=0)
    try:
        port = server.server_address[1]
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        types, samples = _parse_prometheus(text)
        assert ("served_total", {}, 5.0) in samples
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
        assert js["served_total"]["values"][""] == 5
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=10)
    finally:
        server.shutdown()


# ---------------------------------------------------------- host tracing

def test_ring_buffer_is_bounded():
    rec = HostSpanRecorder(capacity=4)
    for i in range(10):
        rec.record(f"s{i}", t0=float(i), dur=0.5)
    assert len(rec) == 4
    assert [s.name for s in rec.spans()] == ["s6", "s7", "s8", "s9"]
    assert rec.dropped == 6
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_record_scope_feeds_three_sinks():
    """One record_scope: XPlane annotation (not assertable without a
    live capture — covered by test_profiler), host span ring buffer,
    and the default-registry span counters."""
    rec = obs.default_recorder()
    reg = obs.default_registry()
    rec.clear()
    calls_before = reg.get("host_span_calls_total") \
        .labels("obs_test/scope").value
    with prof_mod.record_scope("obs_test/scope"):
        with prof_mod.record_scope("obs_test/inner"):
            pass
    names = [s.name for s in rec.spans()]
    assert "obs_test/scope" in names and "obs_test/inner" in names
    assert reg.get("host_span_calls_total") \
        .labels("obs_test/scope").value == calls_before + 1
    assert reg.get("host_span_seconds_total") \
        .labels("obs_test/scope").value > 0


def _overlap_partially(a, b):
    """True if events a and b overlap without containment."""
    a0, a1 = a["ts"], a["ts"] + a["dur"]
    b0, b1 = b["ts"], b["ts"] + b["dur"]
    if a1 <= b0 or b1 <= a0:
        return False                       # disjoint
    eps = 0.5                              # us rounding slack
    contained = (a0 >= b0 - eps and a1 <= b1 + eps) or \
        (b0 >= a0 - eps and b1 <= a1 + eps)
    return not contained


def test_chrome_trace_valid_nesting_stable_pids(tmp_path):
    """Acceptance: the engine's chrome trace is valid JSON, every X
    event carries name/ts/dur/pid/tid, pid is stable, and spans on a
    thread either nest or are disjoint — with real serving/step >
    serving/harvest > serving/sync containment present."""
    rec = obs.default_recorder()
    rec.clear()
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    _drive(eng, np.random.RandomState(0), [(5, 4), (9, 5), (12, 3)])
    path = str(tmp_path / "host_trace.json")
    eng_trace = rec.dump_chrome_trace(path)
    with open(eng_trace) as fh:
        trace = json.load(fh)              # valid JSON
    events = trace["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no spans captured"
    for e in xs:
        assert e["name"] and e["dur"] >= 0 and e["ts"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    assert len({e["pid"] for e in xs}) == 1          # stable pid
    # metadata names the process/threads (Perfetto track labels)
    metas = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in metas)
    # spans nest: no partial overlap on any thread
    by_tid = {}
    for e in xs:
        by_tid.setdefault(e["tid"], []).append(e)
    for tid_events in by_tid.values():
        tid_events.sort(key=lambda e: e["ts"])
        for i, a in enumerate(tid_events):
            for b in tid_events[i + 1:]:
                if b["ts"] >= a["ts"] + a["dur"]:
                    break
                assert not _overlap_partially(a, b), (a, b)
    # real containment: a serving/sync span inside a serving/step span
    steps = [e for e in xs if e["name"] == "serving/step"]
    syncs = [e for e in xs if e["name"] == "serving/sync"]
    assert steps and syncs
    assert any(s["ts"] >= t["ts"] and
               s["ts"] + s["dur"] <= t["ts"] + t["dur"] + 0.5
               for s in syncs for t in steps), "sync never nested in step"


# --------------------------------------------------------------- watchdog

def test_watchdog_flags_after_warmup_with_attribution():
    wd = CompileWatchdog()
    wd.record("k1", "f32[8]")
    assert not wd.report()["steady_state_compiles"]
    wd.declare_warmup_complete()
    ev = wd.record("k2", "f32[16]")
    assert ev["steady_state"]
    rep = wd.report()
    assert rep["compiles_total"] == 2
    assert rep["warmup_compiles"] == 1
    assert rep["steady_state_compiles"] == 1
    viol = rep["steady_state_events"][0]
    assert viol["key"] == "k2" and viol["signature"] == "f32[16]"
    # default call-site attribution: this test file, this function
    assert "test_observability.py" in viol["call_site"]
    assert "test_watchdog_flags_after_warmup" in viol["call_site"]


def test_watchdog_raise_mode():
    wd = CompileWatchdog(mode="raise")
    wd.record("k", "sig")
    wd.declare_warmup_complete()
    with pytest.raises(CompileAfterWarmupError) as ei:
        wd.record("k2", "f32[4,4]")
    msg = str(ei.value)
    assert "k2" in msg and "f32[4,4]" in msg and \
        "test_observability.py" in msg
    with pytest.raises(ValueError):
        CompileWatchdog(mode="explode")


def test_abstract_signature_distinguishes_shapes():
    import jax.numpy as jnp
    a = (jnp.zeros((4, 8), jnp.float32), jnp.zeros((3,), jnp.int32))
    b = (jnp.zeros((4, 9), jnp.float32), jnp.zeros((3,), jnp.int32))
    sa, sb = abstract_signature(a), abstract_signature(b)
    assert sa != sb
    assert sa == abstract_signature(
        (jnp.ones((4, 8), jnp.float32), jnp.ones((3,), jnp.int32)))
    assert "float32[4,8]" in sa and "int32[3]" in sa


def test_watch_jax_lowering_records_generic_compiles():
    import jax
    import jax.numpy as jnp

    wd = CompileWatchdog()
    with watch_jax_lowering(wd):
        jax.jit(lambda x: x * 2).lower(jnp.ones((5,))).compile()
    assert wd.compiles == 1
    ev = wd.events()[0]
    assert ev["key"] == "jax.Lowered.compile"
    assert "test_observability.py" in ev["call_site"]
    # the patch is gone after the block
    import jax.stages
    assert jax.stages.Lowered.compile.__qualname__.startswith("Lowered")


# ------------------------------------------------- serving integration

# ServingMetrics.snapshot() schema contract: bench artifacts and the
# driver tail-parse these keys across PRs — additions are fine,
# renames/removals break parseability and fail here.
_SNAPSHOT_KEYS = {
    "tokens_generated", "tokens_per_sec", "ttft_avg_ms", "queue_depth",
    "slot_occupancy", "prefills", "prefill_requests", "prefill_groups",
    "prefills_without_prefix", "prefills_with_prefix",
    "prefill_prefix_tokens_read",
    "decode_steps", "speculative_masked", "kv_donation", "compiles",
    "requests_admitted", "requests_completed", "dispatch_s", "sync_s",
    "span_s", "latency_percentiles", "slo", "prefix_cache",
    "scheduler", "health", "resilience", "perf", "replica", "cache",
    "trace", "tenants",
}
_SCHEDULER_KEYS = {
    "policy", "prefill_chunk", "prefill_token_budget", "shed",
    "shed_total", "deprioritized", "prefill_chunks",
    "chunked_requests",
}
_PCT_KEYS = {"count", "p50_ms", "p90_ms", "p99_ms"}
# the PR-8 health observatory section: enabled flag + anomaly rollup
# (same key set whether the observatory is on or off)
_HEALTH_KEYS = {
    "enabled", "healthy", "anomalies_total", "detectors",
    "incidents_written", "last_incident", "ledger_steps",
    "degraded", "draining", "restarts",
    # PR 11 replica attribution: which replica this health body is
    "replica_id", "uptime_s",
}
# the PR-11 replica identity section (snapshot()["replica"], also on
# /debug/state and incident bundles)
_REPLICA_KEYS = {"replica_id", "uptime_s", "started_at"}
# the PR-9 resilience section: failure/retry/timeout/abort counters +
# quarantine, supervisor and chaos state (same key set hardened or not)
_RESILIENCE_KEYS = {
    "dispatch_failures", "dispatch_failures_total", "dispatch_retries",
    "requests_timed_out", "requests_aborted", "callback_errors",
    "slots_quarantined_total", "faults_injected",
    "supervisor_restarts", "quarantined_slots", "draining",
    "supervisor", "chaos",
}
# the PR-10 performance observatory section: per-program measured
# time + roofline fractions (same key set whether perf is on or off);
# PR 16 adds the speculative-decoding economy under "spec"
_PERF_KEYS = {
    "enabled", "device", "programs", "attributed_s", "step_total_s",
    "attributed_fraction", "decode_roofline", "spec",
}
_PERF_SPEC_KEYS = {
    "enabled", "k", "drafted_tokens", "accepted_tokens",
    "rejected_tokens", "emitted_tokens", "verify_steps", "slot_steps",
    "fallback_steps", "acceptance_rate",
    "effective_tokens_per_dispatch",
}
_PERF_PROGRAM_KEYS = {
    "dispatches", "dispatch_s", "syncs", "sync_s", "total_s",
    "avg_ms", "cost", "roofline_floor_ms", "roofline_fraction",
    "bound",
}
# the PR-13 cache observatory section: MRC + heat + savings + churn
# (same key set whether the observatory is on or off)
_CACHE_KEYS = {
    "enabled", "accesses", "hits", "hit_rate", "capacity_blocks",
    "sampled", "mrc", "heat", "savings", "churn",
}
# the PR-19 tenant observatory section: per-tenant attribution rows +
# overflow accounting (same key set whether the ledger is on or off)
_TENANT_KEYS = {
    "enabled", "max_tenants", "tenant_count", "overflow", "tenants",
}
_TENANT_ENTRY_KEYS = {
    "requests", "completed", "tokens_in", "tokens_out",
    "goodput_tokens", "attained", "attainment", "violations", "shed",
    "timeouts", "aborts", "cache_saved_tokens", "cache_saved_ms",
    "queued", "queue_wait", "ttft",
}


def test_serving_snapshot_schema_contract(monkeypatch):
    # the CPU has no peaks of its own; state some so the roofline
    # join (cost x measured wall x peaks) is exercised
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_BPS", "819e9")
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    _drive(eng, np.random.RandomState(1), [(4, 3), (9, 4), (6, 3)])
    snap = eng.metrics.snapshot()
    assert set(snap) == _SNAPSHOT_KEYS
    json.dumps(snap)                       # artifact-embeddable
    # the PR-7 scheduling section: policy identity + chunk config +
    # shed/defer/chunk decision counters (all zero on a default FIFO
    # whole-prompt engine, but the SCHEMA is the contract)
    sched = snap["scheduler"]
    assert set(sched) == _SCHEDULER_KEYS
    assert sched["policy"] == "fifo" and sched["shed_total"] == 0
    assert sched["prefill_chunks"] == 0
    # the PR-8 health section: observatory on by default, clean run
    # fires nothing, and the default detector roster is the surface
    health = snap["health"]
    assert set(health) == _HEALTH_KEYS
    assert health["enabled"] is True and health["healthy"] is True
    assert health["anomalies_total"] == 0
    assert set(health["detectors"]) == {
        "cache_thrash", "goodput_collapse", "kv_block_leak",
        "queue_stall", "steady_state_compile", "step_time_spike"}
    assert health["ledger_steps"] > 0
    # the PR-9 resilience section: schema + clean-run zeros + the
    # supervisor enabled by default alongside the observatory
    res = snap["resilience"]
    assert set(res) == _RESILIENCE_KEYS
    assert res["dispatch_failures_total"] == 0
    assert res["requests_timed_out"] == 0
    assert res["requests_aborted"] == 0
    assert res["callback_errors"] == 0
    assert res["quarantined_slots"] == []
    assert res["draining"] is False
    assert res["supervisor"]["enabled"] is True
    assert res["supervisor"]["restarts"] == 0
    assert res["chaos"] == {"enabled": False}   # chaos is opt-in
    # health=False keeps the SAME key shape (schema contract holds)
    eng_off = ServingEngine(m, num_slots=2, bucket_min=8, health=False)
    _drive(eng_off, np.random.RandomState(1), [(4, 3)])
    off = eng_off.metrics.snapshot()["health"]
    assert set(off) == _HEALTH_KEYS
    assert off["enabled"] is False and off["ledger_steps"] == 0
    off_res = eng_off.metrics.snapshot()["resilience"]
    assert set(off_res) == _RESILIENCE_KEYS
    assert off_res["supervisor"] == {"enabled": False}
    # the PR-10 perf section: per-program measured time + roofline
    # fractions, decode always among the attributed programs
    perf = snap["perf"]
    assert set(perf) == _PERF_KEYS
    assert perf["enabled"] is True
    # the spec sub-section keeps its shape with speculation off
    assert set(perf["spec"]) == _PERF_SPEC_KEYS
    assert perf["spec"]["enabled"] is False
    assert "decode" in perf["programs"]
    for entry in perf["programs"].values():
        assert set(entry) == _PERF_PROGRAM_KEYS
        assert entry["dispatches"] > 0
        assert entry["total_s"] >= entry["dispatch_s"] >= 0
    assert perf["programs"]["decode"]["roofline_fraction"] is not None
    assert perf["decode_roofline"]["achieved_fraction"] is not None
    assert 0 < perf["attributed_s"] <= perf["step_total_s"]
    # perf=False keeps the SAME key shape (schema contract holds)
    eng_noperf = ServingEngine(m, num_slots=2, bucket_min=8,
                               perf=False)
    _drive(eng_noperf, np.random.RandomState(1), [(4, 3)])
    off_perf = eng_noperf.metrics.snapshot()["perf"]
    assert set(off_perf) == _PERF_KEYS
    assert off_perf["enabled"] is False and off_perf["programs"] == {}
    assert set(off_perf["spec"]) == _PERF_SPEC_KEYS
    # the PR-11 replica identity: a stable host:pid default id, a
    # live uptime clock, and the same facts on the health section
    rep = snap["replica"]
    assert set(rep) == _REPLICA_KEYS
    assert rep["replica_id"] and ":" in rep["replica_id"]
    assert rep["uptime_s"] > 0
    assert health["replica_id"] == rep["replica_id"]
    assert health["uptime_s"] > 0
    # the PR-13 cache observatory section: on by default, the three
    # admissions counted even though no prompt here fills a block
    cache = snap["cache"]
    assert set(cache) == _CACHE_KEYS
    assert cache["enabled"] is True and cache["capacity_blocks"] > 0
    assert cache["hits"] == 0
    # with blocks the prompts fill: factor-stamped MRC, and
    # cache_observatory=False degrades to the disabled shape
    eng_paged = ServingEngine(m, num_slots=2, bucket_min=8,
                              block_size=8)
    _drive(eng_paged, np.random.RandomState(1), [(9, 3), (9, 3)])
    live = eng_paged.metrics.snapshot()["cache"]
    assert set(live) == _CACHE_KEYS
    assert live["enabled"] is True
    assert live["accesses"] > 0 and live["capacity_blocks"] > 0
    assert [p["factor"] for p in live["mrc"]] == [0.5, 1.0, 2.0, 4.0]
    assert set(live["churn"]) == {"evictions", "thrash_reinserts",
                                  "block_lifetime_ms"}
    eng_nocache = ServingEngine(m, num_slots=2, bucket_min=8,
                                block_size=8,
                                cache_observatory=False)
    _drive(eng_nocache, np.random.RandomState(1), [(9, 3)])
    off_cache = eng_nocache.metrics.snapshot()["cache"]
    assert set(off_cache) == _CACHE_KEYS
    assert off_cache["enabled"] is False
    # the PR-19 tenant observatory: on by default, all three requests
    # attributed to the implicit "default" tenant, entry schema pinned
    ten = snap["tenants"]
    assert set(ten) == _TENANT_KEYS
    assert ten["enabled"] is True
    assert ten["overflow"]["folded_events"] == 0
    assert set(ten["tenants"]) == {"default"}
    entry = ten["tenants"]["default"]
    assert set(entry) == _TENANT_ENTRY_KEYS
    assert entry["requests"] == 3 and entry["completed"] == 3
    # max_tenants=0 disables the ledger but keeps the SAME key shape
    eng_noten = ServingEngine(m, num_slots=2, bucket_min=8,
                              max_tenants=0)
    _drive(eng_noten, np.random.RandomState(1), [(4, 3)])
    off_ten = eng_noten.metrics.snapshot()["tenants"]
    assert set(off_ten) == _TENANT_KEYS
    assert off_ten["enabled"] is False and off_ten["tenants"] == {}
    pcts = snap["latency_percentiles"]
    assert set(pcts) == {"ttft", "request_latency", "queue_wait"}
    for entry in pcts.values():
        assert set(entry) == _PCT_KEYS
        assert entry["count"] == 3
        assert entry["p50_ms"] <= entry["p90_ms"] <= entry["p99_ms"]
    # ttft <= full request latency, always
    assert pcts["ttft"]["p50_ms"] <= pcts["request_latency"]["p50_ms"]


def test_serving_latency_series_bounded():
    """The unbounded ttft/request-latency lists are gone: sustained
    traffic keeps the reservoir at its fixed capacity while the
    histogram keeps exact totals."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    eng.metrics._res["ttft"] = Reservoir(8)    # tiny cap to see it bind
    rs = np.random.RandomState(2)
    _drive(eng, rs, [(int(n), 2) for n in rs.randint(2, 12, 20)])
    assert len(eng.metrics.ttft_s) == 8
    assert eng.metrics._res["ttft"].seen == 20
    assert eng.metrics._h_ttft.count == 20     # exact count kept
    assert eng.metrics.snapshot()["latency_percentiles"]["ttft"][
        "count"] == 20


def test_serving_prometheus_exposition():
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    _drive(eng, np.random.RandomState(3), [(5, 3), (11, 4)])
    types, samples = _parse_prometheus(eng.metrics.prometheus_text())
    assert types["serving_compiles_total"] == "counter"
    assert types["serving_ttft_seconds"] == "histogram"
    assert types["serving_queue_depth"] == "gauge"
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["serving_tokens_generated_total"][0][1] == 7
    # per-scope span counters carry the engine step anatomy
    span_labels = {lb["span"] for lb, _ in
                   by_name["serving_span_seconds_total"]}
    assert {"serving/step", "serving/admit", "serving/harvest",
            "serving/retirement"} <= span_labels


def test_engine_watchdog_zero_steady_state_and_induced_drift():
    """Tier-1 invariant: past warmup, identical traffic compiles
    NOTHING (watchdog-attributed, not just counter equality) — and an
    induced shape drift (a never-warmed bucket) is flagged with the
    engine dispatch call-site and its abstract-shape signature."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    rs = np.random.RandomState(4)
    wave = [(3, 4), (7, 4), (12, 3), (14, 4)]
    _drive(eng, rs, wave)
    warm = eng.metrics.compiles
    assert eng.watchdog.report()["compiles_total"] == warm
    eng.declare_warmup()
    _drive(eng, rs, wave)                  # steady state: same traffic
    rep = eng.watchdog.report()
    assert rep["warmed"] and rep["steady_state_compiles"] == 0
    # induced drift: a prompt in a bucket never compiled
    _drive(eng, rs, [(20, 3)])
    rep = eng.watchdog.report()
    assert rep["steady_state_compiles"] == 1
    viol = rep["steady_state_events"][0]
    assert "engine.py" in viol["call_site"]        # attributed
    assert viol["key"] == "('paged_prefill', 32)"
    assert "#" in viol["signature"]                # shape digest present
    assert eng.metrics.compiles == warm + 1        # counter agrees


def test_engine_watchdog_raise_mode_hard_fails():
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        watchdog_mode="raise")
    rs = np.random.RandomState(5)
    _drive(eng, rs, [(4, 3), (9, 3)])
    eng.declare_warmup()
    _drive(eng, rs, [(4, 3), (9, 3)])      # warm traffic is fine
    eng.add_request(rs.randint(0, 97, (25,)).astype(np.int64),
                    max_new_tokens=2)
    with pytest.raises(CompileAfterWarmupError) as ei:
        eng.run()
    assert "engine.py" in str(ei.value)


def test_engine_serve_metrics_http():
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    _drive(eng, np.random.RandomState(6), [(5, 3)])
    server = eng.serve_metrics()
    try:
        port = server.server_address[1]
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        types, samples = _parse_prometheus(text)
        assert "serving_tokens_generated_total" in types
        assert ("serving_tokens_generated_total", {}, 3.0) in samples
        # /debug (index): every mounted route listed — the operator's
        # discovery surface (trailing slash normalizes to the same)
        idx = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/", timeout=10).read())
        assert {"/metrics", "/metrics.json", "/debug",
                "/debug/requests", "/debug/state", "/debug/perf",
                "/debug/health", "/debug/ledger",
                "/debug/cache"} <= set(idx["routes"])
        assert idx["routes"] == sorted(idx["routes"])
        # /debug/perf: the per-program attribution body
        perf = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/perf", timeout=10).read())
        assert perf["enabled"] is True
        assert "decode" in perf["programs"]
        # /debug/cache: the cache observatory body, live
        cache = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/cache", timeout=10).read())
        assert cache["enabled"] is True and "churn" in cache
        assert cache["capacity_blocks"] == eng.pool.num_blocks - 1
    finally:
        server.shutdown()


def test_metrics_server_debug_index_lists_extra_routes():
    """The bare start_metrics_server also serves the /debug index:
    built-ins plus every extra route, sorted; an explicit /debug
    extra route overrides the built-in index."""
    reg = MetricsRegistry()
    server = start_metrics_server(
        reg, port=0, extra_routes={"/debug/custom": lambda: {"x": 1}})
    try:
        port = server.server_address[1]
        idx = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug", timeout=10).read())
        assert idx["routes"] == ["/debug", "/debug/custom", "/metrics",
                                 "/metrics.json"]
    finally:
        server.shutdown()
    override = start_metrics_server(
        reg, port=0, extra_routes={"/debug": lambda: {"mine": True}})
    try:
        port = override.server_address[1]
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug", timeout=10).read())
        assert body == {"mine": True}
    finally:
        override.shutdown()
