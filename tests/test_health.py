"""Serving health observatory (paddle_tpu.observability.health):
per-step ledger, online anomaly detectors, black-box incident capture.

Acceptance criteria pinned here: every built-in detector has a firing
AND a non-firing case on synthetic ledgers; an induced engine-level
queue stall produces a firing counter in /metrics, healthy=false with
the detector named in /debug/health, and a schema-valid incident
bundle on disk; clean runs fire NOTHING; tools/incident_report.py
self-runs against a synthetic incident and exits nonzero on unhealthy.
"""
import json
import os
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import HostSpanRecorder, MetricsRegistry
from paddle_tpu.observability.health import (
    INCIDENT_KEYS, INCIDENT_SCHEMA, LEDGER_ROW_KEYS, HealthMonitor,
    IncidentRecorder, StepLedger, build_detectors, detector_names,
    register_detector, unregister_detector,
)
from paddle_tpu.observability.health.detectors import (
    CacheThrash, GoodputCollapse, KVBlockLeak, QueueStall,
    SteadyStateCompileAnomaly, StepTimeSpike,
)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DEFAULT_DETECTORS = {"cache_thrash", "goodput_collapse",
                      "kv_block_leak", "queue_stall",
                      "steady_state_compile", "step_time_spike"}


def _model(seed=7):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _row(step, **kw):
    """One synthetic, fully-populated ledger row (healthy defaults)."""
    base = {
        "step": int(step), "t": float(step), "wall_s": 0.01,
        "dispatch_s": 0.004, "sync_s": 0.003, "queue_depth": 0,
        "queue_age_s": 0.0, "occupied_slots": 2, "held_exports": 0,
        "chunked_inflight": 0,
        "admitted": 0, "tokens": 2, "completed": 0,
        "goodput_tokens": 0, "prefill_tokens": 0, "prefill_chunks": 0,
        "shed": 0, "deprioritized": 0, "new_compiles": 0,
        "steady_compiles": 0, "slo_on": False, "prefix_hit_rate": None,
        "pool_free_blocks": None, "pool_evictable_blocks": None,
        "pool_live_blocks": None, "conservation_ok": None,
        "conservation_error": None, "cache_thrash": None,
        "pool_evictable_delta": None,
    }
    assert set(base) == set(LEDGER_ROW_KEYS)
    base.update(kw)
    return base


def _feed(detector, rows):
    """Run rows through a detector over a scratch ledger; returns the
    verdicts that fired."""
    ledger = StepLedger(keep=len(rows) + 1)
    fired = []
    for r in rows:
        ledger.append(r)
        v = detector.observe(r, ledger)
        if v:
            fired.append(v)
    return fired


# ---------------------------------------------------------------- ledger

def test_ledger_bounded_ring_and_export():
    led = StepLedger(keep=4)
    for i in range(10):
        led.append(_row(i + 1))
    assert len(led) == 4 and led.steps == 10
    assert led.last_step_id == 10
    assert [r["step"] for r in led.rows()] == [7, 8, 9, 10]
    assert [r["step"] for r in led.rows(last=2)] == [9, 10]
    d = led.as_dict(last=3)
    assert d["steps"] == 10 and d["kept"] == 4 and d["keep"] == 4
    assert len(d["rows"]) == 3
    json.dumps(d)                       # /debug/ledger-servable
    # rows are copies: mutating an export doesn't corrupt the ring
    d["rows"][0]["step"] = -1
    assert led.rows(last=3)[0]["step"] == 8
    with pytest.raises(ValueError):
        StepLedger(keep=0)


# ------------------------------------------------------ detector registry

def test_register_detector_mirrors_lint_registry():
    assert set(detector_names()) >= _DEFAULT_DETECTORS

    @register_detector("always_fire_test")
    class AlwaysFire:
        def observe(self, row, ledger):
            return {"detector": self.name, "step": row["step"],
                    "reason": "test"}

    try:
        assert "always_fire_test" in detector_names()
        dets = build_detectors(only=["always_fire_test"])
        assert dets[0].name == "always_fire_test"
        # per-detector kwarg overrides reach the constructor
        tight = build_detectors(
            overrides={"queue_stall": {"stall_steps": 3}},
            only=["queue_stall"])
        assert tight[0].stall_steps == 3
        with pytest.raises(ValueError):
            build_detectors(only=["no_such_detector"])
    finally:
        unregister_detector("always_fire_test")
    assert "always_fire_test" not in detector_names()


# ------------------------------------------------------------- detectors

def test_step_time_spike_fires_on_spike_not_on_jitter():
    det = StepTimeSpike(window=32, min_steps=8, min_wall_s=0.05)
    rs = np.random.RandomState(0)
    rows = [_row(i + 1, wall_s=0.008 + rs.rand() * 0.004)
            for i in range(30)]
    rows.append(_row(31, wall_s=0.5))          # 50x the median
    fired = _feed(det, rows)
    assert len(fired) == 1
    v = fired[0]
    assert v["detector"] == "step_time_spike" and v["step"] == 31
    assert v["wall_s"] == pytest.approx(0.5)
    assert v["threshold_s"] < 0.5 and v["rolling_median_s"] < 0.02

    # non-firing: 3x jitter stays under the floor and the MAD band
    det2 = StepTimeSpike(window=32, min_steps=8, min_wall_s=0.05)
    rows = [_row(i + 1, wall_s=0.005 + (i % 3) * 0.005)
            for i in range(60)]
    assert _feed(det2, rows) == []


def test_step_time_spike_exempts_compile_steps():
    det = StepTimeSpike(window=32, min_steps=8, min_wall_s=0.05)
    rows = [_row(i + 1, wall_s=0.01) for i in range(20)]
    # a compiling step is seconds-scale but attributed to XLA, not a
    # service anomaly (steady_state_compile owns post-warmup builds)
    rows.append(_row(21, wall_s=2.0, new_compiles=1))
    assert _feed(det, rows) == []


def test_queue_stall_fires_once_and_rearms_on_progress():
    det = QueueStall(stall_steps=5)
    stalled = [_row(i + 1, queue_depth=3, tokens=0, occupied_slots=0,
                    queue_age_s=1.0 + i) for i in range(12)]
    fired = _feed(det, stalled)
    assert len(fired) == 1                     # once per episode
    v = fired[0]
    assert v["detector"] == "queue_stall" and v["steps_stalled"] == 5
    assert v["queue_depth"] == 3 and v["queue_age_s"] > 0

    # progress of ANY kind resets the streak: a full-but-decoding
    # engine (queue > 0, tokens flowing) is NOT stalled
    det2 = QueueStall(stall_steps=5)
    busy = [_row(i + 1, queue_depth=8, tokens=4) for i in range(40)]
    assert _feed(det2, busy) == []
    # chunked prefill progress also counts
    det3 = QueueStall(stall_steps=5)
    chunking = [_row(i + 1, queue_depth=2, tokens=0, prefill_chunks=1)
                for i in range(40)]
    assert _feed(det3, chunking) == []


def test_queue_behind_parked_exports_is_not_a_stall():
    """A prefill tier whose slots are all parked for export_kv waits
    for the router, and steps idle meanwhile as fast as the host
    allows: no verdict, no supervisor restart (which would wipe the
    parked blocks), however many steps the round trip lasts. The
    export frees the slot and the queue moves; a queue that then does
    not move is still a stall."""
    det = QueueStall(stall_steps=5)
    parked = [_row(i + 1, queue_depth=1, tokens=0, occupied_slots=2,
                   held_exports=2) for i in range(40)]
    assert _feed(det, parked) == []
    wedged = [_row(41 + i, queue_depth=1, tokens=0, occupied_slots=0)
              for i in range(5)]
    assert len(_feed(det, wedged)) == 1

    m = _model()
    eng = ServingEngine(m, num_slots=1, bucket_min=8, role="prefill",
                        health_detectors={"queue_stall":
                                          {"stall_steps": 4}})
    rs = np.random.RandomState(2)
    p1, p2 = (rs.randint(0, 97, (n,)).astype(np.int64) for n in (5, 6))
    r1 = eng.add_request(p1, max_new_tokens=1, hold_kv=True)
    r2 = eng.add_request(p2, max_new_tokens=1, hold_kv=True)
    for _ in range(40):
        eng.step()
    assert r1.done and not r2.done and r2.slot is None
    health = eng.metrics.snapshot()["health"]
    assert health["anomalies_total"] == 0 and health["healthy"]
    assert eng.supervisor.restarts == 0
    assert eng.health.ledger.last()["held_exports"] == 1
    payload = eng.export_kv(r1.rid)
    assert payload["frames"]
    for _ in range(6):
        eng.step()
    assert r2.done
    eng.export_kv(r2.rid)
    eng.pool.check_conservation()
    eng.close()


def test_goodput_collapse_fires_on_cliff_not_gradual_decline():
    def run(rates):
        det = GoodputCollapse(window=16, drop_frac=0.1,
                              healthy_frac=0.5, min_completions=2)
        rows = []
        for i, g in enumerate(rates):
            rows.append(_row(i + 1, slo_on=True, goodput_tokens=g,
                             completed=1, queue_depth=4))
        return _feed(det, rows)

    # cliff: healthy windows then instant zero -> fires
    fired = run([5] * 48 + [0] * 20)
    assert len(fired) >= 1
    v = fired[0]
    assert v["detector"] == "goodput_collapse"
    assert v["current_rate_tps"] < v["previous_rate_tps"]

    # gradual decline (the deliberate-overload shape): each window is
    # only modestly worse than the last -> never the healthy->collapsed
    # adjacent-window cliff, never fires
    gradual = []
    for w in range(12):
        gradual.extend([max(0, 5 - w // 2)] * 16)
    assert run(gradual) == []

    # inert without SLO targets
    det = GoodputCollapse(window=4, min_completions=1)
    rows = [_row(i + 1, slo_on=False, goodput_tokens=5 if i < 8 else 0,
                 completed=1, queue_depth=4) for i in range(16)]
    assert _feed(det, rows) == []


def test_kv_block_leak_fires_on_audit_failure_and_idle_refs():
    det = KVBlockLeak()
    bad_audit = [_row(1, conservation_ok=True),
                 _row(2, conservation_ok=False,
                      conservation_error="refcount underflow")]
    fired = _feed(det, bad_audit)
    assert len(fired) == 1
    assert fired[0]["detector"] == "kv_block_leak"
    assert "underflow" in fired[0]["audit_error"]

    # idle engine with blocks still referenced = the slow leak
    det2 = KVBlockLeak()
    rows = [_row(1, occupied_slots=1, pool_live_blocks=6,
                 pool_free_blocks=2, pool_evictable_blocks=1),
            _row(2, occupied_slots=0, tokens=0, pool_live_blocks=3,
                 pool_free_blocks=2, pool_evictable_blocks=1),
            _row(3, occupied_slots=0, tokens=0, pool_live_blocks=3,
                 pool_free_blocks=2, pool_evictable_blocks=1)]
    fired = _feed(det2, rows)
    assert len(fired) == 1                     # once per episode
    assert fired[0]["live_blocks"] == 3

    # healthy: idle with everything free/evictable, and rows
    # without pool facts (None fields) are inert
    det3 = KVBlockLeak()
    ok = [_row(1, occupied_slots=0, tokens=0, pool_live_blocks=0,
               pool_free_blocks=8, pool_evictable_blocks=2),
          _row(2, occupied_slots=0, tokens=0)]
    assert _feed(det3, ok) == []


def test_cache_thrash_fires_on_sustained_reinserts_and_rearms():
    """PR-13: evict-then-reinsert volume over the window means the
    pool is smaller than the live prefix working set. Fires once per
    episode, re-arms after a quiet window, and legacy rows (None) are
    inert."""
    det = CacheThrash(window=8, min_thrash=12)
    rows = [_row(i + 1, cache_thrash=2) for i in range(8)]
    fired = _feed(det, rows)
    assert len(fired) == 1                     # once per episode
    assert fired[0]["detector"] == "cache_thrash"
    assert fired[0]["thrash_events"] >= 12
    assert "working set" in fired[0]["reason"]
    # quiet window re-arms, a second burst fires again
    det2 = CacheThrash(window=4, min_thrash=6)
    burst = [_row(i + 1, cache_thrash=3) for i in range(4)]
    quiet = [_row(i + 5, cache_thrash=0) for i in range(4)]
    again = [_row(i + 9, cache_thrash=3) for i in range(4)]
    assert len(_feed(det2, burst + quiet + again)) == 2

    # healthy churn (sparse reinserts) and legacy None rows: nothing
    det3 = CacheThrash(window=8, min_thrash=12)
    ok = [_row(i + 1, cache_thrash=(1 if i % 4 == 0 else 0))
          for i in range(16)] + [_row(17)]
    assert _feed(det3, ok) == []


def test_steady_state_compile_fires_only_after_warmup():
    det = SteadyStateCompileAnomaly()
    rows = [_row(1, new_compiles=3, steady_compiles=0),   # warmup
            _row(2, new_compiles=1, steady_compiles=1)]   # violation
    fired = _feed(det, rows)
    assert len(fired) == 1
    assert fired[0]["step"] == 2 and fired[0]["compiles"] == 1


# --------------------------------------------------------------- monitor

def test_monitor_counts_fires_marker_spans_and_survives_broken_detector():
    reg = MetricsRegistry()
    rec = HostSpanRecorder(capacity=64)

    @register_detector("broken_test")
    class Broken:
        def observe(self, row, ledger):
            raise RuntimeError("buggy detector")

    try:
        mon = HealthMonitor(
            reg, recorder=rec,
            detectors=build_detectors(
                overrides={"queue_stall": {"stall_steps": 2}},
                only=["queue_stall", "broken_test"]))
        for i in range(4):
            mon.observe(_row(i + 1, queue_depth=1, tokens=0,
                             occupied_slots=0))
        assert mon.anomalies_total == 1 and not mon.healthy
        assert reg.get("serving_anomalies_total") \
            .labels("queue_stall").value == 1
        # the broken detector was counted and skipped, never fatal
        assert reg.get("serving_detector_errors_total") \
            .labels("broken_test").value == 4
        # the firing dropped a marker span into the host timeline
        marks = [s for s in rec.spans()
                 if s.name == "health/queue_stall"]
        assert len(marks) == 1 and marks[0].args["steps_stalled"] == 2
        rep = mon.report()
        assert rep["healthy"] is False and rep["anomalies_total"] == 1
        assert rep["detectors"]["queue_stall"]["fired"] == 1
        assert rep["detectors"]["queue_stall"]["last_verdict"][
            "reason"]
        json.dumps(rep)
    finally:
        unregister_detector("broken_test")


def test_incident_recorder_debounce_and_rotation(tmp_path):
    clock = {"t": 100.0}
    rec = IncidentRecorder(str(tmp_path), keep_last=3, debounce_s=30.0,
                           clock=lambda: clock["t"])
    led = StepLedger(keep=8)
    for i in range(5):
        led.append(_row(i + 1))
    ctx = {"metrics": lambda: {"ok": 1},
           "watchdog": lambda: {"steady_state_compiles": 0},
           "requests": lambda: {"active": []},
           "spans_tail": lambda: (_ for _ in ()).throw(  # broken
               RuntimeError("span source died"))}
    assert rec.should_capture("queue_stall")
    p1 = rec.capture("queue_stall", {"detector": "queue_stall",
                                     "step": 5, "reason": "r"},
                     led, ctx)
    assert os.path.exists(p1) and rec.written == 1
    # debounced: same detector inside the window doesn't capture...
    assert not rec.should_capture("queue_stall")
    # ...but a DIFFERENT detector does, and time re-arms the first
    assert rec.should_capture("step_time_spike")
    clock["t"] += 31.0
    assert rec.should_capture("queue_stall")
    bundle = json.load(open(p1))
    assert set(bundle) == set(INCIDENT_KEYS)
    assert bundle["schema"] == INCIDENT_SCHEMA
    assert len(bundle["ledger_tail"]) == 5
    # a failing context callable contributes an error stub, not a raise
    assert "RuntimeError" in bundle["spans_tail"]["error"]
    # rotation: keep_last bounds the directory
    for i in range(5):
        clock["t"] += 31.0
        rec.capture("queue_stall", {"step": i, "reason": "r"}, led, ctx)
    files = [f for f in os.listdir(tmp_path)
             if f.startswith("incident_")]
    assert len(files) == 3
    assert rec.list_incidents() == sorted(
        os.path.join(str(tmp_path), f) for f in files)


# ---------------------------------------------------- engine integration

def test_engine_forced_queue_stall_end_to_end(tmp_path):
    """The acceptance path: an induced stall (admission monkeypatched
    dead) produces a firing counter in /metrics, healthy=false with
    the detector named in /debug/health, and a schema-valid incident
    bundle on disk."""
    inc_dir = str(tmp_path / "incidents")
    m = _model()
    eng = ServingEngine(
        m, num_slots=2, bucket_min=8,
        health_detectors={"queue_stall": {"stall_steps": 4}},
        incident_dir=inc_dir)
    eng.add_request(np.arange(5, dtype=np.int64) % 97,
                    max_new_tokens=3)
    # induced fault: admission never admits, queue never drains
    eng.scheduler.admit_paged = lambda *a, **k: None
    for _ in range(8):
        eng.step()
    # 1) the firing counter is in /metrics
    text = eng.metrics.prometheus_text()
    assert 'serving_anomalies_total{detector="queue_stall"} 1' in text
    # 2) /debug/health: unhealthy, detector named
    handle = eng.serve_metrics()
    try:
        port = handle.port
        health = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/health",
            timeout=10).read())
        assert health["healthy"] is False
        assert health["anomalies_total"] >= 1
        assert health["detectors"]["queue_stall"]["fired"] == 1
        assert health["last_incident"]
        # /debug/ledger serves the per-step ring with the full schema
        led = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/ledger",
            timeout=10).read())
        assert led["steps"] == 8 and led["last_step"] == 8
        assert len(led["rows"]) == 8
        for row in led["rows"]:
            assert set(row) == set(LEDGER_ROW_KEYS)
            assert row["queue_depth"] == 1 and row["tokens"] == 0
    finally:
        eng.close()
    # 3) the incident bundle landed with a valid schema
    files = [f for f in os.listdir(inc_dir)
             if f.startswith("incident_")]
    assert len(files) == 1 and "queue_stall" in files[0]
    bundle = json.load(open(os.path.join(inc_dir, files[0])))
    assert set(bundle) == set(INCIDENT_KEYS)
    assert bundle["schema"] == INCIDENT_SCHEMA
    assert bundle["detector"] == "queue_stall"
    assert bundle["verdict"]["steps_stalled"] == 4
    assert bundle["ledger_tail"] and all(
        set(r) == set(LEDGER_ROW_KEYS) for r in bundle["ledger_tail"])
    assert bundle["metrics"]["queue_depth"] == 1   # moment-of-anomaly
    assert bundle["health"]["healthy"] is False
    assert isinstance(bundle["spans_tail"], list) \
        and bundle["spans_tail"]
    # the stalled request is visible in the captured traces
    assert bundle["requests"]["state"]["active"] == 1
    # snapshot rollup agrees
    snap = eng.metrics.snapshot()["health"]
    assert snap["anomalies_total"] >= 1
    assert snap["incidents_written"] == 1
    assert snap["last_incident"].endswith(files[0])


def test_engine_induced_steady_compile_is_an_anomaly():
    """The watchdog's flag becomes a first-class anomaly: induced
    shape drift after declare_warmup() fires steady_state_compile."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    rs = np.random.RandomState(3)
    for n, k in [(4, 3), (9, 3)]:
        eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                        max_new_tokens=k)
    eng.run()
    eng.declare_warmup()
    assert eng.metrics.snapshot()["health"]["anomalies_total"] == 0
    eng.add_request(rs.randint(0, 97, (20,)).astype(np.int64),
                    max_new_tokens=2)          # never-warmed bucket
    eng.run()
    health = eng.metrics.snapshot()["health"]
    assert health["detectors"]["steady_state_compile"] >= 1
    assert health["healthy"] is False
    assert eng.health.report()["detectors"]["steady_state_compile"][
        "last_verdict"]["compiles"] >= 1


def test_engine_clean_runs_fire_nothing():
    """No false positives: plain, small-block and chunked clean
    drains all stay healthy with zero anomalies (the observatory is ON by
    default)."""
    m = _model()
    rs = np.random.RandomState(11)
    for kw in ({}, {"block_size": 8, "health_audit_every": 2},
               {"prefill_chunk": 8, "slo_ttft_ms": 5000.0}):
        eng = ServingEngine(m, num_slots=2, bucket_min=8, **kw)
        for wave in range(2):
            for n, k in [(5, 4), (19, 3), (9, 5)]:
                eng.add_request(rs.randint(0, 97, (n,))
                                .astype(np.int64), max_new_tokens=k)
            eng.run()
        health = eng.metrics.snapshot()["health"]
        assert health["anomalies_total"] == 0, (kw, health)
        assert health["healthy"] is True
        assert health["ledger_steps"] > 0


def test_engine_health_audit_cadence_and_span():
    """ServingConfig(health_audit_every=) drives the periodic paged
    conservation audit; its cost is a visible serving/health_audit
    host span and its verdict lands on the audited rows."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        block_size=8, health_audit_every=2)
    rs = np.random.RandomState(4)
    for n, k in [(5, 4), (9, 4), (6, 3)]:
        eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                        max_new_tokens=k)
    eng.run()
    assert eng.metrics.span_s.get("serving/health_audit", 0.0) > 0
    rows = eng.health.ledger.rows()
    audited = [r for r in rows if r["conservation_ok"] is not None]
    skipped = [r for r in rows if r["conservation_ok"] is None]
    assert audited and all(r["step"] % 2 == 0 for r in audited)
    assert all(r["conservation_ok"] for r in audited)
    assert all(r["step"] % 2 == 1 for r in skipped)
    # paged rows carry the block economy; the audit knob validates
    assert all(r["pool_free_blocks"] is not None for r in rows)
    with pytest.raises(ValueError):
        ServingEngine(m, num_slots=2, health_audit_every=0)


def test_engine_health_disabled_has_no_ledger_or_routes():
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8, health=False)
    eng.add_request(np.arange(5, dtype=np.int64), max_new_tokens=2)
    eng.run()
    assert eng.health is None
    assert eng.metrics.snapshot()["health"]["enabled"] is False
    handle = eng.serve_metrics()
    try:
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{handle.port}/debug/health",
                timeout=10)
    finally:
        eng.close()


# ------------------------------------------------------------ report CLI

def _synthetic_incident(tmp_path):
    led = StepLedger(keep=32)
    for i in range(20):
        led.append(_row(i + 1, wall_s=0.01, sync_s=0.004))
    led.append(_row(21, wall_s=0.8, sync_s=0.7, queue_depth=5))
    rec = IncidentRecorder(str(tmp_path), keep_last=4)
    return rec.capture(
        "step_time_spike",
        {"detector": "step_time_spike", "step": 21,
         "reason": "step wall 800.0ms vs rolling median 10.0ms",
         "wall_s": 0.8},
        led,
        {"metrics": lambda: {"tokens_per_sec": 120.0, "queue_depth": 5,
                             "compiles": 7,
                             "scheduler": {"policy": "fifo",
                                           "shed_total": 0}},
         "watchdog": lambda: {"steady_state_compiles": 0},
         "requests": lambda: {"active": [], "state": {"active": 0}},
         "spans_tail": lambda: []})


def test_incident_report_cli_renders_and_exits_nonzero(tmp_path):
    path = _synthetic_incident(tmp_path)
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools",
                                      "incident_report.py"), path],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 1, res.stderr     # incident => unhealthy
    out = res.stdout
    assert "detector=step_time_spike" in out
    assert "LEDGER TAIL" in out and "TOP REGRESSED STEP PHASES" in out
    # the spiked step is marked in the table and sync_s tops the
    # regression list (0.7s vs ~4ms median)
    assert any(ln.endswith("<<") for ln in out.splitlines())
    reg_lines = out.split("TOP REGRESSED STEP PHASES")[1].splitlines()
    first_phase = [ln for ln in reg_lines if ln.strip()][1]
    assert "sync_s" in first_phase
    assert "ENGINE VITALS" in out and "tokens_per_sec" in out


def test_incident_report_cli_health_body_exit_codes(tmp_path):
    healthy = tmp_path / "health_ok.json"
    healthy.write_text(json.dumps(
        {"healthy": True, "anomalies_total": 0,
         "detectors": {"queue_stall": {"fired": 0}}}))
    sick = tmp_path / "health_bad.json"
    sick.write_text(json.dumps(
        {"healthy": False, "anomalies_total": 2,
         "detectors": {"queue_stall": {"fired": 2, "last_step": 9}},
         "last_incident": "x.json"}))
    tool = os.path.join(_ROOT, "tools", "incident_report.py")
    ok = subprocess.run([sys.executable, tool, str(healthy)],
                        capture_output=True, text=True, timeout=60)
    assert ok.returncode == 0 and "healthy=True" in ok.stdout
    bad = subprocess.run([sys.executable, tool, str(sick)],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1 and "queue_stall" in bad.stdout
