"""Tenant observatory (PR 19): end-to-end per-tenant attribution.

The conservation contract — per-tenant sums equal the global
counters EXACTLY, on both KV pools, across router failover replay
and the disaggregated KV handoff — plus the bounded-cardinality
guarantee under an adversarial tenant-id flood, the fleet fairness
detectors (noisy_neighbor / tenant_starvation) on synthetic poll
rows, and the operator surfaces: ``/debug/tenants``,
``/debug/requests?tenant=``, ``tools/tenant_report.py`` /
``fleet_top --tenants`` / ``incident_report.py`` self-runs.
"""
import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import MetricsRegistry, TenantLedger
from paddle_tpu.observability.fleet.detectors import (NoisyNeighbor,
                                                      TenantStarvation)
from paddle_tpu.observability.tenant import (DEFAULT_TENANT,
                                             OVERFLOW_TENANT,
                                             TENANT_ENTRY_KEYS)
from paddle_tpu.observability.trace import TraceContext
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.router import (EngineGateway,
                                       InProcessTransport, Router,
                                       RouterConfig)
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TENANT_REPORT = os.path.join(_ROOT, "tools", "tenant_report.py")
_FLEET_TOP = os.path.join(_ROOT, "tools", "fleet_top.py")
_INCIDENT_REPORT = os.path.join(_ROOT, "tools", "incident_report.py")


def _model(seed=7):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _drive(eng, rs, specs):
    """specs: [(prompt_len, max_new, tenant_id)]"""
    reqs = [eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                            max_new_tokens=k, tenant_id=t)
            for n, k, t in specs]
    eng.run()
    return reqs


def _assert_conserved(eng):
    """Per-tenant sums == the engine's own global counters, exactly."""
    snap = eng.metrics.snapshot()
    rows = snap["tenants"]["tenants"].values()
    slo = snap["slo"]

    def tsum(key):
        return sum(e[key] for e in rows)

    assert tsum("requests") == snap["requests_admitted"]
    assert tsum("completed") == snap["requests_completed"]
    assert tsum("tokens_out") == slo["total_tokens"]
    assert tsum("goodput_tokens") == slo["goodput_tokens"]
    assert tsum("attained") == slo["attained"]
    # global violations = completion dims + shed reasons + timeout
    # "deadline" entries; tenant timeouts are kept separately
    assert (sum(sum(e["violations"].values()) for e in rows)
            + tsum("timeouts")) == sum(slo["violations"].values())
    assert sum(sum(e["shed"].values()) for e in rows) \
        == snap["scheduler"]["shed_total"]
    # the Prometheus families carry the same sums (what the fleet
    # federation actually merges)
    reg = eng.metrics.registry.snapshot()
    fam = reg["serving_tenant_tokens_out_total"]["values"]
    assert sum(fam.values()) == slo["total_tokens"]
    return snap


# ------------------------------------------------- bounded cardinality

def test_ledger_bounded_under_10k_tenant_flood():
    """The adversarial flood: 10k unique tenant ids against a
    max_tenants=8 ledger cost 9 accounts and 9 series per family —
    the overflow cell absorbs every accrual past the cap, counted."""
    reg = MetricsRegistry()
    led = TenantLedger(reg, max_tenants=8)
    for i in range(10_000):
        led.note_admission(f"tenant-{i}", 5, 0.0)
        led.note_completion(f"tenant-{i}", 3, [])
    rep = led.report()
    assert rep["tenant_count"] == 9            # 8 live + ~other
    assert OVERFLOW_TENANT in rep["tenants"]
    assert rep["overflow"]["folded_events"] == 2 * (10_000 - 8)
    # conservation holds THROUGH the fold: nothing dropped
    assert sum(e["requests"] for e in rep["tenants"].values()) \
        == 10_000
    assert sum(e["tokens_out"] for e in rep["tenants"].values()) \
        == 30_000
    snap = reg.snapshot()
    for fam in ("serving_tenant_requests_total",
                "serving_tenant_tokens_out_total"):
        assert len(snap[fam]["values"]) == 9
    assert snap["serving_tenant_overflow_total"]["values"][""] \
        == 2 * (10_000 - 8)
    for entry in rep["tenants"].values():
        assert set(entry) == set(TENANT_ENTRY_KEYS)


# ------------------------------------- conservation, both SLO verdicts

def test_conservation_attained_path():
    """Default engine, no SLO targets: every completion
    attains, and every per-tenant sum matches the global counters."""
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    rs = np.random.RandomState(3)
    try:
        _drive(eng, rs, [(5, 3, "alice"), (9, 4, "bob"),
                         (6, 2, "alice"), (7, 3, None)])
        # metric-level lifecycle paths move global + tenant together
        eng.metrics.record_shed("overload", "bob")
        eng.metrics.record_timeout("alice")
        eng.metrics.record_abort("bob")
        snap = _assert_conserved(eng)
        ten = snap["tenants"]["tenants"]
        assert set(ten) == {"alice", "bob", DEFAULT_TENANT}
        assert ten["alice"]["requests"] == 2
        assert ten["alice"]["tokens_out"] == 5
        assert ten["alice"]["timeouts"] == 1
        assert ten["bob"]["shed"] == {"overload": 1}
        assert ten["bob"]["aborts"] == 1
        assert ten[DEFAULT_TENANT]["requests"] == 1
        # everything attained (no SLO configured)
        assert ten["alice"]["attainment"] == 1.0
    finally:
        eng.close()


def test_conservation_violation_path():
    """Small blocks and an unmeetable TTFT target: every completion
    violates, goodput is zero, and the sums still match exactly."""
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8,
                        block_size=8,
                        slo_ttft_ms=0.000001)
    rs = np.random.RandomState(5)
    try:
        _drive(eng, rs, [(5, 3, "alice"), (9, 4, "bob"),
                         (11, 3, "bob")])
        snap = _assert_conserved(eng)
        ten = snap["tenants"]["tenants"]
        assert snap["slo"]["attained"] == 0
        assert ten["alice"]["violations"] == {"ttft": 1}
        assert ten["bob"]["violations"] == {"ttft": 2}
        assert ten["alice"]["goodput_tokens"] == 0
        assert ten["alice"]["attainment"] == 0.0
    finally:
        eng.close()


# ------------------------------- resolution, flight filter, HTTP routes

def test_tenant_resolution_and_debug_surfaces():
    """tenant_id param beats trace baggage beats the "default" fall-
    back; the resolved tenant is written BACK into baggage (same
    trace id — annotation, not a new hop), stamped on flight
    lifecycle + retirement events, and served by ``/debug/tenants``
    and the ``/debug/requests?tenant=`` filter."""
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    rs = np.random.RandomState(7)
    try:
        p = rs.randint(0, 97, (6,)).astype(np.int64)
        r_param = eng.add_request(p, 2, tenant_id="alice")
        ctx = TraceContext.mint(baggage={"tenant": "bob"})
        r_bag = eng.add_request(p, 2, trace=ctx)
        r_both = eng.add_request(
            p, 2, trace=TraceContext.mint(baggage={"tenant": "bob"}),
            tenant_id="carol")
        r_none = eng.add_request(p, 2)
        assert r_param.tenant_id == "alice"
        assert r_bag.tenant_id == "bob"
        assert r_both.tenant_id == "carol"      # param wins
        assert r_none.tenant_id == DEFAULT_TENANT
        # resolution annotates baggage without re-rooting the trace
        assert r_param.trace.baggage["tenant"] == "alice"
        assert r_both.trace.baggage["tenant"] == "carol"
        assert r_bag.trace.trace_id == ctx.trace_id
        eng.run()
        # flight retirement carries the attribution (grep-billing)
        completed = eng.flight.debug_requests()["completed"]
        by_rid = {t["rid"]: t for t in completed}
        assert by_rid[r_param.rid]["tenant_id"] == "alice"
        retired = [e for e in by_rid[r_bag.rid]["events"]
                   if e["event"] == "retired"]
        assert retired and retired[0]["tenant"] == "bob"
        handle = eng.serve_metrics()
        try:
            base = f"http://127.0.0.1:{handle.port}"
            body = json.loads(urllib.request.urlopen(
                base + "/debug/tenants", timeout=10).read())
            assert body["enabled"] is True
            assert set(body["tenants"]) == {
                "alice", "bob", "carol", DEFAULT_TENANT}
            filt = json.loads(urllib.request.urlopen(
                base + "/debug/requests?tenant=alice",
                timeout=10).read())
            assert filt["tenant"] == "alice"
            assert [t["rid"] for t in filt["completed"]] \
                == [r_param.rid]
            assert all(t["tenant_id"] == "alice"
                       for t in filt["completed"])
        finally:
            handle.close()
    finally:
        eng.close()


# ------------------------------------------------ disaggregated handoff

def test_kv_handoff_carries_tenant_across_tiers():
    """The two-hop attribution: the tenant rides the handoff
    payload's trace baggage, so the decode tier bills the SAME tenant
    the prefill tier admitted — zero kv_wire format change."""
    def engine(role):
        return ServingEngine(_model(seed=11), num_slots=4,
                             bucket_min=8, role=role,
                             health=False)

    prompt = list(range(1, 20))
    pe, de = engine("prefill"), engine("decode")
    try:
        req = pe.add_request(np.asarray(prompt, np.int64), 1,
                             hold_kv=True, tenant_id="bob")
        pe.run()
        payload = pe.export_kv(req.rid)
        assert payload["trace"]["baggage"]["tenant"] == "bob"
        dreq = de.import_kv(payload, 4)
        assert dreq.tenant_id == "bob"
        de.run()
        assert len(dreq.generated) == 4
        # both tiers' ledgers attribute to bob, conservation per tier
        p_ten = pe.metrics.snapshot()["tenants"]["tenants"]
        d_ten = de.metrics.snapshot()["tenants"]["tenants"]
        assert p_ten["bob"]["requests"] == 1
        assert d_ten["bob"]["completed"] == 1
        assert d_ten["bob"]["tokens_out"] == 4
        _assert_conserved(pe)
        _assert_conserved(de)
    finally:
        pe.close()
        de.close()


# -------------------------------------------- router failover replay

def test_router_failover_replay_bills_original_tenant():
    """Kill a replica mid-request: the journal replay re-dispatches
    under the original admission's trace baggage, so the survivor
    bills the ORIGINAL tenant — failover never launders attribution
    into "default"."""
    def gateway(rid):
        eng = ServingEngine(_model(), num_slots=2, bucket_min=8,
                            replica_id=rid, slo_ttft_ms=60000.0)
        return EngineGateway(eng)

    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 97, (5,)).astype(int).tolist()
               for _ in range(3)]
    ga, gb = gateway("ta"), gateway("tb")
    router = Router([InProcessTransport(ga), InProcessTransport(gb)],
                    config=RouterConfig(max_retries=4, refresh_s=0.05,
                                        backoff_base_s=0.001,
                                        backoff_max_s=0.01,
                                        hedge=False, affinity=False))
    try:
        tickets = [router.submit(p, 8, tenant_id="alice")
                   for p in prompts]
        # the journal carries the attribution for replay
        for row in router.journal.snapshot():
            assert row["tenant"] == "alice"
        deadline = time.monotonic() + 15.0
        while not ga.engine.pending and time.monotonic() < deadline:
            time.sleep(0.002)
        assert ga.engine.pending
        ga.kill()
        results = [t.result(timeout=60.0) for t in tickets]
        assert all(r["ok"] for r in results)
        assert router._stats["failovers"] >= 1
        ten_b = gb.engine.metrics.snapshot()["tenants"]["tenants"]
        assert set(ten_b) == {"alice"}          # nothing leaked to
        assert ten_b["alice"]["completed"] >= 1  # "default"
        _assert_conserved(gb.engine)
    finally:
        router.close()
        gb.close()


# ------------------------------------------------- fairness detectors

def _poll_row(step, tenants):
    return {"step": step, "tenants": tenants}


def _facts(tokens, attained=0.0, violated=0.0, queued=0, requests=0.0):
    return {"tokens_delta": tokens, "attained_delta": attained,
            "violated_delta": violated, "queued": queued,
            "requests_delta": requests, "completed_delta": 0.0}


def test_noisy_neighbor_requires_dominance_and_victim_pain():
    det = NoisyNeighbor(window=3, share_frac=0.6, attain_floor=0.5,
                        min_tokens=30, min_victim_judged=3)
    bad = {"big": _facts(100.0, attained=5.0),
           "small": _facts(4.0, violated=2.0)}
    assert det.observe(_poll_row(1, bad), None) is None   # warming
    assert det.observe(_poll_row(2, bad), None) is None
    v = det.observe(_poll_row(3, bad), None)
    assert v and v["detector"] == "noisy_neighbor"
    assert v["tenant"] == "big" and v["token_share"] > 0.9
    assert v["victim_attainment"] == 0.0
    # once per episode: the same shape doesn't refire
    assert det.observe(_poll_row(4, bad), None) is None
    # victims recovering clears the episode; adversity refires
    good = {"big": _facts(100.0, attained=5.0),
            "small": _facts(4.0, attained=2.0)}
    for i in range(5, 8):
        assert det.observe(_poll_row(i, good), None) is None
    assert det.observe(_poll_row(8, bad), None) is None
    # two bad polls back in the window: victims below the floor again
    assert det.observe(_poll_row(9, bad), None) is not None

    # dominance over a HEALTHY fleet never fires: that's just the
    # biggest customer
    det2 = NoisyNeighbor(window=2, min_tokens=10, min_victim_judged=2)
    for i in range(1, 6):
        assert det2.observe(_poll_row(i, good), None) is None


def test_tenant_starvation_fires_per_tenant_once():
    det = TenantStarvation(sustain=3, min_queued=1)
    starved = {"peer": _facts(10.0, requests=4.0),
               "victim": _facts(0.0, queued=2)}
    assert det.observe(_poll_row(1, starved), None) is None
    assert det.observe(_poll_row(2, starved), None) is None
    v = det.observe(_poll_row(3, starved), None)
    assert v and v["detector"] == "tenant_starvation"
    assert v["tenant"] == "victim" and v["queued"] == 2
    assert v["peer_admissions"] == 4.0
    assert det.observe(_poll_row(4, starved), None) is None  # once
    # an idle fleet HOLDS streaks (nobody admitted != unfair)
    det2 = TenantStarvation(sustain=2, min_queued=1)
    idle = {"peer": _facts(0.0), "victim": _facts(0.0, queued=2)}
    for i in range(1, 5):
        assert det2.observe(_poll_row(i, idle), None) is None
    assert det2.observe(_poll_row(5, starved), None) is None
    assert det2.observe(_poll_row(6, starved), None) is not None
    # an admission clears both the streak and the fired latch
    det3 = TenantStarvation(sustain=2, min_queued=1)
    det3.observe(_poll_row(1, starved), None)
    assert det3.observe(_poll_row(2, starved), None) is not None
    fed = {"peer": _facts(10.0, requests=4.0),
           "victim": _facts(1.0, queued=2, requests=1.0)}
    assert det3.observe(_poll_row(3, fed), None) is None
    det3.observe(_poll_row(4, starved), None)
    assert det3.observe(_poll_row(5, starved), None) is not None


# --------------------------------------------------------- CLI gates

def test_tenant_report_cli_live_scrape_and_noisy_verdict(tmp_path):
    """tools/tenant_report.py: a live engine scrape renders the table
    and exits 0 on a fair tenancy; an adversarial saved body exits 1
    NAMING the noisy tenant; unreadable input exits 2."""
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    rs = np.random.RandomState(9)
    handle = None
    try:
        _drive(eng, rs, [(5, 3, "alice"), (7, 3, "bob")])
        handle = eng.serve_metrics()
        target = f"127.0.0.1:{handle.port}"
        fair = subprocess.run(
            [sys.executable, _TENANT_REPORT, target, "--json",
             "--min-tokens", "1"],
            capture_output=True, text=True, timeout=60)
        assert fair.returncode == 0, (fair.stdout[-800:],
                                      fair.stderr[-800:])
        doc = json.loads(fair.stdout)
        assert set(doc["tenants"]) == {"alice", "bob"}
        assert doc["noisy_tenant"] is None
        assert doc["tenants"]["alice"]["tokens_out"] == 3
    finally:
        if handle is not None:
            handle.close()
        eng.close()
    entry = {k: 0 for k in TENANT_ENTRY_KEYS}
    entry["violations"], entry["shed"] = {}, {}
    big = dict(entry, requests=50, completed=50, tokens_out=5000,
               goodput_tokens=5000, attained=50)
    small = dict(entry, requests=10, completed=2, tokens_out=40,
                 violations={"ttft": 8})
    body = {"enabled": True, "max_tenants": 32, "tenant_count": 2,
            "overflow": {"folded_events": 3},
            "tenants": {"big": big, "small": small}}
    saved = tmp_path / "tenants.json"
    saved.write_text(json.dumps(body))
    noisy = subprocess.run(
        [sys.executable, _TENANT_REPORT, str(saved)],
        capture_output=True, text=True, timeout=60)
    assert noisy.returncode == 1, noisy.stdout[-800:]
    assert "NOISY: tenant big" in noisy.stderr
    assert "big" in noisy.stdout and "folded" in noisy.stdout
    bad = subprocess.run(
        [sys.executable, _TENANT_REPORT, str(tmp_path / "nope.json")],
        capture_output=True, text=True, timeout=60)
    assert bad.returncode == 2


def test_fleet_top_tenants_flag_renders_federated_table():
    """fleet_top --tenants: the federated per-tenant table off a live
    engine's scrape surface (exact counter sums, not report rows)."""
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    rs = np.random.RandomState(11)
    handle = None
    try:
        _drive(eng, rs, [(5, 3, "alice"), (7, 2, "bob"),
                         (6, 3, "alice")])
        handle = eng.serve_metrics()
        proc = subprocess.run(
            [sys.executable, _FLEET_TOP,
             f"127.0.0.1:{handle.port}", "--tenants", "--json"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, (proc.stdout[-800:],
                                      proc.stderr[-800:])
        doc = json.loads(proc.stdout)
        rows = doc["tenants"]["fleet"]["tenants"]
        assert set(rows) == {"alice", "bob"}
        assert rows["alice"]["tokens_out"] == 6
        assert rows["alice"]["token_share"] == 0.75
        table = subprocess.run(
            [sys.executable, _FLEET_TOP,
             f"127.0.0.1:{handle.port}", "--tenants"],
            capture_output=True, text=True, timeout=120)
        assert table.returncode == 0
        assert "tenants: 2" in table.stdout
        assert "alice" in table.stdout
    finally:
        if handle is not None:
            handle.close()
        eng.close()


def test_incident_report_renders_tenant_section(tmp_path):
    """incident_report.py: a bundle carrying the PR-19 ``tenants``
    top-K section renders the who-was-hammering-us table."""
    bundle = {
        "schema": "paddle_tpu.health.incident/v1",
        "written_at": "2026-01-01T00:00:00Z",
        "detector": "queue_stall",
        "verdict": {"detector": "queue_stall", "step": 9,
                    "reason": "queue stalled"},
        "ledger_tail": [],
        "tenants": [
            {"tenant": "big", "tokens_out": 900, "token_share": 0.9,
             "requests": 12, "completed": 10},
            {"tenant": "small", "tokens_out": 100,
             "token_share": 0.1, "requests": 3, "completed": 3},
        ],
    }
    path = tmp_path / "incident_x.json"
    path.write_text(json.dumps(bundle))
    proc = subprocess.run(
        [sys.executable, _INCIDENT_REPORT, str(path)],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1          # bundles are unhealthy
    assert "TOP TENANTS (2)" in proc.stdout
    assert "big" in proc.stdout and "share=0.900" in proc.stdout
