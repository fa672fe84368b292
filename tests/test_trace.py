"""Distributed request tracing (ISSUE 18): cross-replica trace
propagation, per-hop span rings, fleet trace assembly, and the TTFT
critical-path decomposition.

Acceptance criteria pinned here:

  * a two-hop disaggregated request (router -> prefill tier -> KV
    wire -> decode tier) yields ONE assembled trace carrying all nine
    canonical segments, with the unattributed gap under 10% of the
    trace window — proven against live engines through the REAL
    surfaces (``/debug/traces`` + ``/router/trace`` over HTTP,
    assembled by a tools/trace_report.py subprocess, exit 0);
  * the cross-process chrome://tracing export validates under the
    same flow validator as the PR-4 single-process export;
  * graceful degradation everywhere a context can be missing or
    malformed: a direct ``add_request`` (no router above it), an
    old-format journal entry, corrupted wire baggage — each gets a
    locally minted root, never an exception, and serving proceeds.

The failover half of the criterion (a SIGKILLed replica's replayed
request stays ONE trace, annotated router/failover) is audited by
tools/router_drill.py's failover wave, self-run by test_router.py.
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
from paddle_tpu.observability.trace import (
    CANONICAL_SEGMENTS, TRACE_SNAPSHOT_KEYS, TRACEPARENT_RE,
    AssembledTrace, TraceAssembler, TraceContext, TraceRecorder,
    chrome_trace, ttft_breakdown,
)
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.router import (EngineGateway,
                                       InProcessTransport, Router,
                                       RouterConfig)
from paddle_tpu.serving.router.journal import JournalEntry
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

from test_flight import validate_chrome_flows

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACE_REPORT = os.path.join(_ROOT, "tools", "trace_report.py")


def _model(seed=7):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# ------------------------------------------------------- TraceContext

def test_traceparent_round_trip_and_parse():
    ctx = TraceContext.mint(baggage={"rid": "req-1"})
    header = ctx.to_traceparent()
    assert TRACEPARENT_RE.match(header)
    back = TraceContext.from_traceparent(header,
                                         baggage=ctx.baggage)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id
    assert back.baggage == {"rid": "req-1"}
    assert back.minted_local is False
    # the JSON wire form round-trips through coerce
    again = TraceContext.coerce(json.loads(json.dumps(ctx.as_dict())))
    assert again.trace_id == ctx.trace_id
    assert again.minted_local is False
    with pytest.raises(ValueError):
        TraceContext.from_traceparent("00-deadbeef-00-01")


def test_child_same_trace_new_span():
    root = TraceContext.mint(baggage={"rid": "r"})
    kid = root.child(baggage={"hop": "prefill"})
    assert kid.trace_id == root.trace_id
    assert kid.span_id != root.span_id
    assert kid.baggage == {"rid": "r", "hop": "prefill"}


def test_coerce_never_raises_and_marks_local_mints():
    # passthrough
    ctx = TraceContext.mint()
    assert TraceContext.coerce(ctx) is ctx
    # every malformed shape degrades to a locally minted VALID root
    for garbage in (None, "", "not-a-traceparent", "00-zz-zz-01",
                    123, 4.5, [], {"traceparent": "corrupt!"},
                    {"wrong_key": True}, {"traceparent": None},
                    b"00-aa-bb-01", {"traceparent": ["nested"]}):
        got = TraceContext.coerce(garbage)
        assert isinstance(got, TraceContext), garbage
        assert got.minted_local is True, garbage
        assert TRACEPARENT_RE.match(got.to_traceparent()), garbage


def test_baggage_hygiene():
    # non-dict baggage degrades to {}
    assert TraceContext.mint(baggage="junk").baggage == {}
    # oversize values truncate, non-str keys drop, item count bounded
    big = {"v": "x" * 10_000, 7: "dropped", "flag": True,
           "obj": {"nested": 1}}
    big.update({f"k{i}": i for i in range(40)})
    bag = TraceContext.mint(baggage=big).baggage
    assert len(bag) <= 16
    assert len(bag["v"]) == 256
    assert 7 not in bag
    assert bag["flag"] == "True"          # scalars only, stringified
    json.dumps(bag)


# ------------------------------------------------------ TraceRecorder

def test_recorder_ring_bounds_and_snapshot_schema():
    rec = TraceRecorder("r0", capacity=4)
    ctx = TraceContext.mint()
    for i in range(6):
        assert rec.record(ctx, f"s{i}", time.time(), 0.001) is not None
    snap = rec.snapshot()
    assert set(snap) == set(TRACE_SNAPSHOT_KEYS)
    assert snap["enabled"] is True
    assert snap["spans_recorded"] == 6
    assert snap["spans_dropped"] == 2
    assert snap["ring_occupancy"] == snap["ring_capacity"] == 4
    # oldest evicted, newest kept
    assert [s.name for s in rec.spans()] == ["s2", "s3", "s4", "s5"]
    with pytest.raises(ValueError):
        TraceRecorder("r0", capacity=0)


def test_recorder_disabled_keeps_full_surface():
    rec = TraceRecorder("r0", enabled=False)
    assert rec.record(TraceContext.mint(), "x", time.time(), 0) is None
    assert rec.record(None, "x", time.time(), 0) is None
    snap = rec.snapshot()
    assert set(snap) == set(TRACE_SNAPSHOT_KEYS)
    assert snap["enabled"] is False and snap["spans_recorded"] == 0
    body = rec.debug_traces()
    assert set(body) == {"replica_id", "wall_time", "state", "spans"}
    assert body["spans"] == []


def test_recorder_wall_anchor_and_root_parenting():
    rec = TraceRecorder("r0")
    # perf_counter stamps convert onto the wall clock
    assert abs(rec.wall(time.perf_counter()) - time.time()) < 0.25
    ctx = TraceContext.mint()
    rec.record_root(ctx, "router/request", time.time(), 0.01)
    rec.record(ctx, "router/queue", time.time(), 0.002,
               {"rid": "q-0"})
    root, child = rec.spans()
    assert root.span_id == ctx.span_id and root.parent_id is None
    assert child.parent_id == ctx.span_id
    assert child.attrs == {"rid": "q-0"}
    assert rec.trace_ids() == [ctx.trace_id]
    assert len(rec.for_trace(ctx.trace_id)) == 2
    # the context-manager form times and records
    with rec.span(ctx, "kv/wire", {"n": 1}):
        pass
    assert rec.spans()[-1].name == "kv/wire"


# ----------------------------------------------------- TraceAssembler

def _body(replica, spans, wall_shift=0.0):
    return {"replica_id": replica,
            "wall_time": round(time.time() + wall_shift, 6),
            "state": {}, "spans": spans}


def _span(tid, name, t0, dur, replica=None):
    return {"trace_id": tid, "span_id": os.urandom(8).hex(),
            "parent_id": "p" * 16, "name": name,
            "replica": replica, "t0": t0, "dur": dur}


def test_assembler_rejects_non_body():
    with pytest.raises(ValueError):
        TraceAssembler().add_body({"not": "a body"})


def test_assembler_offset_correction():
    """A source whose clock runs 5s ahead has its spans shifted back
    onto the assembler clock — the cross-replica ordering comes out
    causal, not clock-literal."""
    tid = "ab" * 16
    now = time.time()
    asm = TraceAssembler()
    asm.add_body(_body("a", [_span(tid, "first", now, 0.010)]))
    # source b's clock is +5s: its span "starts" 5s in the future
    # although causally it ran 20ms after a's
    skew = 5.0
    t_req = time.time()
    asm.add_body(_body("b", [_span(tid, "second", now + 0.020 + skew,
                                   0.010)], wall_shift=skew),
                 t_req=t_req, t_resp=t_req + 0.002)
    t = asm.assemble(tid)
    names = [r["name"] for r in t.timeline()]
    assert names == ["first", "second"]
    gap = t.timeline()[1]["t_rel_ms"]
    assert 5.0 < gap < 200.0              # ~20ms, not ~5s
    assert not any(r["skew_ambiguous"] for r in t.timeline())


def test_assembler_flags_skew_ambiguous_never_silently_orders():
    """When the scrape round trip is WIDER than the gap between two
    spans from different sources, their rendered order is an estimate
    — both get flagged rather than presented as fact."""
    tid = "cd" * 16
    now = time.time()
    asm = TraceAssembler()
    asm.add_body(_body("a", [_span(tid, "x", now, 0.001)]))
    t_req = time.time()
    # a 2s round trip whose midpoint matches b's clock reading:
    # offset estimates ~0 with +-1s ambiguity, dwarfing the 1ms gap
    asm.add_body(_body("b", [_span(tid, "y", now + 0.001, 0.001)],
                       wall_shift=1.0),
                 t_req=t_req, t_resp=t_req + 2.0)
    t = asm.assemble(tid)
    assert all(r["skew_ambiguous"] for r in t.timeline())
    # unknown id -> None, not an exception
    assert asm.assemble("ee" * 16) is None


def test_assembled_trace_completeness_and_gap():
    tid = "12" * 16
    t0 = 1000.0
    spans = []
    cursor = t0
    for name in CANONICAL_SEGMENTS:
        spans.append(_span(tid, name, cursor, 0.010, replica="r"))
        cursor += 0.010
    # one annotation span outside the canonical set: ignored by the
    # decomposition, rendered in the timeline
    spans.append(_span(tid, "router/retry", t0, 0.0, replica="router"))
    t = AssembledTrace(tid, spans)
    assert t.complete and t.missing_segments() == []
    assert abs(t.window_ms() - 90.0) < 1e-6
    assert t.unattributed_ms() < 1e-6
    partial = AssembledTrace(tid, spans[:3])
    assert not partial.complete
    assert "decode/first_step" in partial.missing_segments()
    d = t.as_dict()
    json.dumps(d)
    assert set(d) >= {"trace_id", "replicas", "complete",
                      "missing_segments", "window_ms",
                      "unattributed_ms", "segments", "timeline"}


def test_chrome_trace_cross_process_flows_validate():
    """One pid per replica, flow arrows across processes — valid
    under the SAME validator as the PR-4 single-process export."""
    tid = "34" * 16
    t0 = 2000.0
    spans, cursor = [], t0
    for i, name in enumerate(CANONICAL_SEGMENTS):
        rep = ("router", "router", "p0", "p0", "p0", "router", "d0",
               "d0", "d0")[i]
        spans.append(_span(tid, name, cursor, 0.010, replica=rep))
        cursor += 0.010
    ct = chrome_trace([AssembledTrace(tid, spans)])
    validate_chrome_flows(ct, expect_finished=True)
    pids = {e["pid"] for e in ct["traceEvents"] if e["ph"] == "X"}
    assert len(pids) == 3                 # one process per replica


def test_ttft_breakdown_stats():
    traces = []
    for j in range(4):
        tid = f"{j:032x}"
        spans, cursor = [], 100.0 * j
        for name in CANONICAL_SEGMENTS:
            spans.append(_span(tid, name, cursor, 0.010 * (j + 1),
                               replica="r"))
            cursor += 0.010 * (j + 1)
        traces.append(AssembledTrace(tid, spans))
    bd = ttft_breakdown(traces)
    assert bd["count"] == bd["complete"] == 4
    assert set(bd["segments"]) == set(CANONICAL_SEGMENTS)
    seg = bd["segments"]["prefill/compute"]
    assert abs(seg["median_ms"] - 25.0) < 1.0     # median of 10/20/30/40
    assert seg["count"] == 4
    assert bd["unattributed"]["median_ms"] < 1e-6
    json.dumps(bd)


# ------------------------------------------------- engine integration

def _drain(eng):
    while eng.pending:
        eng.step()


def test_engine_records_prefill_spans_and_serves_debug_traces():
    eng = ServingEngine(_model(), config=ServingConfig(
        num_slots=2, bucket_min=8, health=False))
    try:
        req = eng.add_request(np.arange(1, 12, dtype=np.int64) % 97,
                              max_new_tokens=3)
        _drain(eng)
        assert req.trace is not None
        names = {s.name for s in eng.trace.spans()}
        assert {"prefill/queue", "prefill/compute"} <= names
        by_name = {s.name: s for s in eng.trace.spans()}
        assert by_name["prefill/compute"].attrs["rid"] == req.rid
        # snapshot()["trace"] pinned shape, live counts
        snap = eng.metrics.snapshot()["trace"]
        assert set(snap) == set(TRACE_SNAPSHOT_KEYS)
        assert snap["enabled"] is True and snap["spans_recorded"] >= 2
        # the /debug/traces surface serves the ring
        handle = eng.serve_metrics()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{handle.port}/debug/traces",
                    timeout=5.0) as resp:
                body = json.loads(resp.read().decode("utf-8"))
            assert body["replica_id"] == eng.replica_id
            assert any(s["name"] == "prefill/compute"
                       for s in body["spans"])
        finally:
            handle.close()
    finally:
        eng.close()


def test_engine_trace_disabled_keeps_schema(monkeypatch):
    monkeypatch.setenv("PADDLE_TRACE_SPANS", "0")
    eng = ServingEngine(_model(), config=ServingConfig(
        num_slots=2, bucket_min=8, health=False))
    try:
        eng.add_request(np.arange(1, 8, dtype=np.int64) % 97,
                        max_new_tokens=2)
        _drain(eng)
        snap = eng.metrics.snapshot()["trace"]
        assert set(snap) == set(TRACE_SNAPSHOT_KEYS)
        assert snap["enabled"] is False
        assert snap["spans_recorded"] == 0
        assert eng.trace.debug_traces()["spans"] == []
    finally:
        eng.close()
    with pytest.raises(ValueError):
        ServingConfig(trace_span_keep=0)


# -------------------------------------------------------- degradation

def test_direct_add_request_mints_local_root():
    """An engine with no router above it serves a traceless
    add_request under a locally minted root — never an exception."""
    eng = ServingEngine(_model(), config=ServingConfig(
        num_slots=2, bucket_min=8, health=False))
    try:
        req = eng.add_request(np.arange(1, 10, dtype=np.int64) % 97,
                              max_new_tokens=2)
        assert req.trace.minted_local is True
        _drain(eng)
        assert req.trace.trace_id in eng.trace.trace_ids()
    finally:
        eng.close()


def test_old_format_journal_entry_tolerated():
    """A journal entry admitted without a trace (an old-format replay
    ledger) carries trace None; the engine coerces to a local root on
    dispatch instead of refusing the replay."""
    entry = JournalEntry("rid-1", [1, 2, 3], 4, None, None, 0.0)
    assert entry.trace is None
    eng = ServingEngine(_model(), config=ServingConfig(
        num_slots=2, bucket_min=8, health=False))
    try:
        req = eng.add_request(np.asarray(entry.prefill_ids,
                                         dtype=np.int64),
                              max_new_tokens=entry.remaining_tokens,
                              trace=entry.trace)
        assert req.trace.minted_local is True
        _drain(eng)
        assert len(req.generated) == 4
    finally:
        eng.close()


def test_corrupted_wire_trace_degrades_import_still_succeeds():
    """Garbage in the handoff payload's trace field costs the decode
    tier its fleet-trace join, NOT the request: import proceeds under
    a local root and the decode stream is unaffected."""
    def engine(role):
        return ServingEngine(_model(seed=11), num_slots=4,
                             bucket_min=8, role=role,
                             health=False)

    prompt = list(range(1, 20))
    pe, de = engine("prefill"), engine("decode")
    try:
        ctx = TraceContext.mint(baggage={"rid": "wire-1"})
        req = pe.add_request(np.asarray(prompt, np.int64), 1,
                             hold_kv=True, trace=ctx)
        pe.run()
        payload = pe.export_kv(req.rid)
        # the clean payload carries the wire form of the context
        assert payload["trace"]["traceparent"] == ctx.to_traceparent()
        corrupted = json.loads(json.dumps(payload))
        corrupted["trace"] = {"traceparent": "!!corrupt!!",
                              "baggage": ["not", "a", "dict"]}
        dreq = de.import_kv(corrupted, 4)
        assert dreq.trace.minted_local is True
        assert dreq.trace.trace_id != ctx.trace_id
        de.run()
        assert len(dreq.generated) == 4
        # the decode-side spans landed under the LOCAL root — degraded
        # attribution, full observability
        assert dreq.trace.trace_id in de.trace.trace_ids()
    finally:
        pe.close()
        de.close()


def test_clean_wire_trace_joins_decode_tier():
    """The intact path: the decode tier's spans land under the
    ORIGINAL trace id carried inside the KV handoff payload."""
    def engine(role):
        return ServingEngine(_model(seed=11), num_slots=4,
                             bucket_min=8, role=role,
                             health=False)

    prompt = list(range(1, 20))
    pe, de = engine("prefill"), engine("decode")
    try:
        ctx = TraceContext.mint(baggage={"rid": "wire-2"})
        req = pe.add_request(np.asarray(prompt, np.int64), 1,
                             hold_kv=True, trace=ctx)
        pe.run()
        dreq = de.import_kv(pe.export_kv(req.rid), 4)
        assert dreq.trace.minted_local is False
        assert dreq.trace.trace_id == ctx.trace_id
        de.run()
        # prefill-side and decode-side rings agree on the id; joining
        # them assembles the kv segments
        asm = TraceAssembler()
        asm.add_recorder(pe.trace)
        asm.add_recorder(de.trace)
        t = asm.assemble(ctx.trace_id)
        names = {s["name"] for s in t.spans}
        assert {"prefill/compute", "kv/export", "kv/import",
                "decode/queue", "decode/first_step"} <= names
    finally:
        pe.close()
        de.close()


# --------------------------------- live 1P+1D + trace_report.py gate

def test_live_disagg_trace_report_cli(tmp_path):
    """The tentpole acceptance gate: a two-hop request through a live
    1 prefill + 1 decode fleet yields ONE assembled trace with all
    nine canonical segments and an unattributed gap under 10% of the
    window — proven by a tools/trace_report.py SUBPROCESS scraping
    the real HTTP surfaces, exactly as an operator would."""
    model = _model()
    rs = np.random.RandomState(3)
    prompt = rs.randint(0, 97, (20,)).astype(int).tolist()

    def gw(rid, role):
        eng = ServingEngine(model, num_slots=2, bucket_min=8,
                            block_size=8, replica_id=rid,
                            role=role, health=False)
        g = EngineGateway(eng)
        warm = g.submit(np.asarray(prompt, dtype=np.int64),
                        max_new_tokens=2)
        g.wait(warm, timeout=120.0)
        with g._lock:
            eng.warmup_kv_handoff()
        return g

    gp, gd = gw("p0", "prefill"), gw("d0", "decode")
    router = Router([InProcessTransport(gp), InProcessTransport(gd)],
                    config=RouterConfig(refresh_s=0.05, seed=3))
    handles = []
    try:
        res = router.generate(prompt, 5, timeout=120.0)
        assert res["ok"] and len(res["tokens"]) == 5
        tids = router.trace.trace_ids()
        assert len(tids) == 1             # ONE trace for the request
        tid = tids[0]

        hp, hd = gp.engine.serve_metrics(), gd.engine.serve_metrics()
        hr = router.serve()
        handles = [hp, hd, hr]
        sources = [f"127.0.0.1:{hp.port}", f"127.0.0.1:{hd.port}",
                   f"http://127.0.0.1:{hr.port}/router/trace"]
        chrome_out = tmp_path / "trace.chrome.json"
        env = dict(os.environ)
        cli = subprocess.run(
            [sys.executable, _TRACE_REPORT, *sources,
             "--trace", tid, "--chrome", str(chrome_out), "--json"],
            capture_output=True, text=True, timeout=120, env=env)
        assert cli.returncode == 0, (cli.stdout[-1500:],
                                     cli.stderr[-1500:])
        doc = json.loads(cli.stdout)
        (trace,) = doc["traces"]
        assert trace["trace_id"] == tid
        assert trace["complete"] is True
        assert trace["missing_segments"] == []
        assert set(trace["segments"]) >= set(CANONICAL_SEGMENTS)
        assert set(trace["replicas"]) == {"router", "p0", "d0"}
        # the decomposition explains >=90% of the window
        gap = trace["unattributed_ms"] / trace["window_ms"]
        assert gap < 0.10, trace
        bd = doc["ttft_breakdown"]
        assert bd["complete"] == 1
        # the cross-process chrome export validates under the PR-4
        # flow validator
        with open(chrome_out, encoding="utf-8") as fh:
            ct = json.load(fh)
        validate_chrome_flows(ct, expect_finished=True)
        pids = {e["pid"] for e in ct["traceEvents"]
                if e["ph"] == "X"}
        assert len(pids) == 3
        # unreadable source -> exit 2; missing id -> exit 1
        bad = subprocess.run(
            [sys.executable, _TRACE_REPORT,
             str(tmp_path / "nope.json")],
            capture_output=True, text=True, timeout=60, env=env)
        assert bad.returncode == 2
        miss = subprocess.run(
            [sys.executable, _TRACE_REPORT, sources[0],
             "--trace", "ff" * 16],
            capture_output=True, text=True, timeout=60, env=env)
        assert miss.returncode == 1, miss.stderr[-500:]
    finally:
        for h in handles:
            h.close()
        router.close()
        gp.close()
        gd.close()
