"""Chaos-hardened serving (ISSUE 9): deterministic fault injection,
deadlines / retry / quarantine / drain, and the self-healing engine
supervisor.

Acceptance criteria pinned here:

  * every fault-injection path is deterministic per seed — two chaos
    runs with the same FaultPlan produce identical fault logs AND
    identical final token streams (both decode attentions);
  * a forced wedge (monkeypatched dispatch failure loop) triggers
    detector -> supervisor restart -> in-flight requests re-queued and
    completed with exact greedy parity vs an unfaulted run, with
    ``/debug/health`` reporting degraded during and healthy after;
  * rollback under injected failure at EVERY chunk boundary of a
    chunked prefill conserves slots/blocks at two block sizes and the
    request completes on retry;
  * a poisoned ``on_token`` callback never kills the step loop;
  * ``close()`` with in-flight work retires it with an explicit
    ``aborted`` stop reason (nothing leaks, nothing silent), while
    ``drain()`` finishes every commitment first;
  * ``tools/chaos_sweep.py --fast`` (the CI fault matrix) passes.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.resilience import (
    FAULT_SITES, FaultInjector, FaultPlan, FaultSpec, InjectedFault,
    resolve_chaos,
)
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_VOCAB = 97


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=_VOCAB, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompts(n, lo=3, hi=14, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, _VOCAB, (int(k),)).astype(np.int64)
            for k in rs.randint(lo, hi, n)]


def _reference(model, prompts, max_new, **kw):
    eng = ServingEngine(model, num_slots=4, bucket_min=8, **kw)
    reqs = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    return [list(r.generated) for r in reqs]


# ------------------------------------------------------- fault harness

def test_chaos_off_by_default(model):
    eng = ServingEngine(model, num_slots=2, bucket_min=8)
    assert eng.chaos is None
    res = eng.metrics.snapshot()["resilience"]
    assert res["chaos"] == {"enabled": False}


def test_paddle_chaos_env_gate(monkeypatch):
    monkeypatch.delenv("PADDLE_CHAOS", raising=False)
    assert resolve_chaos(None) is None
    monkeypatch.setenv("PADDLE_CHAOS", "0")
    assert resolve_chaos(None) is None
    monkeypatch.setenv("PADDLE_CHAOS", "11")
    inj = resolve_chaos(None)
    assert isinstance(inj, FaultInjector) and inj.plan.seed == 11
    monkeypatch.setenv("PADDLE_CHAOS", "11:0.5")
    inj = resolve_chaos(None)
    assert inj.plan.faults["prefill_dispatch"].rate == 0.5
    assert inj.plan.faults["compile_storm"].rate == 0.0  # stays opt-in
    # explicit forms
    assert resolve_chaos(False) is None
    assert resolve_chaos(7).plan.seed == 7
    assert resolve_chaos(FaultPlan(seed=3)).plan.seed == 3
    with pytest.raises(ValueError):
        resolve_chaos("nonsense")


def test_fault_plan_validation():
    with pytest.raises(ValueError):
        FaultPlan(seed=0, faults={"not_a_site": 0.5})
    with pytest.raises(ValueError):
        FaultSpec(rate=1.5)
    plan = FaultPlan(seed=5)
    assert set(plan.faults) == set(FAULT_SITES)
    d = plan.as_dict()
    json.dumps(d)
    assert d["seed"] == 5


def test_injector_determinism_and_exact_scheduling():
    """The i-th check of a site decides identically across injectors
    with the same seed, and after/max_fires pin exact fire points."""
    a = FaultInjector(FaultPlan(seed=9, faults={"transfer": 0.3}))
    b = FaultInjector(FaultPlan(seed=9, faults={"transfer": 0.3}))
    da = [a.fires("transfer") for _ in range(200)]
    db = [b.fires("transfer") for _ in range(200)]
    assert da == db and any(da) and not all(da)
    assert a.fault_log() == b.fault_log()
    # exact scheduling: fail exactly the 3rd crossing
    c = FaultInjector(FaultPlan(seed=1, faults={
        "decode_dispatch": {"rate": 1.0, "after": 2, "max_fires": 1}}))
    fires = [c.fires("decode_dispatch") for _ in range(6)]
    assert fires == [False, False, True, False, False, False]
    with pytest.raises(InjectedFault) as ei:
        d = FaultInjector(FaultPlan(seed=1, faults={"transfer": 1.0}))
        d.maybe_raise("transfer")
    assert ei.value.site == "transfer"


def test_chaos_runs_deterministic_and_greedy_exact(model,
                                                   decode_attention):
    """Acceptance: same FaultPlan seed => identical fault logs and
    identical final token streams — and the hardened engine's streams
    are bit-exact with an unfaulted run (retries/replay never corrupt
    greedy decoding) with nothing leaked, over the gather and over the
    paged decode kernel."""
    prompts = _prompts(8)
    reference = _reference(model, prompts, 6)

    def run():
        plan = FaultPlan(seed=3, faults={
            "prefill_dispatch": 0.2, "decode_dispatch": 0.1,
            "transfer": 0.1, "callback": 0.3, "block_exhaustion": 0.1,
            "step_latency": {"rate": 0.05, "latency_s": 0.001}})
        eng = ServingEngine(model, num_slots=4, bucket_min=8,
                            chaos=plan, max_dispatch_retries=3)
        assert eng.decode_layout == decode_attention
        reqs = [eng.add_request(p, max_new_tokens=6,
                                on_token=lambda r, t: None)
                for p in prompts]
        eng.run()
        return eng, [list(r.generated) for r in reqs]

    e1, s1 = run()
    e2, s2 = run()
    assert e1.chaos.fault_log() == e2.chaos.fault_log()
    assert e1.chaos.total_fires > 0          # chaos actually ran
    assert s1 == s2 == reference
    assert e1.pool.free_count == 4           # no slot leaked
    e1.pool.check_conservation()
    assert e1.pool.live_blocks == 0
    res = e1.metrics.snapshot()["resilience"]
    assert res["chaos"]["enabled"] is True
    assert res["chaos"]["plan"]["seed"] == 3
    assert res["dispatch_retries"] > 0


def test_unhardened_engine_wedges_on_injected_fault(model):
    """max_dispatch_retries=0 keeps the PR-6 contract: the injected
    dispatch failure rolls back leak-free and PROPAGATES (this is the
    baseline the chaos bench demonstrates against)."""
    eng = ServingEngine(model, num_slots=2, bucket_min=8,
                        chaos=FaultPlan(seed=0,
                                        faults={"prefill_dispatch": 1.0}))
    eng.add_request(_prompts(1)[0], max_new_tokens=3)
    with pytest.raises(InjectedFault):
        eng.run()
    assert eng.pool.free_count == 2          # rollback still leak-free
    assert eng.scheduler.queue               # request back in queue


# ------------------------------------------------- retry / quarantine

def test_transient_prefill_failure_retries_to_completion(model):
    prompts = _prompts(3, seed=2)
    reference = _reference(model, prompts, 5)
    eng = ServingEngine(
        model, num_slots=4, bucket_min=8, max_dispatch_retries=3,
        chaos=FaultPlan(seed=0, faults={
            "prefill_dispatch": {"rate": 1.0, "max_fires": 2}}))
    reqs = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    eng.run()
    assert [list(r.generated) for r in reqs] == reference
    res = eng.metrics.snapshot()["resilience"]
    assert res["dispatch_failures"]["prefill"] == 2
    assert res["dispatch_retries"] >= 2
    assert res["requests_aborted"] == 0
    # the flight trace shows the failure + rollback + fresh admission
    tr = eng.request_trace(reqs[0].rid)
    names = [e["event"] for e in tr.events]
    assert "dispatch_failed" in names
    assert "admission_rolled_back" in names
    assert names[-1] == "retired"


def test_retry_budget_exhaustion_aborts_request(model):
    eng = ServingEngine(
        model, num_slots=2, bucket_min=8, max_dispatch_retries=2,
        chaos=FaultPlan(seed=0, faults={"prefill_dispatch": 1.0}))
    req = eng.add_request(_prompts(1)[0], max_new_tokens=3)
    eng.run()                    # terminates: the request is aborted
    assert req.done and req.generated == []
    assert req.dispatch_failures == 3        # budget 2 + the last straw
    res = eng.metrics.snapshot()["resilience"]
    assert res["requests_aborted"] == 1
    assert eng.request_trace(req.rid).reason == "error"
    # no leak: the failing slot was quarantined at its 3rd failure
    # (default quarantine_after), the rest is free
    assert eng.pool.free_count + len(eng.pool.quarantined) == 2


def test_repeated_same_slot_failures_quarantine_the_slot(model):
    prompts = _prompts(1, seed=4)
    reference = _reference(model, prompts, 4)
    eng = ServingEngine(
        model, num_slots=2, bucket_min=8, max_dispatch_retries=5,
        quarantine_after=2,
        chaos=FaultPlan(seed=0, faults={
            "prefill_dispatch": {"rate": 1.0, "max_fires": 3}}))
    req = eng.add_request(prompts[0], max_new_tokens=4)
    eng.run()
    # slot 0 failed twice -> quarantined; the retry moved to slot 1
    assert eng.pool.quarantined == [0]
    assert req.slot is None and req.done
    assert [list(req.generated)] == reference
    res = eng.metrics.snapshot()["resilience"]
    assert res["quarantined_slots"] == [0]
    assert res["slots_quarantined_total"] == 1
    # quarantined slots are neither free nor occupied
    assert eng.pool.free_count == 1 and eng.pool.occupancy == 0.0


def test_quarantine_never_takes_the_last_slot(model):
    eng = ServingEngine(
        model, num_slots=1, bucket_min=8, max_dispatch_retries=5,
        quarantine_after=1,
        chaos=FaultPlan(seed=0, faults={
            "prefill_dispatch": {"rate": 1.0, "max_fires": 2}}))
    req = eng.add_request(_prompts(1)[0], max_new_tokens=3)
    eng.run()
    assert req.done and len(req.generated) == 3
    assert eng.pool.quarantined == []        # the only slot serves on


# ------------------------------------------- chunk-boundary rollback

@pytest.mark.parametrize("block_size", [4, 16])
@pytest.mark.parametrize("boundary", [0, 1, 2, 3])
def test_chunked_prefill_rollback_at_every_boundary(model, block_size,
                                                    boundary):
    """Inject a dispatch failure at EACH chunk boundary of a chunked
    prefill (prompt of 26 tokens, chunk 8 -> 4 chunks), with a chunk
    that spans two blocks and with two chunks to a block: the rollback
    must conserve slots/blocks and the request must complete
    bit-exact on retry."""
    rs = np.random.RandomState(31)
    prompt = rs.randint(0, _VOCAB, (26,)).astype(np.int64)
    ref_eng = ServingEngine(model, num_slots=2, bucket_min=8,
                            prefill_chunk=8, block_size=block_size)
    ref = ref_eng.add_request(prompt, max_new_tokens=4)
    ref_eng.run()
    eng = ServingEngine(
        model, num_slots=2, bucket_min=8, prefill_chunk=8,
        block_size=block_size, max_dispatch_retries=3,
        chaos=FaultPlan(seed=0, faults={"chunk_dispatch": {
            "rate": 1.0, "after": boundary, "max_fires": 1}}))
    req = eng.add_request(prompt, max_new_tokens=4)
    eng.run()
    assert list(req.generated) == list(ref.generated)
    res = eng.metrics.snapshot()["resilience"]
    assert res["dispatch_failures"]["chunk"] == 1
    assert res["dispatch_retries"] == 1
    assert eng.pool.free_count == 2          # slot conservation
    assert not eng._chunk_q and not eng._prefilling
    eng.pool.check_conservation()            # block conservation
    assert eng.pool.live_blocks == 0


# --------------------------------------------------------- deadlines

def test_queued_request_past_deadline_times_out(model):
    eng = ServingEngine(model, num_slots=1, bucket_min=8)
    req = eng.add_request(_prompts(1)[0], max_new_tokens=3,
                          deadline_ms=1.0)
    time.sleep(0.01)
    eng.step()
    assert req.done and req.generated == []
    res = eng.metrics.snapshot()["resilience"]
    assert res["requests_timed_out"] == 1
    # SLO-judged as a violation with zero goodput (never inflates
    # attainment), and the flight trace carries the full story
    slo = eng.metrics.slo.report()
    assert slo["violations"].get("deadline") == 1
    assert slo["goodput_tokens"] == 0
    tr = eng.request_trace(req.rid)
    assert tr.reason == "deadline"
    assert "deadline_exceeded" in [e["event"] for e in tr.events]


def test_decoding_request_past_deadline_retires_mid_flight(model):
    eng = ServingEngine(model, num_slots=1, bucket_min=8)
    req = eng.add_request(_prompts(1, hi=6)[0], max_new_tokens=50,
                          deadline_ms=40.0)
    t0 = time.perf_counter()
    while not req.done:
        eng.step()
        assert time.perf_counter() - t0 < 30.0   # never hangs
    assert 0 < len(req.generated) < 50       # partial answer, retired
    assert eng.request_trace(req.rid).reason == "deadline"
    assert eng.metrics.snapshot()["resilience"]["requests_timed_out"] \
        == 1
    assert eng.pool.free_count == 1          # slot came back
    # a request with no deadline is untouched by the scan
    r2 = eng.add_request(_prompts(1)[0], max_new_tokens=3)
    eng.run()
    assert r2.done and len(r2.generated) == 3


def test_deadline_validation(model):
    eng = ServingEngine(model, num_slots=1, bucket_min=8)
    with pytest.raises(ValueError):
        eng.add_request(_prompts(1)[0], max_new_tokens=2,
                        deadline_ms=0)


# --------------------------------------------------- callback guard

def test_poisoned_on_token_callback_does_not_kill_the_step_loop(model):
    """Satellite regression: a raising user callback is caught and
    counted; every request (the poisoned one included) still streams
    to completion with greedy parity."""
    prompts = _prompts(4, seed=6)
    reference = _reference(model, prompts, 5)
    eng = ServingEngine(model, num_slots=4, bucket_min=8)
    seen = []

    def poisoned(r, t):
        raise ValueError("user bug")

    reqs = [eng.add_request(p, max_new_tokens=5,
                            on_token=poisoned if i == 1 else
                            (lambda r, t: seen.append((r.rid, t))))
            for i, p in enumerate(prompts)]
    eng.run()                                # no raise
    assert [list(r.generated) for r in reqs] == reference
    res = eng.metrics.snapshot()["resilience"]
    assert res["callback_errors"] == len(reqs[1].generated)
    # the healthy callbacks saw every OTHER request's stream
    assert sum(1 for rid, _ in seen if rid == reqs[0].rid) == 5
    tr = eng.request_trace(reqs[1].rid)
    errs = [e for e in tr.events if e["event"] == "callback_error"]
    assert errs and "ValueError" in errs[0]["error"]
    assert tr.reason in ("eos", "max_tokens")


# ------------------------------------------------------ drain / close

def test_close_with_inflight_work_aborts_explicitly(model,
                                                    decode_attention):
    """Satellite pin: close() (and __exit__) with queued + running
    requests retires them with reason "aborted" — counted, flight-
    closed, slots/blocks conserved — instead of silent abandonment."""
    prompts = _prompts(6, seed=8)
    with ServingEngine(model, num_slots=2, bucket_min=8) as eng:
        assert eng.decode_layout == decode_attention
        reqs = [eng.add_request(p, max_new_tokens=30) for p in prompts]
        eng.step()
        eng.step()                            # some running, some queued
    # the context manager closed the engine with work in flight
    assert all(r.done for r in reqs)
    aborted = [r for r in reqs if eng.request_trace(r.rid).reason
               == "aborted"]
    assert aborted                            # in-flight work was owed
    res = eng.metrics.snapshot()["resilience"]
    assert res["requests_aborted"] == len(aborted)
    assert eng.pool.free_count == 2
    eng.pool.check_conservation()
    assert eng.pool.live_blocks == 0
    with pytest.raises(RuntimeError):
        eng.add_request(prompts[0], max_new_tokens=2)
    eng.close()                               # idempotent


def test_drain_finishes_commitments_then_closes(model):
    prompts = _prompts(5, seed=9)
    reference = _reference(model, prompts, 4)
    eng = ServingEngine(model, num_slots=2, bucket_min=8)
    reqs = [eng.add_request(p, max_new_tokens=4) for p in prompts]
    eng.step()
    done = eng.drain()
    assert [list(r.generated) for r in reqs] == reference
    assert {r.rid for r in done} >= {r.rid for r in reqs}
    assert all(eng.request_trace(r.rid).reason in ("eos", "max_tokens")
               for r in reqs)                 # finished, not aborted
    assert eng.metrics.snapshot()["resilience"]["requests_aborted"] == 0
    assert eng.metrics.health_report()["draining"] is True
    with pytest.raises(RuntimeError):
        eng.add_request(prompts[0], max_new_tokens=2)


# ------------------------------------------------------- supervisor

def test_supervisor_restart_on_forced_wedge_end_to_end(model):
    """THE acceptance path: a monkeypatched dispatch-failure loop
    wedges decode; the queue stalls; the queue_stall detector fires;
    the supervisor restarts in-process (fresh pools + rebuilt AOT
    table); in-flight requests re-queue and complete with exact
    greedy parity vs an unfaulted run; /debug/health reports degraded
    during the replay and healthy after."""
    prompts = _prompts(6, seed=12)
    reference = _reference(model, prompts, 8)
    eng = ServingEngine(
        model, num_slots=4, bucket_min=8, max_dispatch_retries=100,
        supervisor_cooldown_s=0.0,
        health_detectors={"queue_stall": {"stall_steps": 4}})
    reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    for _ in range(3):
        eng.step()                            # healthy start
    assert eng.metrics.health_report()["healthy"] is True

    def wedged(*a, **k):
        raise RuntimeError("device wedged")

    eng._exec[("decode",)] = wedged           # the forced failure loop
    steps = 0
    while eng.supervisor.restarts == 0:
        eng.step()
        steps += 1
        assert steps < 50, "supervisor never fired"
    # detector -> restart happened; the wedged executable was dropped
    # from the rebuilt AOT table, so the engine genuinely recovers
    assert ("decode",) not in eng._exec
    rep = eng.metrics.health_report()
    assert rep["degraded"] is True            # replay still draining
    assert rep["healthy"] is False
    assert rep["restarts"] == 1
    assert eng.health.report()["detectors"]["queue_stall"]["fired"] >= 1
    eng.run()
    assert [list(r.generated) for r in reqs] == reference
    rep = eng.metrics.health_report()
    assert rep["degraded"] is False
    assert rep["healthy"] is True             # anomalies resolved
    assert rep["restarts"] == 1
    assert eng.pool.free_count == 4
    # the replayed requests carry the requeued flight event
    requeued = [r for r in reqs if "requeued" in
                [e["event"] for e in eng.request_trace(r.rid).events]]
    assert requeued
    assert eng.metrics.snapshot()["resilience"][
        "supervisor_restarts"] == 1


def test_supervisor_escalation_from_decode_retry_exhaustion(model):
    """The engine-internal trigger: decode failing past the retry
    budget escalates straight to the supervisor (no detector needed)
    and the rebuilt table serves the replay to exact parity."""
    prompts = _prompts(3, seed=13)
    reference = _reference(model, prompts, 6)
    eng = ServingEngine(model, num_slots=4, bucket_min=8,
                        max_dispatch_retries=2,
                        supervisor_cooldown_s=0.0)
    reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.step()                                # compile + first decode
    eng._exec[("decode",)] = lambda *a: (_ for _ in ()).throw(
        RuntimeError("decode dead"))
    eng.run()
    assert eng.supervisor.restarts == 1
    assert [list(r.generated) for r in reqs] == reference
    res = eng.metrics.snapshot()["resilience"]
    assert res["dispatch_failures"]["decode"] == 3   # 2 retries + 1
    assert res["supervisor_restarts"] == 1


def test_supervisor_restart_replays_paged_pool_with_radix_rebuild(
        model):
    """Paged flavor of the wedge: after the restart the pool is a
    FRESH object (clean bookkeeping), conservation holds, and the
    replay is greedy-exact."""
    prompts = _prompts(4, seed=14)
    reference = _reference(model, prompts, 6)
    eng = ServingEngine(model, num_slots=4, bucket_min=8,
                        max_dispatch_retries=1,
                        supervisor_cooldown_s=0.0)
    reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.step()
    pool_before = eng.pool
    eng._exec[("decode",)] = lambda *a: (_ for _ in ()).throw(
        RuntimeError("decode dead"))
    eng.run()
    assert eng.supervisor.restarts == 1
    assert eng.pool is not pool_before
    assert [list(r.generated) for r in reqs] == reference
    eng.pool.check_conservation()
    assert eng.pool.live_blocks == 0


def test_supervisor_gives_up_after_max_restarts(model):
    """The crash-loop bound: past max_restarts the supervisor stops
    absorbing and the raw failure surfaces (gave_up + degraded stay
    truthful)."""
    eng = ServingEngine(model, num_slots=2, bucket_min=8,
                        max_dispatch_retries=1, supervisor_max_restarts=2,
                        supervisor_cooldown_s=0.0)
    eng.add_request(_prompts(1)[0], max_new_tokens=6)
    eng.step()

    class Dead:
        def __call__(self, *a):
            raise RuntimeError("permanently dead")

    # re-wedge after every rebuild: poison the compile helper itself
    orig = eng._compiled

    def poisoned(key, fn, args, donate=()):
        if key == ("decode",):
            return Dead()
        return orig(key, fn, args, donate=donate)

    eng._compiled = poisoned
    eng._exec[("decode",)] = Dead()
    with pytest.raises(RuntimeError, match="permanently dead"):
        eng.run()
    assert eng.supervisor.restarts == 2
    assert eng.supervisor.gave_up is True
    assert eng.supervisor.degraded is True
    assert eng.metrics.health_report()["healthy"] is False


# ------------------------------------------- incidents embed chaos

def test_incident_bundle_embeds_fault_plan_and_renders(model,
                                                       tmp_path):
    """Satellite: with chaos armed, a captured incident embeds the
    active FaultPlan seed + fault log (replayable from the bundle
    alone) and tools/incident_report.py renders the CHAOS section."""
    inc_dir = str(tmp_path / "incidents")
    eng = ServingEngine(
        model, num_slots=2, bucket_min=8, supervisor=False,
        chaos=FaultPlan(seed=17, faults={"transfer": 0.05}),
        health_detectors={"queue_stall": {"stall_steps": 3}},
        incident_dir=inc_dir)
    eng.add_request(_prompts(1)[0], max_new_tokens=3)
    eng.scheduler.admit_paged = lambda *a, **k: None   # wedge
    for _ in range(6):
        eng.step()
    files = [f for f in os.listdir(inc_dir)
             if f.startswith("incident_")]
    assert len(files) == 1
    path = os.path.join(inc_dir, files[0])
    bundle = json.load(open(path))
    assert bundle["chaos"]["enabled"] is True
    assert bundle["chaos"]["plan"]["seed"] == 17
    assert bundle["chaos"]["plan"]["faults"]["transfer"]["rate"] \
        == 0.05
    assert "fault_log_tail" in bundle["chaos"]
    # the renderer prints the replay recipe and exits 1 (incident)
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools",
                                      "incident_report.py"), path],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 1
    assert "CHAOS" in res.stdout and "seed=17" in res.stdout
    # without chaos the section is None (schema key still present)
    eng2 = ServingEngine(model, num_slots=2, bucket_min=8)
    assert eng2.chaos is None


# ---------------------------------------------------- CI fault matrix

@pytest.mark.slow
def test_chaos_sweep_full_matrix_passes():
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools",
                                      "chaos_sweep.py"), "--seeds", "2"],
        capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-500:]


def test_chaos_sweep_fast_gate():
    """Tier-1 self-run: one seed across the reduced site matrix —
    the leak/hang/parity/determinism gate the sweep enforces, at
    smoke cost."""
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools",
                                      "chaos_sweep.py"), "--fast"],
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-500:]
    lines = [json.loads(ln) for ln in res.stdout.splitlines()
             if ln.strip().startswith("{")]
    summary = lines[-1]
    assert summary["summary"] is True and summary["failures"] == 0
    cells = [ln for ln in lines if not ln.get("summary")]
    assert all(c["ok"] for c in cells)
    assert any(sum(c.get("faults", {}).values()) > 0 for c in cells)
