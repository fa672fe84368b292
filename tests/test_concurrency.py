"""Concurrency analysis suite (ISSUE 20): lockdep-style lock patrol
(cycle + held-across-dispatch findings, off-by-default gating, measured
overhead), the static thread-role shared-state auditor with its
evidence-asserted allowlist, the snapshot-discipline lint (the PR-6
``.copy()``-before-upload bug class), and the clean-tree contracts:
audit_default() has zero error findings and a real engine drain under
an armed patrol stays finding-free on both KV pools."""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import concurrency as cc
from paddle_tpu.analysis import threads as th
from paddle_tpu.analysis.lint import lint_jaxpr
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)


def _model():
    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _run_order(first, second):
    """One worker thread acquiring first-then-second, joined."""
    def body():
        with first:
            with second:
                pass
    t = threading.Thread(target=body)
    t.start()
    t.join()


# ---------------------------------------------------------------------
# lock patrol: runtime lockdep
# ---------------------------------------------------------------------


def test_patrol_planted_deadlock_exactly_one_cycle_finding():
    """Two locks taken in inverted order by two threads: exactly one
    lock-order cycle finding naming both creation sites and carrying
    both acquisition stacks."""
    with analysis.lock_patrol(paths=(_HERE,)) as patrol:
        a = threading.Lock()
        b = threading.Lock()
        _run_order(a, b)
        _run_order(b, a)
        # repeat the inversion: the cycle must still dedupe to ONE
        _run_order(a, b)
        _run_order(b, a)
        findings = patrol.findings()
    assert len(findings) == 1
    f = findings[0]
    d = f.to_dict()
    assert d["pass"] == "lock-order" and d["severity"] == "error"
    assert len(d["locks"]) == 2
    assert all("test_concurrency.py" in site for site in d["locks"])
    assert len(d["stacks"]) == 2
    assert all("while holding" in s for s in d["stacks"])


def test_patrol_consistent_order_no_finding():
    with analysis.lock_patrol(paths=(_HERE,)) as patrol:
        a = threading.Lock()
        b = threading.Lock()
        _run_order(a, b)
        _run_order(a, b)
        assert patrol.findings() == []
        assert patrol.report()["edges"] == 1


def test_patrol_rlock_reentrancy_no_self_edge():
    with analysis.lock_patrol(paths=(_HERE,)) as patrol:
        r = threading.RLock()
        with r:
            with r:       # reentrant: no ordering information
                pass
        assert patrol.findings() == []
        assert patrol.report()["edges"] == 0


def test_patrol_condition_wait_releases_held_state():
    """Condition.wait releases the lock: a dispatch entered while
    parked in wait() must NOT be attributed to the waiting thread."""
    with analysis.lock_patrol(paths=(_HERE,)) as patrol:
        cond = threading.Condition()
        woke = []

        def waiter():
            with cond:
                cond.wait(timeout=5)
                woke.append(1)

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        # waiter is parked inside wait(): it holds nothing
        with cond:
            cond.notify_all()
        t.join()
        assert woke == [1]
        assert patrol.findings() == []


def test_patrol_held_across_dispatch_finding_and_dedupe():
    with analysis.lock_patrol(paths=(_HERE,)) as patrol:
        lk = threading.Lock()
        with lk:
            for _ in range(2):   # same call site twice: dedupes to one
                th.note_blocking("aot_dispatch", "decode[8]")
        findings = patrol.findings()
    assert len(findings) == 1
    d = findings[0].to_dict()
    assert d["pass"] == "lock-held-across-dispatch"
    assert d["severity"] == "error"
    assert "test_concurrency.py" in d["lock_site"]
    assert d["blocking_kind"] == "aot_dispatch"
    assert d["blocking_label"] == "decode[8]"
    assert d["blocked_at"] and d["stack"]


def test_patrol_held_across_blocking_socket():
    with analysis.lock_patrol(paths=(_HERE,)) as patrol:
        lk = threading.Lock()
        sa, sb = socket.socketpair()
        try:
            with lk:
                sa.sendall(b"x")
        finally:
            sa.close()
            sb.close()
        findings = patrol.findings()
    assert len(findings) == 1
    d = findings[0].to_dict()
    assert d["blocking_kind"] == "socket"
    assert d["blocking_label"] == "sendall"


def test_patrol_allowlist_suppresses_held_across():
    allow = (("test_concurrency.py", "aot_dispatch", "test fixture"),)
    with analysis.lock_patrol(paths=(_HERE,), allow=allow) as patrol:
        lk = threading.Lock()
        with lk:
            th.note_blocking("aot_dispatch", "decode[8]")
        assert patrol.findings() == []


def test_patrol_package_scoping_and_restoration():
    """Locks created outside the patrolled paths stay REAL locks; on
    exit the threading factories are restored and the disabled report
    keeps the identical shape (PR-8 contract style)."""
    real_lock_type = type(threading.Lock())
    with analysis.lock_patrol():      # default: paddle_tpu package only
        here_lock = threading.Lock()  # this file is outside the package
        assert isinstance(here_lock, real_lock_type)
    assert threading.Lock is th._REAL_LOCK
    assert threading.RLock is th._REAL_RLOCK
    assert threading.Condition is th._REAL_CONDITION
    assert not hasattr(socket.socket.sendall, "_patrol_wrapped")
    rep = analysis.patrol_report()
    assert rep == {"enabled": False, "locks": 0, "edges": 0,
                   "acquires": 0, "findings": []}


def test_patrol_nested_enable_refcounts():
    p1 = analysis.enable_patrol(paths=(_HERE,))
    try:
        with analysis.lock_patrol(paths=(_HERE,)) as p2:
            lk = threading.Lock()
            with lk:
                pass
            assert p2.report()["enabled"]
        # inner exit must NOT tear down the outer patrol
        assert p1.report()["enabled"]
        assert threading.Lock is th._patrol_lock
    finally:
        analysis.disable_patrol()
    assert threading.Lock is th._REAL_LOCK


def test_patrol_lint_pass_registered_and_inert():
    with analysis.lock_patrol(paths=(_HERE,)) as patrol:
        a = threading.Lock()
        b = threading.Lock()
        _run_order(a, b)
        _run_order(b, a)
        findings = lint_jaxpr(None, passes=["lock-patrol"], patrol=patrol)
    assert [f.pass_name for f in findings] == ["lock-order"]
    assert lint_jaxpr(None, passes=["lock-patrol"]) == []


def test_patrol_real_drain_clean_and_overhead_bounded():
    """The real engine drain produces zero patrol findings at two
    block sizes, and the armed per-acquire cost — probe-measured inside the
    armed window, times the drain's own acquire rate — stays under 2%
    of the measured step wall (the PR-8 health-tick contract style:
    micro-measured so CI wall noise can't flake it)."""
    m = _model()
    rs = np.random.RandomState(0)
    specs = [(5, 6), (9, 4), (12, 5)]
    for block_size in (4, 16):
        with analysis.lock_patrol() as patrol:
            eng = ServingEngine(m, num_slots=2, bucket_min=8,
                                block_size=block_size)
            for n, k in specs:
                eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                                max_new_tokens=k)
            t0 = time.perf_counter()
            steps = 0
            while eng.pending and steps < 500:
                eng.step()
                steps += 1
            drain_wall = time.perf_counter() - t0
            assert not eng.pending, "drain hung"
            findings = patrol.findings()
            rep = patrol.report()
            # per-acquire probe INSIDE the armed window: a patrolled
            # proxy pays the full _note_attempt bookkeeping here
            proxy = th._PatrolProxy(th._REAL_LOCK(), "probe:1", "Lock")
            raw = th._REAL_LOCK()
            n_iter = 20000
            t0 = time.perf_counter()
            for _ in range(n_iter):
                with raw:
                    pass
            raw_cost = (time.perf_counter() - t0) / n_iter
            t0 = time.perf_counter()
            for _ in range(n_iter):
                with proxy:
                    pass
            proxy_cost = (time.perf_counter() - t0) / n_iter
        assert findings == [], [f.to_dict() for f in findings]
        assert rep["locks"] > 0 and rep["acquires"] > 0
        per_acquire_overhead = max(0.0, proxy_cost - raw_cost)
        step_wall = drain_wall / max(1, steps)
        acquires_per_step = rep["acquires"] / max(1, steps)
        overhead_frac = per_acquire_overhead * acquires_per_step / step_wall
        assert overhead_frac < 0.02, (
            "patrol overhead %.4f%% of step (%.1f acquires/step, "
            "%.0fns/acquire, %.2fms step)"
            % (overhead_frac * 100, acquires_per_step,
               per_acquire_overhead * 1e9, step_wall * 1e3))


# ---------------------------------------------------------------------
# thread-role shared-state auditor (static)
# ---------------------------------------------------------------------

_PLANTED_RACE = '''
class Engine:
    def step(self):
        self.counter += 1          # step-loop write, unlocked

    def handle_status(self):
        return self.counter        # http-handler read
'''

_PLANTED_LOCKED = '''
class Engine:
    def step(self):
        with self._lock:
            self.counter += 1

    def handle_status(self):
        with self._lock:
            return self.counter
'''

_ROLE_MAP = {
    "planted.py::Engine.step": "step-loop",
    "planted.py::Engine.handle_*": "http-handler",
}


def _audit(src, role_map=_ROLE_MAP, allow=()):
    return lint_jaxpr(
        None, passes=["cross-role-write"],
        thread_audit={"sources": [("planted.py", src)],
                      "role_map": role_map, "allow": allow,
                      "root": _REPO})


def test_auditor_planted_cross_role_unlocked_write():
    findings = [f for f in _audit(_PLANTED_RACE) if f.severity == "error"]
    assert len(findings) == 1
    d = findings[0].to_dict()
    assert d["pass"] == "cross-role-write"
    assert d["attr"] == "counter"
    assert set(d["roles"]) == {"step-loop", "http-handler"}
    assert d["key"] == "planted.py::Engine.step.counter"
    assert "planted.py:4" in d["site"]


def test_auditor_locked_write_negative():
    assert [f for f in _audit(_PLANTED_LOCKED)
            if f.severity == "error"] == []


def test_auditor_single_role_negative():
    src = _PLANTED_RACE
    one_role = {"planted.py::Engine.*": "step-loop"}
    assert [f for f in _audit(src, role_map=one_role)
            if f.severity == "error"] == []


def test_auditor_callgraph_propagation():
    """A helper called from a role-mapped entry point inherits the
    role; its unlocked write to a cross-role attr is a finding."""
    src = '''
class Engine:
    def step(self):
        self._bump()

    def _bump(self):
        self.counter += 1

    def handle_status(self):
        return self.counter
'''
    findings = [f for f in _audit(src) if f.severity == "error"]
    assert len(findings) == 1
    assert findings[0].key == "planted.py::Engine._bump.counter"


def test_auditor_caller_lock_propagation():
    """A helper whose every in-class call site sits inside a lock
    context runs under the caller's lock: not a finding."""
    src = '''
class Engine:
    def step(self):
        with self._lock:
            self._bump()

    def _bump(self):
        self.counter += 1

    def handle_status(self):
        with self._lock:
            return self.counter
'''
    assert [f for f in _audit(src) if f.severity == "error"] == []


def test_auditor_sync_attr_mutators_safe():
    """Mutator calls on attrs bound to internally-synchronized objects
    (Event, Queue, Reservoir, StepLedger) are not unlocked writes."""
    src = '''
import threading

class Engine:
    def __init__(self):
        self._wake = threading.Event()

    def step(self):
        self._wake.clear()

    def handle_submit(self):
        self._wake.set()
'''
    role_map = {"planted.py::Engine.step": "step-loop",
                "planted.py::Engine.handle_*": "http-handler"}
    assert [f for f in _audit(src, role_map=role_map)
            if f.severity == "error"] == []


def test_auditor_allowlist_suppression_and_accounting():
    allow = (cc.AllowRule(
        pattern="planted.py::Engine.step.counter",
        justification="test fixture: counter is a test-only scratch",
        evidence=(("README.md", r"paddle"),),
    ),)
    findings = _audit(_PLANTED_RACE, allow=allow)
    assert [f for f in findings if f.severity == "error"] == []
    infos = [f for f in findings if f.severity == "info"]
    assert len(infos) == 1 and "allowlisted 1 write" in infos[0].detail


def test_auditor_allowlist_rots_loudly():
    """A rule whose evidence regex no longer matches the live source
    becomes an allowlist-rot ERROR and stops suppressing."""
    allow = (cc.AllowRule(
        pattern="planted.py::Engine.step.counter",
        justification="stale rule",
        evidence=(("README.md", r"zz-never-matches-zz"),),
    ),)
    findings = _audit(_PLANTED_RACE, allow=allow)
    errors = [f for f in findings if f.severity == "error"]
    assert len(errors) == 2   # the rot itself + the no-longer-suppressed write
    assert any("allowlist-rot" in f.detail for f in errors)


def test_auditor_unused_rule_warns():
    allow = (cc.AllowRule(
        pattern="planted.py::Engine.never.matches",
        justification="dead rule",
        evidence=(("README.md", r"paddle"),),
    ),)
    findings = _audit(_PLANTED_LOCKED, allow=allow)
    warns = [f for f in findings if f.severity == "warning"]
    assert len(warns) == 1 and "unused allowlist rule" in warns[0].detail


# ---------------------------------------------------------------------
# snapshot-discipline lint (PR-6 bug class)
# ---------------------------------------------------------------------


def _snap(src):
    return lint_jaxpr(None, passes=["snapshot-discipline"],
                      snapshot_audit={"sources": [("planted.py", src)]})


def test_snapshot_planted_live_buffer_dispatch():
    src = '''
class Pool:
    def allocate(self, slot, blocks):
        self.block_tables[slot] = blocks

    def device_tables(self):
        return jnp.asarray(self.block_tables)
'''
    findings = _snap(src)
    assert len(findings) == 1
    d = findings[0].to_dict()
    assert d["pass"] == "snapshot-discipline"
    assert d["severity"] == "error"
    assert d["attr"] == "block_tables"
    assert "planted.py:7" in d["site"]
    assert d["mutated_at"] == [4]


def test_snapshot_copy_launders_negative():
    src = '''
class Pool:
    def allocate(self, slot, blocks):
        self.block_tables[slot] = blocks

    def device_tables(self):
        return jnp.asarray(self.block_tables.copy())
'''
    assert _snap(src) == []


def test_snapshot_unmutated_buffer_negative():
    src = '''
class Pool:
    def device_tables(self):
        return jnp.asarray(self.block_tables)
'''
    assert _snap(src) == []


# ---------------------------------------------------------------------
# clean-tree contracts + wiring
# ---------------------------------------------------------------------


def test_real_tree_audit_clean():
    """audit_default() over the live serving stack: zero error
    findings — every real finding is fixed or allowlisted with
    evidence (ISSUE 20 triage discipline)."""
    findings = cc.audit_default()
    errors = [f for f in findings if f.severity == "error"]
    assert errors == [], [f.to_dict() for f in errors]
    # the engine contract rule must actually be doing work
    assert any("ServingEngine is single-threaded by contract"
               in f.detail for f in findings)


def test_lint_graft_concurrency_target():
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "lint_graft.py"),
         "--targets", "concurrency"],
        capture_output=True, text=True, timeout=300, cwd=_REPO)
    assert res.returncode == 0, res.stderr[-3000:]
    report = json.loads(res.stdout)
    assert report["ok"] is True
    assert report["targets"] == ["concurrency"]
    assert report["counts"]["error"] == 0
    assert {"cross-role-write", "snapshot-discipline",
            "lock-patrol"} <= set(report["passes"])


def test_all_new_passes_inert_without_meta():
    """lint_jaxpr with no meta keys: the concurrency passes contribute
    nothing (the PR-5 inertness contract for meta-gated passes)."""
    assert lint_jaxpr(None, passes=["cross-role-write",
                                    "snapshot-discipline",
                                    "lock-patrol"]) == []
