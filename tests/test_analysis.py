"""paddle_tpu.analysis: tracer-leak detector + jaxpr lint (ISSUE 5).

Three surfaces under test:

* the **birth/leak detector** — a forced leak (constant deliberately
  created under a dead sub-trace) must raise a TracerLeakError naming
  the birth op, birth trace and escape site; reverting the
  `_wrap_scalar` adoption fix must reproduce the historical dy2static
  while/cond leak as an *attributed* error; and the fixed while/cond
  path must run clean (the minimal regression independent of the big
  dy2static suites);
* the **lint passes** — one synthetic positive and one clean negative
  per pass (f64-upcast / donation / dynamic-shape-risk /
  host-callback), machine-readable findings, severity ordering, the
  plugin registry;
* the **real entry points** — the serving decode executable lints
  f64-clean and its donation findings agree with
  ``snapshot()["kv_donation"]`` on both aliasing and non-aliasing
  backends; ``TracedFunction.lint()`` over a compiled to_static entry;
  and ``tools/lint_graft.py`` (the repo self-lint) exits 0 with a
  parseable JSON report.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import (
    Finding, TracerLeakError, donated_invars_from_argnums, findings_to_json,
    lint_fn, lint_jaxpr, lint_passes, register_lint_pass,
)
from paddle_tpu.core import trace as trace_mod
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.observability import CompileWatchdog
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# tracer-leak detector
# ---------------------------------------------------------------------------

def test_forced_leak_raises_attributed_error():
    """A constant deliberately created under a sub-trace and NOT
    registered with the TraceContext must raise a TracerLeakError
    naming the birth op, the birth trace, and the escape site when the
    outer trace captures it — the ISSUE acceptance shape."""
    with analysis.birth_tracking():
        ctx = trace_mod.TraceContext("record")
        with trace_mod.trace_guard(ctx):
            holder = {}

            def body(x):
                with analysis.subtrace("while_cond"):
                    # born under the sub-trace, never register_created
                    holder["leak"] = Tensor(x + 1.0)
                return x

            jax.make_jaxpr(body)(jnp.float32(0.0))
            with pytest.raises(TracerLeakError) as ei:
                ctx.read(holder["leak"])  # outer capture of a dead tracer
    (finding,) = ei.value.findings
    assert finding["birth_op"] == "body"
    assert finding["birth_trace"].startswith("while_cond#")
    assert os.path.basename(__file__) in finding["birth_site"]
    assert os.path.basename(__file__) in finding["escape_site"]
    # the human message carries the same provenance
    msg = str(ei.value)
    for key in ("born in", finding["birth_trace"], "escaped"):
        assert key in msg


def test_check_trace_reports_without_raising():
    """check_trace(raise_error=False) returns machine-readable findings
    instead of raising — the report-only surface."""
    with analysis.birth_tracking():
        ctx = trace_mod.TraceContext("record")
        with trace_mod.trace_guard(ctx):
            holder = {}

            def body(x):
                with analysis.subtrace("cond_true"):
                    holder["leak"] = Tensor(x * 2.0)
                return x

            jax.make_jaxpr(body)(jnp.float32(1.0))
            # stuff it into the captured reads without tripping the
            # capture hook, then ask for the report
            ctx.reads[id(holder["leak"])] = holder["leak"]
            findings = analysis.check_trace(ctx, raise_error=False)
    assert len(findings) == 1
    assert findings[0]["birth_trace"].startswith("cond_true#")
    assert set(findings[0]) == {"tensor", "birth_op", "birth_site",
                                "birth_trace", "escape_site"}


def test_reverting_wrap_scalar_fix_reproduces_attributed_leak(monkeypatch):
    """With trace adoption disabled (the pre-fix behavior), the classic
    dy2static while/cond program leaks — and under birth tracking the
    failure is an attributed TracerLeakError, not jax's opaque
    UnexpectedTracerError."""
    monkeypatch.setattr(trace_mod, "adopt", lambda t: t)

    @paddle.jit.to_static
    def sample(x, n):
        s = x * 0.0
        for _ in range(n):          # tensor bound -> lax.while_loop
            if s.sum() < 100.0:     # tensor pred  -> lax.cond
                s = s + x
        return s

    xp = paddle.to_tensor(np.full((8,), 0.5, np.float32))
    with analysis.birth_tracking():
        with pytest.raises(TracerLeakError) as ei:
            for _ in range(3):      # eager -> record -> compiled
                sample(xp, paddle.to_tensor(np.int64(4)))
    findings = ei.value.findings
    assert findings, "leak must carry machine-readable findings"
    assert any(f["birth_trace"].startswith(("while_cond#", "while_body#",
                                            "cond_true#", "cond_false#"))
               for f in findings)


def test_while_cond_to_static_regression():
    """Minimal while/cond regression (satellite 1): the exact leak
    shape `_wrap_scalar` used to trip — python scalars inside a
    tensor-bound loop with a tensor cond — runs through all three
    to_static phases and matches eager numerics."""
    def program(x, n):
        s = x * 0.0
        i = 0
        for _ in range(n):
            if s.sum() < 6.0:       # scalar 6.0 wrapped inside while_cond
                s = s + x * 1.0     # scalar 1.0 wrapped inside while_body
                i = i + 1
        return s

    traced = paddle.jit.to_static(program)
    xp = paddle.to_tensor(np.full((4,), 0.5, np.float32))
    n = paddle.to_tensor(np.int64(5))
    want = program(xp, 5).numpy()
    for _ in range(3):              # eager -> record -> compiled replay
        got = traced(xp, n)
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-6)
    assert any(e["compiled"] is not None for e in traced.entries.values()), \
        "regression must exercise the compiled replay phase"


def test_birth_tracking_disabled_leaves_hooks_clear():
    """Off by default: no hooks installed, zero per-Tensor work beyond
    the single `is not None` test in Tensor.__init__."""
    assert trace_mod._birth_hook is None
    assert trace_mod._capture_hook is None
    assert not analysis.enabled()
    with analysis.birth_tracking():
        assert analysis.enabled()
        assert trace_mod._birth_hook is not None
        assert trace_mod._capture_hook is not None
    assert trace_mod._birth_hook is None
    assert not analysis.enabled()


def test_birth_of_records_op_and_subtrace():
    with analysis.birth_tracking():
        ctx = trace_mod.TraceContext("record")
        with trace_mod.trace_guard(ctx):
            with analysis.subtrace("while_body"):
                t = Tensor(jnp.zeros((2,)))
            birth = analysis.birth_of(t)
    assert birth is not None
    assert birth.subtrace.startswith("while_body#")
    assert os.path.basename(__file__) in birth.site


def test_created_ids_are_liveness_checked():
    """TraceContext.created must not mistake a recycled id() for a
    trace-created tensor (the nondeterminism the detector exposed)."""
    ctx = trace_mod.TraceContext("record")
    t = Tensor(jnp.zeros((2,)))
    ctx.register_created(t)
    assert ctx.is_created(t)
    dead_ref = ctx.created[id(t)]
    del t
    impostor = Tensor(jnp.ones((2,)))
    # simulate the allocator recycling the dead tensor's address
    ctx.created[id(impostor)] = dead_ref
    assert not ctx.is_created(impostor)


# ---------------------------------------------------------------------------
# lint passes: one synthetic positive + one clean negative each
# ---------------------------------------------------------------------------

def test_f64_upcast_positive_and_negative():
    with jax.enable_x64(True):
        pos = lint_fn(lambda x: x.astype(jnp.float64) * 2.0,
                      jax.ShapeDtypeStruct((4,), jnp.float32),
                      passes=["f64-upcast"])
    assert len(pos) >= 1
    assert pos[0].severity == "error"
    assert "float64" in pos[0].detail
    assert os.path.basename(__file__) in pos[0].site

    neg = lint_fn(lambda x: x * 2.0 + 1.0,
                  jax.ShapeDtypeStruct((4,), jnp.float32),
                  passes=["f64-upcast"])
    assert neg == []


def test_donation_positive_and_negatives():
    big = jax.ShapeDtypeStruct((512, 1024), jnp.float32)  # 2 MiB
    closed = jax.make_jaxpr(lambda a, b: (a + 1.0, b * 2.0))(big, big)
    pos = lint_jaxpr(closed, passes=["donation"],
                     donated_invars=(False, False), backend_aliases=True)
    assert len(pos) == 2
    assert all(f.severity == "warning" and "without donation" in f.detail
               for f in pos)
    # donated -> clean
    assert lint_jaxpr(closed, passes=["donation"],
                      donated_invars=(True, True),
                      backend_aliases=True) == []
    # non-aliasing backend (CPU) -> clean even undonated
    assert lint_jaxpr(closed, passes=["donation"],
                      donated_invars=(False, False),
                      backend_aliases=False) == []
    # below the size floor -> clean
    small = jax.make_jaxpr(lambda a: a + 1.0)(
        jax.ShapeDtypeStruct((8,), jnp.float32))
    assert lint_jaxpr(small, passes=["donation"], donated_invars=(False,),
                      backend_aliases=True) == []


def test_dynamic_shape_risk_positive_and_negative():
    wd = CompileWatchdog()
    wd.record("decode", signature="f32[4,64]", call_site="engine.py:10")
    wd.record("decode", signature="f32[4,96]", call_site="engine.py:10")
    wd.record("prefill", signature="i64[1,32]", call_site="engine.py:20")
    findings = lint_jaxpr(None, passes=["dynamic-shape-risk"], watchdog=wd)
    assert len(findings) == 1
    f = findings[0]
    assert f.severity == "warning"
    assert "decode" in f.detail and "2 distinct" in f.detail
    assert f.site == "engine.py:10"
    # single-signature watchdog -> clean
    wd2 = CompileWatchdog()
    wd2.record("decode", signature="f32[4,64]", call_site="engine.py:10")
    wd2.record("decode", signature="f32[4,64]", call_site="engine.py:10")
    assert lint_jaxpr(None, passes=["dynamic-shape-risk"],
                      watchdog=wd2) == []


def test_host_callback_positive_and_negative():
    def with_cb(x):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct((), jnp.float32), x)

    pos = lint_fn(with_cb, jax.ShapeDtypeStruct((), jnp.float32),
                  passes=["host-callback"])
    assert len(pos) == 1
    assert pos[0].severity == "warning"
    assert "pure_callback" in pos[0].detail

    neg = lint_fn(lambda x: jnp.sin(x),
                  jax.ShapeDtypeStruct((), jnp.float32),
                  passes=["host-callback"])
    assert neg == []


def test_lint_walks_nested_subjaxprs():
    """Findings inside cond branches / while bodies are reached (the
    pass walks every sub-jaxpr, not just the top level)."""
    with jax.enable_x64(True):
        def f(x):
            return jax.lax.cond(x[0] > 0,
                                lambda v: v.astype(jnp.float64).sum(),
                                lambda v: jnp.float64(0.0), x)
        pos = lint_fn(f, jax.ShapeDtypeStruct((4,), jnp.float32),
                      passes=["f64-upcast"])
    assert pos, "upcast inside a lax.cond branch must be found"


def test_findings_machine_readable_and_sorted():
    f = Finding("demo", "warning", "a.py:1", "detail")
    assert f.to_dict() == {"pass": "demo", "severity": "warning",
                           "site": "a.py:1", "detail": "detail"}
    loaded = json.loads(findings_to_json(
        [f, Finding("demo", "error", "b.py:2", "worse")]))
    assert [d["severity"] for d in loaded] == ["warning", "error"]

    @register_lint_pass("_test-multi")
    def _multi(jaxpr, meta):
        return [Finding("_test-multi", "info", "x", "i"),
                Finding("_test-multi", "error", "y", "e"),
                Finding("_test-multi", "warning", "z", "w")]
    try:
        out = lint_jaxpr(None, passes=["_test-multi"])
        assert [x.severity for x in out] == ["error", "warning", "info"]
    finally:
        from paddle_tpu.analysis import lint as lint_mod
        lint_mod._PASSES.pop("_test-multi", None)


def test_registry_and_unknown_pass():
    assert {"f64-upcast", "donation", "dynamic-shape-risk",
            "host-callback"} <= set(lint_passes())
    with pytest.raises(KeyError):
        lint_jaxpr(None, passes=["no-such-pass"])
    with pytest.raises(TypeError):
        lint_jaxpr(object())


def test_donated_invars_from_argnums_flattens_pytrees():
    args = ({"a": jnp.zeros(2), "b": jnp.zeros(2)}, jnp.zeros(3),
            [jnp.zeros(1), jnp.zeros(1)])
    flags = donated_invars_from_argnums(args, (1, 2))
    assert flags == (False, False, True, True, True)


# ---------------------------------------------------------------------------
# real entry points (satellite 3 + 5)
# ---------------------------------------------------------------------------

def _engine(**kw):
    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = ServingEngine(model, num_slots=4, **kw)
    rs = np.random.RandomState(0)
    for n in (5, 9):
        eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                        max_new_tokens=3)
    eng.run()
    return eng


def _donation_findings(eng, backend_aliases, min_bytes=1 << 14):
    """engine.lint's exact donation feed, with the backend aliasing
    behavior overridden so CPU CI can exercise the aliasing branch."""
    args, donate = eng._decode_dispatch_args(eng.pool)
    closed = jax.make_jaxpr(eng._decode_fn)(*args)
    donate = donate if eng._donate else ()
    return lint_jaxpr(
        closed, passes=["donation"],
        donated_invars=donated_invars_from_argnums(args, donate),
        backend_aliases=backend_aliases, min_donation_bytes=min_bytes)


def test_serving_decode_lints_clean():
    """The real decode executable: zero f64-upcast findings, zero
    host-callbacks, and engine.lint() as a whole is clean on this
    backend."""
    eng = _engine()
    eng.declare_warmup()
    assert eng.lint(passes=["f64-upcast"]) == []
    assert eng.lint(passes=["host-callback"]) == []
    assert [f for f in eng.lint() if f.severity == "error"] == []


def test_donation_pass_agrees_with_kv_donation_snapshot():
    """The donation pass and snapshot()["kv_donation"] must tell the
    same story on both backend kinds (satellite 3)."""
    eng = _engine()
    kv = eng.metrics.snapshot()["kv_donation"]
    aliases = eng._device.platform != "cpu"
    # the snapshot's two facts: donation enforced, and actually aliasing
    assert kv["effective"] == (kv["enabled"] and aliases)

    # (a) this backend, engine.lint defaults: no donation findings when
    # the backend doesn't alias OR the buffers are donated — i.e.
    # findings present only when donation is off where it would help.
    on_this_backend = [f for f in eng.lint(min_donation_bytes=1 << 14)
                       if f.pass_name == "donation"]
    if not aliases or kv["enabled"]:
        assert on_this_backend == []

    # (b) simulated NON-aliasing backend (CPU truth): always clean,
    # which is exactly kv_donation {"effective": False} there.
    assert _donation_findings(eng, backend_aliases=False) == []

    # (c) simulated aliasing backend: the undonated kc/vc caches are
    # flagged iff the engine compiled without donation. (Params may be
    # flagged too at this low size floor — they are genuinely undonated
    # — so key the agreement on the cache-shaped findings.)
    def cache_findings(findings, pool):
        shapes = {f"[{','.join(str(d) for d in np.shape(a))}]"
                  for a in jax.tree_util.tree_leaves([pool.kc, pool.vc])}
        return [f for f in findings if any(s in f.detail for s in shapes)]

    aliased = _donation_findings(eng, backend_aliases=True)
    if eng._donate:
        assert cache_findings(aliased, eng.pool) == []
    else:
        assert len(cache_findings(aliased, eng.pool)) >= 2  # kc and vc

    # (d) forcing donation on closes exactly the cache findings
    eng2 = _engine(donate_buffers=True)
    assert eng2.metrics.snapshot()["kv_donation"]["enabled"]
    aliased2 = _donation_findings(eng2, backend_aliases=True)
    assert cache_findings(aliased2, eng2.pool) == []


def test_traced_function_lint_clean_on_compiled_entry():
    @paddle.jit.to_static
    def step(x, n):
        s = x * 0.0
        for _ in range(n):
            if s.sum() < 100.0:
                s = s + x
        return s

    xp = paddle.to_tensor(np.full((8,), 0.5, np.float32))
    for _ in range(3):
        step(xp, paddle.to_tensor(np.int64(6)))
    findings = step.lint()
    assert isinstance(findings, list)
    assert [f for f in findings if f.severity == "error"] == []


def test_lint_graft_self_lints_repo_clean():
    """tools/lint_graft.py (satellite 5): the repo's own jitted entry
    points lint clean — exit 0 and a parseable JSON report."""
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "lint_graft.py")],
        capture_output=True, text=True, timeout=900, cwd=_REPO)
    assert res.returncode == 0, res.stderr[-3000:]
    report = json.loads(res.stdout)
    assert report["ok"] is True
    assert report["counts"]["error"] == 0
    assert set(report["targets"]) == {"serving_decode",
                                      "paged_decode_pallas",
                                      "chunked_prefill", "spec_verify",
                                      "kv_wire", "hapi_train_step",
                                      "to_static_sample", "concurrency"}
    assert {"donation", "dynamic-shape-risk", "f64-upcast",
            "host-callback"} <= set(report["passes"])


def test_lint_graft_to_static_target_fast():
    """A tier-1 (non-slow) slice of the self-lint: the to_static sample
    target alone keeps the CLI contract tested in every run."""
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "lint_graft.py"),
         "--targets", "to_static_sample"],
        capture_output=True, text=True, timeout=600, cwd=_REPO)
    assert res.returncode == 0, res.stderr[-3000:]
    report = json.loads(res.stdout)
    assert report["ok"] is True and report["targets"] == ["to_static_sample"]
