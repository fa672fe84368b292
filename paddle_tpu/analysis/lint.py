"""Jaxpr lint framework: pluggable static-analysis passes over lowered
programs.

The Paddle reference inspects programs at the ProgramDesc/IR level
(graph passes over op descs); our compiled unit is a jaxpr, so this is
the analogue: ``lint_jaxpr(target)`` walks a lowered program (and every
sub-jaxpr: cond branches, while cond/body, scan bodies, inner pjit
calls) through registered passes, each emitting machine-readable
findings ``{"pass", "severity", "site", "detail"}``.

Built-in passes:

``f64-upcast``
    any equation producing float64 from non-float64 inputs (or from
    nothing: a fresh f64 constant/iota) — silent 2x memory + compute
    on the hot path. Severity ``error``.
``donation``
    large array inputs compiled WITHOUT buffer donation on a backend
    that aliases donated buffers — the double-buffering the serving
    engine's kc/vc/pos donation exists to avoid. Needs
    ``donated_invars`` (see :func:`donated_invars_from_argnums`) and
    ``backend_aliases`` metadata; emits nothing on non-aliasing
    backends (CPU), which is exactly what
    ``ServingMetrics.snapshot()["kv_donation"]`` reports there.
    Severity ``warning``.
``dynamic-shape-risk``
    one executable key compiled under more than one distinct
    abstract-shape signature, read from a PR-3 CompileWatchdog
    (``watchdog=`` metadata; ``CompileWatchdog.signature_groups()``)
    — the recompile shape of python-int shapes derived from traced
    values, attributed to the recorded dispatch call-sites. Severity
    ``warning``.
``host-callback``
    ``pure_callback`` / ``io_callback`` / ``debug_callback`` equations
    inside the program — a host round-trip per dispatch inside a
    decode/train step. Severity ``warning``.

Passes are functions ``(jaxpr_or_None, meta) -> list[Finding]``
registered via :func:`register_lint_pass`; unknown metadata keys are
ignored by passes that don't use them, so one ``lint_jaxpr`` call can
feed every pass.
"""
import dataclasses
import json

import numpy as np

SEVERITIES = ("error", "warning", "info")
_SEV_ORDER = {s: i for i, s in enumerate(SEVERITIES)}


@dataclasses.dataclass
class Finding:
    """One lint finding. ``to_dict()`` is the machine-readable schema
    (the ``pass`` key carries the pass name)."""
    pass_name: str
    severity: str
    site: str
    detail: str

    def to_dict(self):
        return {"pass": self.pass_name, "severity": self.severity,
                "site": self.site, "detail": self.detail}

    def __str__(self):
        return (f"[{self.severity}] {self.pass_name} @ {self.site}: "
                f"{self.detail}")


def findings_to_json(findings, indent=2):
    return json.dumps([f.to_dict() for f in findings], indent=indent)


_PASSES = {}


def register_lint_pass(name):
    """Register ``fn(jaxpr_or_None, meta) -> list[Finding]`` under
    ``name``. Re-registering replaces (tests stub passes this way)."""
    def deco(fn):
        _PASSES[name] = fn
        return fn
    return deco


def lint_passes():
    """Names of all registered passes, sorted."""
    return sorted(_PASSES)


# ------------------------------------------------------------ jaxpr walk

def _as_jaxprs(v):
    from jax.extend import core as jex_core
    if isinstance(v, jex_core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, jex_core.Jaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        return [j for x in v for j in _as_jaxprs(x)]
    return []


def iter_eqns(jaxpr):
    """Every equation in ``jaxpr`` including all nested sub-jaxprs
    (cond branches, while cond/body, scan/pjit bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _as_jaxprs(v):
                yield from iter_eqns(sub)


def eqn_site(eqn):
    """``file:line (function)`` of the user frame that emitted the
    equation, via jax's source_info; "<unknown>" when unavailable."""
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return "<unknown>"
    return (f"{frame.file_name}:{frame.start_line} "
            f"({frame.function_name})")


def _resolve(target):
    """target -> core Jaxpr. Accepts ClosedJaxpr (jax.make_jaxpr
    output), a raw Jaxpr, anything exposing ``.jaxpr`` (jax.stages
    Traced), a ServingEngine (delegates to ``engine.lint``'s
    resolution), or None (meta-only passes still run)."""
    from jax.extend import core as jex_core
    if target is None:
        return None
    if isinstance(target, jex_core.Jaxpr):
        return target
    if isinstance(target, jex_core.ClosedJaxpr):
        return target.jaxpr
    inner = getattr(target, "jaxpr", None)
    if inner is not None:
        return _resolve(inner)
    raise TypeError(
        f"lint_jaxpr target {type(target).__name__} is not a jaxpr; "
        "pass a jax.make_jaxpr(...) result, an object with .jaxpr, or "
        "use ServingEngine.lint() / TracedFunction.lint() for compiled "
        "entry points")


def lint_jaxpr(target=None, passes=None, **meta):
    """Run lint passes over a lowered program; returns findings sorted
    most-severe first.

    ``target`` — ClosedJaxpr / Jaxpr / object with ``.jaxpr``; or None
    to run only metadata-driven passes (e.g. ``dynamic-shape-risk``
    over a ``watchdog=``). ``passes`` selects a subset by name.
    Metadata used by the built-ins: ``donated_invars``,
    ``backend_aliases``, ``min_donation_bytes``, ``watchdog``.
    """
    jaxpr = _resolve(target)
    names = list(passes) if passes is not None else lint_passes()
    findings = []
    for name in names:
        fn = _PASSES.get(name)
        if fn is None:
            raise KeyError(f"unknown lint pass {name!r}; registered: "
                           f"{lint_passes()}")
        findings.extend(fn(jaxpr, meta) or [])
    findings.sort(key=lambda f: _SEV_ORDER.get(f.severity, len(SEVERITIES)))
    return findings


def lint_fn(fn, *args, passes=None, **meta):
    """Convenience: ``lint_jaxpr(jax.make_jaxpr(fn)(*args), ...)``.
    ``args`` may be arrays or jax.ShapeDtypeStruct avals."""
    import jax
    return lint_jaxpr(jax.make_jaxpr(fn)(*args), passes=passes, **meta)


def donated_invars_from_argnums(args, donate_argnums):
    """Flattened per-invar donation flags for positional ``args``
    compiled with ``donate_argnums`` — the shape the ``donation`` pass
    consumes (jaxpr invars are the flattened leaves of the positional
    args, in order)."""
    import jax
    donate = set(donate_argnums)
    flags = []
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        flags.extend([i in donate] * n)
    return tuple(flags)


# ---------------------------------------------------------------- passes

_F64 = np.dtype("float64")


def _aval_dtype(atom):
    aval = getattr(atom, "aval", None)
    return getattr(aval, "dtype", None)


@register_lint_pass("f64-upcast")
def _pass_f64_upcast(jaxpr, meta):
    if jaxpr is None:
        return []
    findings = []
    for eqn in iter_eqns(jaxpr):
        out64 = [v for v in eqn.outvars if _aval_dtype(v) == _F64]
        if not out64:
            continue
        in_dtypes = [dt for dt in (_aval_dtype(v) for v in eqn.invars)
                     if dt is not None]
        if in_dtypes and all(dt == _F64 for dt in in_dtypes):
            continue  # f64 flowing through; the original upcast is flagged
        src = ",".join(sorted({str(dt) for dt in in_dtypes})) or "<none>"
        findings.append(Finding(
            "f64-upcast", "error", eqn_site(eqn),
            f"{eqn.primitive.name} produces float64 from [{src}] — "
            "silent f64 promotion on the hot path (2x memory/compute; "
            "TPUs emulate f64)"))
    return findings


@register_lint_pass("donation")
def _pass_donation(jaxpr, meta):
    if jaxpr is None:
        return []
    aliases = meta.get("backend_aliases")
    if aliases is None:
        import jax
        aliases = jax.devices()[0].platform != "cpu"
    if not aliases:
        # non-aliasing backend (CPU): donation is pure dispatch
        # overhead there — matches snapshot()["kv_donation"]
        # {"effective": False}
        return []
    donated = tuple(meta.get("donated_invars") or ())
    min_bytes = int(meta.get("min_donation_bytes", 1 << 20))
    findings = []
    for i, var in enumerate(jaxpr.invars):
        aval = getattr(var, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            continue
        nbytes = int(np.prod(aval.shape or (1,))) * np.dtype(aval.dtype).itemsize
        is_donated = donated[i] if i < len(donated) else False
        if nbytes >= min_bytes and not is_donated:
            findings.append(Finding(
                "donation", "warning", f"invar[{i}]",
                f"{aval.dtype}[{','.join(str(d) for d in aval.shape)}] "
                f"({nbytes} bytes) compiled without donation on an "
                "aliasing backend — the update double-buffers instead "
                "of aliasing in place (serving donates kc/vc/pos; see "
                "ServingConfig(donate_buffers=))"))
    return findings


@register_lint_pass("dynamic-shape-risk")
def _pass_dynamic_shape_risk(jaxpr, meta):
    watchdog = meta.get("watchdog")
    if watchdog is None:
        return []
    findings = []
    for key, group in sorted(watchdog.signature_groups().items()):
        sigs = group["signatures"]
        if len(sigs) <= 1:
            continue
        sites = group["call_sites"]
        findings.append(Finding(
            "dynamic-shape-risk", "warning", sites[-1],
            f"executable {key} compiled under {len(sigs)} distinct "
            "abstract-shape signatures — a python-int shape derived "
            "from traced values re-specializes per value (recompile "
            f"source); signatures: {sigs[:4]}"))
    return findings


_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "host_callback",
    "outside_call", "python_callback",
})


@register_lint_pass("host-callback")
def _pass_host_callback(jaxpr, meta):
    if jaxpr is None:
        return []
    findings = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMITIVES:
            findings.append(Finding(
                "host-callback", "warning", eqn_site(eqn),
                f"{eqn.primitive.name} inside the compiled program — "
                "one host round-trip per dispatch (debug print / "
                "pure_callback left in a decode/train step?)"))
    return findings
