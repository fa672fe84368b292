"""@to_static: whole-step program capture and compilation.

TPU-native replacement for the reference dygraph-to-static system
(reference: python/paddle/fluid/dygraph/dygraph_to_static/
program_translator.py:232 StaticFunction/ProgramTranslator,
partial_program.py PartialProgramLayer running a captured ProgramDesc via
the run_program op). Design difference: instead of AST-rewriting Python
control flow into program ops, we capture the actual execution trace as a
single XLA computation via jax.jit:

call 1 (per input signature): runs eagerly (warmup; lazily-created state
  like optimizer moments materializes).
call 2: runs eagerly under a recording TraceContext that discovers which
  pre-existing Tensors the function reads (compiled inputs) and mutates
  (compiled outputs written back after each call) — parameters, optimizer
  state, RNN/batch-norm stats, RNG state.
call 3+: executes the jit-compiled XLA program; mutated state buffers are
  donated, so parameter updates are in-place at the XLA level.

Host spans (profiler.host_scope): the two set-up passes
are ``jit/eager`` and ``jit/record``; every compiled call is ``jit/call``
(gather the captured arrays, call, write the mutated state back) with
``jit/enqueue`` inside it around the jitted callable alone, which on its
first call per shape also traces and compiles.

Python control flow is supported naturally when it doesn't depend on
traced values (it is unrolled/baked like the reference's static backend);
data-dependent branching inside a compiled step should use tensor ops
(where/cond) — same constraint the reference's static graph has.
"""
import functools
import weakref

import numpy as np

import jax

from .. import profiler as _profiler
from ..core import trace as trace_mod
from ..core.tensor import Tensor
from ..observability import watchdog as _watchdog


def _flatten(obj, leaves):
    """Flatten nested (list/tuple/dict) structure, extracting Tensor leaves.
    Returns a structure token for cache keys."""
    if isinstance(obj, Tensor):
        leaves.append(obj)
        return ("T",)
    if isinstance(obj, (list, tuple)):
        return ("L" if isinstance(obj, list) else "t",
                tuple(_flatten(o, leaves) for o in obj))
    if isinstance(obj, dict):
        return ("D", tuple(sorted((k, _flatten(v, leaves))
                                  for k, v in obj.items())))
    return ("C", obj if _hashable_const(obj) else repr(obj))


def _hashable_const(o):
    try:
        hash(o)
        return True
    except TypeError:
        return False


def _rebuild(struct, leaf_iter):
    kind = struct[0]
    if kind == "T":
        return next(leaf_iter)
    if kind in ("L", "t"):
        seq = [_rebuild(s, leaf_iter) for s in struct[1]]
        return seq if kind == "L" else tuple(seq)
    if kind == "D":
        return {k: _rebuild(s, leaf_iter) for k, s in struct[1]}
    return struct[1]


def _abstract(a):
    """What lowering needs to know of one argument, pinning nothing: a
    ShapeDtypeStruct, with the sharding where the array is committed to
    one (as jit itself resolves it, so that lowering on these finds
    what the call on the arrays lowered)."""
    sharding = a.sharding if getattr(a, "committed", False) else None
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                weak_type=getattr(a, "weak_type", False))


def _text_producer(lowered):
    """The compiled text of a ``Lowered``, when called. All the closure
    pins is the lowering: no Tensor, no array, not the recorded
    function (jax keeps the loaded executable with the lowering, so the
    entry that owns the step has the record resolved when it goes)."""
    def text():
        return lowered.compile().as_text()
    return text


def captured_arrays(compiled):
    """(mutated, read-only) captured state of a compiled entry as jax
    arrays, in the order its jitted callable takes them."""
    mset = set(compiled["mut_cap_idx"])
    captured = compiled["captured"]
    return ([captured[i].value for i in compiled["mut_cap_idx"]],
            [t.value for i, t in enumerate(captured) if i not in mset])


class TracedFunction:
    def __init__(self, fn, input_spec=None, warmup=1, enable_ast=True):
        if enable_ast and not getattr(fn, "__wrapped_dy2static__", False):
            # AST-rewrite tensor-dependent if/while into lax control flow
            # (reference: dygraph_to_static program_translator.py applies
            # its AST suite under @to_static)
            from .dy2static import convert_to_static
            fn = convert_to_static(fn)
        self._fn = fn
        self._input_spec = input_spec
        # warmup=0: skip the eager pass and record on call 1 — valid when
        # all lazily-created state (optimizer moments, BN stats) already
        # exists, e.g. after one eager step at any batch size
        self._warmup = max(0, warmup)
        self._entries = {}  # signature -> dict(state)
        functools.update_wrapper(self, fn)
        self._bound_instance = None

    def __get__(self, instance, owner):
        if instance is None:
            return self
        bound = TracedFunction(self._fn.__get__(instance, owner),
                               self._input_spec, self._warmup)
        bound._entries = self._entries  # share cache across accesses
        # NOTE: methods on the same instance share compiled entries; distinct
        # instances get distinct bound closures via instance id in signature.
        bound._bound_instance = instance
        return bound

    @property
    def entries(self):
        return self._entries

    def _signature(self, args, kwargs):
        leaves = []
        struct = _flatten((args, kwargs), leaves)
        avals = tuple((tuple(t.aval_shape()), str(t.value.dtype))
                      for t in leaves)
        inst = id(self._bound_instance) if self._bound_instance is not None else 0
        return (struct, avals, inst), leaves, struct

    def __call__(self, *args, **kwargs):
        if trace_mod.current_trace() is not None:
            # nested to_static inside a trace: inline
            return self._fn(*args, **kwargs)
        sig, leaves, struct = self._signature(args, kwargs)
        entry = self._entries.get(sig)
        if entry is None:
            entry = {"calls": 0, "compiled": None, "record": None}
            self._entries[sig] = entry
        if entry["compiled"] is None:
            # Shape-polymorphic reuse: the compiled closure re-runs the
            # capture under jax.jit, which re-specializes per shape on
            # its own. A previous record with the same STRUCTURE (same
            # pytree of args, different shapes/dtypes) discovered the
            # same closure state, so new batch sizes skip the eager and
            # record passes entirely — in particular, a large-batch step
            # never executes eagerly (eager holds every intermediate
            # live and OOMs long before the compiled program would).
            donor = self._same_struct_compiled(sig, struct)
            if donor is not None:
                entry["compiled"] = donor
        if entry["compiled"] is not None:
            with _profiler.host_scope("jit/call"):
                return self._run_compiled(entry, struct, leaves)
        entry["calls"] += 1
        if entry["calls"] <= self._warmup:
            with _profiler.host_scope("jit/eager"):
                return self._fn(*args, **kwargs)
        with _profiler.host_scope("jit/record"):
            return self._record_and_compile(entry, args, kwargs, struct,
                                            leaves)

    def _same_struct_compiled(self, sig, struct):
        _, _, inst = sig
        for (struct2, _, inst2), e2 in self._entries.items():
            if struct2 == struct and inst2 == inst \
                    and e2.get("compiled") is not None:
                return e2["compiled"]
        return None

    # -- phase 2: record ---------------------------------------------------
    def _record_and_compile(self, entry, args, kwargs, struct, leaves):
        ctx = trace_mod.TraceContext("record")
        with trace_mod.trace_guard(ctx):
            out = self._fn(*args, **kwargs)
        if trace_mod._capture_hook is not None:
            # birth tracking on: validate the recorded graph BEFORE
            # compiling — a sub-trace value sitting in the captured
            # reads raises an attributed TracerLeakError here instead
            # of an opaque jax error at the first compiled call
            from ..analysis import birth as _birth
            _birth.check_trace(ctx)
        reads = [t for tid, t in ctx.reads.items()]
        writes = [t for tid, t in ctx.writes.items()]
        read_ids = set(ctx.reads)
        captured = reads + [t for t in writes if id(t) not in read_ids]
        mutated = writes
        mutated_in_captured = [i for i, t in enumerate(captured)
                               if id(t) in ctx.writes]
        out_leaves = []
        out_struct = _flatten(out, out_leaves)
        fn = self._fn
        grad_owners = []  # captured tensors whose .grad is created in-trace

        def compiled_fn(arg_arrays, mut_cap_arrays, ro_cap_arrays):
            jctx = trace_mod.TraceContext("jit")
            mut_caps = [captured[i] for i in mutated_in_captured]
            ro_caps = [t for i, t in enumerate(captured)
                       if i not in set(mutated_in_captured)]
            grad_owners.clear()
            with trace_mod.trace_guard(jctx):
                for t, a in zip(mut_caps, mut_cap_arrays):
                    jctx.bind(t, a)
                for t, a in zip(ro_caps, ro_cap_arrays):
                    jctx.bind(t, a)
                arg_tensors = [Tensor(a) for a in arg_arrays]
                for t in arg_tensors:
                    jctx.register_created(t)
                it = iter(arg_tensors)
                cargs, ckwargs = _rebuild(struct, it)
                result = fn(*cargs, **ckwargs)
                res_leaves = []
                _flatten(result, res_leaves)
                out_arrays = [t.value for t in res_leaves]
                mut_arrays = [jctx.final_value(t) for t in mutated]
                # Gradients created during the trace that remain attached to
                # captured tensors (the "backward inside, clear outside"
                # pattern): emit their final values so callers can read
                # .grad after a compiled step.
                grad_arrays = []
                for t in captured:
                    g = t._grad
                    if isinstance(g, Tensor) and jctx.is_created(g):
                        grad_owners.append(t)
                        grad_arrays.append(jctx.final_value(g))
            return out_arrays, mut_arrays, grad_arrays

        jitted = jax.jit(compiled_fn, donate_argnums=(1,))
        entry["compiled"] = {
            "jitted": jitted,
            "fn": compiled_fn,  # re-traceable for analysis.lint_jaxpr
            "captured": captured,
            "mutated": mutated,
            "mut_cap_idx": mutated_in_captured,
            "out_struct": out_struct,
            "grad_owners": grad_owners,
        }
        entry["record"] = None
        return out

    # -- phase 3: run compiled --------------------------------------------
    def _run_compiled(self, entry, struct, leaves):
        c = entry["compiled"]
        mut_caps, ro_caps = captured_arrays(c)
        arg_arrays = [t.value for t in leaves]
        # the first compiled call of a signature: its shapes, taken
        # before the call donates what it mutates
        sds = None if "program" in entry else jax.tree_util.tree_map(
            _abstract, (arg_arrays, mut_caps, ro_caps))
        try:
            with _profiler.host_scope("jit/enqueue"):
                out_arrays, mut_arrays, grad_arrays = c["jitted"](
                    arg_arrays, mut_caps, ro_caps)
        except jax.errors.UnexpectedTracerError as e:
            # structured replacement for jax's opaque leak error: a
            # captured input carried a dead sub-trace tracer into the
            # replay. With birth tracking on the leak usually raises
            # earlier WITH provenance; this is the always-on net.
            from ..analysis.birth import TracerLeakError
            raise TracerLeakError(
                "to_static replay captured a value that escaped a "
                "cond/while sub-trace (a Tensor created inside the "
                "sub-trace was not registered with the active "
                "TraceContext — see trace_mod.adopt). Re-run under "
                "paddle_tpu.analysis.birth_tracking() to attribute "
                "the birth op/trace and escape site.\n\nOriginal "
                f"error: {e}") from e
        if sds is not None:
            self._note_program(entry, c, sds)
        for t, v in zip(c["mutated"], mut_arrays):
            t._value = v
        for t, g in zip(c["grad_owners"], grad_arrays):
            t._grad = Tensor(g, stop_gradient=True)
        out_tensors = iter([Tensor(a) for a in out_arrays])
        return _rebuild(c["out_struct"], out_tensors)

    def _note_program(self, entry, c, sds):
        """After the first compiled call of a signature: hand the
        compile watchdog's table of programs
        (``watchdog.program_scopes``) a way to the text of the program
        that call ran. ``c["jitted"].lower`` on the call's own shapes
        finds what the call just traced and lowered (jax's caches: no
        second trace), and ``compile()`` on it the executable the step
        runs; ``as_text()`` and the parse wait until the table is asked
        for, or until this entry's compiled function is dropped,
        whichever is first, never in a step. The table holds the
        ``Lowered`` until then: no Tensor of the model."""
        name = getattr(self, "__name__", "fn")
        try:
            lowered = c["jitted"].lower(*sds)
        except Exception:  # noqa: BLE001 - an observer never raises
            entry["program"] = None
            return
        entry["program"] = _watchdog.note_program(
            ("to_static", name), producer=_text_producer(lowered),
            signature=_watchdog.abstract_signature(sds),
            owner=_watchdog.new_owner())
        finalizer = weakref.finalize(
            c["fn"], _watchdog.resolve_program, entry["program"])
        finalizer.atexit = False

    def concrete_program(self):
        return self._entries

    # -- static analysis ---------------------------------------------------
    def lint(self, passes=None, **meta):
        """Run the paddle_tpu.analysis jaxpr lint over every compiled
        entry of this traced function (the whole captured step:
        forward + backward + optimizer when they were traced).
        Abstract args are rebuilt from the entry's signature, so no
        device execution happens; the mutated-captures donation the
        compiled step uses is threaded to the ``donation`` pass.
        Returns the combined findings (see analysis.lint_jaxpr)."""
        from ..analysis import lint as lint_mod
        findings = []
        for (struct, avals, _inst), entry in self._entries.items():
            c = entry.get("compiled")
            if not c or "fn" not in c:
                continue
            arg_sds = [jax.ShapeDtypeStruct(shape, np.dtype(dtype))
                       for shape, dtype in avals]
            args = (arg_sds, *captured_arrays(c))
            closed = jax.make_jaxpr(c["fn"])(*args)
            findings.extend(lint_mod.lint_jaxpr(
                closed, passes=passes,
                donated_invars=lint_mod.donated_invars_from_argnums(
                    args, (1,)),
                **meta))
        return findings


def to_static(function=None, input_spec=None, build_strategy=None,
              property=False, warmup=1):  # noqa: A002
    """paddle.jit.to_static equivalent."""
    def deco(fn):
        from ..nn.layer_base import Layer
        if isinstance(fn, Layer):
            layer = fn
            layer.forward = TracedFunction(layer.forward, input_spec,
                                           warmup=warmup)
            return layer
        return TracedFunction(fn, input_spec, warmup=warmup)
    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    return fn
