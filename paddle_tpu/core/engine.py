"""Autograd backward engine.

TPU-native equivalent of the reference dygraph autograd engine (reference:
paddle/fluid/imperative/basic_engine.cc BasicEngine::Execute,
paddle/fluid/imperative/op_base.h:202 GradOpNode,
paddle/fluid/imperative/gradient_accumulator.cc). Differences:

- A GradNode's backward is the jax.vjp of the forward closure (jit-cached),
  rather than a separately-registered grad op; XLA prunes unused primal
  computation from the vjp.
- Gradient accumulation for leaf tensors is an in-place `.value` update so
  the accumulation threads through traced (to_static) steps.
- Topological traversal is an iterative postorder DFS instead of reference
  dependency counting; the visible semantics (sum-accumulation, hooks,
  stop_gradient cuts) match.
"""
import numpy as np
import jax
import jax.numpy as jnp

from . import lazy as lazy_mod
from . import trace as trace_mod

_ones_cache = {}  # backward seed cotangents, keyed by (shape, dtype)


class GradNode:
    __slots__ = ("op", "key", "closure", "arrays", "input_tensors",
                 "out_avals", "out_refs", "pending", "released", "multi_out",
                 "scope")

    def __init__(self, op, key, closure, arrays, input_tensors, out_avals):
        self.op = op
        self.key = key
        self.closure = closure
        self.arrays = arrays
        # Tensor owner (or None for raw-array inputs) per array slot, aligned
        # with `arrays` and with jax.vjp's returned gradients.
        self.input_tensors = input_tensors
        self.out_avals = out_avals  # list of (shape, jnp dtype)
        self.out_refs = None
        self.pending = None  # cotangent slots during a backward run
        self.released = False
        self.multi_out = False
        # the device_scope names open where the node was recorded, kept
        # only while a step is staged (mode "jit"): its backward then
        # runs under "bwd" + these, so a backward op's op_name reads
        # .../bwd/block/mlp/... Eager nodes keep None (their backward
        # is a cached jit shared across call sites).
        ctx = trace_mod.current_trace()
        self.scope = _current_scopes() \
            if ctx is not None and ctx.mode == "jit" else None

    def parents(self):
        seen = []
        for t in self.input_tensors:
            if t is not None and t._grad_node is not None:
                node = t._grad_node[0]
                if node is not self:
                    seen.append(node)
        return seen


def _current_scopes():
    from ..profiler import current_scopes
    return current_scopes()


class _backward_scope:
    """``jax.named_scope("bwd")`` + the forward's scopes (``scope``, a
    node's tuple) around one node's backward: its vjp and the
    accumulation of what it gives."""

    __slots__ = ("_stack",)

    def __init__(self, scope):
        self._stack = [jax.named_scope(n) for n in ("bwd",) + scope]

    def __enter__(self):
        for ns in self._stack:
            ns.__enter__()

    def __exit__(self, *exc):
        for ns in reversed(self._stack):
            ns.__exit__(*exc)
        return False


def register_tensor_hook(tensor, hook):
    """Hook called with the gradient Tensor when it is computed; may return a
    replacement (reference: VarBase::RegisterGradHook via pybind). Fires for
    both leaf gradients (at accumulation) and non-leaf gradients (on the
    cotangent flowing into the producing node). Hooks live on the Tensor
    itself, so their lifetime matches the tensor's."""
    if tensor._hooks is None:
        tensor._hooks = []
    hooks = tensor._hooks
    hooks.append(hook)

    class _Removable:
        def remove(self_inner):
            try:
                hooks.remove(hook)
            except ValueError:
                pass
    return _Removable()


def _apply_hooks(tensor, grad_array):
    from .tensor import Tensor
    if tensor is None or not tensor._hooks:
        return grad_array
    g = Tensor(grad_array, stop_gradient=True)
    for h in list(tensor._hooks):
        out = h(g)
        if out is not None:
            g = out
    return g.value if isinstance(g, Tensor) else g


def _zero_ct(shape, dt):
    if jnp.issubdtype(dt, jnp.floating) or jnp.issubdtype(dt, jnp.complexfloating):
        return jnp.zeros(shape, dt)
    return np.zeros(shape, jax.dtypes.float0)


def _accumulate_into_leaf(tensor, grad_array, create_graph=False):
    from .tensor import Tensor
    from .sparse_grad import IndexedSlices, SparseGradTensor
    if isinstance(grad_array, IndexedSlices) and not create_graph:
        if tensor._hooks:
            # opaque hooks see dense tensors; correctness over sparsity
            grad_array = grad_array.to_dense()
        elif isinstance(tensor._grad, SparseGradTensor):
            tensor._grad.accumulate(grad_array)
            return
        elif tensor._grad is None:
            tensor._grad = SparseGradTensor(grad_array,
                                            name=tensor.name + "@GRAD")
            from . import trace as trace_mod
            ctx = trace_mod.current_trace()
            if ctx is not None:
                ctx.register_created(tensor._grad)
            return
        else:  # existing dense grad: densify the slices into it
            grad_array = grad_array.to_dense()
    elif isinstance(grad_array, IndexedSlices):
        grad_array = grad_array.to_dense()
    if create_graph:
        # grad_array is a live Tensor; keep its graph so grads of grads work
        g = grad_array
        if tensor._hooks:
            raise NotImplementedError(
                "tensor hooks are not supported together with "
                "create_graph=True (the hook would cut the double-grad "
                "chain)")
        tensor._grad = g if tensor._grad is None else tensor._grad + g
        tensor._grad.name = tensor.name + "@GRAD"
        from . import trace as trace_mod
        ctx = trace_mod.current_trace()
        if ctx is not None:
            ctx.register_created(tensor._grad)
        return
    grad_array = _apply_hooks(tensor, grad_array)
    if tensor._grad is None:
        tensor._grad = Tensor(grad_array, stop_gradient=True,
                              name=tensor.name + "@GRAD")
        from . import trace as trace_mod
        ctx = trace_mod.current_trace()
        if ctx is not None:
            ctx.register_created(tensor._grad)
    else:
        # keep the same Tensor object so traced steps functionalize correctly
        tensor._grad.value = lazy_mod.add(tensor._grad.value, grad_array)


def _node_backward(node, cts, create_graph):
    """One node's vjp on its cotangents, and what it gives handed to
    the node's inputs."""
    if create_graph:
        in_grads = _vjp_apply(node, cts)
    else:
        in_grads = None
        # only standard deferrable ops: custom op stand-ins (e.g.
        # _SparseLookupOp) override vjp_fn with semantics autodiff
        # of the closure would not reproduce (IndexedSlices grads)
        if node.closure is not None and getattr(node.op, "defer", False) \
                and lazy_mod.enabled():
            # lazy micro-tracing: the vjp becomes a deferred node so
            # the whole backward fuses into the step's micro-graph
            try:
                in_grads = lazy_mod.dispatch_vjp(node, cts)
            except lazy_mod.Fallback:
                in_grads = None
        if in_grads is None:
            if lazy_mod.ever_enabled():
                cts_c = [
                    _zero_ct(*node.out_avals[i]) if c is None
                    else lazy_mod.concrete(c)
                    for i, c in enumerate(cts)]
            else:
                cts_c = cts
            ct_arg = tuple(cts_c) if node.multi_out else cts_c[0]
            bwd = node.op.vjp_fn(node.key, node.closure)
            arrays = node.arrays
            if arrays is not None and lazy_mod.ever_enabled():
                arrays = [lazy_mod.concrete(a) for a in arrays]
            in_grads = bwd(arrays, ct_arg)
    _distribute(node, in_grads, create_graph)


def run_backward(loss, grad_tensor=None, retain_graph=False,
                 create_graph=False):
    from .tensor import Tensor
    if loss.stop_gradient or loss._grad_node is None:
        raise RuntimeError(
            f"Tensor {loss.name!r} has no grad graph (stop_gradient=True or "
            "no recorded ops)")
    root_node, root_idx = loss._grad_node
    if grad_tensor is None:
        shape, dt = root_node.out_avals[root_idx]
        ck = (tuple(shape), str(dt))
        init_ct = _ones_cache.get(ck)
        if init_ct is None:
            init_ct = jnp.ones(shape, dt)
            # under an active jax trace jnp.ones returns a TRACER;
            # caching it would leak it into every later trace as a
            # foreign constant (observed as "+2 buffers" executable
            # mismatches across tests) — cache concrete arrays only
            if not isinstance(init_ct, jax.core.Tracer):
                if len(_ones_cache) > 512:
                    _ones_cache.clear()
                _ones_cache[ck] = init_ct
    else:
        init_ct = grad_tensor.value if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)
    if create_graph:
        # cotangents flow as live Tensors through differentiable vjp ops
        # (reference: partial_grad_engine.cc create_graph double-grad path);
        # the vjp ops capture closures by value, so the first-order nodes
        # need not be retained unless the caller asks
        if isinstance(grad_tensor, Tensor) and not grad_tensor.stop_gradient:
            init_ct = grad_tensor
        else:
            init_ct = Tensor(init_ct, stop_gradient=True)

    # Postorder DFS for reverse-topological order over reachable nodes.
    order = []
    state = {}  # node -> 0 visiting, 1 done
    stack = [(root_node, False)]
    while stack:
        node, processed = stack.pop()
        if processed:
            state[node] = 1
            order.append(node)
            continue
        if state.get(node) is not None:
            continue
        state[node] = 0
        stack.append((node, True))
        for p in node.parents():
            if state.get(p) is None:
                stack.append((p, False))

    for node in order:
        node.pending = [None] * len(node.out_avals)
    root_node.pending[root_idx] = init_ct

    lazy_bwd = not create_graph and lazy_mod.enabled()
    for node in reversed(order):
        cts = []
        any_ct = False
        for i, (shape, dt) in enumerate(node.out_avals):
            ct = node.pending[i]
            if ct is None:
                if lazy_bwd:
                    # deferred vjp treats None as an absent cotangent
                    # (builds the zeros inside the fused graph); avoids
                    # one eager bind per missing output
                    cts.append(None)
                    continue
                ct = _zero_ct(shape, dt)
                if create_graph:
                    from .tensor import Tensor as _T
                    if not (jnp.issubdtype(dt, jnp.floating)
                            or jnp.issubdtype(dt, jnp.complexfloating)):
                        ct = jnp.zeros(shape, dt)  # placeholder, see vjp_fn
                    ct = _T(ct, stop_gradient=True)
            else:
                any_ct = True
                if node.out_refs is not None and i < len(node.out_refs) \
                        and node.out_refs[i] is not None \
                        and node.out_refs[i]._hooks:
                    if create_graph:
                        # an opaque python hook would detach the cotangent
                        # and silently corrupt higher-order grads
                        raise NotImplementedError(
                            "tensor hooks are not supported together with "
                            "create_graph=True (the hook would cut the "
                            "double-grad chain)")
                    ct = _apply_hooks(node.out_refs[i], ct)
            cts.append(ct)
        node.pending = None
        if not any_ct:
            continue
        if node.released:
            raise RuntimeError(
                "trying to backward through a released graph; pass "
                "retain_graph=True to backward()")
        if node.scope is None:
            _node_backward(node, cts, create_graph)
        else:
            with _backward_scope(node.scope):
                _node_backward(node, cts, create_graph)
        if not retain_graph:
            node.released = True
            node.arrays = None
            node.closure = None


_vjp_op_cache = {}


def _vjp_apply(node, ct_tensors):
    """Run a node's backward THROUGH the op dispatcher so the produced
    gradients carry their own grad nodes (double grad; reference:
    partial_grad_engine.cc). The vjp computation itself becomes a
    differentiable op over (original inputs..., cotangents...)."""
    from .tensor import Tensor
    from .dispatch import Op
    if node.closure is None:
        # PyLayer / custom nodes: user backward is opaque python — run it
        # normally; the chain stops there (grads are constants), matching
        # the reference, where PyLayer needs explicit double-grad support
        ct_vals = [c.value if isinstance(c, Tensor) else c
                   for c in ct_tensors]
        ct_arg = tuple(ct_vals) if node.multi_out else ct_vals[0]
        bwd = node.op.vjp_fn(node.key, node.closure)
        grads = bwd(node.arrays, ct_arg)
        return [Tensor(g, stop_gradient=True) if g is not None else None
                for g in grads]
    need = [i for i, t in enumerate(node.input_tensors)
            if t is not None and not t.stop_gradient]
    ckey = ("vjp", node.key, tuple(need))
    op = _vjp_op_cache.get(ckey)
    if op is None:
        closure = node.closure
        n_in = len(node.arrays)
        multi = node.multi_out
        need_c = list(need)

        def vjp_fn(*flat):
            arrays = flat[:n_in]
            cts = list(flat[n_in:])
            primals, vjp = jax.vjp(closure, *arrays)
            plist = list(primals) if isinstance(primals, (tuple, list)) \
                else [primals]
            for i, p in enumerate(plist):
                if not (jnp.issubdtype(p.dtype, jnp.floating)
                        or jnp.issubdtype(p.dtype, jnp.complexfloating)):
                    cts[i] = np.zeros(np.shape(p), jax.dtypes.float0)
                elif cts[i].dtype != p.dtype:
                    cts[i] = cts[i].astype(p.dtype)
            ct_arg = tuple(cts) if multi else cts[0]
            grads = vjp(ct_arg)
            outs = [grads[i] for i in need_c]
            return tuple(outs) if len(outs) != 1 else outs[0]

        # unique name per ckey: the dispatcher's jit cache keys on
        # (name, slots, attrs, cast), and distinct forward attrs (sum
        # axis, transpose perm, ...) produce distinct closures that would
        # otherwise collide under one shared name. A monotonic counter is
        # collision-free and deterministic within the process (a truncated
        # randomized hash would neither be).
        op = Op(f"vjp<{node.op.name}>#{len(_vjp_op_cache)}",
                vjp_fn, differentiable=True)
        _vjp_op_cache[ckey] = op
    # the vjp must see the FORWARD-TIME values (node.arrays), not the
    # tensors' current values (params may have been mutated by opt.step
    # since) — but the Tensor objects themselves must flow into the op so
    # the double-grad graph connects. Temporarily rebind each tensor's
    # value to its saved array around the dispatch (single-threaded eager).
    args = []
    stash = []
    for t, a in zip(node.input_tensors, node.arrays):
        if t is not None:
            stash.append((t, t._value))
            t._value = a
            args.append(t)
        else:
            args.append(a)
    try:
        outs = op(*args, *ct_tensors)
    finally:
        for t, v in stash:
            t._value = v
    outs = list(outs) if isinstance(outs, tuple) else [outs]
    in_grads = [None] * len(node.input_tensors)
    for j, i in enumerate(need):
        in_grads[i] = outs[j]
    return in_grads


def _distribute(node, in_grads, create_graph=False):
    from .sparse_grad import IndexedSlices
    # in_grads aligns with closure's positional arrays (= input_tensors slots)
    for t, g in zip(node.input_tensors, in_grads):
        if t is None or t.stop_gradient:
            continue
        if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
            continue
        if isinstance(g, IndexedSlices) and t._grad_node is not None:
            # non-leaf consumer: cotangent must be a dense array for the
            # upstream vjp
            g = g.to_dense()
        if t._grad_node is not None:
            pnode, pidx = t._grad_node
            if pnode.released:
                raise RuntimeError(
                    "trying to backward through a released graph; pass "
                    "retain_graph=True to backward()")
            if pnode.pending is None:
                pnode.pending = [None] * len(pnode.out_avals)
            if pnode.pending[pidx] is None:
                pnode.pending[pidx] = g
            elif create_graph:
                pnode.pending[pidx] = pnode.pending[pidx] + g
            else:
                pnode.pending[pidx] = lazy_mod.add(pnode.pending[pidx], g)
        else:
            _accumulate_into_leaf(t, g, create_graph)
