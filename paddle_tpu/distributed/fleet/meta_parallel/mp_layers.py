"""Megatron-style tensor-parallel layers.

Reference parity: python/paddle/distributed/fleet/meta_parallel/
parallel_layers/mp_layers.py (VocabParallelEmbedding:30,
ColumnParallelLinear:97, RowParallelLinear:170, ParallelCrossEntropy:249)
over c_embedding / c_identity / c_allreduce_sum ops.

TPU-native design: instead of materializing per-rank weight shards and
inserting explicit collectives, each layer holds the FULL logical weight
annotated with a NamedSharding over the 'mp' mesh axis. Under jit/pjit,
GSPMD partitions the matmuls and inserts the same all-reduce/all-gather
pattern Megatron does (column-parallel: activations sharded on features,
row-parallel: psum on output) — laid out on ICI. The user-visible layer
API matches the reference, and state_dict holds full weights (so
checkpoints are topology-independent, an improvement over per-rank
shards).
"""
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ....core.dispatch import register_op
from ....nn.layer_base import Layer
from ....nn import initializer as init_mod
from ....ops import nn_ops
from ... import topology


def _axis_is_manual(name):
    """True when `name` is currently a bound (manual) axis — i.e. we
    are tracing inside a shard_map/pmap body over it. A GSPMD sharding
    constraint over a manual axis is invalid (the data is already
    per-device there), so callers skip the hint."""
    try:
        from jax._src.core import axis_frame
    except ImportError:
        return False
    try:
        axis_frame(name)
        return True
    except NameError:
        return False


@register_op("sharding_constraint")
def _constraint(x, *, spec, mesh_id):
    mesh = _MESH_REGISTRY[mesh_id]
    axes = []
    for ax in spec:  # spec entries: name | tuple of names | None
        if isinstance(ax, str):
            axes.append(ax)
        elif isinstance(ax, (tuple, list)):
            axes.extend(a for a in ax if isinstance(a, str))
    if any(_axis_is_manual(ax) for ax in axes):
        # full-manual shard_map (older jax without partial-auto
        # axis_names): data is per-device; the hint is meaningless —
        # and with_sharding_constraint would reject the spec at
        # lowering time with an opaque manual_axes ValueError
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))
    except ValueError as e:
        if "manual" in str(e):
            return x
        raise


_MESH_REGISTRY = {}


def shard_constraint(t, spec, mesh=None):
    """Annotate a tensor with a mesh sharding INSIDE a compiled (to_static)
    graph. Outside a jit trace this is a no-op by design: eager phases stay
    single-device so no eager sub-group collectives are ever launched (the
    CPU backend deadlocks on those, and on TPU they would serialize);
    GSPMD materializes all sharding when the step compiles."""
    from ....core import trace as trace_mod
    if not trace_mod.in_compiled_step():
        return t
    mesh = mesh or topology.get_mesh()
    if mesh is None:
        return t
    mid = id(mesh)
    _MESH_REGISTRY[mid] = mesh
    return _constraint(t, spec=tuple(spec), mesh_id=mid)


def _shard_param(param, spec, mesh=None):
    """Record the parameter's tensor-parallel placement; applied as a
    sharding constraint in the layer's forward when the step compiles."""
    param.tp_spec = tuple(spec)
    return param


class VocabParallelEmbedding(Layer):
    """Reference: mp_layers.py:30 — vocab dimension sharded over mp."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim),
            attr=init_mod.ParamAttr._to_attr(weight_attr),
            default_initializer=init_mod.XavierNormal())
        _shard_param(self.weight, ("mp", None))

    def forward(self, x):
        w = shard_constraint(self.weight, self.weight.tp_spec)
        out = nn_ops.embedding(x, w)
        return out


class ColumnParallelLinear(Layer):
    """Reference: mp_layers.py:97 — output features sharded over mp;
    gather_output=False keeps activations feature-sharded for the following
    RowParallelLinear (the Megatron pattern)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            (in_features, out_features),
            attr=init_mod.ParamAttr._to_attr(weight_attr))
        self.bias = self.create_parameter(
            (out_features,), is_bias=True) if has_bias else None
        _shard_param(self.weight, (None, "mp"))
        if self.bias is not None:
            _shard_param(self.bias, ("mp",))

    def forward(self, x):
        w = shard_constraint(self.weight, self.weight.tp_spec)
        b = None if self.bias is None else \
            shard_constraint(self.bias, self.bias.tp_spec)
        out = nn_ops.linear(x, w, b)
        if self.gather_output:
            out = shard_constraint(out, (None,) * len(out.shape))
        else:
            out = shard_constraint(
                out, (None,) * (len(out.shape) - 1) + ("mp",))
        return out


class RowParallelLinear(Layer):
    """Reference: mp_layers.py:170 — input features sharded over mp; output
    is the psum of partial matmuls (GSPMD inserts it)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            (in_features, out_features),
            attr=init_mod.ParamAttr._to_attr(weight_attr))
        self.bias = self.create_parameter(
            (out_features,), is_bias=True) if has_bias else None
        _shard_param(self.weight, ("mp", None))

    def forward(self, x):
        if self.input_is_parallel:
            x = shard_constraint(x, (None,) * (len(x.shape) - 1) + ("mp",))
        w = shard_constraint(self.weight, self.weight.tp_spec)
        out = nn_ops.linear(x, w, self.bias)
        out = shard_constraint(out, (None,) * len(out.shape))
        return out


class ParallelCrossEntropy(Layer):
    """Reference: mp_layers.py:249 over c_softmax_with_cross_entropy —
    cross entropy on vocab-sharded logits. GSPMD computes the partitioned
    log-softmax reduction without materializing gathered logits when the
    logits carry an mp sharding."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):  # noqa: A002
        return nn_ops.softmax_with_cross_entropy(
            input, label, ignore_index=self.ignore_index)
