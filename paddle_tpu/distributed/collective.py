"""Collective communication API.

Reference parity: python/paddle/distributed/collective.py (broadcast:348,
all_reduce:415, reduce:495, all_gather:589, scatter:667, alltoall,
barrier:167) over the reference's c_* NCCL ops
(paddle/fluid/operators/collective/). TPU-native mapping (SURVEY §5):

    c_allreduce_sum  -> lax.psum       over a mesh axis
    c_reducescatter  -> lax.psum_scatter
    c_allgather      -> lax.all_gather
    send_v2/recv_v2  -> lax.ppermute
    alltoall         -> lax.all_to_all

A Group names a mesh axis (ring_id -> axis name). Collectives are valid in
two contexts:
  1. inside an SPMD region (shard_map / pjit manual axes) — lowers to the
     XLA collective on ICI;
  2. eagerly on a Tensor — executed via a one-op shard_map over the
     group's mesh so single-controller eager code sees paddle semantics
     (the tensor's leading-axis shards are the "per-rank" values).
If the group spans a single device, collectives are identities, matching
single-process paddle.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from ..core.dispatch import register_op
from . import topology

_GROUPS = {}
_next_group_id = [1]  # gid 0 is the default group
_default = [None]


class Group:
    """A communication group = a mesh axis (reference: collective.py:79
    Group over NCCL ring ids)."""

    def __init__(self, axis=None, mesh=None, ranks=None, gid=None):
        self.axis = axis
        self.mesh = mesh if mesh is not None else topology.get_mesh()
        self.ranks = ranks
        self.id = gid if gid is not None else _next_group_id[0]
        _next_group_id[0] += 1

    @property
    def nranks(self):
        if self.mesh is not None and self.axis in (self.mesh.shape or {}):
            return int(self.mesh.shape[self.axis])
        if self.ranks:
            return len(self.ranks)
        return jax.device_count()

    @property
    def world_size(self):
        return self.nranks

    def __repr__(self):
        return f"Group(axis={self.axis}, nranks={self.nranks})"


def _default_group():
    mesh = topology.get_mesh()
    if mesh is None:
        # implicit flat dp mesh over all devices
        hc = topology.HybridCommunicateGroup(dp=jax.device_count())
        mesh = hc.mesh
    cached = _default[0]
    if cached is None or cached.mesh is not mesh:
        cached = Group(axis="dp", mesh=mesh, gid=0)
        _default[0] = cached
        _GROUPS[0] = cached
    return cached


def new_group(ranks=None, backend=None, timeout=None):
    """Reference: collective.py:209. Creates a group over the given global
    ranks; in the mesh model sub-groups map to mesh axes — a custom rank
    subset gets a dedicated 1-axis mesh over those devices. The group is
    registered so get_group(g.id) finds it again."""
    if ranks is None:
        g = _default_group()
    else:
        devs = jax.devices()
        sub = [devs[r] for r in ranks]
        import numpy as np
        mesh = jax.sharding.Mesh(np.asarray(sub), ("sub",))
        g = Group(axis="sub", mesh=mesh, ranks=list(ranks))
    _GROUPS[g.id] = g
    return g


def get_group(gid=0):
    if gid == 0:
        return _default_group()
    g = _GROUPS.get(gid)
    if g is None:
        from ..core.errors import InvalidArgumentError
        raise InvalidArgumentError(
            f"no group with id {gid}; create it via new_group")
    return g


def _axis_in_scope(axis):
    """True when `axis` is a manual (shard_map) axis in the current trace —
    collectives then lower directly to XLA collectives over ICI."""
    try:
        from jax._src import core as _core
        return axis in _core.unsafe_get_axis_names()
    except Exception:
        return False


_REDUCE_FNS = {
    "sum": jax.lax.psum,
    "max": jax.lax.pmax,
    "min": jax.lax.pmin,
}


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _eager_collective(x, group, per_shard_fn, out_spec_fn=None):
    """Run an XLA collective eagerly over the group's mesh axis via a
    one-op shard_map. x is sharded (or replicated) on the leading dim."""
    mesh = group.mesh
    axis = group.axis
    n = int(mesh.shape[axis])
    if n == 1:
        return per_shard_fn(x, single=True)
    in_spec = P(axis)
    out_spec = out_spec_fn(axis) if out_spec_fn is not None else P(axis)
    fn = jax.shard_map(lambda v: per_shard_fn(v, single=False),
                       mesh=mesh, in_specs=(in_spec,), out_specs=out_spec)
    return fn(x)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
               use_calc_stream=True):
    """paddle.distributed.all_reduce. Inside SPMD: psum over the axis.
    Eager: reduces the per-rank values along the tensor's leading shards;
    single-device groups are identity."""
    g = group or _default_group()
    axis = g.axis
    if isinstance(tensor, Tensor) and _axis_in_scope(axis):
        out = _spmd_allreduce(tensor, axis=axis,
                              op=op if isinstance(op, str) else "sum")
        tensor.value = out.value
        return tensor
    n = g.nranks
    if n == 1:
        return tensor
    red_name = op if isinstance(op, str) else "sum"
    out = _eager_collective(
        tensor.value, g,
        lambda v, single: _reduce_shard(v, axis, red_name, n))
    tensor.value = out
    return tensor


def _reduce_shard(v, axis, red_name, n):
    """Per-shard reduction body (runs inside shard_map)."""
    if red_name == "avg":
        return jax.lax.psum(v, axis) / n
    if red_name == "prod":
        # no pprod primitive in lax: gather the n shard values and take
        # the product (log-psum would break on zeros/negatives)
        g_all = jax.lax.all_gather(v, axis)
        return jnp.prod(g_all, axis=0)
    return _REDUCE_FNS.get(red_name, jax.lax.psum)(v, axis)


@register_op("c_allreduce", differentiable=True)
def _spmd_allreduce(x, *, axis, op):
    if op == "sum":
        return jax.lax.psum(x, axis)
    if op == "max":
        return jax.lax.pmax(x, axis)
    if op == "min":
        return jax.lax.pmin(x, axis)
    if op == "avg":
        return jax.lax.pmean(x, axis)
    if op == "prod":
        return jnp.prod(jax.lax.all_gather(x, axis), axis=0)
    raise ValueError(op)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    g = group or _default_group()
    n = g.nranks
    if _axis_in_scope(g.axis):
        gathered = _spmd_allgather(tensor, axis=g.axis)
        from ..ops import manipulation
        tensor_list.extend(manipulation.unbind(gathered, axis=0))
        return tensor_list
    if n == 1:
        tensor_list.append(tensor)
        return tensor_list
    # Eager single-controller: the tensor's shards along the group axis are
    # the per-rank values; gather them to host-visible tensors. A leading
    # dim that does not divide the group size has no per-rank meaning —
    # silently replicating would be a wrong result.
    v = jnp.asarray(tensor.value)
    if v.ndim == 0 or v.shape[0] % n != 0:
        raise ValueError(
            f"all_gather: leading dim of shape {tuple(v.shape)} is not "
            f"divisible by group size {n}; eager collectives treat the "
            "leading-axis shards as the per-rank values")
    tensor_list.extend(Tensor(s) for s in jnp.split(v, n, axis=0))
    return tensor_list


@register_op("c_allgather", differentiable=False)
def _spmd_allgather(x, *, axis):
    return jax.lax.all_gather(x, axis)


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Single-controller: all mesh shards already share the controller's
    value for replicated tensors; for sharded tensors broadcast copies the
    src shard to all shards."""
    g = group or _default_group()
    n = g.nranks
    if n == 1 or not isinstance(tensor, Tensor):
        return tensor

    def shard_fn(v, single):
        g_all = jax.lax.all_gather(v, g.axis)
        return g_all[src]

    out = _eager_collective(tensor.value, g, shard_fn)
    tensor.value = out
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):  # noqa: A001
    """paddle.distributed.reduce: only rank `dst` receives the reduction;
    other ranks keep their input (reference collective.py:495). Inside an
    SPMD region dst semantics collapse (every program instance is the same
    program) and this is an all_reduce; eagerly the dst *shard* gets the
    reduced value and the other shards are left unchanged."""
    g = group or _default_group()
    if _axis_in_scope(g.axis):
        return all_reduce(tensor, op, group, sync_op)
    n = g.nranks
    if n == 1:
        return tensor
    # dst is a GLOBAL rank; convert to the group-local index the axis
    # compares against (reference: group.get_group_rank(dst))
    if g.ranks is not None:
        if dst not in g.ranks:
            raise ValueError(f"reduce: dst rank {dst} not in group "
                             f"{g.ranks}")
        dst_local = g.ranks.index(dst)
    else:
        if not 0 <= dst < n:
            raise ValueError(f"reduce: dst rank {dst} out of range for "
                             f"group of size {n}")
        dst_local = dst
    red_name = op if isinstance(op, str) else "sum"
    axis = g.axis

    def shard_fn(v, single):
        red = _reduce_shard(v, axis, red_name, n)
        idx = jax.lax.axis_index(axis)
        return jnp.where(idx == dst_local, red, v)

    out = _eager_collective(tensor.value, g, shard_fn)
    tensor.value = out
    return tensor


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = group or _default_group()
    if g.nranks == 1:
        if tensor_list:
            tensor.value = tensor_list[0].value
        return tensor
    # Single-controller: scatter = shard the stacked list over the group
    # axis; the receiving "rank's" view is the sharded array itself.
    from ..ops import manipulation
    from jax.sharding import NamedSharding, PartitionSpec
    stacked = manipulation.concat(tensor_list, axis=0)
    sharded = jax.device_put(stacked.value,
                             NamedSharding(g.mesh, PartitionSpec(g.axis)))
    tensor.value = sharded
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    g = group or _default_group()
    n = g.nranks
    if _axis_in_scope(g.axis):
        from ..ops import manipulation
        stacked = manipulation.stack(in_tensor_list, axis=0)
        out = _spmd_alltoall(stacked, axis=g.axis)
        outs = manipulation.unbind(out, axis=0)
        if out_tensor_list is not None:
            out_tensor_list.extend(outs)
            return out_tensor_list
        return outs
    if n == 1:
        if out_tensor_list is not None:
            out_tensor_list.extend(in_tensor_list)
            return out_tensor_list
        return list(in_tensor_list)
    # Eager single-controller (reference: imperative alltoall is an eager
    # op — paddle/fluid/imperative eager collectives): each tensor's
    # leading-axis blocks are the per-rank values; out[j] block r =
    # in[r] block j. One shard_map'd lax.all_to_all over the slot axis
    # does the exchange on ICI.
    if len(in_tensor_list) != n:
        raise ValueError(
            f"alltoall: need exactly {n} input tensors (one per rank), "
            f"got {len(in_tensor_list)}")
    vals = [jnp.asarray(t.value if isinstance(t, Tensor) else t)
            for t in in_tensor_list]
    if vals[0].ndim == 0 or vals[0].shape[0] % n != 0:
        raise ValueError(
            f"alltoall: leading dim of shape {tuple(vals[0].shape)} is "
            f"not divisible by group size {n}; eager collectives treat "
            "the leading-axis blocks as the per-rank values")
    stacked = jnp.stack(vals, axis=1)  # [B, n_slots, ...]
    axis = g.axis
    out = _eager_collective(
        stacked, g,
        lambda v, single: jax.lax.all_to_all(
            v, axis, split_axis=1, concat_axis=1, tiled=False))
    outs = [Tensor(out[:, j]) for j in range(n)]
    if out_tensor_list is not None:
        out_tensor_list.extend(outs)
        return out_tensor_list
    return outs


@register_op("c_alltoall", differentiable=True)
def _spmd_alltoall(x, *, axis):
    return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=False)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    g = group or _default_group()
    if _axis_in_scope(g.axis):
        from ..ops import manipulation
        stacked = manipulation.stack(tensor_list, axis=0) \
            if tensor_list is not None else tensor
        out = _spmd_reduce_scatter(stacked, axis=g.axis)
        tensor.value = out.value
        return tensor
    if g.nranks == 1:
        if tensor_list:
            tensor.value = tensor_list[0].value
        return tensor
    # Eager single-controller: rank r's output = reduce over ranks j of
    # (rank j's tensor_list[r]); with leading-axis blocks as per-rank
    # values this is one shard_map'd psum_scatter (SUM fast path) or an
    # all_gather + local reduction (other ops) over the slot axis.
    n = g.nranks
    axis = g.axis
    red_name = op if isinstance(op, str) else "sum"

    def _scatter_reduce(v, scatter_dim):
        # v per-device: slot dim `scatter_dim` has size n; keep column
        # axis_index after reducing over ranks
        if red_name == "sum":
            return jax.lax.psum_scatter(v, axis,
                                        scatter_dimension=scatter_dim,
                                        tiled=False)
        g_all = jax.lax.all_gather(v, axis)      # [n_ranks, ...local...]
        idx = jax.lax.axis_index(axis)
        mine = jnp.take(g_all, idx, axis=1 + scatter_dim)  # my column
        if red_name == "max":
            return jnp.max(mine, axis=0)
        if red_name == "min":
            return jnp.min(mine, axis=0)
        if red_name == "prod":
            return jnp.prod(mine, axis=0)
        if red_name == "avg":
            return jnp.mean(mine, axis=0)
        raise ValueError(f"unknown reduce op {red_name!r}")

    if tensor_list is not None:
        if len(tensor_list) != n:
            raise ValueError(
                f"reduce_scatter: need exactly {n} input tensors (one "
                f"per rank), got {len(tensor_list)}")
        vals = [jnp.asarray(t.value if isinstance(t, Tensor) else t)
                for t in tensor_list]
        if vals[0].ndim == 0 or vals[0].shape[0] % n != 0:
            raise ValueError(
                f"reduce_scatter: leading dim of shape "
                f"{tuple(vals[0].shape)} is not divisible by group size "
                f"{n}; eager collectives treat the leading-axis blocks "
                "as the per-rank values")
        stacked = jnp.stack(vals, axis=1)  # [B, n_slots, ...]
        tensor.value = _eager_collective(
            stacked, g, lambda v, single: _scatter_reduce(v, 1))
        return tensor
    # single-input form: each rank's block is split n ways and scattered
    v = jnp.asarray(tensor.value)
    if v.ndim == 0 or v.shape[0] % (n * n) != 0:
        raise ValueError(
            f"reduce_scatter: leading dim of shape {tuple(v.shape)} must "
            f"divide by group_size^2 ({n * n}) in single-tensor eager "
            "form (each per-rank block is split n ways)")
    tensor.value = _eager_collective(
        v, g,
        lambda s, single: _scatter_reduce(
            s.reshape((n, s.shape[0] // n) + s.shape[1:]), 0))
    return tensor


@register_op("c_reducescatter", differentiable=True)
def _spmd_reduce_scatter(x, *, axis):
    return jax.lax.psum_scatter(x, axis, scatter_dimension=0, tiled=False)


def barrier(group=None):
    """XLA executions are ordered per device; a controller-level barrier is
    a device sync (reference: barrier op -> here effects_barrier)."""
    jax.effects_barrier()


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        v = tensor.value
        if hasattr(v, "block_until_ready"):
            v.block_until_ready()
    return tensor


def get_rank(group=None):
    from . import env
    return env.get_rank()


def get_world_size(group=None):
    from . import env
    return env.get_world_size()


def is_initialized():
    return True


# --- TP helper primitives (reference: collective.py:748-921 _c_identity,
# _c_concat, _c_split, _mp_allreduce, _c_lookup_table) -----------------------

@register_op("c_identity_op")
def _c_identity_impl(x, *, axis):
    # forward identity; backward all-reduces over the mp axis — implemented
    # via custom vjp so the autograd tape gets the psum on the grad path.
    @jax.custom_vjp
    def ident(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        return (jax.lax.psum(g, axis),)
    ident.defvjp(fwd, bwd)
    return ident(x)


def _c_identity(tensor, group=None):
    g = group or _default_group()
    if not _axis_in_scope(g.axis):
        return tensor
    return _c_identity_impl(tensor, axis=g.axis)


@register_op("mp_allreduce_op")
def _mp_allreduce_impl(x, *, axis):
    # forward allreduce; backward identity (reference c_allreduce with
    # use_model_parallel=True)
    @jax.custom_vjp
    def ar(v):
        return jax.lax.psum(v, axis)

    def fwd(v):
        return jax.lax.psum(v, axis), None

    def bwd(_, g):
        return (g,)
    ar.defvjp(fwd, bwd)
    return ar(x)


def _mp_allreduce(tensor, op=ReduceOp.SUM, group=None,
                  use_calc_stream=True, use_model_parallel=True):
    g = group or _default_group()
    if not _axis_in_scope(g.axis):
        return tensor
    return _mp_allreduce_impl(tensor, axis=g.axis)


@register_op("send_recv_shift", differentiable=True)
def _ppermute_shift(x, *, axis, perm):
    return jax.lax.ppermute(x, axis, perm=list(perm))


def send(tensor, dst=0, group=None, sync_op=True, src=0):
    """Reference: collective.py send (send_v2 NCCL p2p). SPMD form: one
    ppermute edge src->dst (both ends named — every rank executes the
    same program); the destination rank receives the value, other ranks
    zeros. Eager single-controller: the value is staged on the group so
    the matching recv returns it (loopback, same process)."""
    g = group or _default_group()
    if _axis_in_scope(g.axis):
        n = g.nranks
        return _ppermute_shift(tensor, axis=g.axis,
                               perm=((src % n, dst % n),))
    _P2P_STAGE.setdefault(id(g) if g.id == 0 else g.id, []).append(
        tensor)
    return tensor


def recv(tensor, src=0, group=None, sync_op=True, dst=None):
    """Reference: collective.py recv (recv_v2). Inside an SPMD region a
    p2p edge must name BOTH ends (every rank runs the same program, so
    'the current rank' is not a static quantity): pass dst=. The
    destination rank's buffer gets src's value; other ranks get zeros
    (recv_v2 overwrites only the destination buffer). For uniform
    neighbor exchange use the pipeline/ppermute APIs instead."""
    g = group or _default_group()
    if _axis_in_scope(g.axis):
        if dst is None:
            from ..core.errors import InvalidArgumentError
            raise InvalidArgumentError(
                "recv inside an SPMD region needs dst= (the receiving "
                "rank); a single-program p2p edge must name both ends")
        n = g.nranks
        out = _ppermute_shift(tensor, axis=g.axis,
                              perm=((src % n, dst % n),))
        tensor.value = out.value
        return tensor
    staged = _P2P_STAGE.get(id(g) if g.id == 0 else g.id, [])
    if staged:
        tensor.value = staged.pop(0).value
    return tensor


_P2P_STAGE = {}


def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Reference: collective.py split — auto-sharded layer factory
    (parallel linear / embedding over the mp axis). TPU-native: build
    the matching Megatron TP layer and apply it."""
    from .fleet.meta_parallel.mp_layers import (
        ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
    if operation == "linear":
        in_f, out_f = size
        if axis == 1:
            layer = ColumnParallelLinear(in_f, out_f,
                                         gather_output=gather_out,
                                         weight_attr=weight_attr,
                                         has_bias=bias_attr is not False)
        else:
            layer = RowParallelLinear(in_f, out_f,
                                      input_is_parallel=False,
                                      weight_attr=weight_attr,
                                      has_bias=bias_attr is not False)
        return layer(x)
    if operation == "embedding":
        vocab, dim = size
        layer = VocabParallelEmbedding(vocab, dim,
                                       weight_attr=weight_attr)
        return layer(x)
    raise ValueError(f"unknown split operation {operation!r}")
