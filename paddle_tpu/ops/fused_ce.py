"""Linear + softmax-cross-entropy as one op (the LM head and its loss).

The GPT head computes logits = x @ W^T over a ~50k vocabulary and
reduces them at once to one scalar a token. Two ways of doing it live
here, and the code chooses between them from what it can see.

**One chip, the default: plain ``jnp``, three logits-sized passes.** The
f32 logits are written once, with their row maximum ``m`` fused into the
matmul by XLA. A call that will be differentiated takes the softmax's sum
OUT OF THE dx MATMUL: ``exp(logits - m) @ [W | 1]`` is dx unnormalised
and, in the columns of ones, the sum (``_exp_fwd``), so the loss has no
pass of its own over the logits; dW is a third matmul whose producer
forms the logits' gradient (``_exp_bwd``). A forward-only call
(evaluation) is the plain composition, ``_reference``: one matmul and the
log-sum-exp's pass.

**The Pallas kernels: the logits never exist in HBM.** Vocabulary TILES
stream through VMEM with an online logsumexp (the flash-attention trick
applied to the classifier). The backward is two pallas_calls (dx
accumulates over the vocab grid dim, dW over the token grid dim: each
accumulator needs ITS dim innermost), so each recomputes the logits
tiles: TWO extra x@W matmul passes. That lost 46 ms a step to the
composition on one chip (GPT-124M, 2026-08-02 sweep), so there they are
opt-in; the vocab-sharded path below runs them by default, for the
per-shard logits they never hold.

Reference analogue: the reference fuses this pair as
softmax_with_cross_entropy_op on the [T, V] logits its matmul wrote
(paddle/fluid/operators/softmax_with_cross_entropy_op.cu).

Weight layout is [V, H] (paddle embedding layout), so tied-embedding
heads pass word_embeddings.weight with no transpose.
"""
import functools
import math
import os as _os

import jax
import jax.numpy as jnp

from ..core.dispatch import is_grad_enabled, register_op
from .pallas_compat import trace_32bit as _trace_32bit

_BLOCK_T = int(_os.environ.get("PADDLE_FUSED_CE_BLOCK_T", "256"))
_BLOCK_V = int(_os.environ.get("PADDLE_FUSED_CE_BLOCK_V", "1024"))
_FORCE_INTERPRET = [False]


def _interpret():
    return _FORCE_INTERPRET[0]


def _dot_f32(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _use_pallas(x, w_vh, tp=False):
    if _os.environ.get("PADDLE_FUSED_CE_DISABLE") == "1":
        return False  # perf-ablation knob (tools/gpt_mfu_sweep.py)
    t, h = x.shape
    v = w_vh.shape[0]
    if x.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    ok = (t % 128 == 0 and h % 128 == 0 and v % 128 == 0
          and t >= 128 and v >= 1024)
    if _FORCE_INTERPRET[0]:
        return ok
    if jax.default_backend() == "cpu":
        return False
    if tp:
        # Vocab-sharded TP path: Pallas ON by default (ADVICE r5). The
        # single-chip opt-in below exists because the 2026-08-02 sweep
        # showed XLA wins on SPEED there — but the TP kernel's point is
        # that the per-shard [T, V/mp] logits tensor never exists in
        # HBM, the memory property the path is chosen for, so it keeps
        # its own gate: PADDLE_FUSED_CE_TP=0 opts out (the global
        # PADDLE_FUSED_CE_DISABLE kill switch above still wins).
        return ok and _os.environ.get("PADDLE_FUSED_CE_TP", "1") != "0"
    # Default OFF on real hardware since the 2026-08-02 on-chip sweep:
    # the Pallas kernels cost ~46 ms/step on GPT-124M vs the XLA
    # composition (the bwd recomputes the 633-GFLOP head matmul in both
    # dx and dw kernels at below-XLA MXU efficiency; their win is
    # logits-tensor MEMORY, which matters for big-batch/long-seq
    # configs). With the gate off a differentiated call takes the jnp
    # rule below (_exp_fwd / _exp_bwd: the logits written once in f32,
    # the softmax's sum out of the dx matmul, no recompute), the CPU
    # included. PADDLE_FUSED_CE=1 opts in; the vocab-sharded TP path has
    # its own default-on gate above (PADDLE_FUSED_CE_TP).
    return ok and _os.environ.get("PADDLE_FUSED_CE") == "1"


def _block_for(n, want):
    b = 128
    while b * 2 <= want and n % (b * 2) == 0:
        b *= 2
    return b if n % b == 0 else n


# ---- forward: online logsumexp over vocab tiles ----------------------------

def _fwd_kernel(x_ref, w_ref, lab_ref, loss_ref, lse_ref,
                m_sc, s_sc, ll_sc, *, block_t, block_v, nv,
                ignore_index):
    from jax.experimental import pallas as pl
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -1e30)
        s_sc[...] = jnp.zeros_like(s_sc)
        ll_sc[...] = jnp.zeros_like(ll_sc)

    x = x_ref[...]                       # [bt, H]
    w = w_ref[...]                       # [bv, H]
    tile = _dot_f32(x, w, ((1,), (1,)))  # [bt, bv] logits tile

    labels = lab_ref[...][0]             # [bt] int32
    local = labels - vi * jnp.int32(block_v)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    hit = col == local[:, None]          # out-of-tile labels never match
    ll_sc[...] += jnp.sum(jnp.where(hit, tile, 0.0),
                          axis=1)[None, :]

    m = m_sc[...][0]
    new_m = jnp.maximum(m, jnp.max(tile, axis=1))
    s_sc[...] = (s_sc[...][0] * jnp.exp(m - new_m)
                 + jnp.sum(jnp.exp(tile - new_m[:, None]),
                           axis=1))[None, :]
    m_sc[...] = new_m[None, :]

    @pl.when(vi == nv - 1)
    def _store():
        lse = m_sc[...][0] + jnp.log(s_sc[...][0])
        valid = labels != jnp.int32(ignore_index)
        loss_ref[...] = jnp.where(valid, lse - ll_sc[...][0],
                                  0.0)[None, :]
        lse_ref[...] = lse[None, :]


@_trace_32bit
def _pallas_fwd(x, w_vh, labels, ignore_index):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    t, h = x.shape
    v = w_vh.shape[0]
    bt = _block_for(t, _BLOCK_T)
    bv = _block_for(v, _BLOCK_V)
    nt, nv = t // bt, v // bv
    lab2 = labels.astype(jnp.int32)[None, :]          # [1, T]
    kernel = functools.partial(_fwd_kernel, block_t=bt, block_v=bv,
                               nv=nv, ignore_index=ignore_index)
    loss, lse = pl.pallas_call(kernel, name="fused_ce_fwd",
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((bt, h), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bv, h), lambda ti, vi: (vi, 0)),
            pl.BlockSpec((1, bt), lambda ti, vi: (0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt), lambda ti, vi: (0, ti)),
            pl.BlockSpec((1, bt), lambda ti, vi: (0, ti)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, t), jnp.float32),
            jax.ShapeDtypeStruct((1, t), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, bt), jnp.float32),
            pltpu.VMEM((1, bt), jnp.float32),
            pltpu.VMEM((1, bt), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, w_vh, lab2)
    return loss[0], lse[0]


# ---- backward: recompute tiles, never materialize d_logits ------------------

def _dtile(x, w, labels, lse, g, vi, block_t, block_v, ignore_index):
    """d_logits tile = (softmax - onehot) * g, recomputed in VMEM."""
    tile = _dot_f32(x, w, ((1,), (1,)))
    p = jnp.exp(tile - lse[:, None])
    local = labels - vi * jnp.int32(block_v)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_t, block_v), 1)
    onehot = (col == local[:, None]).astype(jnp.float32)
    valid = (labels != jnp.int32(ignore_index)).astype(jnp.float32)
    return (p - onehot) * (g * valid)[:, None]


def _bwd_dx_kernel(x_ref, w_ref, lab_ref, lse_ref, g_ref, dx_ref, *,
                   block_t, block_v, ignore_index):
    from jax.experimental import pallas as pl
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    d = _dtile(x_ref[...], w_ref[...], lab_ref[...][0], lse_ref[...][0],
               g_ref[...][0], vi, block_t, block_v, ignore_index)
    w = w_ref[...]
    dx_ref[...] += _dot_f32(d.astype(w.dtype), w, ((1,), (0,)))


def _bwd_dw_kernel(x_ref, w_ref, lab_ref, lse_ref, g_ref, dw_ref, *,
                   block_t, block_v, ignore_index):
    from jax.experimental import pallas as pl
    ti = pl.program_id(1)
    vi = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    x = x_ref[...]
    d = _dtile(x, w_ref[...], lab_ref[...][0], lse_ref[...][0],
               g_ref[...][0], vi, block_t, block_v, ignore_index)
    dw_ref[...] += _dot_f32(d.astype(x.dtype), x, ((0,), (0,)))


@_trace_32bit
def _pallas_bwd(x, w_vh, labels, lse, g, ignore_index):
    from jax.experimental import pallas as pl
    t, h = x.shape
    v = w_vh.shape[0]
    bt = _block_for(t, _BLOCK_T)
    bv = _block_for(v, _BLOCK_V)
    nt, nv = t // bt, v // bv
    lab2 = labels.astype(jnp.int32)[None, :]
    lse2 = lse[None, :]
    g2 = g.astype(jnp.float32)[None, :]

    dx_kernel = functools.partial(_bwd_dx_kernel, block_t=bt, block_v=bv,
                                  ignore_index=ignore_index)
    dx = pl.pallas_call(dx_kernel, name="fused_ce_bwd_dx",
        grid=(nt, nv),
        in_specs=[
            pl.BlockSpec((bt, h), lambda ti, vi: (ti, 0)),
            pl.BlockSpec((bv, h), lambda ti, vi: (vi, 0)),
            pl.BlockSpec((1, bt), lambda ti, vi: (0, ti)),
            pl.BlockSpec((1, bt), lambda ti, vi: (0, ti)),
            pl.BlockSpec((1, bt), lambda ti, vi: (0, ti)),
        ],
        out_specs=pl.BlockSpec((bt, h), lambda ti, vi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        interpret=_interpret(),
    )(x, w_vh, lab2, lse2, g2)

    dw_kernel = functools.partial(_bwd_dw_kernel, block_t=bt, block_v=bv,
                                  ignore_index=ignore_index)
    dw = pl.pallas_call(dw_kernel, name="fused_ce_bwd_dw",
        grid=(nv, nt),
        in_specs=[
            pl.BlockSpec((bt, h), lambda vi, ti: (ti, 0)),
            pl.BlockSpec((bv, h), lambda vi, ti: (vi, 0)),
            pl.BlockSpec((1, bt), lambda vi, ti: (0, ti)),
            pl.BlockSpec((1, bt), lambda vi, ti: (0, ti)),
            pl.BlockSpec((1, bt), lambda vi, ti: (0, ti)),
        ],
        out_specs=pl.BlockSpec((bv, h), lambda vi, ti: (vi, 0)),
        out_shape=jax.ShapeDtypeStruct((v, h), jnp.float32),
        interpret=_interpret(),
    )(x, w_vh, lab2, lse2, g2)
    return dx.astype(x.dtype), dw.astype(w_vh.dtype)


# ---- reference composition + custom vjp ------------------------------------

def _reference(x, w_vh, labels, ignore_index):
    logits = _dot_f32(x, w_vh, ((1,), (1,)))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, jnp.clip(labels, 0, w_vh.shape[0] - 1)[:, None].astype(
            jnp.int32), axis=-1)[:, 0]
    valid = labels != ignore_index
    return jnp.where(valid, lse - ll, 0.0)


# width of the block of ones beside W in the dx matmul: one lane tile,
# so that the softmax's sum is a column of a matmul the step runs anyway
_ONES = 128


def _exp_fwd(x, w_vh, labels, ignore_index):
    """The differentiated call's forward, in plain ``jnp``: the softmax's
    sum is NOT a pass of its own over the f32 logits. With ``m`` the row
    maximum (XLA fuses it into the logits' matmul), ``e = exp(logits -
    m)`` needs no sum, and ``e @ [W | 1]`` is dx unnormalised AND, in
    the columns of ones, ``s = sum(e)``: the MXU reduces inside the dx
    matmul. XLA forms ``e`` and ``[W | 1]`` as that matmul's producers,
    so nothing logits-sized is written but the logits. ``s >= 1``
    always: the maximum's own term is exp(0)."""
    v, h = w_vh.shape
    logits = _dot_f32(x, w_vh, ((1,), (1,)))
    m = jnp.max(logits, axis=-1)
    e = jnp.exp(logits - m[:, None])
    w1 = jnp.concatenate([w_vh, jnp.ones((v, _ONES), w_vh.dtype)], axis=1)
    acc = _dot_f32(e, w1, ((1,), (0,)))            # [T, H + 128] f32
    u, s = acc[:, :h], acc[:, h]
    lab = jnp.clip(labels, 0, v - 1).astype(jnp.int32)
    ll = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
    loss = jnp.where(labels != ignore_index, m + jnp.log(s) - ll, 0.0)
    return loss, (x, w_vh, labels, (logits, m, s, u))


def _exp_bwd(x, w_vh, labels, saved, g, ignore_index):
    """dx is the forward's matmul scaled by row AFTER it, less the
    label's row of W; dW is one matmul whose producer forms ``(softmax -
    onehot) * g`` from the saved logits, ``m`` and ``r = g / s``."""
    logits, m, s, u = saved
    v = w_vh.shape[0]
    lab = jnp.clip(labels, 0, v - 1).astype(jnp.int32)
    gv = jnp.where(labels != ignore_index, g.astype(jnp.float32), 0.0)
    r = gv / s
    dx = u * r[:, None] - gv[:, None] * w_vh[lab].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    d = (jnp.exp(logits - m[:, None]) * r[:, None]
         - jnp.where(col == lab[:, None], gv[:, None], 0.0))
    dw = _dot_f32(d, x, ((0,), (0,)))
    return dx.astype(x.dtype), dw.astype(w_vh.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_core(x, w_vh, labels, ignore_index, taped=False):
    if _use_pallas(x, w_vh):
        return _pallas_fwd(x, w_vh, labels, ignore_index)[0]
    if taped:
        # the framework's tape runs THIS call for the loss and replays
        # _fused_fwd for the backward in the same program (core/
        # dispatch.py: vjp_fn): spelled alike, XLA merges the two
        return _exp_fwd(x, w_vh, labels, ignore_index)[0]
    # forward only (evaluation): one matmul, no second one for a sum
    return _reference(x, w_vh, labels, ignore_index)


def _fused_fwd(x, w_vh, labels, ignore_index, taped):
    if _use_pallas(x, w_vh):
        loss, lse = _pallas_fwd(x, w_vh, labels, ignore_index)
        return loss, (x, w_vh, labels, lse)
    return _exp_fwd(x, w_vh, labels, ignore_index)


def _fused_bwd(ignore_index, taped, res, g):
    # the kernels save the log-sum-exp, the jnp rule its four arrays
    bwd = _exp_bwd if isinstance(res[3], tuple) else _pallas_bwd
    dx, dw = bwd(*res, g, ignore_index)
    return dx, dw, None


_fused_core.defvjp(_fused_fwd, _fused_bwd)


@register_op("fused_linear_cross_entropy")
def _fused_op(x, w_vh, labels, *, ignore_index, taped):
    """Per-token loss [T] for logits = x @ w_vh.T, labels [T] int.
    ignore_index rows contribute 0 loss and 0 gradient."""
    return _fused_core(x, w_vh, labels, ignore_index, taped)


def fused_linear_cross_entropy(x, weight_vh, labels, ignore_index=-100):
    """Public wrapper over Tensors: x [T, H], weight_vh [V, H] (paddle
    embedding layout — tied heads pass the embedding table directly),
    labels [T]. Returns per-token loss [T] (reduce outside)."""
    # a call the tape records is one whose backward will be asked for
    taped = is_grad_enabled() and not (x.stop_gradient
                                       and weight_vh.stop_gradient)
    return _fused_op(x, weight_vh, labels,
                     ignore_index=int(ignore_index), taped=taped)


# ---- tensor-parallel (vocab-sharded) variant --------------------------------
#
# The reference's TP loss IS a fused vocab-sharded kernel:
# paddle/fluid/operators/collective/c_softmax_with_cross_entropy_op.cu:1
# — each rank computes its local logits shard's max / sum-exp / label
# hit, then combines with cross-rank allreduce(max) + allreduce(sum).
# TPU-native translation: shard_map over the 'mp' mesh axis; each shard
# runs the SAME single-chip Pallas streaming kernel on its local
# [V/mp, H] vocab tile, then lax.pmax/psum over 'mp' combine the
# per-shard logsumexp and label log-likelihood. The [tokens, vocab]
# logits tensor never exists in HBM on ANY shard, in either direction.

# out-of-vocab sentinel: never equals any (shifted) label, so the local
# kernels treat every row as "valid" and validity is applied OUTSIDE
# (ignore_index handling must be global, not per-shard: a shifted
# ignore label could alias a real local id on shard 0 otherwise)
_NEVER = -(2 ** 31 - 123)

# mesh registry keyed by CONTENT (axis names + device ids + shape), not
# id(): id-keyed entries pinned meshes forever and a recycled id could
# have mapped a jit-cached mesh key onto the wrong mesh. Equal meshes
# share one entry, so the registry is bounded by the number of distinct
# topologies in the process.
_TP_MESHES = {}


def _register_mesh(mesh):
    key = (tuple(mesh.axis_names),
           tuple(int(d.id) for d in mesh.devices.flat),
           tuple(mesh.devices.shape))
    _TP_MESHES[key] = mesh
    return key


def _local_fwd(x_l, w_l, lab_local):
    """(per-token local loss, local lse) for ONE vocab shard; labels
    already shifted to local coords, out-of-shard labels miss (ll=0,
    so local loss == local lse for them)."""
    if _use_pallas(x_l, w_l, tp=True):
        return _pallas_fwd(x_l, w_l, lab_local, _NEVER)
    logits = _dot_f32(x_l, w_l, ((1,), (1,)))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    v_l = w_l.shape[0]
    hit = (lab_local >= 0) & (lab_local < v_l)
    ll = jnp.where(
        hit,
        jnp.take_along_axis(
            logits, jnp.clip(lab_local, 0, v_l - 1)[:, None].astype(
                jnp.int32), axis=-1)[:, 0],
        0.0)
    return lse - ll, lse


def _tp_specs(mesh, P):
    tok = "dp" if "dp" in mesh.axis_names else None
    return P(tok, None), P("mp", None), P(tok)


def _tp_fwd_impl(x, w_vh, labels, mesh_id, ignore_index):
    from jax.sharding import PartitionSpec as P
    mesh = _TP_MESHES[mesh_id]
    v_local = w_vh.shape[0] // mesh.shape["mp"]
    x_spec, w_spec, t_spec = _tp_specs(mesh, P)

    def body(x_l, w_l, lab_l):
        lab = lab_l.astype(jnp.int32)
        valid = lab != jnp.int32(ignore_index)
        shifted = (jnp.where(valid, lab, jnp.int32(_NEVER))
                   - jax.lax.axis_index("mp") * jnp.int32(v_local))
        loss_l, lse_l = _local_fwd(x_l, w_l, shifted)
        ll_l = lse_l - loss_l           # local label log-likelihood
        # distributed logsumexp: allreduce(max) + allreduce(sum), the
        # c_softmax_with_cross_entropy combine, on ICI via GSPMD
        m = jax.lax.pmax(lse_l, "mp")
        lse_g = m + jnp.log(jax.lax.psum(jnp.exp(lse_l - m), "mp"))
        ll_g = jax.lax.psum(ll_l, "mp")
        loss = jnp.where(valid, lse_g - ll_g, 0.0)
        return loss, lse_g

    return jax.shard_map(
        body, mesh=mesh, in_specs=(x_spec, w_spec, t_spec),
        out_specs=(t_spec, t_spec), check_vma=False)(x, w_vh, labels)


def _tp_bwd_impl(x, w_vh, labels, lse_g, g, mesh_id, ignore_index):
    from jax.sharding import PartitionSpec as P
    mesh = _TP_MESHES[mesh_id]
    v_local = w_vh.shape[0] // mesh.shape["mp"]
    x_spec, w_spec, t_spec = _tp_specs(mesh, P)

    def body(x_l, w_l, lab_l, lse_l, g_l):
        lab = lab_l.astype(jnp.int32)
        valid = lab != jnp.int32(ignore_index)
        shifted = (jnp.where(valid, lab, jnp.int32(_NEVER))
                   - jax.lax.axis_index("mp") * jnp.int32(v_local))
        # validity zeroes the cotangent (the kernels' sentinel
        # ignore_index treats every row as valid)
        g_eff = g_l * valid.astype(g_l.dtype)
        if _use_pallas(x_l, w_l, tp=True):
            # global lse → each shard's recomputed tile exponentiates
            # to the GLOBAL softmax slice; dx partial-sums over shards
            dx_l, dw_l = _pallas_bwd(x_l, w_l, shifted, lse_l, g_eff,
                                     _NEVER)
        else:
            logits = _dot_f32(x_l, w_l, ((1,), (1,)))
            p = jnp.exp(logits - lse_l[:, None])
            col = jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 1)
            onehot = (col == shifted[:, None]).astype(jnp.float32)
            d = (p - onehot) * g_eff.astype(jnp.float32)[:, None]
            dx_l = _dot_f32(d.astype(w_l.dtype), w_l, ((1,), (0,)))
            dw_l = _dot_f32(d.astype(x_l.dtype), x_l, ((0,), (0,)))
        # dx partial-sums over the vocab ('mp') shards; dw over the
        # token ('dp') shards — each axis reduces the dim it splits
        dx = jax.lax.psum(dx_l.astype(x_l.dtype), "mp")
        dw = dw_l.astype(w_l.dtype)
        if "dp" in mesh.axis_names:
            dw = jax.lax.psum(dw, "dp")
        return dx, dw

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, w_spec, t_spec, t_spec, t_spec),
        out_specs=(x_spec, w_spec), check_vma=False)(
            x, w_vh, labels, lse_g, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_tp_core(x, w_vh, labels, mesh_id, ignore_index):
    return _tp_fwd_impl(x, w_vh, labels, mesh_id, ignore_index)[0]


def _fused_tp_fwd(x, w_vh, labels, mesh_id, ignore_index):
    loss, lse_g = _tp_fwd_impl(x, w_vh, labels, mesh_id, ignore_index)
    return loss, (x, w_vh, labels, lse_g)


def _fused_tp_bwd(mesh_id, ignore_index, res, g):
    x, w_vh, labels, lse_g = res
    dx, dw = _tp_bwd_impl(x, w_vh, labels, lse_g, g, mesh_id,
                          ignore_index)
    return dx, dw, None


_fused_tp_core.defvjp(_fused_tp_fwd, _fused_tp_bwd)


@register_op("fused_linear_cross_entropy_tp")
def _fused_tp_op(x, w_vh, labels, *, mesh_id, ignore_index):
    return _fused_tp_core(x, w_vh, labels, mesh_id, ignore_index)


def tp_fused_applicable(mesh, t, h, v):
    """The fused TP head handles meshes whose parallel axes are
    dp/mp/sharding (pp stages slice the program before the head; the
    pipelined loss keeps the composition) with the vocab and token dims
    dividing evenly over their axes."""
    if mesh is None or "mp" not in mesh.axis_names:
        return False
    mp = int(mesh.shape["mp"])
    if mp <= 1 or v % mp != 0:
        return False
    if int(mesh.shape.get("pp", 1)) != 1:
        return False
    dp = int(mesh.shape.get("dp", 1))
    return t % max(dp, 1) == 0


def fused_linear_cross_entropy_tp(x, weight_vh, labels, mesh,
                                  ignore_index=-100):
    """Vocab-sharded fused linear+CE: weight_vh [V, H] sharded over the
    'mp' mesh axis, x [T, H] (tokens dp-sharded when the mesh has a dp
    axis), labels [T]. Per-token loss [T]. Reference:
    c_softmax_with_cross_entropy_op.cu (allreduce-max/sum combine)."""
    return _fused_tp_op(x, weight_vh, labels,
                        mesh_id=_register_mesh(mesh),
                        ignore_index=int(ignore_index))
