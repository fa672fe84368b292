"""Fused attention.

TPU-native: flash attention as Pallas kernels for the hot path —
FORWARD (online-softmax, VMEM-resident) and BACKWARD (recompute-based,
O(seq) memory: the full [s, t] score matrix is never materialized),
the greenfield requirement SURVEY §5 sets for long-context. Reference
analogue: paddle/fluid/operators/math/bert_encoder_functor.cu and the
fused multihead-matmul passes — here it's fused kernels instead of
fusion passes. Falls back to the XLA softmax(QK^T)V composition for
small shapes or on CPU where Pallas TPU kernels are unavailable
(interpret mode exercises the kernels in CPU tests). Layouts: [batch,
heads, seq, head_dim], or a fused projection's [batch, seq, 3 * hidden].
"""
import functools
import math
import types

import jax
import jax.numpy as jnp

from ..profiler import device_scope
from ..core import trace as trace_mod
from ..core.dispatch import register_op
from .fused_ce import _TP_MESHES, _register_mesh
from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the Pallas kernels in interpret mode on CPU
_FORCE_INTERPRET = [False]


def _dot_f32(a, b, dims):
    """MXU matmul in the operands' native dtype (bf16 runs at full MXU
    rate — casting to f32 first would cut throughput 4-8x on v5e) with
    float32 accumulation. dims = ((a_contract,), (b_contract,))."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _reference_attention(q, k, v, mask, scale, causal):
    qk = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
    if causal:
        s, t = qk.shape[-2], qk.shape[-1]
        causal_mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        qk = jnp.where(causal_mask, qk, jnp.asarray(-1e30, qk.dtype))
    if mask is not None:
        qk = qk + mask
    w = jax.nn.softmax(qk.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", w, v)


def _use_pallas(q):
    b, h, s, d = q.shape
    # f64 cannot lower on Mosaic (and the kernels trace in 32-bit mode)
    if q.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return False
    shape_ok = s >= 256 and d in (64, 128, 256) and s % 128 == 0
    if _FORCE_INTERPRET[0]:
        return s % 128 == 0 and s >= 128
    if jax.default_backend() == "cpu":
        return False
    return shape_ok


def _interpret():
    return _FORCE_INTERPRET[0]


# ---- tiles ------------------------------------------------------------------
# One (block x block) tile of the score square a grid step, computed
# TRANSPOSED ([keys, queries]) a strip of queries at a time: a strip
# meets every key it may see in one matmul. In that orientation the
# softmax statistics are rows ((1, queries): what the tile arithmetic
# broadcasts over sublanes for free, what the max and the sum reduce to
# with VPU work alone, and what lse is stored as), so no tile relayouts
# them; the scale rides on q where that is exact; and only the
# (strip x strip) square on the diagonal ever sees a mask. Under causal
# attention a diagonal tile computes its strips up to the diagonal only.
#
# Measured on a v5e at [24, 12, 1024, 64] bf16 causal (PERF.md, PR 31):
# the tile is bound by neither the exponential, the mask nor the
# reductions (taking each out moved the forward by 0-8 %) but by how
# long a stream each MXU weight tile gets, so wide strips win although
# they compute more of the square above the diagonal. A forward call
# takes 0.30 ms less at strips of 512 than at 256 (0.17 less than at
# 1024); a backward layer 0.31 ms less at 256 than at 512 (0.05 less
# than at 128). In the training step a forward call takes 0.73 ms and
# a backward layer 1.31 ms.

_NEG = -1e30
_FWD_STRIP = 512
_BWD_STRIP = 256


def _block(s, d, itemsize):
    """Tile edge for sequences of s (a multiple of 128), head dimension
    d and operands of itemsize bytes: the largest power of two that
    divides s and keeps an operand block within 256 KB of VMEM (1024
    at d = 64 in bf16: one grid step a head at sequences of 1024, K/V
    streamed block by block beyond)."""
    block = 1024
    while s % block or block * d * itemsize > (256 << 10):
        block //= 2
    return block


def _exact_scale(scale):
    """True where multiplying by scale only moves the exponent (a power
    of two, as 1/sqrt(64)): then it folds into q bit for bit."""
    return math.frexp(scale)[0] == 0.5


def _scores_t(q, k, scale, diagonal):
    """[keys, queries] scores of one strip of queries against keys
    [0, w), f32 accumulation. With `diagonal` the last len(q) keys are
    the square on the diagonal, the only part where a key can lie after
    its query. Also returns q as the MXU saw it (scaled where exact)."""
    fold = _exact_scale(scale)
    if fold:
        q = q * scale
    st = _dot_f32(k, q, ((1,), (1,)))
    if not fold:
        st = st * jnp.float32(scale)
    if diagonal:
        w, strip = st.shape
        keep = (jax.lax.broadcasted_iota(jnp.int32, (strip, strip), 1)
                >= jax.lax.broadcasted_iota(jnp.int32, (strip, strip), 0))
        below, square = st[:w - strip], st[w - strip:]
        square = jnp.where(keep, square, jnp.float32(_NEG))
        st = square if w == strip else jnp.concatenate([below, square])
    return st, q


def _causal_walk(walk, causal, qi, ki):
    """Run walk(diagonal) for the tile (qi, ki): tiles below the
    diagonal whole and unmasked, tiles on it up to the diagonal, tiles
    above it not at all."""
    from jax.experimental import pallas as pl
    if not causal:
        walk(False)
        return
    pl.when(ki < qi)(lambda: walk(False))
    pl.when(ki == qi)(lambda: walk(True))


# ---- forward kernel --------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, scale, causal,
                      block, strip, nk):
    """Grid (b, h, nq, nk): K/V stream through VMEM one block at a
    time, so VMEM use is O(block) — independent of seq length. The
    online-softmax state (acc [d, block], m and l [1, block]) lives in
    VMEM scratch, which persists across the sequentially-executed inner
    ki grid steps; the o/lse output blocks are revisited and written
    once at the last ki."""
    from jax.experimental import pallas as pl
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _walk(diagonal):
        for j in range(block // strip):
            cols = slice(j * strip, (j + 1) * strip)
            w = (j + 1) * strip if diagonal else block
            st, _ = _scores_t(q_ref[cols, :], k_ref[:w, :], scale, diagonal)
            m_prev = m_ref[:, cols]
            m_new = jnp.maximum(m_prev, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[:, cols] = alpha * l_ref[:, cols] + jnp.sum(
                pt, axis=0, keepdims=True)
            m_ref[:, cols] = m_new
            # [d, strip] = v.T @ p.T
            pvt = _dot_f32(v_ref[:w, :], pt.astype(v_ref.dtype),
                           ((0,), (0,)))
            acc_ref[:, cols] = acc_ref[:, cols] * alpha + pvt

    _causal_walk(_walk, causal, qi, ki)

    @pl.when(ki == nk - 1)
    def _store():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / l).T.astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _pallas_flash_fwd(q, k, v, scale, causal):
    return _pallas_flash_fwd_32(q, k, v, scale, causal, _interpret())


# jitted, so that a step which calls the kernel once a layer traces and
# lowers it once, not once a call site (the strips are unrolled: left
# to each of a 12-layer step's 36 sites, a warm set-up took 9 s longer,
# PERF.md, PR 31); identical calls are then also identical to XLA, which
# merges the forward that a tape replays with the first. x64 guard
# shared by every Pallas entry point (pallas_compat)
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@_trace_32bit
def _pallas_flash_fwd_32(q, k, v, scale, causal, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, s, d = q.shape
    block = _block(s, d, q.dtype.itemsize)
    n = s // block
    kernel = functools.partial(_flash_fwd_kernel, scale=scale,
                               causal=causal, block=block,
                               strip=min(_FWD_STRIP, block), nk=n)

    def kv_map(bi, hi, qi, ki):
        # a tile above the diagonal computes nothing: name the block
        # already in VMEM, so the skipped step fetches nothing either
        return (bi, hi, jnp.minimum(ki, qi) if causal else ki, 0)

    out, lse = pl.pallas_call(kernel, name="flash_fwd",
        grid=(b, h, n, n),
        in_specs=[
            pl.BlockSpec((None, None, block, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block, d), kv_map),
            pl.BlockSpec((None, None, block, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            # mosaic needs the last two block dims ~(8,128)-aligned or
            # full; a [b,h,1,s] layout makes the lse block (1, block)
            pl.BlockSpec((None, None, 1, block),
                         lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, block), jnp.float32),
            pltpu.VMEM((1, block), jnp.float32),
            pltpu.VMEM((1, block), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---- backward kernel (flash-attention-2 style, O(seq) memory) --------------
# One body. With the scores transposed lse and delta broadcast from the
# rows they are stored as, and dK, dV and dQ.T are all plain matmuls of
# the tile as it stands. Where one block holds the sequence it is one
# kernel and s, p, dp are computed once for all three gradients;
# beyond, dQ needs the key loop innermost and dK/dV the query loop, so
# the body runs twice (grid (b, h, nq, nk) for dQ, (b, h, nk, nq) for
# dK/dV), each time with block-sized VMEM. Gradients accumulate in f32
# scratch and leave once, in the inputs' dtype, at the last inner step.

def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      *refs, scale, causal, block, strip, n, want_dq,
                      want_dkv):
    from jax.experimental import pallas as pl
    outs, accs = refs[:len(refs) // 2], refs[len(refs) // 2:]
    # dQ alone walks keys innermost; with dK/dV the queries are inner
    outer, inner = pl.program_id(2), pl.program_id(3)
    qi, ki = (inner, outer) if want_dkv else (outer, inner)
    dqt_acc = accs[0] if want_dq else None     # [d, block]
    dk_acc, dv_acc = accs[-2:] if want_dkv else (None, None)

    @pl.when(inner == 0)
    def _init():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def _walk(diagonal):
        for j in range(block // strip):
            cols = slice(j * strip, (j + 1) * strip)
            w = (j + 1) * strip if diagonal else block
            do = do_ref[cols, :]
            k = k_ref[:w, :]
            # delta = rowsum(dO * O), a row like lse
            delta = jnp.sum((do.astype(jnp.float32)
                             * o_ref[cols, :].astype(jnp.float32)).T,
                            axis=0, keepdims=True)
            st, qs = _scores_t(q_ref[cols, :], k, scale, diagonal)
            pt = jnp.exp(st - lse_ref[:, cols])
            dpt = _dot_f32(v_ref[:w, :], do, ((1,), (1,)))
            dst = (pt * (dpt - delta)).astype(k.dtype)
            if want_dkv:
                dv_acc[:w, :] += _dot_f32(pt.astype(do.dtype), do,
                                          ((1,), (0,)))
                dk_acc[:w, :] += _dot_f32(dst, qs, ((1,), (0,)))
            if want_dq:
                # [d, strip] = k.T @ ds.T
                dqt_acc[:, cols] += _dot_f32(k, dst, ((0,), (0,)))

    _causal_walk(_walk, causal, qi, ki)

    @pl.when(inner == n - 1)
    def _store():
        # s = scale * q.k: dQ takes the factor here; dK got it with
        # the folded q, or takes it here too
        grads = []
        if want_dq:
            grads.append((dqt_acc[...] * jnp.float32(scale)).T)
        if want_dkv:
            dk = dk_acc[...]
            grads += [dk if _exact_scale(scale)
                      else dk * jnp.float32(scale), dv_acc[...]]
        for out, g in zip(outs, grads):
            out[...] = g.astype(out.dtype)


def _tile_maps(causal, keys_outer):
    """(q_at, k_at): the query and the key tile of grid step (.., i, j),
    j innermost: (i, j) = (query, key) tile (the forward, dQ alone) or,
    with `keys_outer`, (key, query) (dK/dV). A tile above the diagonal
    computes nothing and names the block already in VMEM."""
    if keys_outer:
        return ((lambda i, j: jnp.maximum(j, i)) if causal else (
            lambda i, j: j)), (lambda i, j: i)
    return (lambda i, j: i), (
        (lambda i, j: jnp.minimum(j, i)) if causal else (lambda i, j: j))


def _pallas_flash_bwd(q, k, v, out, lse, g, scale, causal):
    return _pallas_flash_bwd_32(q, k, v, out, lse, g, scale, causal,
                                _interpret())


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
@_trace_32bit
def _pallas_flash_bwd_32(q, k, v, out, lse, g, scale, causal, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, s, d = q.shape
    block = _block(s, d, q.dtype.itemsize)
    n = s // block

    def call(name, want_dq, want_dkv):
        q_at, k_at = _tile_maps(causal, want_dkv)

        def blk(at):
            return pl.BlockSpec((None, None, block, d),
                                lambda bi, hi, i, j: (bi, hi, at(i, j), 0))

        row = pl.BlockSpec((None, None, 1, block),
                           lambda bi, hi, i, j: (bi, hi, 0, q_at(i, j)))
        # outputs by the OUTER tile (with dK/dV, dQ only where n == 1)
        grads = [x for x, want in ((q, want_dq), (k, want_dkv),
                                   (v, want_dkv)) if want]
        kernel = functools.partial(
            _flash_bwd_kernel, scale=scale, causal=causal, block=block,
            strip=min(_BWD_STRIP, block), n=n, want_dq=want_dq,
            want_dkv=want_dkv)
        return pl.pallas_call(kernel, name=name,
            grid=(b, h, n, n),
            in_specs=[blk(q_at), blk(k_at), blk(k_at), blk(q_at), blk(q_at),
                      row],
            out_specs=[blk(lambda i, j: i) for _ in grads],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in grads],
            # dQ.T, then dK and dV
            scratch_shapes=[pltpu.VMEM(
                (d, block) if x is q else (block, d), jnp.float32)
                for x in grads],
            interpret=interpret,
        )(q, k, v, g, out, lse)

    if n == 1:
        return tuple(call("flash_bwd_dqkv", True, True))
    (dq,) = call("flash_bwd_dq", True, False)
    dk, dv = call("flash_bwd_dkv", False, True)
    return dq, dk, dv


# ---- custom-vjp wrapper ----------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention_core(q, k, v, scale, causal):
    if _use_pallas(q):
        return _pallas_flash_fwd(q, k, v, scale, causal)[0]
    return _reference_attention(q, k, v, None, scale, causal)


def _flash_fwd(q, k, v, scale, causal):
    if _use_pallas(q):
        out, lse = _pallas_flash_fwd(q, k, v, scale, causal)
        return out, (q, k, v, out, lse)
    out = _reference_attention(q, k, v, None, scale, causal)
    return out, (q, k, v, None, None)


def _flash_bwd(scale, causal, res, g):
    q, k, v, out, lse = res
    if lse is not None and _use_pallas(q):
        return _pallas_flash_bwd(q, k, v, out, lse, g, scale, causal)
    # small-shape / CPU fallback: recompute through the reference
    # composition (XLA fuses it; memory is O(s^2), fine at these sizes)
    _, vjp = jax.vjp(lambda q_, k_, v_: _reference_attention(
        q_, k_, v_, None, scale, causal), q, k, v)
    return vjp(g)


_flash_attention_core.defvjp(_flash_fwd, _flash_bwd)


def _flash_over_mesh(q, k, v, scale, causal, mesh):
    """The Pallas kernels inside a multi-device (GSPMD) program: Mosaic
    kernels cannot be partitioned automatically, so the call is wrapped
    in a shard_map — batch over the data-parallel axes, heads over 'mp'
    (the Megatron split the QKV projection already produced), where
    they divide; attention is independent per (batch, head), so no
    collective is needed inside."""
    from jax.sharding import PartitionSpec as P
    b, h = q.shape[0], q.shape[1]
    batch_axes = tuple(a for a in ("dp", "sharding")
                       if int(mesh.shape.get(a, 1)) > 1)
    n_b = math.prod(int(mesh.shape[a]) for a in batch_axes)
    mp = int(mesh.shape.get("mp", 1))
    spec = P(batch_axes if batch_axes and b % n_b == 0 else None,
             "mp" if mp > 1 and h % mp == 0 else None, None, None)
    return jax.shard_map(
        lambda q_, k_, v_: _flash_attention_core(q_, k_, v_, scale,
                                                 causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


# ---- packed entry: q, k, v where the qkv projection left them --------------
# The same two bodies over another operand layout. `qkv` is the fused
# projection's [b, s, 3 * heads * d] output (column = (part, head, i)),
# passed three times: a grid step reads a [block, 128] column block of
# each part by its index map (two heads where d is 64, one head of 128
# or 256), writes o and the gradients as column blocks of [b, s,
# heads * d], and runs the body once a head on views of the block's
# lanes. No [b, heads, s, d] array exists on either side of the kernels.

def _heads_a_step(d):
    return max(1, 128 // d)


class _Lanes:
    """Columns [lo, lo + d) of a block's ref as a ref of their own, for
    the loads and stores the kernel bodies make (all of them take the
    columns whole). Mosaic slices a memref by whole tiles of 128 lanes
    only; a load or a store may start at any."""

    def __init__(self, ref, lo, d):
        self.ref, self.lanes = ref, slice(lo, lo + d)
        self.shape, self.dtype = (ref.shape[0], d), ref.dtype

    def _rows(self, idx):
        return slice(None) if idx is Ellipsis else idx[0]

    def __getitem__(self, idx):
        return self.ref[self._rows(idx), self.lanes]

    def __setitem__(self, idx, value):
        self.ref[self._rows(idx), self.lanes] = value


def _head_views(body, g, d, kinds):
    """`body` once for each of the `g` heads that share a grid step, on
    views of its refs. `kinds`, a letter a ref: `c` the head's `d`
    columns of a [rows, g * d] block, `s` its own [g, ...] rows or
    scratch."""
    def kernel(*refs):
        for u in range(g):
            body(*[_Lanes(r, u * d, d) if kind == "c" else r.at[u]
                   for r, kind in zip(refs, kinds)])
    return kernel


def _packed_specs(heads, d, block):
    """BlockSpec makers for the packed layout, grid (b, heads // g, i,
    j): `cols(part, at)` the column block of q (part 0), k (1) or v (2)
    in [b, s, 3 * heads * d], and of o or a gradient (part 0) in [b, s,
    heads * d], at the row tile `at(i, j)`; `rows(at)` the heads' rows
    of lse [b, heads, 1, s]."""
    from jax.experimental import pallas as pl
    g = _heads_a_step(d)

    def cols(part, at):
        return pl.BlockSpec(
            (None, block, g * d),
            lambda bi, hi, i, j: (bi, at(i, j), part * (heads // g) + hi))

    def rows(at):
        return pl.BlockSpec((None, g, 1, block),
                            lambda bi, hi, i, j: (bi, hi, 0, at(i, j)))
    return cols, rows


def _pallas_flash_qkv_fwd(qkv, heads, scale, causal):
    return _pallas_flash_qkv_fwd_32(qkv, heads, scale, causal, _interpret())


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
@_trace_32bit
def _pallas_flash_qkv_fwd_32(qkv, heads, scale, causal, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, s, width = qkv.shape
    d = width // (3 * heads)
    g = _heads_a_step(d)
    block = _block(s, g * d, qkv.dtype.itemsize)
    n = s // block
    cols, rows = _packed_specs(heads, d, block)
    kernel = _head_views(functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, block=block,
        strip=min(_FWD_STRIP, block), nk=n), g, d, "ccccssss")
    q_at, k_at = _tile_maps(causal, False)
    return pl.pallas_call(kernel, name="flash_fwd",
        grid=(b, heads // g, n, n),
        in_specs=[cols(0, q_at), cols(1, k_at), cols(2, k_at)],
        out_specs=[cols(0, q_at), rows(q_at)],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, heads * d), qkv.dtype),
            jax.ShapeDtypeStruct((b, heads, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, d, block), jnp.float32),
            pltpu.VMEM((g, 1, block), jnp.float32),
            pltpu.VMEM((g, 1, block), jnp.float32),
        ],
        interpret=interpret,
    )(qkv, qkv, qkv)


def _write_dqkv(body, parts, block, width, n, *refs):
    """The backward `body` with its gradients written where the qkv
    projection's backward reads them: dqkv [b, s, 3 * heads * d] stays
    in HBM, a grid step's gradient blocks (`parts` of dq 0, dk 1, dv 2)
    are staged in VMEM and copied from there into their column blocks,
    so no pass over dqkv assembles it. Two stages a part, taken in
    turn: a store's copies run under the next store's arithmetic and
    are waited for where their stage is written again, and at the
    call's last step. refs: the body's inputs, an aliased dqkv that is
    not read (where another call wrote the other parts), dqkv, the
    stages [2, block, width], the body's accumulators, the copies'
    semaphores [2, parts]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    k = len(parts)
    (*ins, dqkv), stages, accs, sem = (
        refs[:-2 * k - 1], refs[-2 * k - 1:-k - 1], refs[-k - 1:-1], refs[-1])
    bi, hi, outer, inner = (pl.program_id(i) for i in range(4))
    cols = pl.num_programs(1)
    store = (bi * cols + hi) * n + outer     # one a (bi, hi, outer)
    last = pl.num_programs(0) * cols * n - 1
    half = store % 2
    stores = inner == n - 1

    def copies(half, go):
        rows = pl.ds(pl.multiple_of(outer * block, block), block)
        for i, (part, stage) in enumerate(zip(parts, stages)):
            go(pltpu.make_async_copy(stage.at[half], dqkv.at[bi, rows, pl.ds(
                pl.multiple_of((part * cols + hi) * width, width), width)],
                sem.at[half, i]))

    wait = lambda copy: copy.wait()   # by its size: the place is any

    @pl.when(jnp.logical_and(stores, store >= 2))
    def _drain():
        copies(half, wait)

    body(*ins[:6], *[stage.at[half] for stage in stages], *accs)

    @pl.when(stores)
    def _send():
        copies(half, lambda copy: copy.start())

    @pl.when(jnp.logical_and(stores, store == last))
    def _finish():
        copies(half, wait)
        pl.when(last > 0)(lambda: copies(1 - half, wait))


def _pallas_flash_qkv_bwd(qkv, out, lse, do, heads, scale, causal):
    return _pallas_flash_qkv_bwd_32(qkv, out, lse, do, heads, scale, causal,
                                    _interpret())


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
@_trace_32bit
def _pallas_flash_qkv_bwd_32(qkv, out, lse, do, heads, scale, causal,
                             interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, s, width = qkv.shape
    d = width // (3 * heads)
    g = _heads_a_step(d)
    block = _block(s, g * d, qkv.dtype.itemsize)
    n = s // block
    cols, rows = _packed_specs(heads, d, block)

    def call(name, parts, dqkv=None):
        want_dq, want_dkv = 0 in parts, 1 in parts
        q_at, k_at = _tile_maps(causal, want_dkv)
        body = _head_views(functools.partial(
            _flash_bwd_kernel, scale=scale, causal=causal, block=block,
            strip=min(_BWD_STRIP, block), n=n, want_dq=want_dq,
            want_dkv=want_dkv), g, d, "cccccs" + "c" * len(parts)
            + "".join("s" if part == 0 else "c" for part in parts))
        given = [] if dqkv is None else [dqkv]
        return pl.pallas_call(
            functools.partial(_write_dqkv, body, parts, block, g * d, n),
            name=name,
            grid=(b, heads // g, n, n),
            in_specs=[cols(0, q_at), cols(1, k_at), cols(2, k_at),
                      cols(0, q_at), cols(0, q_at), rows(q_at)]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in given],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
            # the thirds of dqkv that another call wrote stay
            input_output_aliases={6: 0} if given else {},
            # a gradient's block staged in its dtype, twice; then the
            # f32 accumulators: dQ.T a head, dK and dV side by side
            scratch_shapes=[pltpu.VMEM((2, block, g * d), qkv.dtype)
                            for _ in parts]
            + [pltpu.VMEM((g, d, block) if part == 0 else (block, g * d),
                          jnp.float32) for part in parts]
            + [pltpu.SemaphoreType.DMA((2, len(parts)))],
            interpret=interpret,
        )(qkv, qkv, qkv, do, out, lse, *given)

    if n == 1:
        return call("flash_bwd_dqkv", (0, 1, 2))
    return call("flash_bwd_dkv", (1, 2), call("flash_bwd_dq", (0,)))


def packed_qkv_viable(shape, dtype, heads):
    """True where the packed kernels take a qkv of `shape` [b, s, 3 *
    heads * d]: what `_use_pallas` takes as [b, heads, s, d], with
    heads that fill whole blocks of 128 lanes."""
    b, s, width = shape
    d = width // (3 * heads)
    return (width == 3 * heads * d and heads % _heads_a_step(d) == 0
            and _use_pallas(types.SimpleNamespace(shape=(b, heads, s, d),
                                                  dtype=dtype)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_qkv_core(qkv, heads, scale, causal):
    return _pallas_flash_qkv_fwd(qkv, heads, scale, causal)[0]


def _flash_qkv_fwd(qkv, heads, scale, causal):
    out, lse = _pallas_flash_qkv_fwd(qkv, heads, scale, causal)
    return out, (qkv, out, lse)


def _flash_qkv_bwd(heads, scale, causal, res, do):
    return (_pallas_flash_qkv_bwd(*res, do, heads, scale, causal),)


_flash_qkv_core.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


@register_op("flash_attention_qkv")
def _flash_qkv_op(qkv, *, heads, causal):
    b, s, width = qkv.shape
    d = width // (3 * heads)
    scale = 1.0 / math.sqrt(d)
    if packed_qkv_viable(qkv.shape, qkv.dtype, heads):
        return _flash_qkv_core(qkv, heads, scale, causal)
    q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, heads, d), (2, 3), (0, 2))
    o = _flash_attention_core(q, k, v, scale, causal)
    return jnp.swapaxes(o, 1, 2).reshape(b, s, heads * d)


def flash_attention_qkv(qkv, heads, causal=False):
    """Attention of a fused projection's output where it lies: `qkv`
    [batch, seq, 3 * heads * head_dim] with columns ordered (q | k | v,
    head, head_dim) -> [batch, seq, heads * head_dim], scale
    head_dim ** -0.5, no mask. The flash kernels read q, k, v from
    `qkv` by their index maps and hand the output, and in the backward
    the gradient of `qkv`, back in these layouts (`packed_qkv_viable`);
    other shapes split the heads and take `_flash_attention_core`."""
    return _flash_qkv_op(qkv, heads=int(heads), causal=bool(causal))


@register_op("flash_attention")
def _flash_op(q, k, v, mask, *, scale, causal, mesh_id=None):
    if mask is not None:
        return _reference_attention(q, k, v, mask, scale, causal)
    if mesh_id is not None and _use_pallas(q):
        return _flash_over_mesh(q, k, v, scale, causal,
                                _TP_MESHES[mesh_id])
    return _flash_attention_core(q, k, v, scale, causal)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    """query, key, value [batch, heads, seq, head_dim] -> the same
    layout; this entry takes no other (a caller that holds [batch, seq,
    heads, head_dim] transposes first; one that holds a fused
    projection's [batch, seq, 3 * heads * head_dim] and no mask calls
    `flash_attention_qkv`, which reads it where it lies). With
    `attn_mask` (added to the scores) the dense composition runs, else
    the flash kernels where `_use_pallas` takes the shape. `dropout_p`
    is accepted and not applied: no path fuses dropout into attention."""
    sc = scale if scale is not None else 1.0 / math.sqrt(query.shape[-1])
    # inside a compiled step over a multi-device mesh the kernel has to
    # be told the mesh (see _flash_over_mesh)
    mesh_id = None
    if trace_mod.in_compiled_step():
        from ..distributed import topology
        mesh = topology.get_mesh()
        if mesh is not None and mesh.size > 1:
            mesh_id = _register_mesh(mesh)
    return _flash_op(query, key, value, attn_mask, scale=float(sc),
                     causal=bool(is_causal), mesh_id=mesh_id)


def cached_slot_attention(q, k_cache, v_cache, lengths):
    """Single-token decode attention over a slot-contiguous static
    cache with per-slot cache-length masking: what
    cached_paged_attention runs over its gathered view, and the
    reference the paged gather and the Pallas kernel are tested
    against.

    q [S, nh, hd] — one new-token query per slot;
    k_cache/v_cache [S, nkv, C, hd] — each slot's full static cache
    (grouped queries: ``nkv`` divides ``nh``, query head ``h`` reads KV
    head ``h // (nh // nkv)``; a GPT has ``nkv == nh``);
    lengths [S] int — live prefix length per slot (prompt + generated
    so far, INCLUDING the row just written for this step).

    Key positions >= lengths[s] get -1e30 before the f32 softmax, so
    stale K/V from a slot's previous occupant (and prefill pad rows)
    carry exactly-zero weight — a recycled slot is bit-identical to a
    fresh one. Same score scale / mask value / softmax as the causal
    decode in generate(): for lengths = pos + 1 this IS its mask,
    vectorized over slots."""
    hd = q.shape[-1]
    cache_len = k_cache.shape[2]
    nh, nkv = q.shape[1], k_cache.shape[1]
    if nh != nkv:
        # the queries of a group as a batch over their one KV head
        S = q.shape[0]
        qg = q.reshape(S, nkv, nh // nkv, hd)
        s = jnp.einsum("sngd,snkd->sngk", qg, k_cache,
                       preferred_element_type=jnp.float32) / jnp.sqrt(
            jnp.float32(hd))
        kpos = jnp.arange(cache_len)[None, None, None, :]
        s = jnp.where(kpos < lengths[:, None, None, None], s,
                      jnp.float32(-1e30))
        # a value may be narrower than its key
        return jnp.einsum("sngk,snkd->sngd", jax.nn.softmax(s, axis=-1),
                          v_cache, preferred_element_type=jnp.float32
                          ).astype(q.dtype).reshape(S, nh,
                                                    v_cache.shape[-1])
    # f32 score accumulation (the _dot_f32 discipline): bf16 caches
    # keep full MXU rate but never sum scores in bf16; a no-op for f32
    s = jnp.einsum("shd,shkd->shk", q, k_cache,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(hd))
    kpos = jnp.arange(cache_len)[None, None, :]
    s = jnp.where(kpos < lengths[:, None, None], s,
                  jnp.float32(-1e30))
    # f32 accumulation, output in the query's dtype (what the Pallas
    # paged kernel returns too; a no-op for f32)
    return jnp.einsum("shk,shkd->shd", jax.nn.softmax(s, axis=-1),
                      v_cache, preferred_element_type=jnp.float32
                      ).astype(q.dtype)


def softmax_with_sink(s, sink):
    """Softmax of f32 scores ``s`` over the last axis with one more
    column of logit ``sink`` (broadcastable to ``s[..., :1]``; None:
    none) that carries no value: the weights of the REAL columns, which
    then sum to less than 1."""
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    b = sink.astype(jnp.float32)
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), b)
    e = jnp.exp(s - m)
    return e / (jnp.exp(b - m) + jnp.sum(e, axis=-1, keepdims=True))


def grouped_causal_attention(q, k_view, v_view, q_pos, q_block=128,
                             k_pos=None, window=None, sink=None):
    """Causal attention of ONE sequence's run of queries over a
    position-ordered view of its cache, with grouped queries (a paged
    PREFILL: the run's own keys and values are already in the view).

    q ``[T, nh, hd]`` at absolute positions ``q_pos [T]``; k_view
    ``[nkv, C, hd]``, v_view ``[nkv, C, dv]`` (a value may be narrower
    than its key; ``nkv`` divides ``nh``, query head ``h`` reads KV
    head ``h // (nh // nkv)``). Key ``c`` is at position ``k_pos[c]``
    (default: its index; a key that holds nothing is given a negative
    one) and is seen by query ``t`` when ``0 <= k_pos[c] <= q_pos[t]``
    and, with ``window``, ``k_pos[c] > q_pos[t] - window``. ``sink
    [nh]`` (f32 logits) joins each head's softmax as one more column
    that carries no value. Scores and softmax in f32, scale ``hd **
    -0.5``; computed ``q_block`` query rows at a time so that the
    ``[nh, rows, C]`` scores stay a temporary of that size. Returns
    ``[T, nh, dv]`` in q's dtype."""
    T, nh, hd = q.shape
    nkv, C = k_view.shape[:2]
    dv = v_view.shape[-1]
    kpos = jnp.arange(C, dtype=jnp.int32) if k_pos is None else k_pos
    if sink is not None:
        sink = sink.reshape(nkv, nh // nkv, 1, 1)

    def rows(qb, pos):
        qg = qb.reshape(qb.shape[0], nkv, nh // nkv, hd)
        s = jnp.einsum("tngd,ncd->ngtc", qg, k_view,
                       preferred_element_type=jnp.float32) / jnp.sqrt(
            jnp.float32(hd))
        kp, qp = kpos[None, None, None, :], pos[None, None, :, None]
        seen = kp <= qp
        if k_pos is not None:
            seen = jnp.logical_and(seen, kp >= 0)
        if window is not None:
            seen = jnp.logical_and(seen, kp > qp - jnp.int32(window))
        s = jnp.where(seen, s, jnp.float32(-1e30))
        p = softmax_with_sink(s, sink)
        o = jnp.einsum("ngtc,ncd->tngd", p.astype(v_view.dtype), v_view,
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype).reshape(qb.shape[0], nh, dv)

    qb = min(int(q_block), T)
    if T % qb or T == qb:
        return rows(q, q_pos)
    out = jax.lax.map(lambda a: rows(*a),
                      (q.reshape(T // qb, qb, nh, hd),
                       q_pos.reshape(T // qb, qb)))
    return out.reshape(T, nh, dv)


def cached_paged_attention(q, k_cache, v_cache, block_tables, lengths,
                           q_rot=None, k_rot=None):
    """Single-token decode attention over a PAGED cache addressed
    through a fixed-shape block table (the serving paged decode step,
    serving.paged.programs.build_paged_fns).

    q [S, nh, hd] — one new-token query per slot;
    k_cache/v_cache [num_blocks, nkv, block_size, hd] — one layer's
    pooled block arrays (``nkv`` divides ``nh``: grouped queries);
    block_tables [S, max_blocks] int — each slot's logical->physical
    block row (padding/released entries point at the trash block);
    lengths [S] int — live prefix length per slot, INCLUDING the row
    just written for this step.

    Gathers each slot's blocks into a position-ordered contiguous view
    [S, nh, max_blocks*block_size, hd] (view index block*BS + offset IS
    the cache position) and defers to cached_slot_attention's length
    masking — positions >= lengths[s], which includes every trash-block
    row a padding entry gathered, get -1e30 before the f32 softmax and
    carry exactly-zero weight. For block tables describing the same
    live prefixes this computes bit-for-bit what the slot-contiguous
    path computes. It is the XLA-composed gather: what the decode
    program runs where the Pallas paged decode kernel
    (ops.paged_attention, which reads the live blocks in place) cannot
    (the CPU, shapes ``kernel_viable`` refuses), and that kernel's
    parity oracle. Its cost is the capacity's, whatever is live.

    ``q_rot [S, nh, d2]``, ``k_rot [num_blocks, nkv, d2, BS]``: the
    second part of a key wider than its value, its pool stored
    transposed (``ops.paged_attention``); the two parts are put side by
    side and the scores scaled by the whole width."""
    S, _, hd = q.shape
    nh = k_cache.shape[1]
    with device_scope("kv_gather"):
        k = jnp.take(k_cache, block_tables, axis=0)  # [S, MB, nh, BS, hd]
        v = jnp.take(v_cache, block_tables, axis=0)
        k = k.transpose(0, 2, 1, 3, 4).reshape(S, nh, -1, hd)
        v = v.transpose(0, 2, 1, 3, 4).reshape(S, nh, -1, v.shape[-1])
        if k_rot is not None:
            k2 = jnp.take(k_rot, block_tables, axis=0)  # [S,MB,nh,d2,BS]
            k2 = k2.transpose(0, 2, 1, 4, 3).reshape(S, nh, -1,
                                                     k_rot.shape[2])
            k = jnp.concatenate([k, k2], axis=-1)
            q = jnp.concatenate([q, q_rot], axis=-1)
    return cached_slot_attention(q, k, v, lengths)


def causal_attention_lse(q, k, v):
    """Causal attention of a run of queries over the run's OWN keys and
    values, with each row's log-sum-exp: ``(out [b, h, s, d]`` in q's
    dtype, ``lse [b, h, 1, s]`` f32``)``, scale ``d ** -0.5``. The flash
    forward kernel where it takes the shape (``_use_pallas``: on a TPU
    sequences from 256 in steps of 128, interpreted on request), else
    ``jnp`` with the ``[h, s, s]`` scores as a temporary (short and odd
    runs; the CPU). f32 scores, statistics and accumulation on either;
    the kernel rounds ``p`` to the value dtype for the MXU, the ``jnp``
    form keeps it f32 as ``forward_t`` does."""
    scale = float(q.shape[-1]) ** -0.5
    if _use_pallas(q):
        return _pallas_flash_fwd(q, k, v, scale, True)
    s = q.shape[2]
    st = jnp.einsum("bhsd,bhtd->bhst", q, k,
                    preferred_element_type=jnp.float32) * jnp.float32(scale)
    st = jnp.where(jnp.tril(jnp.ones((s, s), bool)), st, jnp.float32(_NEG))
    m = jnp.max(st, axis=-1, keepdims=True)
    p = jnp.exp(st - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhst,bhtd->bhsd", p, v,
                     preferred_element_type=jnp.float32) / l
    return out.astype(q.dtype), jnp.swapaxes(m + jnp.log(l), 2, 3)


# positions of cached prefix a step of paged_prefill_attention's walk
# reads: [h, run, 256] f32 scores are the walk's largest temporary
_PREFIX_WALK = 256


def paged_prefill_attention(q, k, v, k_cache, v_cache, bt_row, start):
    """Attention of ONE sequence's run of queries at positions
    ``start..start + T`` (a paged PREFILL: an uncached tail, or a chunk
    of one) whose keys and values below ``start`` are in the paged cache
    and whose own are in hand.

    q, k, v ``[nh, T, hd]``; k_cache, v_cache ``[num_blocks, nh,
    block_size, hd]`` (one layer's pool, or the flat pool with
    ``bt_row`` offset to the layer); bt_row ``[MB]`` the slot's table
    row; start a traced scalar. Two parts, merged by their softmax
    statistics: the run over itself, causal (``causal_attention_lse``:
    no key of the cache, no score tensor beyond the run's own square),
    and a walk over the cached prefix ``_PREFIX_WALK`` positions at a
    time that reads the blocks below ``start`` and no others, so a run
    without a prefix pays nothing for one and its cost follows
    ``start``, never the slot's capacity. A position ``>= start`` that
    the walk's last step gathers (the rest of a block, trash behind a
    padded table) gets ``-1e30`` before the f32 softmax: exactly zero
    weight. Returns ``[nh, T, hd]`` in q's dtype."""
    nh, T, hd = q.shape
    BS, MB = k_cache.shape[2], bt_row.shape[0]
    out, lse = causal_attention_lse(q[None], k[None], v[None])
    per = max(1, min(MB, _PREFIX_WALK // BS))      # blocks a step
    width = per * BS
    scale = jnp.float32(float(hd) ** -0.5)

    def step(i, carry):
        m, l, acc = carry
        col = i * per + jnp.arange(per, dtype=jnp.int32)
        rows = bt_row[jnp.minimum(col, MB - 1)]
        with device_scope("kv_gather"):
            kb = k_cache[rows].transpose(1, 0, 2, 3).reshape(nh, width, hd)
            vb = v_cache[rows].transpose(1, 0, 2, 3).reshape(nh, width, hd)
        st = jnp.einsum("htd,hsd->hts", q, kb.astype(q.dtype),
                        preferred_element_type=jnp.float32) * scale
        kpos = i * width + jnp.arange(width, dtype=jnp.int32)
        st = jnp.where(kpos < start, st, jnp.float32(_NEG))
        m_new = jnp.maximum(m, jnp.max(st, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(st - m_new[..., None])
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hts,hsd->htd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # the run's own part as the walk's first state: m = lse, l = 1
    _, l, acc = jax.lax.fori_loop(
        0, (start + width - 1) // width, step,
        (lse[0, :, 0], jnp.ones((nh, T), jnp.float32),
         out[0].astype(jnp.float32)))
    return (acc / l[..., None]).astype(q.dtype)


def cached_slot_block_attention(q, k_cache, v_cache, qpos):
    """Multi-query decode attention over a slot-contiguous static
    cache: the t-token generalization of cached_slot_attention, what
    cached_paged_block_attention runs over its gathered view for the
    speculative k-token verify program (serving.spec.programs), where
    every slot scores t = k+1 candidate positions in one dispatch.

    q [S, nh, t, hd] — t new-token queries per slot (the slot's last
    accepted token plus its k drafted continuations);
    k_cache/v_cache [S, nh, C, hd] — each slot's full static cache,
    INCLUDING the t candidate rows this dispatch just wrote;
    qpos [S, t] int — the cache position of each query.

    Per-query causal masking ``kpos <= qpos[s, i]`` makes query i see
    exactly the slot's live prefix plus candidates 0..i — so logits at
    position i are conditioned only on the (accepted-by-construction)
    prefix of the draft, which is what makes longest-accepted-prefix
    harvest bit-exact with one-token-at-a-time greedy decode. For
    t = 1 and qpos = lengths - 1 this IS cached_slot_attention's mask;
    stale rows beyond qpos (a recycled slot's previous occupant, or a
    rejected draft tail from a previous verify step) carry exactly-zero
    softmax weight."""
    hd = q.shape[-1]
    cache_len = k_cache.shape[2]
    s = jnp.einsum("shtd,shkd->shtk", q, k_cache,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
        jnp.float32(hd))
    kpos = jnp.arange(cache_len)[None, None, None, :]
    s = jnp.where(kpos <= qpos[:, None, :, None], s,
                  jnp.float32(-1e30))
    return jnp.einsum("shtk,shkd->shtd", jax.nn.softmax(s, axis=-1),
                      v_cache, preferred_element_type=jnp.float32
                      ).astype(q.dtype)


def cached_paged_block_attention(q, k_cache, v_cache, block_tables,
                                 qpos):
    """Multi-query decode attention over a PAGED cache: the t-token
    generalization of cached_paged_attention for the speculative
    verify program on the paged pool. Same gather-to-contiguous
    baseline (view index block*BS + offset IS the cache position),
    then cached_slot_block_attention's per-query causal mask — trash-
    block rows a padding table entry gathered sit beyond every qpos
    and carry exactly-zero weight."""
    S, nh, t, hd = q.shape
    k = jnp.take(k_cache, block_tables, axis=0)  # [S, MB, nh, BS, hd]
    v = jnp.take(v_cache, block_tables, axis=0)
    k = k.transpose(0, 2, 1, 3, 4).reshape(S, nh, -1, hd)
    v = v.transpose(0, 2, 1, 3, 4).reshape(S, nh, -1, hd)
    return cached_slot_block_attention(q, k, v, qpos)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None):
    out = scaled_dot_product_attention(query, key, value, is_causal=causal)
    if return_softmax:
        return out, None
    return out
