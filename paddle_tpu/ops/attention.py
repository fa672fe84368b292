"""Fused attention.

TPU-native: flash attention as Pallas kernels for the hot path —
FORWARD (online-softmax, VMEM-resident) and BACKWARD (recompute-based,
O(seq) memory: the full [s, t] score matrix is never materialized),
the greenfield requirement SURVEY §5 sets for long-context. Reference
analogue: paddle/fluid/operators/math/bert_encoder_functor.cu and the
fused multihead-matmul passes — here it's fused kernels instead of
fusion passes. Falls back to the XLA softmax(QK^T)V composition for
small shapes or on CPU where Pallas TPU kernels are unavailable
(interpret mode exercises the kernels in CPU tests).

Layout: [batch, num_heads, seq, head_dim].
"""
import functools
import math

import jax
import jax.numpy as jnp

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


# ---- forward kernel --------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref, *, scale, causal,
                      block_q, block_k, nk):
    """Grid (b, h, nq, nk): K/V stream through VMEM one block at a
    time, so VMEM use is O(block) — independent of seq length (a
    full-seq-resident K/V caps out near seq 16k on the 16MB budget).
    The online-softmax state (acc, m, l) lives in VMEM scratch, which
    persists across the sequentially-executed inner ki grid steps; the
    o/lse output blocks are revisited and written once at the last ki."""
    from jax.experimental import pallas as pl
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        # [block_q, block_k] = q @ k.T, f32 accumulation
        s = _dot_f32(q, k, ((1,), (1,))) * jnp.float32(scale)
        if causal:
            q_pos = qi * jnp.int32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * jnp.int32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(-1e30))
        m_prev = m_ref[...][0]
        l_prev = l_ref[...][0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = (alpha * l_prev + jnp.sum(p, axis=1))[None, :]
        m_ref[...] = m_new[None, :]
        pv = _dot_f32(p.astype(v.dtype), v, ((1,), (0,)))
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    if causal:
        # fully-future K blocks contribute nothing: skip their matmuls
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _store():
        l = l_ref[...][0]
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)[None, :]


def _pallas_flash_fwd(q, k, v, scale, causal):
    # x64 guard shared by every Pallas entry point (pallas_compat)
    return _trace_32bit(_pallas_flash_fwd_32)(q, k, v, scale, causal)


import os as _os

# Block sizes: 128-row blocks leave the MXU underfed (64-deep contractions
# on 128x128 tiles) and pay per-grid-cell DMA/semaphore overhead; 512
# amortizes both while staying well inside the 16MB VMEM budget at
# d=64..256. Measured on v5e at [8,12,1024,64] bf16 causal: grad
# 7.4ms (block 128) -> 4.7ms (block 512), 1.9x faster than
# jax.experimental.pallas.ops.tpu.flash_attention on the same shape.
_BLOCK_Q = int(_os.environ.get("PADDLE_FLASH_BLOCK_Q", "512"))
_BLOCK_K = int(_os.environ.get("PADDLE_FLASH_BLOCK_K", "512"))
_BLOCK_BWD = int(_os.environ.get("PADDLE_FLASH_BLOCK_BWD", "512"))


def _block_for(s, want):
    """Largest power-of-two block <= want that divides s (s is a
    multiple of 128 per the _use_pallas gate, so the halving loop
    terminates by 128; non-power-of-two env overrides are rounded down
    so it cannot degenerate below that)."""
    want = max(128, 1 << (max(want, 1).bit_length() - 1))
    blk = min(want, s)
    while s % blk:
        blk //= 2
    return blk


def _pallas_flash_fwd_32(q, k, v, scale, causal):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, s, d = q.shape
    block_q = _block_for(s, _BLOCK_Q)
    block_k = _block_for(s, _BLOCK_K)
    nq, nk = s // block_q, s // block_k
    kernel = functools.partial(_flash_fwd_kernel, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, nk=nk)
    out, lse = pl.pallas_call(kernel, name="flash_fwd",
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((None, None, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            # mosaic needs the last two block dims ~(8,128)-aligned or
            # full; a [b,h,1,s] layout makes the lse block (1, block_q)
            pl.BlockSpec((None, None, 1, block_q),
                         lambda bi, hi, qi, ki: (bi, hi, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
        ],
        interpret=_interpret(),
    )(q, k, v)
    return out, lse


# ---- backward kernels (flash-attention-2 style, O(seq) memory) -------------
# 4D grid (b, h, outer, inner): the inner loop is a GRID dimension, so
# only block-sized tiles live in VMEM at a time (full-seq tiles blew the
# 16MB scoped-vmem budget at seq 16k); the output block is revisited
# across inner steps and accumulated (TPU grids execute sequentially).

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, scale, causal, block_q, block_k):
    from jax.experimental import pallas as pl
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def _compute():
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][0]
        delta = delta_ref[...][0]
        k = k_ref[...]
        v = v_ref[...]
        s = _dot_f32(q, k, ((1,), (1,))) * jnp.float32(scale)
        if causal:
            q_pos = qi * jnp.int32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * jnp.int32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(-1e30))
        p = jnp.exp(s - lse[:, None])
        dp = _dot_f32(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        dq_ref[...] += _dot_f32(ds.astype(k.dtype), k,
                                ((1,), (0,))) * jnp.float32(scale)

    if causal:
        pl.when(qi >= ki)(_compute)  # fully-future blocks contribute 0
    else:
        _compute()


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, scale, causal, block_q,
                          block_k):
    from jax.experimental import pallas as pl
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    def _compute():
        k = k_ref[...]
        v = v_ref[...]
        q = q_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][0]
        delta = delta_ref[...][0]
        s = _dot_f32(q, k, ((1,), (1,))) * jnp.float32(scale)
        if causal:
            q_pos = qi * jnp.int32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * jnp.int32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(-1e30))
        p = jnp.exp(s - lse[:, None])
        # p.T @ do and ds.T @ q, contracting over the block_q axis
        dv_ref[...] += _dot_f32(p.astype(do.dtype), do, ((0,), (0,)))
        dp = _dot_f32(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        dk_ref[...] += _dot_f32(ds.astype(q.dtype), q,
                                ((0,), (0,))) * jnp.float32(scale)

    if causal:
        pl.when(qi >= ki)(_compute)
    else:
        _compute()


def _pallas_flash_bwd(q, k, v, out, lse, g, scale, causal):
    return _trace_32bit(_pallas_flash_bwd_32)(q, k, v, out, lse, g,
                                              scale, causal)


def _pallas_flash_bwd_32(q, k, v, out, lse, g, scale, causal):
    from jax.experimental import pallas as pl
    b, h, s, d = q.shape
    block = _block_for(s, _BLOCK_BWD)
    n = s // block
    # delta = rowsum(dO * O): O(s d) precompute outside the kernels
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, :, None, :]  # [b, h, 1, s]

    def blk(which):  # index by grid dim 2 or 3
        return pl.BlockSpec(
            (None, None, block, d),
            (lambda bi, hi, i, j: (bi, hi, i, 0)) if which == 2
            else (lambda bi, hi, i, j: (bi, hi, j, 0)))

    def vec(which):
        return pl.BlockSpec(
            (None, None, 1, block),
            (lambda bi, hi, i, j: (bi, hi, 0, i)) if which == 2
            else (lambda bi, hi, i, j: (bi, hi, 0, j)))

    f32 = jnp.float32
    # dq: grid (b, h, nq, nk); dq block revisited across nk
    dq_kernel = functools.partial(_flash_bwd_dq_kernel, scale=scale,
                                  causal=causal, block_q=block,
                                  block_k=block)
    dq = pl.pallas_call(dq_kernel, name="flash_bwd_dq",
        grid=(b, h, n, n),
        in_specs=[blk(2), blk(3), blk(3), blk(2), vec(2), vec(2)],
        out_specs=blk(2),
        out_shape=jax.ShapeDtypeStruct(q.shape, f32),
        interpret=_interpret(),
    )(q, k, v, g, lse, delta)

    # dk/dv: grid (b, h, nk, nq); dk/dv blocks revisited across nq
    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, scale=scale,
                                   causal=causal, block_q=block,
                                   block_k=block)
    dk, dv = pl.pallas_call(dkv_kernel, name="flash_bwd_dkv",
        grid=(b, h, n, n),
        in_specs=[blk(3), blk(2), blk(2), blk(3), vec(3), vec(3)],
        out_specs=[blk(2), blk(2)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, f32),
                   jax.ShapeDtypeStruct(v.shape, f32)],
        interpret=_interpret(),
    )(q, k, v, g, lse, delta)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


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
    """Inputs [batch, heads, seq, head_dim] (or [b, s, h, d] paddle-style
    is accepted via transpose by callers). Dropout inside attention is not
    fused; applied to weights only in the fallback path when requested."""
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
    k_cache/v_cache [S, nh, C, hd] — each slot's full static cache;
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


def cached_paged_attention(q, k_cache, v_cache, block_tables, lengths):
    """Single-token decode attention over a PAGED cache addressed
    through a fixed-shape block table (the serving paged decode step,
    serving.paged.programs.build_paged_fns).

    q [S, nh, hd] — one new-token query per slot;
    k_cache/v_cache [num_blocks, nh, block_size, hd] — one layer's
    pooled block arrays;
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
    parity oracle. Its cost is the capacity's, whatever is live."""
    S, nh, hd = q.shape
    with jax.named_scope("kv_gather"):
        k = jnp.take(k_cache, block_tables, axis=0)  # [S, MB, nh, BS, hd]
        v = jnp.take(v_cache, block_tables, axis=0)
        k = k.transpose(0, 2, 1, 3, 4).reshape(S, nh, -1, hd)
        v = v.transpose(0, 2, 1, 3, 4).reshape(S, nh, -1, hd)
    return cached_slot_attention(q, k, v, lengths)


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
