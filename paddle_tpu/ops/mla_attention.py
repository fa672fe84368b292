"""Multi-head latent attention (DeepSeek-V2/V3): the plain ``jnp``
formulations of both of its forms and the Pallas TPU kernel for the
absorbed form over the paged latent cache.

A token's cache entry is ONE latent ``c`` (``kv_lora_rank`` values,
RMS-normed) and ONE rotary key ``k_pe`` (``qk_rope_head_dim`` values)
shared by every head. The two forms give the same numbers:

  expanded (prefill):  ``[k_nope_h, v_h] = c W_kvb[h]`` per cached
      position, ``score_h = q_nope_h . k_nope_h + q_pe_h . k_pe``;
  absorbed (decode):   ``q_lat_h = q_nope_h W_kvb[K, h]^T``,
      ``score_h = q_lat_h . c + q_pe_h . k_pe``,
      ``o_lat_h = sum_s p c(s)`` and ``o_h = o_lat_h W_kvb[V, h]``:
      the latent is read once for all heads and never expanded.

The paged cache keeps the latent as ``[blocks, block_size, rank]`` and
the rotary key TRANSPOSED, ``[blocks, dr, block_size]``: with the tokens
on the minor axis a 64-wide key neither pads to 128 lanes in HBM nor
makes XLA pick a layout of its own for the array (it did, and then
copied the whole pool into row-major in front of the kernel, every
layer of every step: AOT, PR 28), and its scores are a plain ``[nh, dr]
x [dr, BS]`` matmul.

``mla_paged_decode_attn`` is the absorbed form's middle (scores,
softmax, ``o_lat``) for every head of a slot over the slot's blocks,
read in place through scalar-prefetched table rows under the length
mask; ``mla_decode_attn_jnp`` is the same arithmetic in ``jnp`` over a
gathered view (the CPU path and the interpret-mode oracle).
"""
import functools

import jax
import jax.numpy as jnp

from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the kernel in interpret mode on CPU
_FORCE_INTERPRET = [False]
_NEG = -1e30


def kernel_viable(block_size, rank, rope_dim, dtype):
    """Static facts Mosaic needs of the cache blocks: the token axis is
    the sublane dim (a multiple of 16 for 2-byte types, 8 for f32) and
    the latent rides the lanes whole (a multiple of 128)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    sub = 8 if dtype == jnp.dtype(jnp.float32) else 16
    return block_size % sub == 0 and rank % 128 == 0 and rope_dim % 8 == 0


# ------------------------------------------------------------ jnp forms
def expanded_attention(q_nope, q_pe, c, k_pe, w_kvb, q_pos, scale,
                       q_block=256):
    """Causal expanded-form attention of ONE sequence.

    q_nope ``[T, nh, dn]``, q_pe ``[T, nh, dr]`` at absolute positions
    ``q_pos [T]``; the cache view c ``[C, rank]``, k_pe ``[C, dr]`` (view
    index == position); w_kvb ``[rank, nh, dn + dv]``. Key ``s`` is seen
    by query ``t`` when ``s <= q_pos[t]``. Scores and softmax in f32;
    computed ``q_block`` query rows at a time so that the ``[nh, rows,
    C]`` scores of a 10k-token prompt stay a temporary of that size.
    Returns ``[T, nh, dv]`` in q's dtype."""
    T, nh, dn = q_nope.shape
    C = c.shape[0]
    kv = jnp.einsum("cr,rhd->chd", c, w_kvb)            # [C, nh, dn+dv]
    k_nope, v = kv[..., :dn], kv[..., dn:]
    kpos = jnp.arange(C, dtype=jnp.int32)

    def rows(qn, qp, pos):
        s = jnp.einsum("thd,chd->htc", qn, k_nope,
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("thd,cd->htc", qp, k_pe,
                           preferred_element_type=jnp.float32)
        s = s * jnp.float32(scale)
        s = jnp.where(kpos[None, None, :] <= pos[None, :, None], s,
                      jnp.float32(_NEG))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("htc,chd->thd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).astype(qn.dtype)

    qb = min(int(q_block), T)
    if T % qb:
        return rows(q_nope, q_pe, q_pos)
    nb = T // qb
    if nb == 1:
        return rows(q_nope, q_pe, q_pos)
    out = jax.lax.map(
        lambda a: rows(*a),
        (q_nope.reshape(nb, qb, nh, dn),
         q_pe.reshape(nb, qb, nh, -1), q_pos.reshape(nb, qb)))
    return out.reshape(T, nh, -1)


def mla_decode_attn_jnp(q_lat, q_pe, c, k_pe, lengths, scale):
    """Absorbed-form middle over gathered views: q_lat ``[S, nh, rank]``,
    q_pe ``[S, nh, dr]``, c ``[S, C, rank]``, k_pe ``[S, C, dr]``,
    positions ``>= lengths[s]`` masked. Returns o_lat ``[S, nh, rank]``
    f32."""
    s = jnp.einsum("shr,scr->shc", q_lat, c,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("shd,scd->shc", q_pe, k_pe,
                       preferred_element_type=jnp.float32)
    s = s * jnp.float32(scale)
    kpos = jnp.arange(c.shape[1], dtype=jnp.int32)
    s = jnp.where(kpos[None, None, :] < lengths[:, None, None], s,
                  jnp.float32(_NEG))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("shc,scr->shr", p.astype(c.dtype), c,
                      preferred_element_type=jnp.float32)


def gather_paged(cache, tables, token_axis_last=False):
    """``[NBflat, BS, d]`` (or ``[NBflat, d, BS]``) + tables ``[S, MB]``
    -> ``[S, MB*BS, d]``, position-ordered."""
    g = cache[tables]                                   # [S, MB, ., .]
    if token_axis_last:
        g = g.transpose(0, 1, 3, 2)
    return g.reshape(g.shape[0], -1, g.shape[-1])


def mla_paged_decode_attn_jnp(q_lat, q_pe, c_cache, pe_cache, tables,
                              lengths, scale):
    """The kernel's signature in ``jnp``: gathers every slot's blocks."""
    with jax.named_scope("kv_gather"):
        c = gather_paged(c_cache, tables)
        k_pe = gather_paged(pe_cache, tables, token_axis_last=True)
    return mla_decode_attn_jnp(q_lat, q_pe, c, k_pe, lengths, scale)


# --------------------------------------------------------------- kernel
def _mla_decode_kernel(bt_ref, len_ref, ql_ref, qp_ref, c_ref, pe_ref,
                       o_ref, acc_ref, m_ref, l_ref, *, block_size,
                       max_blocks, scale):
    """Grid (S, MB), MB innermost: one slot's blocks arrive in order,
    the online-softmax state lives in VMEM scratch across them and the
    output block is written once at the last step. All heads of the
    slot share each block: scores ``[nh, BS]`` and ``p @ c`` are two
    MXU matmuls over the latent, one small one over the rotary key."""
    from jax.experimental import pallas as pl
    si = pl.program_id(0)
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[si]

    def _compute():
        c = c_ref[...]                                   # [BS, rank]
        nt = (((1,), (1,)), ((), ()))                    # a @ b^T
        s = jax.lax.dot_general(ql_ref[...], c, nt,
                                preferred_element_type=jnp.float32)
        s = s + jnp.dot(qp_ref[...], pe_ref[...],        # [dr, BS]
                        preferred_element_type=jnp.float32)
        s = s * jnp.float32(scale)                       # [nh, BS]
        kpos = bi * jnp.int32(block_size) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, jnp.float32(_NEG))
        m_prev = m_ref[...]                              # [nh, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new
        pv = jnp.dot(p.astype(c.dtype), c,
                     preferred_element_type=jnp.float32)  # [nh, rank]
        acc_ref[...] = acc_ref[...] * alpha + pv

    # blocks wholly beyond the live length weigh nothing: no math (the
    # index map re-presents the last live block, so no DMA either)
    pl.when(bi * jnp.int32(block_size) < length)(_compute)

    @pl.when(bi == max_blocks - 1)
    def _store():
        l = jnp.maximum(l_ref[...], jnp.float32(1e-37))
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _mla_paged_decode_32(q_lat, q_pe, c_cache, pe_cache, tables, lengths,
                         scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, nh, rank = q_lat.shape
    dr = q_pe.shape[-1]
    BS = c_cache.shape[1]
    MB = tables.shape[1]
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def q_index(si, bi, bt_ref, len_ref):
        return (si, 0, 0)

    def kv_index(si, bi, bt_ref, len_ref):
        # physical block from the prefetched table row, clamped to the
        # slot's last live block: steps beyond it repeat an index and
        # their DMA is elided
        last = jnp.minimum(jnp.maximum(len_ref[si] - 1, 0)
                           // jnp.int32(BS), MB - 1)
        return (bt_ref[si, jnp.minimum(bi, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, MB),
        in_specs=[
            pl.BlockSpec((None, nh, rank), q_index),
            pl.BlockSpec((None, nh, dr), q_index),
            pl.BlockSpec((None, BS, rank), kv_index),
            pl.BlockSpec((None, dr, BS), kv_index),
        ],
        out_specs=pl.BlockSpec((None, nh, rank), q_index),
        scratch_shapes=[
            pltpu.VMEM((nh, rank), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_mla_decode_kernel, block_size=BS,
                               max_blocks=MB, scale=float(scale))
    return pl.pallas_call(
        kernel, name="mla_paged_decode_attn", grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_FORCE_INTERPRET[0],
    )(tables, lengths, q_lat, q_pe, c_cache, pe_cache)


def mla_paged_decode_attn(q_lat, q_pe, c_cache, pe_cache, tables, lengths,
                          scale):
    """Absorbed latent attention for all heads of every slot over its
    blocks, read in place. q_lat ``[S, nh, rank]``, q_pe ``[S, nh, dr]``
    in the cache's dtype; c_cache ``[NB, BS, rank]``, pe_cache ``[NB,
    dr, BS]``; tables ``[S, MB]`` physical block ids; positions ``>=
    lengths[s]`` carry exactly zero weight. Returns o_lat ``[S, nh,
    rank]`` f32. Same signature and numbers as
    ``mla_paged_decode_attn_jnp``."""
    return _trace_32bit(_mla_paged_decode_32)(
        q_lat, q_pe, c_cache, pe_cache, tables, lengths, scale)
