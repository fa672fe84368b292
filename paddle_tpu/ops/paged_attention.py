"""Pallas TPU paged decode-attention kernel.

Single-token decode attention computed DIRECTLY over the paged pool
layout (vLLM's PagedAttention idea, SOSP'23, done TPU-natively): q
``[S, nh, hd]``, pooled ``k_cache``/``v_cache``
``[num_blocks, nh, BS, hd]``, fixed-shape ``block_tables [S, MB]``,
per-slot ``lengths``. Each slot's physical blocks stream through VMEM
one at a time under an online softmax — the ``[S, nh, MB*BS, hd]``
gathered view the XLA composition (``ops.attention.
cached_paged_attention``) materializes is never built, which deletes
the ~3x gather traffic the roofline model prices as
``PAGED_GATHER_FACTOR``.

How the table drives the DMA schedule: grid ``(S, MB)`` with
``PrefetchScalarGridSpec(num_scalar_prefetch=2)`` — ``block_tables``
and ``lengths`` arrive ahead of the kernel body as scalar-prefetch
refs, and the K/V BlockSpec index maps read ``bt_ref[s, ...]`` to
return PHYSICAL block ids, so Pallas' pipelining fetches exactly the
blocks the table names. The index map clamps the logical block index
to the slot's last LIVE block (``(lengths[s]-1) // BS``): grid steps
beyond the live length re-present the previous block index, and
Pallas elides the re-DMA for an unchanged block — the kernel never
over-reads past a slot's live length, the fixed-shape over-read the
roofline's ``paged_pallas`` layout (gather factor 1.0) models away.

In-kernel masking mirrors the fallback exactly: key positions
``>= lengths[s]`` (trash-block padding rows, a recycled slot's stale
rows, the tail of a partially-filled block) get ``-1e30`` before the
f32 online softmax, so they carry exactly-zero weight. Scores and the
output accumulator are f32 (the ``_dot_f32`` discipline); scores are
computed as a VPU multiply-reduce over ``hd`` — per (slot, head) the
contraction is ``[1, hd] x [hd, BS]``, far too skinny to feed the MXU,
and the whole op is HBM-bound anyway.

Gating follows the fused-CE playbook: ``PADDLE_PAGED_ATTN=1`` env
opt-in (or the ``ServingConfig(paged_attn=...)`` knob), a
``kernel_viable`` shape/dtype/backend guard, and interpret mode on CPU
(tests flip ``_FORCE_INTERPRET``) so tier-1 exercises the real kernel
while the XLA composition stays the default measured fallback.
"""
import functools
import os

import jax
import jax.numpy as jnp

from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the kernel in interpret mode on CPU
_FORCE_INTERPRET = [False]


def _interpret():
    return _FORCE_INTERPRET[0]


def kernel_requested(override=None):
    """The gate: ``ServingConfig(paged_attn=...)`` when set, else the
    PADDLE_PAGED_ATTN env var. Default OFF — the XLA gather
    composition stays the measured fallback until the kernel is
    explicitly enabled (mirroring PADDLE_FUSED_CE)."""
    if override is not None:
        return bool(override)
    return os.environ.get("PADDLE_PAGED_ATTN", "0") == "1"


def kernel_viable(num_heads, head_dim, block_size, dtype):
    """Shape/dtype/backend guard (the ``_use_pallas`` discipline).
    Static facts only, so the engine can resolve the active decode
    layout once at build time and bind it to the roofline."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                     jnp.dtype(jnp.float16)):
        return False  # f64 cannot lower on Mosaic
    if _FORCE_INTERPRET[0]:
        return True   # interpret mode handles any shape
    if jax.default_backend() == "cpu":
        return False
    # Mosaic wants the K/V block's sublane dim (BS) tiling-aligned;
    # nh and hd ride in full so they only need the lane minimum
    sub = 8 if dtype == jnp.dtype(jnp.float32) else 16
    return block_size % sub == 0 and head_dim % 8 == 0


def _paged_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, block_size,
                         max_blocks):
    """Grid (S, MB), MB innermost: one slot's blocks arrive
    sequentially, so the online-softmax state (acc, m, l) lives in
    VMEM scratch across the inner steps and the o block is revisited
    and written once at the last step — the flash-forward idiom, per
    slot instead of per query-block."""
    from jax.experimental import pallas as pl
    si = pl.program_id(0)
    bi = pl.program_id(1)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[si]

    def _compute():
        q = q_ref[...]   # [nh, hd]
        k = k_ref[...]   # [nh, BS, hd]
        v = v_ref[...]
        hd = q.shape[-1]
        # scores [nh, BS] in f32; same scale and mask value as the
        # fallback so masked softmax terms agree exactly. Cast BEFORE
        # the [:, None, :]: Mosaic has no bf16 [1,nh,hd]->[nh,1,hd]
        # shape cast ("unsupported shape cast"), the f32 one it has
        s = jnp.sum(q.astype(jnp.float32)[:, None, :]
                    * k.astype(jnp.float32), axis=-1)
        s = s / jnp.sqrt(jnp.float32(hd))
        kpos = bi * jnp.int32(block_size) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, jnp.float32(-1e30))
        m_prev = m_ref[...]          # [nh, 1]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        pv = jnp.sum(p[:, :, None] * v.astype(jnp.float32), axis=1)
        acc_ref[...] = acc_ref[...] * alpha + pv

    # blocks entirely beyond the live length contribute zero weight:
    # skip the math (their DMA is already elided by the index-map
    # clamp re-presenting the previous block)
    pl.when(bi * jnp.int32(block_size) < length)(_compute)

    @pl.when(bi == max_blocks - 1)
    def _store():
        # l >= 1 whenever any block computed (the max's own exp term);
        # the floor only guards a length<=0 slot, whose output is
        # as-unused as the fallback's uniform-over-garbage row
        l = jnp.maximum(l_ref[...], jnp.float32(1e-37))
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_32(q, k_cache, v_cache, block_tables, lengths):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, nh, hd = q.shape
    BS = k_cache.shape[2]
    MB = block_tables.shape[1]
    block_tables = block_tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def q_index(si, bi, bt_ref, len_ref):
        return (si, 0, 0)

    def kv_index(si, bi, bt_ref, len_ref):
        # physical block id straight from the prefetched table; clamp
        # to the slot's last live block so beyond-length grid steps
        # repeat an index and their DMA is elided (no over-read)
        last = jnp.minimum(jnp.maximum(len_ref[si] - 1, 0)
                           // jnp.int32(BS), MB - 1)
        return (bt_ref[si, jnp.minimum(bi, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, MB),
        in_specs=[
            pl.BlockSpec((None, nh, hd), q_index),
            pl.BlockSpec((None, nh, BS, hd), kv_index),
            pl.BlockSpec((None, nh, BS, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((None, nh, hd), q_index),
        scratch_shapes=[
            pltpu.VMEM((nh, hd), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
            pltpu.VMEM((nh, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, block_size=BS,
                               max_blocks=MB)
    return pl.pallas_call(kernel, name="paged_decode_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, nh, hd), q.dtype),
        interpret=_interpret(),
    )(block_tables, lengths, q, k_cache, v_cache)


def paged_decode_attention(q, k_cache, v_cache, block_tables, lengths):
    """Drop-in for ``ops.attention.cached_paged_attention`` (same
    signature, same semantics) reading K/V blocks in place. Callers
    check ``kernel_viable`` first; ``cached_paged_attention`` is
    the bit-exact-fallback parity oracle."""
    # x64 guard shared by every Pallas entry point (pallas_compat)
    return _trace_32bit(_paged_decode_32)(q, k_cache, v_cache,
                                          block_tables, lengths)
