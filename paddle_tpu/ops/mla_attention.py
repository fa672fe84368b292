"""Multi-head latent attention (DeepSeek-V2/V3): the plain ``jnp``
formulations of both of its forms and the Pallas TPU kernel for the
absorbed form computed DIRECTLY over the paged latent cache.

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
softmax, ``o_lat``) for every head of a slot over the slot's LIVE
blocks, read in place, and the placing of the step's new entry in
them; ``mla_decode_attn_jnp`` is the same arithmetic in ``jnp`` over a
gathered view (the CPU path and the interpret-mode oracle).

How the table drives the DMA schedule (after ``ops/paged_attention.py``):
grid ``(S,)``, one step a slot, with ``tables`` and ``lengths``
scalar-prefetched, both pools left in HBM (``memory_space=ANY``) and
``q_lat``, ``q_pe`` and the output resident in VMEM for the whole call.
A slot's live blocks, ``ceil(length / BS)`` clipped to ``MB``, are
walked in CHUNKS of ``G`` blocks (``blocks_per_chunk``: from the shapes
and a VMEM budget, not an option): one chunk is ``G`` latent and ``G``
rotary-key block copies (``make_async_copy``, physical ids from the
table) into one half of a double-buffered pair, the latent's blocks one
under the other in ``[2, G*BS, rank]``, the rotary key's as the planes
of ``[2, G, dr, BS]``, while the other half is computed. Only live
blocks are copied: a slot with nothing live (length 0: a released slot)
costs its grid step, a parked one (length 1) one block of DMA and one
chunk of arithmetic, a full one ``MB / G`` chunks, and the next chunk
(the next SLOT's first chunk after a slot's last) is always in flight
behind the one being computed. On the chip the copies are the bound and
the arithmetic hides behind them (``_CHUNK_VMEM_BYTES``).

A chunk's arithmetic covers its ``T = G*BS`` positions at once:
scores ``q_lat [nh, rank] x c [T, rank]^T`` + ``q_pe [nh, dr]
x k_pe [dr, BS]`` a block, side by side on the lanes; the f32 online
softmax; ``p`` rounded once to the cache's dtype; ``p [nh, T] x c [T,
rank]`` accumulated in f32. The heads are the rows of every matmul and
the latent is the MXU's stationary operand in both (streaming the latent
past a stationary ``q_lat`` read a third slower on the chip, PR 36).

In-kernel masking mirrors the oracle exactly: positions ``>=
lengths[s]`` (the tail of a partially-filled block, the part of a
chunk's buffer no copy refreshed, a chunk's reach past the slot's
capacity) get ``-1e30`` before the softmax, so they carry exactly-zero
weight. The latent buffer, which is the value operand too, is zeroed
once a call, so what lies behind a zero weight is always finite.

Who writes the step's new entry (``new=``, ``write_pos=``: what the
decode program calls, ``latent_write_attention``; after
``ops/paged_attention.py``'s ``place_entry``). The KERNEL does. A
slot's new entry sits at position ``lengths[s] - 1``, in its last live
block, which the slot's LAST chunk brings into ``cbuf`` / ``pebuf``
anyway. After that chunk's copies are waited for, the kernel selects
the latent into row ``write_pos % (G*BS)`` of ``cbuf`` (an ``iota ==``
select over the 16-row tile that holds it, via f32: exact for a 16-bit
pool) and the rotary key into lane ``write_pos % BS`` of its block's
plane of ``pebuf`` (positions ride the lanes there, so the entry's
``dr`` values are turned from lanes onto sublanes by a ``diag(entry) x
onehot`` matmul, one exact term a sum), runs the chunk's arithmetic on
the buffer as for any other chunk (the attention reads the entry from
the same bits HBM will hold: no second source), and copies back to HBM
only what changed: the latent's ``[16, rank]`` tile (8 rows of an f32
pool) and the 128 lanes around the entry of the rotary key's block,
``[dr, 128]``: 16 + 16 KB at the cell's widths where the ``jnp`` block
write read and wrote 288 KB a slot a layer (PR 49). Both pools are the
call's aliased outputs (``input_output_aliases``), so a donated pool
carried through a layer loop is updated in place and no gather or
scatter of a block is left in front of the kernel. The write-back is
started before the chunk's arithmetic and waited for after the slot's
last chunk, before its half of the double buffer can be a copy's target
again and before the grid step ends; the next slot's prefetched first
chunk never holds the block, because a slot's last block is private
(``pool.acquire`` asserts it). Only a LIVE entry is written
(``write_pos[s] == lengths[s] - 1`` inside the slot's row,
``ops.paged_attention.live_write_pos``): a parked or released slot
(``write_pos`` -1), whose stray row the ``jnp`` write pins to the last
entry of its table row or drops in the trash block, writes nothing, and
a slot with nothing live copies nothing in either direction. Without
``new`` no ref, copy or select of the write is traced and the call is
what it was (the prefill-side tests and the oracle's comparisons).
"""
import functools
import math

import jax
import jax.numpy as jnp

from ..profiler import device_scope
from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the kernel in interpret mode on CPU
_FORCE_INTERPRET = [False]
_NEG = -1e30


# both chunk buffers' two halves. At rank 512 + a rotary key of 64 in
# bf16, blocks of 256 (288 KB a block), this is G = 4 blocks = 1,024
# positions a chunk: the smallest that runs a full slot at the rate of
# larger ones (90.5 % of 819 GB/s at G = 4, 90.8 at 8, 90.5 at 16; 72.9
# at G = 2 and 51.6 at G = 1, where a short chunk's arithmetic outlasts
# its copies) and cheaper than G = 8 for a slot with one live block,
# whose chunk is computed whole (1.6 us against 2.2) (kernel timed in a
# chain of 120 calls, my chip runs, PR 36)
_CHUNK_VMEM_BYTES = 5 << 19


def kernel_viable(block_size, rank, rope_dim, dtype):
    """Static facts Mosaic needs of the cache blocks: the token axis is
    the sublane dim (a multiple of 16 for 2-byte types, 8 for f32: a
    block lands in the chunk buffer at a multiple of it), the latent
    rides the lanes whole (a multiple of 128), and a chunk holds at
    least one block."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    sub = 8 if dtype == jnp.dtype(jnp.float32) else 16
    return (block_size % sub == 0 and rank % 128 == 0
            and rope_dim % 8 == 0
            and blocks_per_chunk(block_size, rank, rope_dim, 1,
                                 dtype) == 1)


def blocks_per_chunk(block_size, rank, rope_dim, max_blocks, dtype):
    """``G``: how many blocks one chunk holds. As many as keep the chunk
    buffers (latent and rotary key, two halves each) inside the budget,
    at most a slot's capacity; 1 where blocks cannot sit side by side on
    the lanes of the scores (``block_size`` not whole lane tiles); 0
    where not even one block fits."""
    block = block_size * (rank + rope_dim) * jnp.dtype(dtype).itemsize
    fit = _CHUNK_VMEM_BYTES // (2 * block)
    if block_size % 128:
        fit = min(fit, 1)
    return int(min(max_blocks, fit))


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
    with device_scope("kv_gather"):
        c = gather_paged(c_cache, tables)
        k_pe = gather_paged(pe_cache, tables, token_axis_last=True)
    return mla_decode_attn_jnp(q_lat, q_pe, c, k_pe, lengths, scale)


# --------------------------------------------------------------- kernel
def _mla_decode_kernel(bt_ref, len_ref, *refs, block_size, group, scale,
                       write=False):
    """Grid (S,), sequential: one step a slot. The table row of the
    slot names the blocks to copy, ``lengths`` how many of them are
    live; ``cbuf [2, G*BS, rank]`` / ``pebuf [2, G, dr, BS]`` are the
    two halves of the chunk buffers (``G`` blocks a chunk: the 4 that
    the cell's 288 KB blocks give ran a full slot at 90.5 % of the
    chip's bandwidth, ``_CHUNK_VMEM_BYTES``), ``sem[0/1, half]`` the
    latent / rotary-key copies' semaphores, ``half_ref`` (SMEM) the half
    that holds this slot's first chunk: the previous grid step started
    its copies. All heads of the slot share each chunk: scores ``[nh,
    T]`` and ``p @ c`` are two MXU matmuls over the latent, ``G`` small
    ones over the rotary key; the online-softmax state lives in VMEM
    scratch across the chunks and the slot's output row is written
    once. With ``write`` the step's new entry is placed (module
    docstring): ``wpos_ref`` (a third prefetched scalar a slot),
    ``cn_ref [S, rank]`` / ``pn_ref [S, dr]`` resident like ``ql_ref``,
    the pools the call's aliased OUTPUTS (read and written through the
    one ref), the write-backs on ``wsem``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    it = iter(refs)
    wpos_ref = next(it) if write else None
    ql_ref, qp_ref, c_hbm, pe_hbm = (next(it) for _ in range(4))
    cn_ref, pn_ref = (next(it), next(it)) if write else (None, None)
    o_ref = next(it)
    if write:
        # the aliased outputs: the same HBM as the inputs
        c_hbm, pe_hbm = next(it), next(it)
    cbuf, pebuf, acc_ref, m_ref, l_ref, sem, half_ref = (
        next(it) for _ in range(7))
    wsem = next(it) if write else None
    BS, G, MB = block_size, group, bt_ref.shape[1]
    T = G * BS
    si = pl.program_id(0)
    num_slots = pl.num_programs(0)

    def live_blocks(s):
        return jnp.clip((len_ref[s] + (BS - 1)) // BS, 0, MB)

    def chunk_copies(s, c, half, go):
        """``go`` (start or wait) the two copies of each live block of
        slot s's chunk c: the latent to rows [g*BS, (g+1)*BS), the
        rotary key to plane g."""
        def block(g, carry):
            blk = bt_ref[s, c * G + g]
            rows = pl.ds(pl.multiple_of(g * BS, BS), BS)
            go(pltpu.make_async_copy(
                c_hbm.at[blk], cbuf.at[half, rows, :], sem.at[0, half]))
            go(pltpu.make_async_copy(
                pe_hbm.at[blk], pebuf.at[half, g], sem.at[1, half]))
            return carry
        jax.lax.fori_loop(
            0, jnp.clip(live_blocks(s) - c * G, 0, G), block, 0)

    def start_chunk(s, c, half):
        chunk_copies(s, c, half, lambda copy: copy.start())

    def wait_chunk(s, c, half):
        chunk_copies(s, c, half, lambda copy: copy.wait())

    # the step's new entry (``write``): position ``wpos``, which the
    # slot's LAST chunk holds at buffer row ``wpos - c * T``. Rows of a
    # latent tile, lanes of a rotary-key tile (whole ones on a chip:
    # ``kernel_viable``; interpret mode takes smaller blocks)
    sub = math.gcd(BS, 8 if cbuf.dtype == jnp.float32 else 16)
    lane = math.gcd(BS, 128)

    def entry_copies(c, half, go):
        """``go`` (start or wait) the copies back to HBM of the tiles of
        chunk c's buffers that hold this slot's new entry: the ``sub``
        rows around it of the latent, the 128 lanes around it of the
        rotary key's block."""
        p = wpos_ref[si]
        blk = bt_ref[si, p // BS]
        src = pl.ds(pl.multiple_of((p - c * T) // sub * sub, sub), sub)
        dst = pl.ds(pl.multiple_of(p % BS // sub * sub, sub), sub)
        go(pltpu.make_async_copy(
            cbuf.at[half, src, :], c_hbm.at[blk, dst, :], wsem.at[0]))
        lanes = pl.ds(pl.multiple_of(p % BS // lane * lane, lane), lane)
        go(pltpu.make_async_copy(
            pebuf.at[half, (p - c * T) // BS, :, lanes],
            pe_hbm.at[blk, :, lanes], wsem.at[1]))

    def place_entry(c, half):
        """Select the new entry into chunk c's buffers (an ``iota ==``
        select, via f32: exact for a 16-bit pool) and start the tiles'
        way back."""
        f32 = jnp.float32
        r = wpos_ref[si] - c * T
        at = pl.ds(pl.multiple_of(r // sub * sub, sub), sub)
        rank = cbuf.shape[2]
        hot = jax.lax.broadcasted_iota(
            jnp.int32, (sub, rank), 0) == r % sub
        row = jnp.broadcast_to(cn_ref[pl.ds(si, 1), :], (sub, rank))
        cbuf[half, at, :] = jnp.where(
            hot, row, cbuf[half, at, :].astype(f32)).astype(cbuf.dtype)
        # positions ride the lanes of the rotary key's block and the
        # entry's dr values must ride the sublanes: diag(entry) x (ones
        # on the entry's lane), one exact term a sum, is the column
        g, dr = r // BS, pebuf.shape[2]
        eye = jax.lax.broadcasted_iota(jnp.int32, (dr, dr), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (dr, dr), 1)
        here = jax.lax.broadcasted_iota(jnp.int32, (dr, BS), 1) == r % BS
        diag = jnp.where(eye, jnp.broadcast_to(
            pn_ref[pl.ds(si, 1), :], (dr, dr)), f32(0))
        col = jnp.dot(
            diag.astype(pebuf.dtype),
            jnp.where(here, f32(1), f32(0)).astype(pebuf.dtype),
            precision=(None if pebuf.dtype != f32
                       else jax.lax.Precision.HIGHEST),
            preferred_element_type=f32)
        pebuf[half, g] = jnp.where(
            here, col, pebuf[half, g].astype(f32)).astype(pebuf.dtype)
        entry_copies(c, half, lambda copy: copy.start())

    @pl.when(si == 0)
    def _first():
        # what no copy has written yet must be finite behind its zero
        # weight (the latent is the value operand too); the rotary
        # key's garbage is replaced by the mask itself
        cbuf[...] = jnp.zeros_like(cbuf)
        half_ref[0] = 0
        start_chunk(0, 0, 0)

    # a chunk may reach past the slot's capacity: nothing is live there
    length = jnp.minimum(len_ref[si], MB * BS)
    chunks = (live_blocks(si) + (G - 1)) // G
    half0 = half_ref[0]
    has_next = si + 1 < num_slots
    nxt = jnp.minimum(si + 1, num_slots - 1)
    nt = (((1,), (1,)), ((), ()))                        # a @ b^T
    # only a LIVE entry inside the row is written: a parked or released
    # slot's write position is not its last live one (module docstring)
    placed = write and jnp.logical_and(wpos_ref[si] == len_ref[si] - 1,
                                       len_ref[si] <= MB * BS)

    def chunk(c, carry):
        half = (half0 + c) % 2

        @pl.when(c + 1 < chunks)
        def _():
            start_chunk(si, c + 1, 1 - half)

        @pl.when(jnp.logical_and(c + 1 == chunks, has_next))
        def _():
            start_chunk(nxt, 0, 1 - half)

        wait_chunk(si, c, half)
        if write:
            @pl.when(jnp.logical_and(c + 1 == chunks, placed))
            def _():
                place_entry(c, half)
        lat = cbuf[half]                                 # [T, rank]
        qp = qp_ref[si]
        s = jax.lax.dot_general(ql_ref[si], lat, nt,
                                preferred_element_type=jnp.float32)
        s = s + jnp.concatenate(
            [jnp.dot(qp, pebuf[half, g],                 # [dr, BS]
                     preferred_element_type=jnp.float32)
             for g in range(G)], axis=1)
        s = s * jnp.float32(scale)                       # [nh, T]
        kpos = c * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, jnp.float32(_NEG))
        m_prev = m_ref[...]                              # [nh, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new
        pv = jnp.dot(p.astype(lat.dtype), lat,
                     preferred_element_type=jnp.float32)  # [nh, rank]
        acc_ref[...] = acc_ref[...] * alpha + pv
        return carry

    @pl.when(chunks > 0)
    def _live():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        jax.lax.fori_loop(0, chunks, chunk, 0)
        if write:
            # the tiles are in HBM before this half is a copy's target
            # again, and before the grid step ends
            @pl.when(placed)
            def _():
                entry_copies(chunks - 1, (half0 + chunks - 1) % 2,
                             lambda copy: copy.wait())
        # l >= 1 (the max's own exp term)
        o_ref[si] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(chunks == 0)
    def _idle():
        # nothing live (a released slot): no copy, no arithmetic, a
        # finite row nobody reads; the hand-over still happens
        o_ref[si] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(has_next)
        def _():
            start_chunk(nxt, 0, half0)

    half_ref[0] = (half0 + chunks) % 2


def _mla_paged_decode_32(q_lat, q_pe, c_cache, pe_cache, tables, lengths,
                         scale, new=None, write_pos=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, nh, rank = q_lat.shape
    dr = q_pe.shape[-1]
    BS = c_cache.shape[1]
    MB = tables.shape[1]
    dtype = c_cache.dtype
    G = blocks_per_chunk(BS, rank, dr, MB, dtype)
    write = new is not None
    scalars = [tables.astype(jnp.int32), lengths.astype(jnp.int32)]
    if write:
        scalars.append(write_pos.astype(jnp.int32))

    def whole(shape):
        # q, o and the new entry stay in VMEM for the whole call (a
        # block a grid step would put three small copies' latency into
        # every step)
        return pl.BlockSpec(shape, lambda si, *scalar_refs:
                            (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q_lat, q_pe, c_cache, pe_cache]
    in_specs = [whole(q_lat.shape), whole(q_pe.shape), in_hbm, in_hbm]
    out_shape = [jax.ShapeDtypeStruct((S, nh, rank), jnp.float32)]
    out_specs = [whole((S, nh, rank))]
    scratch = [
        pltpu.VMEM((2, G * BS, rank), dtype),
        pltpu.VMEM((2, G, dr, BS), dtype),
        pltpu.VMEM((nh, rank), jnp.float32),
        pltpu.VMEM((nh, 1), jnp.float32),
        pltpu.VMEM((nh, 1), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((1,), jnp.int32),
    ]
    aliases = {}
    if write:
        # the pools come back, updated in place: operands 2 and 3 after
        # the prefetched scalars, each aliased onto a result
        for at in (2, 3):
            aliases[len(scalars) + at] = len(out_shape)
            out_shape.append(jax.ShapeDtypeStruct(
                operands[at].shape, operands[at].dtype))
            out_specs.append(in_hbm)
        # the entry in f32 (exact): the kernel reads slot si's row of
        # it, which a packed 16-bit tile does not give
        new = [a.astype(jnp.float32) for a in new]
        operands += new
        in_specs += [whole(a.shape) for a in new]
        scratch.append(pltpu.SemaphoreType.DMA((2,)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_mla_decode_kernel, block_size=BS, group=G,
                               scale=float(scale), write=write)
    o, *pools = pl.pallas_call(
        kernel, name="mla_paged_decode_attn", grid_spec=grid_spec,
        out_shape=out_shape, input_output_aliases=aliases,
        # sequential: a slot's last chunk starts the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_FORCE_INTERPRET[0],
    )(*scalars, *operands)
    return (o, tuple(pools)) if write else o


def mla_paged_decode_attn(q_lat, q_pe, c_cache, pe_cache, tables, lengths,
                          scale, new=None, write_pos=None):
    """Absorbed latent attention for all heads of every slot over its
    blocks, read in place. q_lat ``[S, nh, rank]``, q_pe ``[S, nh, dr]``
    in the cache's dtype; c_cache ``[NB, BS, rank]``, pe_cache ``[NB,
    dr, BS]``; tables ``[S, MB]`` physical block ids; positions ``>=
    lengths[s]`` carry exactly zero weight. Returns o_lat ``[S, nh,
    rank]`` f32. Same signature and numbers as
    ``mla_paged_decode_attn_jnp``.

    With ``new`` the kernel places the step's new entry itself before
    it attends (module docstring): ``new = (c_new [S, rank], pe_new [S,
    dr])`` in the cache's dtype, ``write_pos [S]`` the position each
    slot's entry goes to. It is written where that is the slot's last
    live position (``lengths[s] - 1``: ``lengths`` counts it) inside
    the slot's row, and nowhere otherwise (-1: nothing). The entry
    reaches VMEM as two small RESIDENT inputs, widened to f32, held for
    the whole call as ``q_lat`` and ``q_pe`` are (72 KB at 32 slots),
    not as copies a slot. Returns ``(o_lat, (c_cache, pe_cache))``, the
    pools updated in place where the caller donates them."""
    return _trace_32bit(_mla_paged_decode_32)(
        q_lat, q_pe, c_cache, pe_cache, tables, lengths, scale, new=new,
        write_pos=write_pos)


def latent_write_attention(q_lat, q_pe, new, pools, tables, write_pos,
                           lengths, scale, kernel):
    """A decode step's cache write and attention over the latent pool
    (the sibling of ``ops.paged_attention.paged_write_attention``): the
    new entry of each slot (``new = (c [S, rank], k_pe [S, dr])`` in the
    pools' dtype) goes to position ``write_pos[s]``
    (``ops.paged_attention.live_write_pos``) of the slot's table row in
    ``pools = (c_cache, pe_cache)``, then the queries attend over the
    ``lengths`` live positions. Returns ``(o_lat, pools)``.

    ``kernel`` (the engine's choice, ``kernel_viable``): the Pallas
    kernel, which places the entry itself and writes nothing for -1.
    Otherwise the ``jnp`` block write (each slot's block read, given
    its row and set back whole: in place on a donated pool, ISSUE 26),
    then ``mla_paged_decode_attn_jnp``, the parity oracle. There every
    slot writes, and -1 goes to the row's last entry AS A WHOLE (column
    MB-1 AND offset BS-1): private or trash, and behind the length mask
    either way."""
    if kernel:
        return mla_paged_decode_attn(q_lat, q_pe, *pools, tables, lengths,
                                     scale, new=new, write_pos=write_pos)
    cf, pf = pools
    BS = cf.shape[1]
    with device_scope("kv_write"):
        wpos = jnp.where(write_pos < 0,
                         jnp.int32(tables.shape[1] * BS - 1), write_pos)
        fb = jnp.take_along_axis(
            tables, (wpos // jnp.int32(BS))[:, None], axis=1)[:, 0]
        row = (jnp.arange(BS, dtype=jnp.int32)[None, :]
               == (wpos % jnp.int32(BS))[:, None])           # [S, BS]
        cf = cf.at[fb].set(jnp.where(
            row[:, :, None], new[0][:, None, :], cf[fb]))
        # the rotary key's positions are its last axis
        pf = pf.at[fb].set(jnp.where(
            row[:, None, :], new[1][:, :, None], pf[fb]))
    return mla_paged_decode_attn_jnp(q_lat, q_pe, cf, pf, tables, lengths,
                                     scale), (cf, pf)
