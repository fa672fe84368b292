"""Pallas TPU paged decode-attention kernel.

Single-token decode attention computed DIRECTLY over the paged pool
layout (vLLM's PagedAttention idea, SOSP'23, done TPU-natively): q
``[S, nq, hd]``, pooled ``k_cache``/``v_cache``
``[num_blocks, nh, BS, hd]`` (``nh`` KV heads; ``nq = nh`` for a GPT,
a multiple of it for grouped queries), fixed-shape ``block_tables [S,
MB]``, per-slot ``lengths``. The ``[S, nh, MB*BS, hd]`` gathered view the XLA
composition (``ops.attention.cached_paged_attention``) materializes is
never built, and the kernel's work follows each slot's LIVE length,
not its capacity.

How the table drives the DMA schedule: grid ``(S,)``, one step a slot,
with ``block_tables`` and ``lengths`` scalar-prefetched, both pools
left in HBM (``memory_space=ANY``) and q and the output resident in
VMEM for the whole call. A slot's live blocks are walked in
CHUNKS of ``G`` blocks (``blocks_per_chunk``: from the shapes and a
VMEM budget, not an option): one chunk is ``G`` key and ``G`` value
block copies (``make_async_copy``, physical ids from the table) into
one half of a double-buffered ``[2, nh, G*BS, hd]`` VMEM pair, side by
side along the position axis, while the other half is computed. Only
live blocks are copied: a slot with nothing live (length 0: a released
slot) costs its grid step, a parked one (length 1) one block of DMA
and one chunk of arithmetic, a full one ``MB / G`` chunks, and the next
chunk (the next SLOT's first chunk after a slot's last) is always in
flight behind the one being computed.

A chunk's arithmetic is two batched MXU matmuls over the heads:
scores ``q [nh, R, hd] x K [nh, T, hd]^T`` and ``p [nh, R, T] x
V [nh, T, hd]``. The operand tile's ``R`` rows are the query heads of
the KV head's GROUP: one row replicated where ``nq = nh`` (a lane
reduce over ``hd`` on the VPU is what held the block-a-step kernel to a
few percent of the bandwidth), the group's ``nq / nh`` heads, padded to
whole sublane tiles, where queries are grouped (``_group_rows``). Precision is
the oracle's: products of pool-dtype values accumulate in f32, the
softmax is f32, and its f32 weights are NOT rounded to the pool's
dtype for the second matmul: with a 16-bit pool rows ``[0, R/2)`` carry
the weights' upper half (``p`` rounded to the pool dtype) and rows
``[R/2, R)`` the remainder, and the two partial products are added, so
``p @ V`` is f32-grade at one pass over ``V``; an f32 pool multiplies
at ``HIGHEST``.

In-kernel masking mirrors the fallback exactly: key positions
``>= lengths[s]`` (trash-block padding rows, a recycled slot's stale
rows, the tail of a partially-filled block, the part of a chunk's
buffer no copy refreshed) get ``-1e30`` before the f32 online softmax,
so they carry exactly-zero weight. The value buffer is zeroed once a
call, so what lies behind a zero weight is always finite.

A key may be WIDER than its value and come in two parts
(``paged_decode_attention(..., q_rot=, k_rot=)``: ``text.mimo_v2``, keys
of 192 beside values of 128): the part of the value's width in
``k_cache`` as above, the other lanes in a second pool stored
TRANSPOSED, ``[num_blocks, nh, d2, BS]`` (a width that is no multiple of
the 128 lanes rides the sublanes and the block's positions fill the
lanes: nothing is padded in HBM). Its blocks are copied as the planes
of a third chunk buffer ``[2, G, nh, d2, BS]`` and add ``q_rot x
k_rot`` to the scores, which are scaled by the whole key's width. The
same body: without the second part no ref, copy or product of it is
traced, and a call's jaxpr is what it was before there was one.

How the step's new entry is placed (``new=``, ``write_pos=``: what
the decode programs call, ``paged_write_attention``). A slot's new
entry sits at position ``lengths[s] - 1``, in its last live block,
which the slot's LAST chunk brings into ``kbuf`` / ``vbuf`` (/
``k2buf``) anyway. After that chunk's copies are waited for, the kernel
selects the entry into the buffer (an ``iota ==`` select over the one
tile that holds its row, in f32, exact for a 16-bit pool), runs the
chunk's arithmetic on the buffer as for any other chunk, and copies the
tile back to HBM; the pools are the call's aliased outputs
(``input_output_aliases``), so a donated pool carried through a layer
loop is updated in place and no gather, scatter or second read of the
block is left in front of the kernel (PR 43: the ``jnp`` block
read-modify-write moved 30-500 times the bytes that change, at 30-45 %
of the bandwidth). A TILE and not a row: ``BS`` rides the sublanes, two
rows of a 16-bit pool share a 32-bit word, and Mosaic copies whole
tiles, so the write-back is the ``[nh, 16, hd]`` group of rows around
the entry (8 rows of an f32 pool) and, for the transposed second part,
where positions ride the lanes, the 128 lanes around it; its 64
rotated values are turned from lanes onto sublanes by a ``diag(entry) x
onehot`` matmul a head, one exact term a sum. The write-back is started
before the chunk's arithmetic and waited for after it, before its half
of the double buffer can be a copy's target again and before the grid
step ends; the next slot's prefetched first chunk never holds the
block, because a slot's last block is private (``pool.acquire`` asserts
it). Only a LIVE entry is written (``write_pos[s] == lengths[s] - 1``,
``live_write_pos``): a parked or released slot, whose stray row the
``jnp`` write pins to the last entry of its table row or drops in the
trash block, writes nothing, and a slot with nothing live copies
nothing in either direction. Without ``new`` no ref, copy or select of
the write is traced and the call is what it was.

Where it runs: ``kernel_viable`` (backend has Mosaic, shapes tile) is
the only gate; ``ServingEngine`` asks it once at build time and the
paged decode programs use the kernel wherever it says yes. The
CPU and refused shapes keep ``write_block_rows`` in front of
``cached_paged_attention``, which is also the parity oracle; tests
flip ``_FORCE_INTERPRET`` to run the real kernel in interpret mode on
the CPU.
"""
import functools
import math

import jax
import jax.numpy as jnp

from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the kernel in interpret mode on CPU
_FORCE_INTERPRET = [False]
_NEG = -1e30
# both pools' double-buffered chunks. At 16 heads x 128 in bf16 this is
# G = 8 blocks of 16 = 128 positions a chunk: the smallest that runs a
# full slot at the rate of larger ones (82 % of 819 GB/s; 65 % at G = 4)
# and cheaper than G = 16 for a slot with one live block, whose chunk
# is computed whole (2.3 us against 3.0) (my chip runs, PR 29)
_CHUNK_VMEM_BYTES = 2 << 20


def _interpret():
    return _FORCE_INTERPRET[0]


def kernel_viable(num_heads, head_dim, block_size, dtype, rot_dim=0):
    """Shape/dtype/backend guard (the ``_use_pallas`` discipline).
    Static facts only, so the engine can resolve the active decode
    layout once at build time and bind it to the roofline. ``rot_dim``:
    lanes of the key beyond ``head_dim``, kept in a transposed pool of
    their own (module docstring)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                     jnp.dtype(jnp.float16)):
        return False  # f64 cannot lower on Mosaic
    if _FORCE_INTERPRET[0]:
        return True   # interpret mode handles any shape
    if jax.default_backend() == "cpu":
        return False
    # a block lands in the chunk buffer at a multiple of BS along the
    # sublane dim, so BS must be whole tiles, and Mosaic copies into
    # such a slice only where hd fills the lanes; a chunk holds at least
    # one block
    sub = 8 if dtype == jnp.dtype(jnp.float32) else 16
    # the transposed part: its width on the sublanes, a block's
    # positions whole lane tiles (the planes sit side by side on the
    # lanes of the scores)
    if rot_dim and (rot_dim % sub or block_size % 128):
        return False
    return (block_size % sub == 0 and head_dim % 128 == 0
            and blocks_per_chunk(num_heads, head_dim, block_size, 1,
                                 dtype, rot_dim) == 1)


def blocks_per_chunk(num_heads, head_dim, block_size, max_blocks, dtype,
                     rot_dim=0):
    """``G``: how many blocks one chunk holds. As many as keep the
    chunk buffers (K and V, and a key's second part, two halves each)
    inside the budget, at most a slot's capacity; 0 where not even one
    block fits."""
    row = 2 * head_dim + rot_dim
    block = num_heads * block_size * row * jnp.dtype(dtype).itemsize
    return int(min(max_blocks, _CHUNK_VMEM_BYTES // (2 * block)))


def _paged_decode_kernel(bt_ref, len_ref, *refs, block_size, group,
                         q_group=1, rot=False, write=False):
    """Grid (S,), sequential. ``kbuf``/``vbuf`` ``[2, nh, G*BS, hd]``
    are the two halves of the chunk buffers, ``sem[0/1, half]`` the K/V
    copies' semaphores, ``half_ref`` (SMEM) the half that holds this
    slot's first chunk: the previous grid step started its copies.
    With ``rot`` the key's second part rides along: ``q2_ref`` (the
    queries' lanes for it, laid out like ``q_ref``), its pool ``k2_hbm``
    and ``k2buf [2, G, nh, d2, BS]``, copies on ``sem[2, half]``.
    With ``write`` the step's new entry is placed (module docstring):
    ``wpos_ref`` (a third prefetched scalar a slot), ``nk_ref`` /
    ``nv_ref`` ``[S, nh, hd]`` (/ ``nk2_ref [S, nh, d2]``) resident like
    ``q_ref``, the pools the call's aliased OUTPUTS (read and written
    through the one ref), the write-backs on ``wsem``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    it = iter(refs)
    wpos_ref = next(it) if write else None
    q_ref, k_hbm, v_hbm = next(it), next(it), next(it)
    q2_ref, k2_hbm = (next(it), next(it)) if rot else (None, None)
    nk_ref, nv_ref = (next(it), next(it)) if write else (None, None)
    nk2_ref = next(it) if write and rot else None
    o_ref = next(it)
    if write:
        # the aliased outputs: the same HBM as the inputs, read and
        # written through the one ref
        k_hbm, v_hbm = next(it), next(it)
        k2_hbm = next(it) if rot else None
    kbuf, vbuf, q_rows, acc_ref, m_ref, l_ref, sem, half_ref = (
        next(it) for _ in range(8))
    k2buf = next(it) if rot else None
    wsem = next(it) if write else None
    BS, G, MB = block_size, group, bt_ref.shape[1]
    T = G * BS
    si = pl.program_id(0)
    num_slots = pl.num_programs(0)
    nh, rows, hd = q_rows.shape
    width = hd + (k2buf.shape[3] if rot else 0)   # the whole key's
    split = kbuf.dtype != jnp.float32   # 16-bit pool: p as upper + rest

    def live_blocks(s):
        return jnp.clip((len_ref[s] + (BS - 1)) // BS, 0, MB)

    def chunk_copies(s, c, half, go):
        """``go`` (start or wait) the K and V copies of the live blocks
        of slot s's chunk c, block g to positions [g*BS, (g+1)*BS)."""
        def block(g, carry):
            blk = bt_ref[s, c * G + g]
            dst = pl.ds(pl.multiple_of(g * BS, BS), BS)
            go(pltpu.make_async_copy(
                k_hbm.at[blk], kbuf.at[half, :, dst, :], sem.at[0, half]))
            go(pltpu.make_async_copy(
                v_hbm.at[blk], vbuf.at[half, :, dst, :], sem.at[1, half]))
            if rot:
                go(pltpu.make_async_copy(
                    k2_hbm.at[blk], k2buf.at[half, g], sem.at[2, half]))
            return carry
        jax.lax.fori_loop(
            0, jnp.clip(live_blocks(s) - c * G, 0, G), block, 0)

    def start_chunk(s, c, half):
        chunk_copies(s, c, half, lambda copy: copy.start())

    def wait_chunk(s, c, half):
        chunk_copies(s, c, half, lambda copy: copy.wait())

    # the step's new entry (``write``): position length - 1, which the
    # slot's LAST chunk holds at buffer row ``length - 1 - c * T``.
    # Rows of a K/V tile, lanes of a transposed tile (whole ones on a
    # chip: ``kernel_viable``; interpret mode takes smaller blocks)
    sub = math.gcd(BS, 8 if kbuf.dtype == jnp.float32 else 16)
    lane = math.gcd(BS, 128)

    def entry_copies(c, half, go):
        """``go`` (start or wait) the copies back to HBM of the tiles
        of chunk c's buffers that hold this slot's new entry: the
        ``sub`` rows around it of K and V, the 128 lanes around it of
        the transposed part."""
        p = len_ref[si] - 1
        blk = bt_ref[si, p // BS]
        src = pl.ds(pl.multiple_of((p - c * T) // sub * sub, sub), sub)
        dst = pl.ds(pl.multiple_of(p % BS // sub * sub, sub), sub)
        go(pltpu.make_async_copy(
            kbuf.at[half, :, src, :], k_hbm.at[blk, :, dst, :], wsem.at[0]))
        go(pltpu.make_async_copy(
            vbuf.at[half, :, src, :], v_hbm.at[blk, :, dst, :], wsem.at[1]))
        if rot:
            lanes = pl.ds(pl.multiple_of(p % BS // lane * lane, lane), lane)
            go(pltpu.make_async_copy(
                k2buf.at[half, (p - c * T) // BS, :, :, lanes],
                k2_hbm.at[blk, :, :, lanes], wsem.at[2]))

    def place_entry(c, half):
        """Select the new entry into chunk c's buffers (an ``iota ==``
        select over the one tile that holds its row, via f32: exact for
        a 16-bit pool) and start the tiles' way back."""
        f32 = jnp.float32
        r = len_ref[si] - 1 - c * T
        at = pl.ds(pl.multiple_of(r // sub * sub, sub), sub)
        hot = jax.lax.broadcasted_iota(
            jnp.int32, (nh, sub, hd), 1) == r % sub
        for buf, new_ref in ((kbuf, nk_ref), (vbuf, nv_ref)):
            new = jnp.broadcast_to(
                new_ref[si].astype(f32)[:, None, :], (nh, sub, hd))
            buf[half, :, at, :] = jnp.where(
                hot, new, buf[half, :, at, :].astype(f32)).astype(buf.dtype)
        if rot:
            # positions ride the lanes here and the entry's d2 values
            # must ride the sublanes: diag(entry) x (ones on the
            # entry's lane), one exact term a sum, is the column. A
            # head at a time: plain 2-D tiles
            g, d2 = r // BS, k2buf.shape[3]
            eye = jax.lax.broadcasted_iota(jnp.int32, (d2, d2), 0) \
                == jax.lax.broadcasted_iota(jnp.int32, (d2, d2), 1)
            here = jax.lax.broadcasted_iota(
                jnp.int32, (d2, BS), 1) == r % BS
            ones = jnp.where(here, f32(1), f32(0)).astype(k2buf.dtype)
            for n in range(nh):
                diag = jnp.where(eye, jnp.broadcast_to(
                    nk2_ref[si, n:n + 1, :], (d2, d2)), f32(0))
                col = jnp.dot(diag.astype(k2buf.dtype), ones,
                              precision=prec, preferred_element_type=f32)
                k2buf[half, g, n] = jnp.where(
                    here, col, k2buf[half, g, n].astype(f32)).astype(
                        k2buf.dtype)
        entry_copies(c, half, lambda copy: copy.start())

    @pl.when(si == 0)
    def _first():
        # what no copy has written yet must be finite behind its zero
        # weight; K's garbage is replaced by the mask itself
        vbuf[...] = jnp.zeros_like(vbuf)
        half_ref[0] = 0
        start_chunk(0, 0, 0)

    length = len_ref[si]
    chunks = (live_blocks(si) + (G - 1)) // G
    half0 = half_ref[0]
    has_next = si + 1 < num_slots
    nxt = jnp.minimum(si + 1, num_slots - 1)
    nt = (((2,), (2,)), ((0,), (0,)))      # [nh,R,hd] x [nh,T,hd]^T
    nn = (((2,), (1,)), ((0,), (0,)))      # [nh,R,T]  x [nh,T,hd]
    prec = None if split else jax.lax.Precision.HIGHEST
    # only a LIVE entry is written: a parked or released slot's write
    # position is not its last live one (module docstring)
    placed = write and wpos_ref[si] == length - 1

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
        s = jax.lax.dot_general(q_rows[...], kbuf[half], nt,
                                precision=prec,
                                preferred_element_type=jnp.float32)
        if rot:
            q2 = q2_ref[si]                           # [nh, R, d2]
            s = s + jnp.concatenate(
                [jax.lax.dot_general(
                    q2, k2buf[half, g], nn, precision=prec,
                    preferred_element_type=jnp.float32)
                 for g in range(G)], axis=2)
        s = s / jnp.sqrt(jnp.float32(width))          # [nh, R, T]
        kpos = c * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos < length, s, jnp.float32(_NEG))
        m_prev = m_ref[...]                           # [nh, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2,
                                                  keepdims=True)
        m_ref[...] = m_new
        if split:
            hi = p.astype(vbuf.dtype)
            lo = (p - hi.astype(jnp.float32)).astype(vbuf.dtype)
            upper = jax.lax.broadcasted_iota(
                jnp.int32, p.shape, 1) < rows // 2
            p = jnp.where(upper, hi, lo)
        pv = jax.lax.dot_general(p, vbuf[half], nn, precision=prec,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        return carry

    @pl.when(chunks > 0)
    def _live():
        if q_group > 1:
            # the group's query heads, laid out by the wrapper
            q_rows[...] = q_ref[si]
        else:
            # the one query row, replicated to the operand tile's rows:
            # via f32 (Mosaic has no 16-bit [nh,hd] -> [nh,1,hd] shape
            # cast) and through VMEM (its batched matmul cannot take the
            # broadcast's replicated layout as an operand:
            # apply-vector-layout aborts)
            q_rows[...] = jnp.broadcast_to(
                q_ref[si].astype(jnp.float32)[:, None, :],
                (nh, rows, hd)).astype(q_rows.dtype)
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
        acc, l = acc_ref[...], l_ref[...]
        if split:
            acc = acc[:, :rows // 2] + acc[:, rows // 2:]
        if q_group > 1:
            # l >= 1 (the max's own exp term); a row a query head
            o_ref[si] = (acc / l[:, :acc.shape[1]]).astype(o_ref.dtype)
        else:
            # every row of acc is the same
            o_ref[si] = (jnp.max(acc, axis=1)
                         / jnp.max(l, axis=1)).astype(o_ref.dtype)

    @pl.when(chunks == 0)
    def _idle():
        # nothing live (a released slot): no copy, no arithmetic, a
        # finite row nobody reads; the hand-over still happens
        o_ref[si] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(has_next)
        def _():
            start_chunk(nxt, 0, half0)

    half_ref[0] = (half0 + chunks) % 2


def _group_rows(q_group, dtype):
    """(rows of the operand tile, rows that are distinct query heads):
    the group padded to whole 8-row tiles, twice over for a 16-bit pool
    (whose two halves carry the softmax weights' two parts)."""
    heads = -(-q_group // 8) * 8
    return (heads if dtype == jnp.float32 else 2 * heads), heads


def _paged_decode_32(q, k_cache, v_cache, block_tables, lengths,
                     q_rot=None, k_rot=None, new=None, write_pos=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, nq, hd = q.shape
    nh, BS = k_cache.shape[1:3]
    MB = block_tables.shape[1]
    dtype = k_cache.dtype
    q_group = nq // nh
    rot = k_rot is not None
    write = new is not None
    d2 = k_rot.shape[2] if rot else 0
    if rot and q_group == 1:
        raise NotImplementedError(
            "a key in two parts is brought for grouped queries only")
    G = blocks_per_chunk(nh, hd, BS, MB, dtype, d2)
    # the operand tile's sublanes: 8 of f32, 16 of a 16-bit type (whose
    # two halves carry the softmax weights' two parts)
    rows, heads = _group_rows(q_group, dtype)
    scalars = [block_tables.astype(jnp.int32), lengths.astype(jnp.int32)]
    if write:
        scalars.append(write_pos.astype(jnp.int32))

    def group_tile(q):
        # [S, nh, rows, .]: a KV head's query heads as the tile's rows
        q = q.reshape(S, nh, q_group, q.shape[-1])
        q = jnp.pad(q, ((0, 0), (0, 0), (0, heads - q_group), (0, 0)))
        return jnp.concatenate([q, q], axis=2) if rows > heads else q

    if q_group > 1:
        q = group_tile(q)
        o_shape = (S, nh, heads, hd)
    else:
        o_shape = (S, nh, hd)

    def resident(shape):
        # q, o and the new entry stay in VMEM for the whole call (a
        # block a grid step would put two small copies' latency into
        # every step, which is most of a step that has little or
        # nothing live)
        return pl.BlockSpec(shape, lambda si, *scalar_refs:
                            (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands = [q, k_cache, v_cache]
    in_specs = [resident(q.shape), in_hbm, in_hbm]
    scratch = [
        pltpu.VMEM((2, nh, G * BS, hd), dtype),
        pltpu.VMEM((2, nh, G * BS, hd), dtype),
        pltpu.VMEM((nh, rows, hd), dtype),
        pltpu.VMEM((nh, rows, hd), jnp.float32),
        pltpu.VMEM((nh, rows, 1), jnp.float32),
        pltpu.VMEM((nh, rows, 1), jnp.float32),
        pltpu.SemaphoreType.DMA((3 if rot else 2, 2)),
        pltpu.SMEM((1,), jnp.int32),
    ]
    kernel = functools.partial(_paged_decode_kernel, block_size=BS,
                               group=G, q_group=q_group, rot=rot,
                               write=write)
    if rot:
        q2 = group_tile(q_rot)
        operands += [q2, k_rot]
        in_specs += [resident(q2.shape), in_hbm]
        scratch.append(pltpu.VMEM((2, G, nh, d2, BS), dtype))
    out_shape = [jax.ShapeDtypeStruct(o_shape, q.dtype)]
    out_specs = [resident(o_shape)]
    aliases = {}
    if write:
        # the pools come back, updated in place: operands 1, 2 (and 4)
        # after the prefetched scalars, each aliased onto a result
        for at in (1, 2, 4)[:2 + rot]:
            aliases[len(scalars) + at] = len(out_shape)
            out_shape.append(jax.ShapeDtypeStruct(
                operands[at].shape, operands[at].dtype))
            out_specs.append(in_hbm)
        # the second part's entry in f32 (exact): the kernel reads it a
        # row at a time, which a packed 16-bit tile does not give
        new = list(new[:2]) + [a.astype(jnp.float32) for a in new[2:]]
        operands += new
        in_specs += [resident(a.shape) for a in new]
        scratch.append(pltpu.SemaphoreType.DMA((2 + rot,)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    o, *pools = pl.pallas_call(
        kernel, name="paged_decode_attn", grid_spec=grid_spec,
        out_shape=out_shape, input_output_aliases=aliases,
        # sequential: a slot's last chunk starts the next slot's first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(*scalars, *operands)
    if q_group > 1:
        o = o[:, :, :q_group].reshape(S, nq, hd)
    return (o, tuple(pools)) if write else o


def paged_decode_attention(q, k_cache, v_cache, block_tables, lengths,
                           q_rot=None, k_rot=None, new=None,
                           write_pos=None):
    """Drop-in for ``ops.attention.cached_paged_attention`` (same
    signature, same numbers for every slot with something live) reading
    the live K/V blocks in place; a slot of length 0 gets a row of
    zeros where the oracle averages garbage, and nobody reads either.
    ``q_rot [S, nq, d2]``, ``k_rot [num_blocks, nh, d2, BS]``: the
    second part of a key wider than its value (module docstring).

    With ``new`` the kernel places the step's new entry itself before
    it attends (module docstring): ``new = (new_k, new_v)`` ``[S, nh,
    hd]`` in the pools' dtype (and ``new_k_rot [S, nh, d2]`` third for a
    key in two parts), ``write_pos [S]`` the position each slot's entry
    goes to. It is written where that is the slot's last live position
    (``lengths[s] - 1``: ``lengths`` counts it) and nowhere otherwise.
    Returns ``(o, pools)``, the pools in the order given (``k_cache``,
    ``v_cache``, then ``k_rot``), updated in place where the caller
    donates them.

    Callers check ``kernel_viable`` first; ``cached_paged_attention`` is
    the parity oracle."""
    # x64 guard shared by every Pallas entry point (pallas_compat)
    second = () if k_rot is None else (q_rot, k_rot)
    return _trace_32bit(_paged_decode_32)(
        q, k_cache, v_cache, block_tables, lengths, *second, new=new,
        write_pos=write_pos)


def write_block_rows(pool, new, blocks, offsets, axis=2):
    """Put ``new[s]`` at row ``offsets[s]`` (along ``axis``, the block's
    positions) of block ``blocks[s]`` of ``pool`` by whole-block
    read-modify-write: gather the S blocks, select the row in, scatter
    the blocks back. A scatter whose window is every trailing dimension
    leaves the pool's layout row-major from parameter to result, so a
    donated pool carried through a layer loop is updated in place (the
    row scatter ``at[blocks, :, offsets]`` made XLA relayout the whole
    pool around the loop: ISSUE 26). Safe because a decode step's write
    blocks are private to their slots; the only duplicates are parked
    or released slots meeting in the trash block, where any winner is
    garbage behind the length mask."""
    hot = jnp.arange(pool.shape[axis], dtype=jnp.int32) \
        == offsets[:, None]                                   # [S, BS]
    hot = jnp.expand_dims(hot, [a for a in (1, 2, 3) if a != axis])
    return pool.at[blocks].set(jnp.where(
        hot, jnp.expand_dims(new.astype(pool.dtype), axis), pool[blocks]))


def live_write_pos(pos, lengths):
    """Where each slot's new entry goes: ``pos[s]`` where that is the
    slot's last live position (``lengths[s] - 1``: ``lengths`` counts
    the entry), -1 where it is not. A parked slot (its position past
    what its row holds) and a released one (nothing live) write
    nothing anybody reads."""
    return jnp.where(pos + 1 == lengths.astype(jnp.int32), pos,
                     jnp.int32(-1)).astype(jnp.int32)


def paged_write_attention(q, new, pools, block_tables, write_pos, lengths,
                          kernel, q_rot=None):
    """A decode step's cache write and attention, what every paged
    decode program does with its pools: the new entry of each slot
    (``new = (k, v)`` ``[S, nh, hd]``, and ``k_rot [S, nh, d2]`` third
    for a key in two parts) goes to position ``write_pos[s]``
    (``live_write_pos``) of the slot's table row in ``pools =
    (k_cache, v_cache[, k_rot])``, then q attends over the ``lengths``
    live positions. Returns ``(o, pools)``.

    ``kernel`` (the engine's choice, ``kernel_viable``): the Pallas
    kernel, which places the entry itself and writes nothing for -1.
    Otherwise the ``jnp`` block write, then ``cached_paged_attention``,
    the parity oracle. There every slot writes, and -1 goes to the
    row's last entry AS A WHOLE (column MB-1 AND offset BS-1): private
    or trash, and behind the length mask either way. A position counting
    on past the row, clamped by its column alone, would spray a parked
    slot's stray entry over block MB-1 as ``pos % BS`` cycles."""
    from ..profiler import device_scope
    from . import attention as attn_ops
    second = () if q_rot is None else (q_rot, pools[2])
    if kernel:
        return paged_decode_attention(
            q, pools[0], pools[1], block_tables, lengths, *second,
            new=new, write_pos=write_pos)
    BS = pools[0].shape[2]
    with device_scope("kv_write"):
        wpos = jnp.where(write_pos < 0,
                         jnp.int32(block_tables.shape[1] * BS - 1),
                         write_pos)
        blocks = jnp.take_along_axis(
            block_tables, (wpos // jnp.int32(BS))[:, None], axis=1)[:, 0]
        off = wpos % jnp.int32(BS)
        # the transposed part's positions are its last axis
        pools = tuple(write_block_rows(pool, entry, blocks, off,
                                       axis=3 if i == 2 else 2)
                      for i, (pool, entry) in enumerate(zip(pools, new)))
    second = () if q_rot is None else (q_rot, pools[2])
    return attn_ops.cached_paged_attention(
        q, pools[0], pools[1], block_tables, lengths, *second), pools
