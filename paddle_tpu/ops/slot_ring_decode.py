"""Pallas TPU decode kernel for a WINDOW attention layer whose cache is
a per-slot RING (``text.mimo_v2``: ``serving/paged/mixed_programs.py``
``PagedAccess.win_decode``). Not ``ops/ring_attention.py``, which is
sequence-parallel attention around a ring of DEVICES; the ring here is a
slot's last ``W`` positions, entry ``t % W`` holding position ``t``.

One call a window layer over all ``S`` slots: q ``[S, nq, hd]``, the
step's new key ``[S, nkv, hd]`` and value ``[S, nkv, dv]``, and the
WHOLE rings as the decode program carries them, ``kring [Lw, S, nkv,
hd, W]`` (keys TRANSPOSED: a width of 192 rides the sublanes, the ring's
entries fill the lanes, nothing is padded) and ``vring [Lw, S, nkv, W,
dv]``, left in HBM (``memory_space=ANY``) and aliased onto the call's
results (``input_output_aliases``). The layer ``wi`` and ``write_entry
[S]`` are scalar-prefetched: nothing of a ring is sliced out before the
call or put back after it, and a donated ring carried through a layer
loop is updated in place (the ``jnp`` formulation moved a layer's whole
key ring and value ring twice to write ``S`` entries and read them a
third time to attend: 27 % of the bytes' bound, ledger, PR 45).

Grid ``(S / P,)``, sequential, ``P`` slots a step
(``slots_per_step``). The step's rings come into one half of a
double-buffered VMEM pair by copies (``make_async_copy``) that the
PREVIOUS step started, the ``P`` slots side by side on the batch axis,
so the next step's ``P`` x 640 KB are in flight behind the one being
computed. Then, in VMEM:

* each slot's new entry is selected into its rings, entry
  ``write_entry[s]`` (``pos % W``). The value's is a ROW: an ``iota ==``
  select over the one tile of rows that holds it, via f32 (exact for a
  16-bit ring). The key's is a LANE COLUMN of every tile of the
  transposed ring. The wrapper hands the new keys over transposed as
  well, ``[S / T, nkv, hd, T]`` with a slot a lane (``T`` = 128), so a
  lane ROTATION by ``write_entry - s`` puts slot ``s``'s key on the
  entry's lane with ``hd`` already on the sublanes, and a select over
  the 128 lanes around the entry takes it in: no transpose in the
  kernel. (Mosaic rotates no 16-bit array: a 16-bit key travels two
  rows a 32-bit word, as the ring's tiles pack them);
* the tiles that changed start their way back to HBM: the value's
  ``[nkv, 16, dv]`` rows (8 of an f32 ring) and the key's ``[nkv, hd,
  128]`` lanes around the entry, which for ``W = 128`` is the slot's
  whole key ring (one lane column of a tile is no copy Mosaic makes;
  PERF.md, PR 46, has what the row-major layouts read). They are waited
  for before their half of the double buffer is a copy's target again,
  one step later, behind that step's arithmetic;
* the ``nq / nkv`` query heads of each KV head attend the ``W``
  entries: scores ``q [b, R, hd] x K [b, hd, W]`` in f32, entries the
  sequence has not reached masked (``held [S, W]``, the position each
  entry holds, negative where there is none: the caller's
  ``text.mimo_v2.ring_positions``, so prefill, the ``jnp`` step and
  this kernel see a ring through ONE rule), the layer's ``sink`` one
  more softmax column that carries no value
  (``ops.attention.softmax_with_sink``), and ``p @ V`` at f32 grade in
  one pass over a 16-bit ring: rows ``[0, R/2)`` of the operand carry
  the weights' upper half, rows ``[R/2, R)`` their remainder, and the
  two partial products are added (``ops.paged_attention`` does the
  same); an f32 ring multiplies at ``HIGHEST``.

``write_entry[s] == -1`` (a slot parked between the chunks of its
prefill, a released slot) selects nothing and copies nothing back: the
rings keep what they held, bit for bit. Such a slot still reads its
rings and attends (the ``jnp`` formulation does too; nobody reads the
row).

Where it runs: ``kernel_viable`` is the only gate, asked once when the
programs are built (``mixed_programs.decode_kernels``). The CPU keeps
the ``jnp`` formulation in ``win_decode``, which is also the parity
oracle; tests flip ``_FORCE_INTERPRET`` to run the kernel in interpret
mode.
"""
import functools
import math

import jax
import jax.numpy as jnp

from .paged_attention import _group_rows
from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the kernel in interpret mode on CPU
_FORCE_INTERPRET = [False]
_NEG = -1e30
# the rings of a step's slots, two halves each
_RING_VMEM_BYTES = 8 << 20


def slot_ring_bytes(num_kv_heads, head_dim, v_head_dim, window, dtype):
    """A slot's key ring and value ring of one layer."""
    return num_kv_heads * window * (head_dim + v_head_dim) \
        * jnp.dtype(dtype).itemsize


def kernel_viable(num_kv_heads, head_dim, v_head_dim, window, dtype):
    """Shape/dtype/backend guard. Static facts only."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                     jnp.dtype(jnp.float16)):
        return False  # f64 cannot lower on Mosaic
    if _FORCE_INTERPRET[0]:
        return True   # interpret mode handles any shape
    if jax.default_backend() == "cpu":
        return False
    # the entries fill the key ring's lanes and the value ring's
    # sublanes, a key's width rides the sublanes, a value's the lanes
    sub = 8 if dtype == jnp.dtype(jnp.float32) else 16
    return (window % 128 == 0 and v_head_dim % 128 == 0
            and head_dim % sub == 0
            and slots_per_step(1, slot_ring_bytes(
                num_kv_heads, head_dim, v_head_dim, window, dtype)) == 1)


def slots_per_step(num_slots, ring_bytes):
    """``P``: how many slots one grid step takes (their rings side by
    side on the batch axis of one matmul): as many of 4, 2, 1 as divide
    the slots and keep two halves of the buffers inside the budget; 0
    where not even one slot's rings fit."""
    for p in (4, 2, 1):
        if num_slots % p == 0 and 2 * p * ring_bytes <= _RING_VMEM_BYTES:
            return p
    return 0


def _ring_decode_kernel(wi_ref, ent_ref, *refs, sink, group):
    """``kbuf [2, P * nkv, hd, W]`` / ``vbuf [2, P * nkv, W, dv]``: the
    two halves of the ring buffers, the step's ``P`` slots side by side
    on the batch axis; ``rsem[k/v, half]`` the reads' semaphores,
    ``wsem[k/v, half]`` the write-backs'."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    it = iter(refs)
    q_ref, nk_ref, nv_ref, held_ref = next(it), next(it), next(it), next(it)
    sink_ref = next(it) if sink else None
    next(it), next(it)          # the rings as inputs: the same HBM as
    o_ref, k_hbm, v_hbm = next(it), next(it), next(it)   # the outputs
    kbuf, vbuf, rsem, wsem = next(it), next(it), next(it), next(it)
    f32 = jnp.float32
    P = group
    gi, steps = pl.program_id(0), pl.num_programs(0)
    half = gi % 2
    wi = wi_ref[0]
    nkv = kbuf.shape[1] // P
    hd, W = kbuf.shape[2:]
    dv = vbuf.shape[3]
    rows = q_ref.shape[2]
    split = kbuf.dtype != f32          # 16-bit ring: p as upper + rest
    prec = None if split else jax.lax.Precision.HIGHEST
    # rows of a value tile, lanes of a key tile (whole ones on a chip:
    # ``kernel_viable``; interpret mode takes smaller rings)
    sub = math.gcd(W, 8 if kbuf.dtype == f32 else 16)
    lane = nk_ref.shape[2]    # the wrapper's: gcd(W, 128)

    def ring_copies(g, h, go):
        for j in range(P):
            mine = pl.ds(j * nkv, nkv)
            go(pltpu.make_async_copy(k_hbm.at[wi, g * P + j],
                                     kbuf.at[h, mine], rsem.at[0, h]))
            go(pltpu.make_async_copy(v_hbm.at[wi, g * P + j],
                                     vbuf.at[h, mine], rsem.at[1, h]))

    def entry_copies(g, j, h, go):
        """``go`` (start or wait) the copies back to HBM of the tiles
        that hold the new entry of step g's slot j."""
        e = ent_ref[g * P + j]
        mine = pl.ds(j * nkv, nkv)
        lanes = pl.ds(pl.multiple_of(e // lane * lane, lane), lane)
        at = pl.ds(pl.multiple_of(e // sub * sub, sub), sub)
        go(pltpu.make_async_copy(kbuf.at[h, mine, :, lanes],
                                 k_hbm.at[wi, g * P + j, :, :, lanes],
                                 wsem.at[0, h]))
        go(pltpu.make_async_copy(vbuf.at[h, mine, at, :],
                                 v_hbm.at[wi, g * P + j, :, at, :],
                                 wsem.at[1, h]))

    @pl.when(gi == 0)
    def _first():
        ring_copies(0, 0, lambda copy: copy.start())

    # the last step's tiles are in HBM before its half is a copy's
    # target again
    last = jnp.maximum(gi - 1, 0)
    for j in range(P):
        @pl.when(jnp.logical_and(gi >= 1, ent_ref[last * P + j] >= 0))
        def _(j=j):
            entry_copies(last, j, 1 - half, lambda copy: copy.wait())

    @pl.when(gi + 1 < steps)
    def _():
        ring_copies(gi + 1, 1 - half, lambda copy: copy.start())

    ring_copies(gi, half, lambda copy: copy.wait())

    for j in range(P):
        entry = ent_ref[gi * P + j]

        @pl.when(entry >= 0)
        def _place(j=j, entry=entry):
            mine = pl.ds(j * nkv, nkv)
            # the key: the slot's lane of the transposed new keys
            # rotated onto the entry's lane, selected into the lanes
            # around it (in 32-bit words where the ring is 16-bit)
            lanes = pl.ds(pl.multiple_of(entry // lane * lane, lane), lane)
            new = pltpu.roll(
                nk_ref[...],
                (entry % lane - (gi * P + j) % lane + lane) % lane, 2)
            hot = jax.lax.broadcasted_iota(
                jnp.int32, new.shape, 2) == entry % lane
            old = kbuf[half, mine, :, lanes]
            if split:
                old = pltpu.bitcast(old, jnp.uint32)
            new = jnp.where(hot, new, old)
            if split:
                new = pltpu.bitcast(new, kbuf.dtype)
            kbuf[half, mine, :, lanes] = new
            # the value: a row of the one tile that holds it
            at = pl.ds(pl.multiple_of(entry // sub * sub, sub), sub)
            hot = jax.lax.broadcasted_iota(
                jnp.int32, (nkv, sub, dv), 1) == entry % sub
            new = jnp.broadcast_to(nv_ref[j][:, None, :], (nkv, sub, dv))
            vbuf[half, mine, at, :] = jnp.where(
                hot, new,
                vbuf[half, mine, at, :].astype(f32)).astype(vbuf.dtype)
            entry_copies(gi, j, half, lambda copy: copy.start())

    nn = (((2,), (1,)), ((0,), (0,)))      # [b,R,a] x [b,a,c]
    s = jax.lax.dot_general(q_ref[...].reshape(P * nkv, rows, hd),
                            kbuf[half], nn, precision=prec,
                            preferred_element_type=f32) \
        * f32(float(hd) ** -0.5)                        # [P*nkv, R, W]
    # entries the slot's sequence has not reached are unseen
    held = jnp.concatenate(
        [jnp.broadcast_to(held_ref[j], (nkv, rows, W)) for j in range(P)],
        axis=0)
    s = jnp.where(held >= 0, s, f32(_NEG))
    m = jnp.max(s, axis=2, keepdims=True)
    if sink:
        m = jnp.maximum(m, sink_ref[...])
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=2, keepdims=True)
    if sink:
        den = den + jnp.exp(sink_ref[...] - m)
    p = p / den
    if split:
        hi = p.astype(vbuf.dtype)
        lo = (p - hi.astype(f32)).astype(vbuf.dtype)
        upper = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) < rows // 2
        p = jnp.where(upper, hi, lo)
    o = jax.lax.dot_general(p, vbuf[half], nn, precision=prec,
                            preferred_element_type=f32)  # [P*nkv, R, dv]
    if split:
        o = o[:, :rows // 2] + o[:, rows // 2:]
    o_ref[...] = o.reshape(o_ref.shape)

    for j in range(P):
        @pl.when(jnp.logical_and(gi + 1 == steps,
                                 ent_ref[gi * P + j] >= 0))
        def _(j=j):
            entry_copies(gi, j, half, lambda copy: copy.wait())


def _ring_decode_32(q, new_k, new_v, kring, vring, wi, held, write_entry,
                    sink):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, nq, hd = q.shape
    nkv, W = kring.shape[2], kring.shape[4]
    dv = vring.shape[4]
    dtype = kring.dtype
    if q.dtype != dtype:
        raise ValueError(
            f"ring_decode_attn takes queries in the rings' dtype (the "
            f"model casts them to the cache's): got {q.dtype} beside "
            f"{dtype}")
    g = nq // nkv
    rows, heads = _group_rows(g, dtype)
    lane = math.gcd(W, 128)
    # a step's slots share a lane tile of the new keys
    P = math.gcd(slots_per_step(
        S, slot_ring_bytes(nkv, hd, dv, W, dtype)), lane)
    f32 = jnp.float32

    def group_tile(a):
        # [., nkv, rows, .]: a KV head's query heads as the tile's rows
        a = a.reshape((a.shape[0], nkv, g) + a.shape[2:])
        a = jnp.pad(a, ((0, 0), (0, 0), (0, heads - g))
                    + ((0, 0),) * (a.ndim - 3))
        return jnp.concatenate([a, a], axis=2) if rows > heads else a

    # the new keys with a slot a lane: [S / lane, nkv, hd, lane]
    tiles = -(-S // lane)
    nk = new_k.astype(dtype)
    if dtype.itemsize == 2:
        # two rows of the transposed ring a 32-bit word, the even one low
        nk = jax.lax.bitcast_convert_type(
            nk.reshape(S, nkv, hd // 2, 2), jnp.uint32)
    nk = jnp.pad(nk, ((0, tiles * lane - S), (0, 0), (0, 0)))
    nk = nk.reshape((tiles, lane) + nk.shape[1:]).transpose(0, 2, 3, 1)
    scalars = [jnp.asarray(wi, jnp.int32).reshape(1),
               write_entry.astype(jnp.int32)]

    def a_step(shape):
        # the step's P slots
        return pl.BlockSpec((P,) + shape, lambda gi, *scalar_refs:
                            (gi,) + (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    # the value's entry in f32 (exact): a row of it is broadcast over a
    # tile's sublanes, which a packed 16-bit tile does not give
    operands = [group_tile(q), nk, new_v.astype(f32),
                held.astype(jnp.int32).reshape(S, 1, W)]
    in_specs = [a_step((nkv, rows, hd)),
                pl.BlockSpec((None,) + nk.shape[1:],
                             lambda gi, *scalar_refs:
                             (gi * P // lane, 0, 0, 0)),
                a_step((nkv, dv)), a_step((1, W))]
    if sink is not None:
        operands.append(jnp.tile(group_tile(
            sink.astype(f32).reshape(1, nq, 1))[0], (P, 1, 1)))
        in_specs.append(pl.BlockSpec(
            (P * nkv, rows, 1), lambda gi, *scalar_refs: (0, 0, 0)))
    rings_at = len(scalars) + len(operands)
    operands += [kring, vring]
    in_specs += [in_hbm, in_hbm]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S // P,),
        in_specs=in_specs,
        out_specs=[a_step((nkv, heads, dv)), in_hbm, in_hbm],
        scratch_shapes=[
            pltpu.VMEM((2, P * nkv, hd, W), dtype),
            pltpu.VMEM((2, P * nkv, W, dv), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    o, kring, vring = pl.pallas_call(
        functools.partial(_ring_decode_kernel, sink=sink is not None,
                          group=P),
        name="ring_decode_attn", grid_spec=grid_spec,
        # the rings' results (and with them the operands they alias) in
        # HBM by name: given the choice XLA stages two whole rings (50 MB
        # each at the cell's sizes) in VMEM around the layer loop, 100 MB
        # copied in and 100 MB out a step where the kernel moves 203
        # (AOT, PR 46). The price: the caller DONATES the rings (the
        # engine's programs do); a ring XLA has to copy in front of this
        # call meets a check of its memory-space assignment and aborts
        # the compile ("Conflicting pending required assignment")
        out_shape=[jax.ShapeDtypeStruct((S, nkv, heads, dv), f32),
                   pltpu.HBM(kring.shape, dtype),
                   pltpu.HBM(vring.shape, dtype)],
        # the rings come back, updated in place
        input_output_aliases={rings_at: 1, rings_at + 1: 2},
        # sequential: a step starts the next step's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_FORCE_INTERPRET[0],
    )(*scalars, *operands)
    return o[:, :, :g].reshape(S, nq, dv), kring, vring


def ring_decode_attention(q, new_k, new_v, kring, vring, wi, held,
                          write_entry, sink=None):
    """One decode step of window layer ``wi`` over every slot's rings
    (module docstring): q ``[S, nq, hd]``, ``new_k [S, nkv, hd]``,
    ``new_v [S, nkv, dv]``, ``kring [Lw, S, nkv, hd, W]``, ``vring [Lw,
    S, nkv, W, dv]``, ``held [S, W]`` the position each entry holds once
    the step's is placed, negative where the sequence has not reached
    it (``text.mimo_v2.ring_positions``), ``write_entry [S]`` the ring
    entry the new key and value go to (``pos % W``) or -1 for a slot
    that may not write, ``sink [nq]`` f32 logits or None. Returns ``(o
    [S, nq, dv] f32, kring, vring)``, the rings updated in place: the
    caller DONATES them to its jitted program (the comment at
    ``out_shape``).

    Callers check ``kernel_viable`` first; the ``jnp`` formulation in
    ``mixed_programs.PagedAccess.win_decode`` is the parity oracle."""
    # x64 guard shared by every Pallas entry point (pallas_compat)
    return _trace_32bit(_ring_decode_32)(
        q, new_k, new_v, kring, vring, wi, held, write_entry, sink)
