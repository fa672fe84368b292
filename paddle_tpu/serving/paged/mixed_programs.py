"""The engine's paged prefill and decode programs for a model in which
FULL and WINDOW attention layers live side by side (``text.mimo_v2``):
a full layer keeps every position in the paged pool behind the block
tables, a window layer keeps a RING of ``W`` entries a slot in per-slot
arrays (``cache_spec``: per-slot leaves, ``ring=W``) and NOTHING that
grows with the position. Signatures, slot bookkeeping and sampling are
``shell.py``'s, as every model's are, and the model's block is IMPORTED,
not written out again. What is here is how a layer reaches its cache
(``PagedAccess``), the kernels it needs and the two bodies the shell
wraps.

The pool's arrays, in the order the programs take them: ``k [Lf, NB,
nkv, BS, dn]`` (a key's un-rotated lanes, as wide as a value), ``kr [Lf,
NB, nkv, dr, BS]`` (its rotated lanes, TRANSPOSED: a width under 128
rides the sublanes, nothing is padded), ``v [Lf, NB, nkv, BS, dv]``;
``kring [Lw, S, nkw, hd, W]`` (transposed likewise: 192 lanes would be
padded to 256), ``vring [Lw, S, nkw, W, dv]``. Position ``t`` of a slot
is ring entry ``t % W``.

  ``paged_prefill(params, tokens [1, B], tail_len, start, slot, final,
                  bt_row [MB], toks [S], pos [S], k, kr, v, kring, vring
                  [, samp...])
      -> (first [1], toks', pos', k, kr, v, kring, vring)``
      One request's run of ``tail_len`` tokens from position ``start``:
      a whole short prompt, or one chunk of a long one (``B`` is the one
      bucket; a ring model shares no prefix, so ``start`` is only ever a
      chunk boundary of the request's own). A full layer writes the
      run's keys and values into the blocks it fills and walks the
      slot's blocks up to the run's end, a block at a time, under the
      causal mask: its cost follows ``start + B``, never the capacity.
      A window layer attends the run over the slot's ring and the run's
      own rows in bands of ``2 W`` keys and leaves the ring holding the
      run's last ``W`` real positions. A ring entry is only ever seen
      through the POSITION it must hold (``ring_positions``): an entry
      the sequence has not reached is unseen whatever a slot's last
      owner left there, and ``start == 0`` takes a cleared ring besides.

  ``paged_decode(params, toks [S], pos [S], tables [S, MB], k, kr, v,
                 kring, vring, moe_counts[, samp...])
      -> (next [S], pos + 1, k, kr, v, kring, vring, moe_counts')``
      One token a slot. A full layer: the new entry into all three
      pools and attention over the LIVE blocks in place through
      ``tables + layer*NB`` (key in two parts), both by
      ``ops.paged_attention.paged_write_attention`` (the kernel places
      the entry itself; the ``jnp`` path writes blocks first). A window
      layer: ``ops.slot_ring_decode`` in ONE call over the rings as they
      are carried (entry ``pos % W`` placed in VMEM, the ``W`` entries
      and the sink attended, the changed tiles copied back); ``jnp``: CPU.

Parked and released slots: their entries are nobody's
(``ops.paged_attention.live_write_pos``: the kernel writes none, the
``jnp`` path pins them to the row's last entry), free rows point at the
trash block, the length mask hides what they hold (``programs.py``). A
slot parked between the chunks of
its prefill (``pos == C - 1``; a live sequence never feeds a token
there) keeps its ring through the decode steps in between: its write
is masked.
"""
from ...profiler import device_scope

_NEG = -1e30


def _weighted_values(p, v, spec):
    """``p @ v`` (einsum ``spec``) at f32 grade in one pass over a
    16-bit ``v``: the weights' upper half and their remainder as two
    sets of rows of one matmul (``ops.paged_attention`` does the same)."""
    import jax.numpy as jnp
    if v.dtype == jnp.float32:
        return jnp.einsum(spec, p, v, preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    g = p.shape[-2]
    o = jnp.einsum(spec, jnp.concatenate([hi, lo], axis=-2), v,
                   preferred_element_type=jnp.float32)
    return o[..., :g, :] + o[..., g:, :]


class PagedAccess:
    """A layer's way to its cache. State: ``(k, kr, v)`` flat over the
    full layers (``[Lf*NB, ...]``) and the rings. DECODE (built with all
    table rows) carries every slot's rings ``[Lw, S, ...]``; PREFILL
    (built with one table row) ONE slot's, ``[Lw, ...]``, cut out before
    the layer loop and put back after it."""

    def __init__(self, cfg, num_slots, num_blocks, block_size,
                 blocks_per_slot, bt_row=None, tables=None, kernel=False):
        self.cfg, self.S = cfg, int(num_slots)
        self.NB, self.BS = int(num_blocks), int(block_size)
        self.MB = int(blocks_per_slot)
        self.bt_row, self.tables, self.kernel = bt_row, tables, kernel

    # ---------------------------------------------------------- prefill
    def full_prefill(self, state, li, start, q, k, v, positions, length):
        """q ``[1, B, nq, hd]``, k ``[1, B, nkv, hd]``, v ``[1, B, nkv,
        dv]`` -> o ``[1, B, nq, dv]`` f32."""
        import jax
        import jax.numpy as jnp

        from .pool import TRASH_BLOCK
        kf, krf, vf, kring, vring = state
        cfg, BS, MB = self.cfg, self.BS, self.MB
        B, nq = q.shape[1:3]
        nkv, rd, dv = k.shape[2], cfg.rot_dim, cfg.v_head_dim
        base = li * jnp.int32(self.NB)
        # where the run's keys and values go (programs.py): the table
        # entries from start // BS on, only the run's REAL rows change
        nW = (B + 2 * BS - 2) // BS
        off = start % jnp.int32(BS)
        wcol = start // jnp.int32(BS) + jnp.arange(nW, dtype=jnp.int32)
        wblk = base + jnp.where(
            wcol < MB, self.bt_row[jnp.minimum(wcol, MB - 1)],
            jnp.int32(TRASH_BLOCK))
        wrow = jnp.arange(nW * BS, dtype=jnp.int32)
        mine = ((wrow >= off) & (wrow < off + length)).reshape(nW, BS)

        def blocks(new):
            # [B, nkv, d] -> [nW, BS, nkv, d], row t at flat row off + t
            buf = jax.lax.dynamic_update_slice(
                jnp.zeros((nW * BS,) + new.shape[1:], new.dtype), new,
                (off, jnp.int32(0), jnp.int32(0)))
            return buf.reshape((nW, BS) + new.shape[1:])

        with device_scope("kv_write"):
            kf = kf.at[wblk].set(jnp.where(
                mine[:, None, :, None],
                blocks(k[0, :, :, rd:]).transpose(0, 2, 1, 3), kf[wblk]))
            vf = vf.at[wblk].set(jnp.where(
                mine[:, None, :, None],
                blocks(v[0]).transpose(0, 2, 1, 3), vf[wblk]))
            krf = krf.at[wblk].set(jnp.where(
                mine[:, None, None, :],
                blocks(k[0, :, :, :rd]).transpose(0, 2, 3, 1), krf[wblk]))
        # the walk: the slot's blocks up to the run's end, one a step,
        # keys and values as the cache holds them, under the causal mask
        qg = q[0].reshape(B, nkv, nq // nkv, -1).transpose(1, 2, 0, 3)
        qpos = positions[0]
        scale = jnp.float32(float(cfg.head_dim) ** -0.5)

        def step(i, carry):
            m, l, acc = carry
            blk = base + self.bt_row[jnp.minimum(i, MB - 1)]
            with device_scope("kv_gather"):
                kb = jnp.concatenate(
                    [krf[blk].transpose(0, 2, 1), kf[blk]], axis=-1)
                vb = vf[blk]                           # [nkv, BS, dv]
            s = jnp.einsum("ngtd,nsd->ngts", qg, kb,
                           preferred_element_type=jnp.float32) * scale
            kpos = i * BS + jnp.arange(BS, dtype=jnp.int32)
            s = jnp.where(kpos[None, None, None, :]
                          <= qpos[None, None, :, None], s,
                          jnp.float32(_NEG))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = alpha * l + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "ngts,nsd->ngtd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        shape = (nkv, nq // nkv, B)
        _, l, acc = jax.lax.fori_loop(
            0, jnp.minimum((start + B + BS - 1) // BS, MB), step,
            (jnp.full(shape, _NEG, jnp.float32),
             jnp.zeros(shape, jnp.float32),
             jnp.zeros(shape + (dv,), jnp.float32)))
        # a bucket row past the capacity saw nothing: l == 0, nobody
        # reads it
        o = acc / jnp.maximum(l, jnp.float32(1e-30))[..., None]
        o = o.transpose(2, 0, 1, 3).reshape(1, B, nq, dv)
        return (kf, krf, vf, kring, vring), o

    def win_prefill(self, state, wi, start, q, k, v, positions, length,
                    sink):
        import jax
        import jax.numpy as jnp

        from ...ops import attention as attn_ops
        from ...text.mimo_v2 import ring_positions
        kf, krf, vf, kring, vring = state
        W = self.cfg.window
        B = q.shape[1]
        if B % W:
            raise ValueError(f"a prefill bucket ({B}) is whole windows "
                             f"of {W} positions")
        fresh = start == 0
        kr0 = jnp.where(fresh, jnp.zeros_like(kring[wi]), kring[wi])
        vr0 = jnp.where(fresh, jnp.zeros_like(vring[wi]), vring[wi])
        # the ring's entries by the positions they must hold, then the
        # run's rows: a band of 2 W keys covers a block of W queries
        k_all = jnp.concatenate(
            [kr0.transpose(0, 2, 1), k[0].transpose(1, 0, 2)], axis=1)
        v_all = jnp.concatenate([vr0, v[0].transpose(1, 0, 2)], axis=1)
        pos_all = jnp.concatenate(
            [ring_positions(start - 1, W), positions[0]])

        def band(b):
            at = b * jnp.int32(W)
            cut = lambda a, n, ax: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                a, at, n, axis=ax)
            return attn_ops.grouped_causal_attention(
                cut(q[0], W, 0), cut(k_all, 2 * W, 1),
                cut(v_all, 2 * W, 1), cut(positions[0], W, 0),
                k_pos=cut(pos_all, 2 * W, 0), window=W, sink=sink)

        o = jax.lax.map(band, jnp.arange(B // W, dtype=jnp.int32))
        o = o.reshape((1, B) + o.shape[2:])
        # what the ring holds once the run's last REAL row is in it
        held = ring_positions(start + length - 1, W)           # [W]
        take = held >= start
        row = jnp.clip(held - start, 0, B - 1)
        with device_scope("kv_write"):
            kring = jax.lax.dynamic_update_index_in_dim(
                kring, jnp.where(take[None, None, :],
                                 k[0][row].transpose(1, 2, 0), kr0),
                wi, axis=0)
            vring = jax.lax.dynamic_update_index_in_dim(
                vring, jnp.where(take[None, :, None],
                                 v[0][row].transpose(1, 0, 2), vr0),
                wi, axis=0)
        return (kf, krf, vf, kring, vring), o

    # ----------------------------------------------------------- decode
    def full_decode(self, state, li, pos, q, k, v):
        import jax.numpy as jnp

        from ...ops import paged_attention as paged_ops
        from .pool import TRASH_BLOCK
        kf, krf, vf, kring, vring = state
        rd = self.cfg.rot_dim
        # what attention may read of a slot: its positions so far, never
        # more than the blocks its row holds (a released slot: nothing)
        held = jnp.sum((self.tables != TRASH_BLOCK).astype(jnp.int32),
                       axis=1)
        lengths = jnp.minimum(pos + 1, held * jnp.int32(self.BS))
        with device_scope("kv_write"):
            new = (k[..., rd:].astype(kf.dtype), v.astype(vf.dtype),
                   k[..., :rd].astype(krf.dtype))
            wpos = paged_ops.live_write_pos(pos, lengths)
        o, (kf, vf, krf) = paged_ops.paged_write_attention(
            q[..., rd:], new, (kf, vf, krf),
            self.tables + li * jnp.int32(self.NB), wpos, lengths, self.kernel,
            q_rot=q[..., :rd])
        return (kf, krf, vf, kring, vring), o

    def win_decode(self, state, wi, pos, q, k, v, sink):
        """q ``[S, nq, hd]``, k ``[S, nkv, hd]``, v ``[S, nkv, dv]`` ->
        o ``[S, nq, dv]`` f32. With ``kernel`` (the constructor's:
        ``decode_kernels``) ``ops.slot_ring_decode`` in ONE call over
        the rings as they are carried; otherwise the ``jnp`` formulation
        below, the CPU's path and the parity oracle."""
        import jax
        import jax.numpy as jnp

        from ...ops import attention as attn_ops
        from ...ops import slot_ring_decode as ring_ops
        from ...text.mimo_v2 import ring_positions
        kf, krf, vf, kring, vring = state
        W = self.cfg.window
        # a slot parked mid-prefill keeps its ring as the last chunk
        # left it; a released one's is nobody's
        park = jnp.int32(self.MB * self.BS - 1)
        if self.kernel:
            # where the entry goes is the write's arithmetic, staged as
            # a full layer stages ``live_write_pos``
            with device_scope("kv_write"):
                entry = jnp.where(pos < park, pos % jnp.int32(W),
                                  jnp.int32(-1))
            with device_scope("window"):
                o, kring, vring = ring_ops.ring_decode_attention(
                    q, k, v, kring, vring, wi, ring_positions(pos, W),
                    entry, sink)
            return (kf, krf, vf, kring, vring), o
        S, nq, hd = q.shape
        nkv = k.shape[1]
        kr, vr = kring[wi], vring[wi]       # [S, nkv, hd, W], [., W, dv]
        active = pos < park
        hot = jnp.logical_and(
            jnp.arange(W, dtype=jnp.int32)[None, :]
            == (pos % jnp.int32(W))[:, None], active[:, None])  # [S, W]
        with device_scope("window"):
            with device_scope("kv_write"):
                kr = jnp.where(hot[:, None, None, :], k[:, :, :, None], kr)
                vr = jnp.where(hot[:, None, :, None], v[:, :, None, :], vr)
                kring = jax.lax.dynamic_update_index_in_dim(kring, kr, wi,
                                                            0)
                vring = jax.lax.dynamic_update_index_in_dim(vring, vr, wi,
                                                            0)
            qg = q.reshape(S, nkv, nq // nkv, hd)
            s = jnp.einsum("sngd,sndw->sngw", qg, kr,
                           preferred_element_type=jnp.float32) \
                * jnp.float32(float(hd) ** -0.5)
            seen = ring_positions(pos, W) >= 0                   # [S, W]
            s = jnp.where(seen[:, None, None, :], s, jnp.float32(_NEG))
            p = attn_ops.softmax_with_sink(
                s, None if sink is None
                else sink.reshape(nkv, nq // nkv, 1))
            o = _weighted_values(p, vr, "sngw,snwd->sngd")
        return (kf, krf, vf, kring, vring), \
            o.reshape(S, nq, vr.shape[-1])


def decode_kernels(cfg, num_slots, block_size):
    """Whether the decode program runs its three Pallas kernels
    (``shell.resolve_decode_kernels``)."""
    from ...ops import moe_experts as moe_ops
    from ...ops import paged_attention as paged_ops
    from ...ops import slot_ring_decode as ring_ops
    from .shell import resolve_decode_kernels

    def full_viable():
        if cfg.nope_dim != cfg.v_head_dim:
            raise ValueError(
                f"paged_decode_attn takes a key whose un-rotated lanes "
                f"are as wide as its value: got {cfg.nope_dim} beside "
                f"{cfg.v_head_dim}")
        return not cfg.count("full") or paged_ops.kernel_viable(
            cfg.kv_heads["full"], cfg.v_head_dim, block_size,
            cfg.cache_dtype, cfg.rot_dim)

    win = (cfg.kv_heads["win"], cfg.head_dim, cfg.v_head_dim, cfg.window,
           cfg.cache_dtype)
    moe = (num_slots, cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype)
    return resolve_decode_kernels([
        (paged_ops, "paged_decode_attn",
         "kv heads, key lanes, rotated lanes, block_size, cache dtype",
         (cfg.kv_heads["full"], cfg.v_head_dim, cfg.rot_dim, block_size,
          cfg.cache_dtype), full_viable),
        (ring_ops, "ring_decode_attn",
         "kv heads, key width, value width, window, cache dtype", win,
         lambda: not cfg.count("win") or ring_ops.kernel_viable(*win)),
        (moe_ops, "moe_experts_swiglu_decode",
         "slots, hidden, expert width, dtype", moe,
         lambda: not cfg.count("moe") or moe_ops.kernel_viable(*moe))])


def build_paged_mixed_fns(cfg, num_slots, block_size, num_blocks,
                          blocks_per_slot, sampling=False, kernels=None):
    """(paged_prefill, paged_decode) for a ``MimoV2Config``. Pure and
    shape-stable; ``kernels=None`` asks ``decode_kernels``."""
    import jax
    import jax.numpy as jnp

    from ...text import mimo_v2 as block
    from .shell import build_paged_programs, flat

    if kernels is None:
        kernels = decode_kernels(cfg, num_slots, block_size)
    S = int(num_slots)
    NB, BS, MB = int(num_blocks), int(block_size), int(blocks_per_slot)

    def prefill_body(params, tokens, tail_len, start, slot, bt_row, cache):
        k, kr, v, kring, vring = cache
        B = tokens.shape[1]
        access = PagedAccess(cfg, S, NB, BS, MB, bt_row=bt_row)
        with device_scope("embed"):
            x = params["wemb"][tokens]                       # [1, B, h]
        positions = (start + jnp.arange(B, dtype=jnp.int32))[None]
        mine = (jax.lax.dynamic_index_in_dim(kring, slot, 1, False),
                jax.lax.dynamic_index_in_dim(vring, slot, 1, False))
        x, (kf, krf, vf, kring_s, vring_s), _ = block.run_layers(
            cfg, params, x, positions, access,
            (flat(k), flat(kr), flat(v)) + mine, start, "prefill",
            length=tail_len)
        with device_scope("kv_write"):
            kring = jax.lax.dynamic_update_index_in_dim(kring, kring_s,
                                                        slot, axis=1)
            vring = jax.lax.dynamic_update_index_in_dim(vring, vring_s,
                                                        slot, axis=1)
        # ONE row through the head, as a [1, h] matmul: as a vector the
        # product is elementwise and XLA upcasts the whole head to f32
        last = block.lm_head(cfg, params, jax.lax.dynamic_slice_in_dim(
            x[0], tail_len - 1, 1, axis=0))[0]
        return last, (kf, krf, vf, kring, vring)

    def decode_body(params, toks, pos, tables, cache, state):
        k, kr, v, kring, vring = cache
        access = PagedAccess(cfg, S, NB, BS, MB, tables=tables,
                             kernel=kernels)
        with device_scope("embed"):
            x = params["wemb"][toks]                         # [S, h]
        x, cache, counts = block.run_layers(
            cfg, params, x, pos, access,
            (flat(k), flat(kr), flat(v), kring, vring), mode="decode",
            kernel=kernels, counts=state[0])
        return block.lm_head(cfg, params, x), cache, (counts,)

    return build_paged_programs(
        prefill_body, decode_body, cfg.vocab_size, sampling,
        park=MB * BS - 1, num_state=len(block.mixed_cache_spec(cfg).state))
