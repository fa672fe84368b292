"""The engine's paged programs for a model whose cache ENTRIES are not
positions (``text.evabyte``: ``CacheSpec.window``): signatures, slot
bookkeeping and sampling are ``shell.py``'s, as every model's are, the
model's block is IMPORTED, not written out again, and there is a third
program that the other models have no use for.

A slot's table row addresses its entries: ``S = W / C`` summaries for
every window that is over, then the raw keys and values of the current
window; position ``t`` is written at entry ``S (t // W) + t % W``.

  ``paged_prefill(params, tokens [1, B], tail_len, start, slot, final,
                  bt_row [MB], toks [S], pos [S], k, v[, samp...])
      -> (first [1], toks', pos', k, v)``
      One request's run of ``tail_len <= B <= W`` positions from window
      boundary ``start`` (the engine tiles a prompt by windows). The
      run attends its own rows and the summaries before it, read from
      the slot's first blocks; a run that FILLS its window (``tail_len
      == W``) leaves the window's ``S`` summaries, any other its raw
      rows. No host read decides which: both are computed, one is kept.

  ``paged_decode(params, toks [S], pos [S], tables [S, MB], k, v
                 [, samp...]) -> (next [S], pos + 1, k, v)``
      One position a slot: the pool rides the layer loop flat (``[L*NB,
      H, BS, d]``), each slot's new entry is placed and attention is
      ordinary attention over the slot's entries so far
      (``ops.paged_attention.paged_write_attention``, what the GPT's
      program calls, with ``lengths`` = entries: on a chip the kernel
      places the entry itself, elsewhere the slot's current block is
      read, given its new row and written back whole).

  ``paged_compact(params, window, bt_row [MB], k, v) -> (k, v)``
      One slot's finished window ``window``: its ``W`` raw entries
      read, its ``S`` pooled pairs written over the first of them, in
      every layer, in place. The step loop dispatches it between the
      decode step that wrote the window's last position and the next
      one; which slot and when it knows from its own counts. A program
      of its own and not a branch of ``paged_decode``: the host has to
      know every slot's position anyway (it hands out and takes back the
      blocks), a parked or released slot needs no guard, the decode
      program that runs 2,047 steps of 2,048 stays free of a 33 MB
      gather under a condition, and the trace shows its time by name.

Parked and released slots: a slot parked between the chunks of its
prefill sits at the model's last position, whose entry (clamped to the
row's last) no live sequence uses and is written nowhere a length mask
shows (``ops.paged_attention.live_write_pos``); free rows point at the
trash block; ``lengths`` never exceeds what the row's blocks hold.
"""
from ...profiler import device_scope


class PagedAccess:
    """A layer's way to the flat entry pool ``k, v [L*NB, H, BS, d]``.
    Built per trace with the table it reads: one row (``bt_row``,
    prefill) or all of them (``tables``, decode)."""

    def __init__(self, cfg, num_blocks, block_size, blocks_per_slot,
                 bt_row=None, tables=None):
        self.cfg = cfg
        self.NB, self.BS = int(num_blocks), int(block_size)
        self.MB = int(blocks_per_slot)
        self.bt_row, self.tables = bt_row, tables

    # ---------------------------------------------------------- prefill
    def summaries(self, state, layer, start):
        """The slot's leading entries as far as summaries can reach,
        ``S (start // W)`` of them live."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        S = cfg.summaries_per_window
        most = min(self.MB * self.BS,
                   S * ((cfg.max_seq_len - 1) // cfg.window_size))
        rows = layer * jnp.int32(self.NB) \
            + self.bt_row[:-(-most // self.BS)]
        out = []
        with device_scope("kv_gather"):
            for cache in state:
                H, d = cache.shape[1], cache.shape[3]
                out.append(cache[rows].transpose(1, 0, 2, 3).reshape(
                    H, -1, d)[None, :, :most])
        return out[0], out[1], (start // cfg.window_size) * S

    def store(self, state, layer, start, k, v, kbar, vbar, length):
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        W, S = cfg.window_size, cfg.summaries_per_window
        B, H, d = k.shape[1:]
        C = self.MB * self.BS
        rows = layer * jnp.int32(self.NB) + self.bt_row          # [MB]
        at = (start // W) * S + jnp.arange(B, dtype=jnp.int32)
        out = []
        for cache, raw, bar in zip(state, (k, v), (kbar, vbar)):
            new = raw[0]                                      # [B, H, d]
            if B == W:
                # a run that fills its window leaves its summaries
                new = new.at[:S].set(jnp.where(
                    length == W, bar[0].transpose(1, 0, 2), new[:S]))
            with device_scope("kv_gather"):
                view = cache[rows].transpose(1, 0, 2, 3).reshape(H, C, d)
            # rows past the slot's capacity are dropped, not shifted
            view = view.at[:, at].set(
                new.transpose(1, 0, 2).astype(cache.dtype), mode="drop")
            with device_scope("kv_write"):
                out.append(cache.at[rows].set(
                    view.reshape(H, self.MB, self.BS, d)
                    .transpose(1, 0, 2, 3)))
        return tuple(out)

    # ----------------------------------------------------------- decode
    def decode(self, state, layer, pos, q, k, v, kernel):
        import jax
        import jax.numpy as jnp

        from ...ops import paged_attention as paged_ops
        from .pool import TRASH_BLOCK
        cfg = self.cfg
        kf, vf = state
        BS, C = self.BS, self.MB * self.BS
        tables = self.tables + layer * jnp.int32(self.NB)
        # a position past the model's last (a parked slot, counting on)
        # stays there, and so does its entry
        entry = jnp.minimum(cfg.entries(jnp.minimum(
            pos, jnp.int32(cfg.max_seq_len - 1))), jnp.int32(C - 1))
        # what attention may read of a slot: its ENTRIES so far, never
        # more than the blocks its row holds (a released slot: nothing)
        held = jnp.sum((self.tables != TRASH_BLOCK).astype(jnp.int32),
                       axis=1)
        lengths = jnp.minimum(entry + 1, held * jnp.int32(BS))
        with device_scope("kv_write"):
            new = (k.astype(kf.dtype), v.astype(vf.dtype))
            wpos = paged_ops.live_write_pos(entry, lengths)
        o, pools = paged_ops.paged_write_attention(
            q, new, (kf, vf), tables, wpos, lengths, kernel)
        return pools, o


def decode_kernel(cfg, block_size):
    """Whether the decode program's attention is the Pallas paged
    kernel (``shell.resolve_decode_kernels``)."""
    from ...ops import paged_attention as paged_ops
    from .shell import resolve_decode_kernels
    given = (cfg.num_heads, cfg.head_dim, block_size, cfg.cache_dtype)
    return resolve_decode_kernels([
        (paged_ops, "paged_decode_attn",
         "heads, head dim, block_size, cache dtype", given,
         lambda: paged_ops.kernel_viable(*given))])


def build_paged_eva_fns(cfg, num_slots, block_size, num_blocks,
                        blocks_per_slot, sampling=False, kernel=None):
    """(paged_prefill, paged_decode, paged_compact) for an
    ``EvaByteConfig``. Pure and shape-stable; ``kernel=None`` asks
    ``decode_kernel``."""
    import jax
    import jax.numpy as jnp

    from ...ops import eva as eva_ops
    from ...text import evabyte as block
    from .shell import build_paged_programs, flat

    NB, BS, MB = int(num_blocks), int(block_size), int(blocks_per_slot)
    W, S = cfg.window_size, cfg.summaries_per_window
    if S % BS or W % BS:
        raise ValueError(
            f"block_size {BS} must divide a window's {S} summaries "
            f"(window_size {W} / chunk_size {cfg.chunk_size}): a "
            f"compacted window ends on a block boundary")
    if kernel is None:
        kernel = decode_kernel(cfg, block_size)
    L, H, d = cfg.num_layers, cfg.num_heads, cfg.head_dim

    def prefill_body(params, tokens, tail_len, start, slot, bt_row, cache):
        k, v = cache
        B = tokens.shape[1]
        if B > W:
            raise ValueError(f"a prefill run of {B} positions is wider "
                             f"than the window ({W}): prompts are "
                             f"prefilled by windows")
        access = PagedAccess(cfg, NB, BS, MB, bt_row=bt_row)
        x = block.embed(cfg, params, tokens)                 # [1, B, h]
        positions = (start + jnp.arange(B, dtype=jnp.int32))[None]
        x, cache = block.run_layers(
            cfg, params, x, positions, access, (flat(k), flat(v)), start,
            "prefill", length=tail_len)
        # ONE row through the head, as a [1, h] matmul
        last = block.lm_head(cfg, params, jax.lax.dynamic_slice_in_dim(
            x[0], tail_len - 1, 1, axis=0))[0]
        return last, cache

    def decode_body(params, toks, pos, tables, cache, state):
        k, v = cache
        access = PagedAccess(cfg, NB, BS, MB, tables=tables)
        x = block.embed(cfg, params, toks)                   # [S, h]
        x, cache = block.run_layers(
            cfg, params, x, pos, access, (flat(k), flat(v)),
            mode="decode", kernel=kernel)
        return block.lm_head(cfg, params, x), cache, state

    def paged_compact(params, window, bt_row, k, v):
        nw, ns = W // BS, S // BS
        blocks = jax.lax.dynamic_slice_in_dim(bt_row, window * ns, nw)

        def layer(carry, inp):
            mu, phi, li = inp
            rows = li * jnp.int32(NB) + blocks                   # [nw]
            with device_scope("kv_gather"):
                kw, vw = (c[rows].transpose(1, 0, 2, 3).reshape(H, W, d)
                          for c in carry)
            bars = eva_ops.window_compact(kw, vw, mu, phi,
                                          cfg.chunk_size)     # [H, S, d]
            with device_scope("kv_write"):
                return tuple(
                    c.at[rows[:ns]].set(
                        bar.reshape(H, ns, BS, d).transpose(1, 0, 2, 3))
                    for c, bar in zip(carry, bars)), None

        (kf, vf), _ = jax.lax.scan(
            layer, (flat(k), flat(v)),
            (params["layers"]["mu"], params["layers"]["phi"],
             jnp.arange(L, dtype=jnp.int32)))
        return kf.reshape(k.shape), vf.reshape(v.shape)

    # a slot parked between the chunks of its prefill sits at the
    # model's last POSITION: its entries are fewer than its positions.
    # An array made here, outside the trace, as it has been since PR 37:
    # the prefill program's jaxpr holds it as a constant, not a literal
    return build_paged_programs(
        prefill_body, decode_body, cfg.vocab_size, sampling,
        park=jnp.int32(cfg.max_seq_len - 1)) + (paged_compact,)
