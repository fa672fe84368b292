"""The engine's paged prefill and decode programs for a model whose
cache is a LATENT a token (``text.deepseek_v3``): signatures, slot
bookkeeping and sampling are ``shell.py``'s, as every model's are, and
the model's block is IMPORTED, not written out again. What is here is
how a layer reaches the paged pool (``PagedAccess``), the kernels it
needs and the two bodies the shell wraps.

  ``paged_prefill(params, tokens [1, B], tail_len, start, slot, final,
                  bt_row [MB], toks [S], pos [S], c, k_pe[, samp...])
      -> (first [1], toks', pos', c, k_pe)``
      One request's uncached tail in the EXPANDED attention form: every
      layer gathers the slot's ``MB`` blocks into a position-ordered
      view, puts the tail's latents in at ``start..`` and scatters the
      blocks back whole. The head runs on the ONE row that is read
      (``tail_len - 1``), never on the bucket.

  ``paged_decode(params, toks [S], pos [S], tables [S, MB], c, k_pe,
                 moe_counts[, samp...])
      -> (next [S], pos + 1, c, k_pe, moe_counts')``
      One token a slot in the ABSORBED form. The pool rides the layer
      loops' carry flat (``[L*NB, BS, .]``) and attention reads the
      blocks in place through ``tables + layer*NB``. The step's new
      entry is written by ``ops.mla_attention.latent_write_attention``:
      on a TPU ``mla_paged_decode_attn`` places it itself, in the chunk
      it has copied, and writes back the two tiles that changed (both
      pools aliased in and out: no block is gathered or scattered in
      front of it); elsewhere each slot's current block is read, given
      its new row and written back whole (in place on a donated pool:
      ISSUE 26's write). ``moe_counts`` is the model's
      carried state (``CacheSpec.state``): routing counters that stay on
      the device, returned new each step and never donated, so that a
      reader in another thread holds a live array whenever it looks.

Parked and released slots behave as in ``programs.py``: their entries
are nobody's (``ops.paged_attention.live_write_pos``: the kernel writes
none, the ``jnp`` write pins them to the row's last entry), free rows
point at the trash block and nothing of them is live (length 0), the
length mask hides what a parked slot's row holds.
"""
from ...profiler import device_scope


class PagedAccess:
    """A layer's way to the flat paged pool ``(c [L*NB, BS, rank], k_pe
    [L*NB, dr, BS])`` (the rotary key's blocks are kept transposed:
    ``ops.mla_attention``). Built per trace with the table it reads: one
    row (``bt_row``, prefill) or all of them (``tables``, decode)."""

    def __init__(self, num_blocks, block_size, blocks_per_slot,
                 bt_row=None, tables=None, kernel=False):
        self.NB, self.BS = int(num_blocks), int(block_size)
        self.MB = int(blocks_per_slot)
        self.bt_row, self.tables, self.kernel = bt_row, tables, kernel

    def prefill(self, state, layer, start, c, k_pe):
        import jax
        import jax.numpy as jnp
        C = self.MB * self.BS
        rows = layer * jnp.int32(self.NB) + self.bt_row          # [MB]
        at = start + jnp.arange(c.shape[1], dtype=jnp.int32)
        out, views = [], []
        for cache, new, flip in zip(state, (c, k_pe), (False, True)):
            d = new.shape[-1]
            with device_scope("kv_gather"):
                blocks = cache[rows]                 # [MB, BS, d] or flipped
                if flip:
                    blocks = blocks.transpose(0, 2, 1)
                view = blocks.reshape(C, d)
            # rows past the slot's capacity are dropped, not shifted
            view = view.at[at].set(new[0].astype(cache.dtype),
                                   mode="drop")
            with device_scope("kv_write"):
                blocks = view.reshape(self.MB, self.BS, d)
                if flip:
                    blocks = blocks.transpose(0, 2, 1)
                out.append(cache.at[rows].set(blocks))
            views.append(view[None])
        return tuple(out), tuple(views)

    def decode(self, state, layer, pos, c, k_pe, q_lat, q_pe, scale):
        import jax.numpy as jnp

        from ...ops import mla_attention as mla_ops
        from ...ops.paged_attention import live_write_pos
        from .pool import TRASH_BLOCK
        # what attention may read of a slot: its positions so far, never
        # more than the blocks its row holds (a released slot: nothing)
        held = jnp.sum((self.tables != TRASH_BLOCK).astype(jnp.int32),
                       axis=1)
        lengths = jnp.minimum(pos + 1, held * jnp.int32(self.BS))
        with device_scope("kv_write"):
            new = (c.astype(state[0].dtype), k_pe.astype(state[1].dtype))
            wpos = live_write_pos(pos, lengths)
        o_lat, state = mla_ops.latent_write_attention(
            q_lat, q_pe, new, state, self.tables + layer * jnp.int32(self.NB),
            wpos, lengths, scale, self.kernel)
        return state, o_lat


def decode_kernels(cfg, num_slots, block_size):
    """Whether the decode program runs its two Pallas kernels
    (``shell.resolve_decode_kernels``)."""
    from ...ops import mla_attention as mla_ops
    from ...ops import moe_experts as moe_ops
    from .shell import resolve_decode_kernels
    attn = (block_size, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
            cfg.cache_dtype)
    moe = (num_slots, cfg.hidden_size, cfg.moe_intermediate_size, cfg.dtype)
    return resolve_decode_kernels([
        (mla_ops, "mla_paged_decode_attn",
         "block_size, rank, rope dim, cache dtype", attn,
         lambda: mla_ops.kernel_viable(*attn)),
        (moe_ops, "moe_experts_swiglu_decode",
         "slots, hidden, expert width, dtype", moe,
         lambda: not cfg.num_moe_layers or moe_ops.kernel_viable(*moe))])


def build_paged_latent_fns(cfg, num_slots, block_size, num_blocks,
                           blocks_per_slot, sampling=False, kernels=None):
    """(paged_prefill, paged_decode) for a ``DeepseekV3Config``. Pure
    and shape-stable; ``kernels=None`` asks ``decode_kernels``."""
    import jax.numpy as jnp

    from ...text import deepseek_v3 as block
    from .shell import build_paged_programs, flat

    if kernels is None:
        kernels = decode_kernels(cfg, num_slots, block_size)
    NB, BS, MB = int(num_blocks), int(block_size), int(blocks_per_slot)

    def prefill_body(params, tokens, tail_len, start, slot, bt_row, cache):
        c, k_pe = cache
        B = tokens.shape[1]
        access = PagedAccess(NB, BS, MB, bt_row=bt_row)
        with device_scope("embed"):
            x = params["wemb"][tokens]                       # [1, B, h]
        positions = (start + jnp.arange(B, dtype=jnp.int32))[None]
        x, cache, _ = block.run_layers(
            cfg, params, x, positions, access, (flat(c), flat(k_pe)),
            start, "prefill")
        return block.lm_head(
            cfg, params, jnp.take(x[0], tail_len - 1, axis=0)), cache

    def decode_body(params, toks, pos, tables, cache, state):
        c, k_pe = cache
        access = PagedAccess(NB, BS, MB, tables=tables, kernel=kernels)
        with device_scope("embed"):
            x = params["wemb"][toks]                         # [S, h]
        x, cache, counts = block.run_layers(
            cfg, params, x, pos, access, (flat(c), flat(k_pe)),
            mode="decode", kernel=kernels, counts=state[0])
        return block.lm_head(cfg, params, x), cache, (counts,)

    return build_paged_programs(
        prefill_body, decode_body, cfg.vocab_size, sampling,
        park=MB * BS - 1, num_state=len(block.latent_cache_spec(cfg).state))
