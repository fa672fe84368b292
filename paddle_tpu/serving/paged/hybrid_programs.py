"""The engine's paged prefill and decode programs for a model that
keeps TWO kinds of state side by side: keys and values a token owns, in
the paged pool behind the block tables, and a convolution window and a
recurrent state a SLOT owns, in per-slot arrays (``cache_spec``:
per-slot leaves). Two families run through this one file:
``text.nemotron_h`` (ONE mixer a layer, chosen by a pattern: a layer
owns one kind of cache or none) and ``text.falcon_h1`` (a state-space
mixer AND an attention mixer in EVERY layer: a layer owns both kinds,
attention layer ``l`` and state-space layer ``l`` are the same layer).
Signatures, slot bookkeeping and sampling are ``shell.py``'s, as every
model's are, and the model's block is TAKEN FROM THE CONFIGURATION
(``text.stacked_lm.block_of``: the module of its class; ``embed``,
``run_layers``, ``lm_head``, ``split_channels``, ``hybrid_cache_spec``),
not written out again. What is here is how a layer reaches its cache
(``PagedAccess``), the kernels it needs and the two bodies the shell
wraps.

  ``paged_prefill(params, tokens [1, B], tail_len, start, slot, final,
                  bt_row [MB], toks [S], pos [S], k, v, conv, ssm
                  [, samp...])
      -> (first [1], toks', pos', k, v, conv, ssm)``
      One request's run of ``tail_len`` tokens from position ``start``.
      An attention layer gathers the slot's ``MB`` blocks into a
      position-ordered view, puts the run's keys and values in and
      scatters the blocks back whole. A state-space layer starts from
      the slot's window and state when ``start > 0`` (the next chunk of
      a chunked prefill) and from ZEROS when ``start == 0``, whatever
      the slot's last owner left; it writes the window and the state as
      they are after row ``tail_len - 1``: rows of the bucket past the
      run change neither (their step size is set to zero). A model with
      such state shares no prefix (``CacheSpec.shareable``), so
      ``start`` is only ever a chunk boundary of the request's own.

  ``paged_decode(params, toks [S], pos [S], tables [S, MB], k, v, conv,
                 ssm[, moe_counts][, samp...])
      -> (next [S], pos + 1, k, v, conv, ssm[, moe_counts'])``
      One token a slot. Keys and values ride the layer loop flat
      (``[La*NB, nkv, BS, hd]``); the new row is placed and attention
      reads the LIVE blocks in place through ``tables + layer*NB``
      (``ops.paged_attention.paged_write_attention``: the kernel places
      the row itself, the ``jnp`` path writes the slot's current block
      back whole in front of the gather). The recurrent
      state rides it flat too (``[Lm*S, ...]``) and is updated in place
      by ``ops.ssm``'s kernel, a layer's rows a call. ``moe_counts`` is
      carried where the model's cache spec names it (a model with
      expert layers).

Parked and released slots: their entries are nobody's
(``ops.paged_attention.live_write_pos``: the kernel writes none, the
``jnp`` path pins them to the row's last entry), free rows point at the
trash block, the length mask hides what they hold (``programs.py``). A
slot parked between the chunks of its prefill (``pos == C - 1``; a live
sequence never feeds a token there) keeps its window and its state
through the decode steps in between: its step size is set to zero.
"""
from ...profiler import device_scope


class PagedAccess:
    """A layer's way to keys and values ``k, v [La*NB, nkv, BS, hd]``
    and to slot state. DECODE (built with all table rows) carries every
    slot's: ``conv [Lm, S, .]``, ``ssm [Lm*S, ...]``. PREFILL (built
    with one table row) carries ONE slot's, ``conv [Lm, .]``, ``ssm
    [Lm, ...]``, cut out before the layer loop and put back after it:
    with the whole state in the loop's carry XLA gave all 1.6 GB of it a
    layout that suits the chunked scan's small transposes and copied it
    in and out of every prefill (AOT, PR 35)."""

    def __init__(self, cfg, num_slots, num_blocks, block_size,
                 blocks_per_slot, bt_row=None, tables=None):
        self.cfg, self.S = cfg, int(num_slots)
        self.NB, self.BS = int(num_blocks), int(block_size)
        self.MB = int(blocks_per_slot)
        self.bt_row, self.tables = bt_row, tables

    # ---------------------------------------------------------- prefill
    def attn_prefill(self, state, li, start, k, v):
        import jax
        import jax.numpy as jnp
        kf, vf, conv, ssm = state
        nkv, hd = k.shape[2:]
        C = self.MB * self.BS
        rows = li * jnp.int32(self.NB) + self.bt_row             # [MB]
        at = start + jnp.arange(k.shape[1], dtype=jnp.int32)
        out, views = [], []
        for cache, new in ((kf, k), (vf, v)):
            with device_scope("kv_gather"):
                view = cache[rows].transpose(1, 0, 2, 3).reshape(
                    nkv, C, hd)
            # rows past the slot's capacity are dropped, not shifted
            view = view.at[:, at].set(
                new[0].transpose(1, 0, 2).astype(cache.dtype), mode="drop")
            with device_scope("kv_write"):
                out.append(cache.at[rows].set(
                    view.reshape(nkv, self.MB, self.BS, hd)
                    .transpose(1, 0, 2, 3)))
            views.append(view[None])
        return (out[0], out[1], conv, ssm), tuple(views)

    def ssm_init(self, state, mi, start, b):
        import jax.numpy as jnp
        _, _, conv, ssm = state
        fresh = start == 0
        return (jnp.where(fresh, jnp.zeros_like(conv[mi]), conv[mi])[None],
                jnp.where(fresh, jnp.zeros_like(ssm[mi]), ssm[mi])[None])

    def ssm_commit(self, state, mi, window, S):
        import jax
        kf, vf, conv, ssm = state
        with device_scope("state_write"):
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, window[0].astype(conv.dtype), mi, axis=0)
            ssm = jax.lax.dynamic_update_index_in_dim(
                ssm, S[0].astype(ssm.dtype), mi, axis=0)
        return kf, vf, conv, ssm

    # ----------------------------------------------------------- decode
    def attn_decode(self, state, li, pos, q, k, v, kernel):
        import jax
        import jax.numpy as jnp

        from ...ops import paged_attention as paged_ops
        from .pool import TRASH_BLOCK
        kf, vf, conv, ssm = state
        # what attention may read of a slot: its positions so far, never
        # more than the blocks its row holds (a released slot: nothing)
        held = jnp.sum((self.tables != TRASH_BLOCK).astype(jnp.int32),
                       axis=1)
        lengths = jnp.minimum(pos + 1, held * jnp.int32(self.BS))
        with device_scope("kv_write"):
            new = (k.astype(kf.dtype), v.astype(vf.dtype))
            wpos = paged_ops.live_write_pos(pos, lengths)
        o, (kf, vf) = paged_ops.paged_write_attention(
            q, new, (kf, vf), self.tables + li * jnp.int32(self.NB), wpos,
            lengths, kernel)
        return (kf, vf, conv, ssm), o

    def ssm_decode(self, state, mi, pos, u, dt, A, conv_w, conv_b, kernel):
        import jax.numpy as jnp

        from ...ops import ssm as ssm_ops
        from ...text.stacked_lm import block_of
        split_channels = block_of(self.cfg).split_channels
        kf, vf, conv, ssm = state
        active = pos < jnp.int32(self.MB * self.BS - 1)
        conv, ssm, xs, y = ssm_ops.ssm_decode_step(
            conv, ssm, mi, u, dt, A,
            lambda act: split_channels(self.cfg, act), conv_w, conv_b,
            self.S, active, kernel)
        return (kf, vf, conv, ssm), xs, y


def decode_kernels(cfg, num_slots, block_size):
    """Whether the decode program runs its Pallas kernels, one a kind
    of layer the model has (``cfg.count``: attention ``*``, state-space
    ``M``, experts ``E``) (``shell.resolve_decode_kernels``)."""
    from ...ops import moe_experts as moe_ops
    from ...ops import paged_attention as paged_ops
    from ...ops import ssm as ssm_ops
    from .shell import resolve_decode_kernels
    checks = []
    if cfg.count("*"):
        attn = (cfg.num_kv_heads, cfg.head_dim, block_size,
                cfg.cache_dtype)
        checks.append((paged_ops, "paged_decode_attn",
                       "kv heads, head dim, block_size, cache dtype", attn,
                       lambda: paged_ops.kernel_viable(*attn)))
    if cfg.count("M"):
        ssm = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.state_size,
               cfg.n_groups)
        checks.append((ssm_ops, "ssm_decode_step",
                       "heads, head dim, state size, groups", ssm,
                       lambda: ssm_ops.kernel_viable(*ssm)))
    if cfg.count("E"):
        moe = (num_slots, cfg.hidden_size, cfg.moe_intermediate_size,
               cfg.dtype)
        checks.append((moe_ops, "moe_experts_relu2_decode",
                       "slots, hidden, expert width, dtype", moe,
                       lambda: moe_ops.kernel_viable(*moe, gated=False)))
    return resolve_decode_kernels(checks)


def build_paged_hybrid_fns(cfg, num_slots, block_size, num_blocks,
                           blocks_per_slot, sampling=False, kernels=None):
    """(paged_prefill, paged_decode) for a ``NemotronHConfig`` or a
    ``FalconH1Config``. Pure and shape-stable; ``kernels=None`` asks
    ``decode_kernels``."""
    import jax
    import jax.numpy as jnp

    from ...text.stacked_lm import block_of
    from .shell import build_paged_programs, flat

    block = block_of(cfg)

    if kernels is None:
        kernels = decode_kernels(cfg, num_slots, block_size)
    S = int(num_slots)
    NB, BS, MB = int(num_blocks), int(block_size), int(blocks_per_slot)

    def prefill_body(params, tokens, tail_len, start, slot, bt_row, cache):
        k, v, conv, ssm = cache
        B = tokens.shape[1]
        access = PagedAccess(cfg, S, NB, BS, MB, bt_row=bt_row)
        with device_scope("embed"):
            x = block.embed(cfg, params, tokens)             # [1, B, h]
        positions = (start + jnp.arange(B, dtype=jnp.int32))[None]
        mine = (jax.lax.dynamic_index_in_dim(conv, slot, 1, False),
                jax.lax.dynamic_index_in_dim(ssm, slot, 1, False))
        x, (kf, vf, conv_s, ssm_s), _ = block.run_layers(
            cfg, params, x, positions, access, (flat(k), flat(v)) + mine,
            start, "prefill", length=tail_len)
        with device_scope("state_write"):
            conv = jax.lax.dynamic_update_index_in_dim(conv, conv_s, slot,
                                                       axis=1)
            ssm = jax.lax.dynamic_update_index_in_dim(ssm, ssm_s, slot,
                                                      axis=1)
        # ONE row through the head, as a [1, h] matmul: as a vector the
        # product is elementwise and XLA upcasts the whole head to f32
        last = block.lm_head(cfg, params, jax.lax.dynamic_slice_in_dim(
            x[0], tail_len - 1, 1, axis=0))[0]
        return last, (kf, vf, conv, ssm)

    def decode_body(params, toks, pos, tables, cache, state):
        k, v, conv, ssm = cache
        access = PagedAccess(cfg, S, NB, BS, MB, tables=tables)
        with device_scope("embed"):
            x = block.embed(cfg, params, toks)               # [S, h]
        # the expert-routing counters, where the model keeps them
        x, cache, counts = block.run_layers(
            cfg, params, x, pos, access,
            (flat(k), flat(v), conv, flat(ssm)), mode="decode",
            kernel=kernels, counts=state[0] if state else None)
        return block.lm_head(cfg, params, x), cache, \
            ((counts,) if state else ())

    return build_paged_programs(
        prefill_body, decode_body, cfg.vocab_size, sampling,
        park=MB * BS - 1, num_state=len(block.hybrid_cache_spec(cfg).state))
