"""The engine's paged prefill and decode programs for a LOOPED model
(``text.ouro``): one stack of layers run ``R`` times over the same
weights, every pass of every layer with keys and values of its own. The
pool's arrays are ``k, v [R * L, NB, nkv, BS, hd]``: pass ``r`` of layer
``l`` is entry ``r * L + l``, reached through the ONE block table a
slot as ``tables + (r * L + l) * NB``. Signatures, slot
bookkeeping and sampling are ``shell.py``'s, as every model's are, the
model's block is IMPORTED, not written out again, and keys and values are
reached through ``hybrid_programs.PagedAccess`` (``attn_prefill`` /
``attn_decode``, whose index is the entry; of its state ``(k, v, conv,
ssm)`` this model has the first two).

  ``paged_prefill(params, tokens [1, B], tail_len, start, slot, final,
                  bt_row [MB], toks [S], pos [S], k, v[, samp...])
      -> (first [1], toks', pos', k, v)``
      One request's run of ``tail_len`` tokens from position ``start``:
      a whole short prompt, the uncached tail behind a shared prefix, or
      one chunk of a long prompt. ALL passes run over the run before the
      next chunk is dispatched: pass ``r`` of a run attends pass ``r``'s
      entries of the positions before it (a shared prefix's blocks, an
      earlier chunk's) and starts from pass ``r - 1`` of its own rows.
      The spec has token arrays only, so a cached block means the same
      to every request that reaches it and the radix index shares it.

  ``paged_decode(params, toks [S], pos [S], tables [S, MB], k, v,
                 loop_counts, loop_gate_mass[, samp...])
      -> (next [S], pos + 1, k, v, loop_counts', loop_gate_mass')``
      One token a slot through every pass: ``R * L`` calls of
      ``ops.paged_attention.paged_write_attention`` (the kernel places
      the entry itself; the ``jnp`` path writes blocks first), the pool
      in the carry of the layer loop and of the pass loop. The loop's
      counters ride beside the cache (``CacheSpec.state``): tokens of
      LIVE slots by the pass they were read from, the passes run for
      them, and the exit distribution's summed mass a pass.

Parked and released slots: as in ``hybrid_programs.py``; neither is
counted.
"""
from ...profiler import device_scope


def decode_kernel(cfg, block_size):
    """Whether the decode program runs the paged attention kernel
    (``shell.resolve_decode_kernels``)."""
    from ...ops import paged_attention as paged_ops
    from .shell import resolve_decode_kernels
    given = (cfg.num_kv_heads, cfg.head_dim, block_size, cfg.cache_dtype)
    return resolve_decode_kernels([
        (paged_ops, "paged_decode_attn",
         "kv heads, head dim, block_size, cache dtype", given,
         lambda: paged_ops.kernel_viable(*given))])


def build_paged_looped_fns(cfg, num_slots, block_size, num_blocks,
                           blocks_per_slot, sampling=False, kernel=None):
    """(paged_prefill, paged_decode) for an ``OuroConfig``. Pure and
    shape-stable; ``kernel=None`` asks ``decode_kernel``."""
    import jax
    import jax.numpy as jnp

    from ...text import ouro as block
    from .hybrid_programs import PagedAccess
    from .pool import TRASH_BLOCK
    from .shell import build_paged_programs, flat

    if kernel is None:
        kernel = decode_kernel(cfg, block_size)
    S = int(num_slots)
    NB, BS, MB = int(num_blocks), int(block_size), int(blocks_per_slot)
    C = MB * BS
    R = cfg.num_passes

    def prefill_body(params, tokens, tail_len, start, slot, bt_row, cache):
        k, v = cache
        B = tokens.shape[1]
        access = PagedAccess(cfg, S, NB, BS, MB, bt_row=bt_row)
        with device_scope("embed"):
            x = params["wemb"][tokens]                       # [1, B, h]
        positions = (start + jnp.arange(B, dtype=jnp.int32))[None]
        hs, p, (kf, vf, _, _) = block.run_passes(
            cfg, params, x, positions, access,
            (flat(k), flat(v), None, None), start, "prefill")
        # ONE row through the gate's choice and the head, as a [1, h]
        # matmul (hybrid_programs.py)
        row = jax.lax.dynamic_slice_in_dim(hs[:, 0], tail_len - 1, 1, 1)
        last = block.head(params, block.read_exit(
            cfg, row, jax.lax.dynamic_slice_in_dim(
                p[:, 0], tail_len - 1, 1, 1))[0])[0]
        return last, (kf, vf)

    def decode_body(params, toks, pos, tables, cache, state):
        k, v = cache
        counts, mass = state
        access = PagedAccess(cfg, S, NB, BS, MB, tables=tables)
        with device_scope("embed"):
            x = params["wemb"][toks]                         # [S, h]
        hs, p, (kf, vf, _, _) = block.run_passes(
            cfg, params, x, pos, access, (flat(k), flat(v), None, None),
            mode="decode", kernel=kernel)
        h, at = block.read_exit(cfg, hs, p)
        with device_scope("loop/gate"):
            # a slot that decodes: it holds blocks and is not parked
            # between the chunks of its prefill
            live = jnp.logical_and(
                jnp.any(tables != TRASH_BLOCK, axis=1),
                pos < jnp.int32(C - 1))
            exits = jnp.sum(jnp.logical_and(
                at[None, :] == jnp.arange(R, dtype=jnp.int32)[:, None],
                live[None, :]), axis=1, dtype=jnp.int32)
            counts = counts + jnp.concatenate(
                [exits, jnp.int32(R) * jnp.sum(live, dtype=jnp.int32)[None]])
            mass = mass + jnp.sum(jnp.where(live[None, :], p, 0.0), axis=1)
        return block.head(params, h), (kf, vf), (counts, mass)

    return build_paged_programs(
        prefill_body, decode_body, cfg.vocab_size, sampling, park=C - 1,
        num_state=len(block.looped_cache_spec(cfg).state))
