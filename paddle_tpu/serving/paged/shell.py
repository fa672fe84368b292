"""What every paged program does AROUND a model's layers, written once:
the engine's positional calling convention, the flat addressing of the
pool, the tail that samples and keeps the slots' bookkeeping, and which
kernels a decode program runs. A served architecture's builder file
(``programs.py`` and the five ``*_programs.py``) holds what differs: how
a layer kind reaches its cache (``PagedAccess``), the kernels it needs,
and two bodies.

  ``prefill_body(params, tokens [1, B], tail_len, start, slot, bt_row
                 [MB], cache) -> (last [vocab], cache')``
      One request's run of ``tail_len`` tokens from position ``start``
      through the layers, and the logits of the ONE row that is read
      (``tail_len - 1``): embed to head, whatever the head reads.

  ``decode_body(params, toks [S], pos [S], tables [S, MB], cache, state)
        -> (logits [S, vocab], cache', state')``
      One token a slot.

``cache`` is the pool's arrays as the engine holds them (``CacheSpec
.arrays``: ``[layers, NB, ...]`` a token array, ``[layers, S, ...]`` a
per-slot one), ``state`` what the decode program carries beside them
(``CacheSpec.state``). A body addresses an array flat where its layer
loop wants it so (``flat``: layer ``l``'s block ``b`` is row ``l*NB +
b``, a bitcast of the row-major pool) and hands back what it carried;
the shell gives every array its own shape again.

The engine's order is data (``ServingEngine._decode_dispatch_args``):
the cache arrays, then the state, then the sampler's four. The programs
split their arguments by those counts and spell no array by name:

  ``paged_prefill(params, tokens, tail_len, start, slot, final, bt_row,
                  toks [S], pos [S], *cache[, seed, temp, topk, topp])
      -> (first [1], toks', pos', *cache')``
      Only a ``final != 0`` dispatch emits the first token and sets
      ``pos[slot] = start + tail_len``; an interior chunk PARKS the slot
      at ``park``, a position no live sequence feeds a token at, so the
      decode steps between two chunks write nothing a length mask shows.

  ``paged_decode(params, toks, pos, tables, *cache, *state[, seeds,
                 temps, topks, topps])
      -> (next [S], pos + 1, *cache', *state')``

They are NAMED so whatever wraps them: a jitted function's module is
named after it, and a trace's device time is found by the modules
``jit_paged_prefill`` and ``jit_paged_decode`` (PERF.md section 3).
"""
from ...profiler import device_scope


def flat(a):
    """``[layers, n, ...]`` -> ``[layers * n, ...]``."""
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


def _tail():
    # the ONE scope both programs' tails are staged under: the choice of
    # a token and the slots' bookkeeping (``lm_head_dev_ms_per_step``
    # joins device time to it)
    return device_scope("sample")


def _as_held(new, held):
    # an array the body did not address flat is left as it is
    return tuple(a.reshape(h.shape) for a, h in zip(new, held))


def build_paged_programs(prefill_body, decode_body, vocab_size, sampling,
                         park, num_state=0):
    """(paged_prefill, paged_decode) around a model's two bodies. Pure
    and shape-stable; ``sampling`` threads per-slot sampling parameters
    (``serving.sched.sampling``) through both, greedy is the default;
    ``num_state`` is ``len(CacheSpec.state)``."""
    import jax.numpy as jnp

    from ..sched.sampling import build_sampling_head

    head = build_sampling_head(vocab_size) if sampling else None

    def split(rest):
        return (rest[:-4], rest[-4:]) if sampling else (rest, None)

    def paged_prefill(params, tokens, tail_len, start, slot, final,
                      bt_row, toks, pos, *rest):
        cache, samp = split(rest)
        last, new = prefill_body(params, tokens, tail_len, start, slot,
                                 bt_row, cache)
        with _tail():
            if samp is None:
                first = jnp.argmax(last, -1).astype(jnp.int32)
            else:
                seed, temp, topk, topp = samp
                first = head(last[None], seed[None],
                             (start + tail_len - 1)[None], temp[None],
                             topk[None], topp[None])[0]
            toks = jnp.where(final > 0, toks.at[slot].set(first), toks)
            # final: the next decode writes this slot at prompt_len;
            # interior chunk: park
            pos = pos.at[slot].set(
                jnp.where(final > 0, start + tail_len, jnp.int32(park)))
        return (first[None], toks, pos) + _as_held(new, cache)

    def paged_decode(params, toks, pos, tables, *rest):
        rest, samp = split(rest)
        n = len(rest) - num_state
        cache, state = rest[:n], rest[n:]
        logits, new, state = decode_body(params, toks, pos, tables, cache,
                                         state)
        with _tail():
            if samp is None:
                nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            else:
                seeds, temps, topks, topps = samp
                nxt = head(logits, seeds, pos, temps, topks, topps)
        return (nxt, pos + jnp.int32(1)) + _as_held(new, cache) \
            + tuple(state)

    return paged_prefill, paged_decode


def resolve_decode_kernels(checks):
    """Whether a decode program runs its Pallas kernels: yes on any
    backend that has Mosaic, and then a shape one of them cannot take is
    refused here, by name; no on the CPU (the ``jnp`` formulations),
    unless one of the ops is forced to interpret its kernel.

    ``checks``: ``(ops module, kernel name, what it is given, the
    values, viable)`` a kernel the model needs, ``viable`` a thunk over
    the module's ``kernel_viable`` (true where the model has no layer of
    that kind). The first that fails is the one named."""
    import jax
    if jax.default_backend() == "cpu" and not any(
            ops._FORCE_INTERPRET[0] for ops, *_ in checks):
        return False
    for ops, kernel, what, given, viable in checks:
        if not viable():
            raise ValueError(
                f"{kernel} cannot take ({what}) = "
                f"({', '.join(str(g) for g in given)}): "
                f"ops.{ops.__name__.rpartition('.')[2]}.kernel_viable")
    return True
