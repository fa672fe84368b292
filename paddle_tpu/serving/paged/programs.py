"""The GPT's AOT-compilable prefill/decode programs over the paged cache.

Same decode math as ``GPTForCausalLM.generate()`` (its ``forward_t``,
from ``_decode_forward_builder``, is the parity oracle the tests hold
both programs to), with the cache addressed through the fixed-shape
block table instead of a slot-contiguous region:

  ``paged_prefill(params, tokens [1, B], tail_len, start, slot, final,
                  bt_row [MB], toks [S], pos [S], kc, vc[, samp...])``
      One request's UNCACHED TAIL (or, under chunked prefill, one
      CHUNK of it) prefills in one dispatch, and its work follows the
      run, never the slot's capacity (ISSUE 38). A layer computes the
      bucket's q, k, v; attends the run over its OWN keys causally
      (``ops.attention.paged_prefill_attention``: the flash forward
      kernel where it takes the bucket, no score tensor in HBM) and,
      only where ``start > 0`` (a radix prefix hit, a later chunk),
      walks the cached blocks below ``start`` through ``bt_row`` and
      merges the two by their softmax statistics; then writes the
      run's k and v into the ``B / BS + 1`` table entries from
      ``start // BS`` on by whole-block read-modify-write into the
      carried flat pool (as decode's ``jnp`` path does; one entry more
      than the run holds because an end-aligned final chunk starts
      inside a block).
      Only the run's REAL positions ``start .. start + tail_len``
      change: rows below ``start`` in the first block, the bucket's
      padding rows and every block the row's padding entries name
      (trash, as a column past the row is) are written back as they
      were read, so a shared (pinned) prefix block, which is always
      whole and below ``start``, is never touched. The head runs over
      the ONE row whose logits are used. ``start``, ``tail_len`` and
      ``final`` are TRACED scalars: every (prefix length, tail length,
      chunk index) triple reuses the one compiled program per tail
      bucket B — prefix AND chunk variety cost zero compiles. Only a
      ``final != 0`` dispatch emits the first token and sets
      ``pos[slot] = start + tail_len``; interior chunk dispatches PARK
      the slot at the row's last addressable position instead
      (``MB*BS - 1`` — trash-backed or legitimately overwritten before
      its length mask exposes it), so the decode steps interleaving
      between chunks never write inside prompt rows earlier chunks
      filled. ``tests/test_chip_compile.py`` holds the compiled
      program to this at the 1.3B cell's four buckets (aliased pool,
      no view of a slot at capacity, no scores over it).

  ``paged_decode(params, toks [S], pos [S], tables [S, MB], kc, vc
                 [, samp...])``
      One fused program advancing every slot a token: each slot's new
      K/V row goes to block ``tables[s, pos//BS]`` at offset ``pos %
      BS`` (always a privately-owned block: decode positions are >=
      prompt_len and only full-prompt blocks are ever shared), then
      the slot attends under the per-slot length mask:
      ``ops.paged_attention.paged_write_attention``, one function for
      every paged decode program. A parked or released slot's entry is
      nobody's (``live_write_pos``: -1). The Pallas kernel, which
      places the entry itself, writes nothing for it; the ``jnp`` block
      write pins it to the row's last entry (``MB*BS - 1``), which is
      always private, never a shared prefix block (see the invariant
      asserted in ``pool.acquire``), or trash.

How the decode program carries the pool (ISSUE 26, ISSUE 43). The layer
loop is a ``lax.scan`` over (stacked weights, layer index) with the
pool in its CARRY, addressed flat as ``[L*NB, nh, BS, hd]`` (a reshape
of the row-major pool: a bitcast); layer ``l`` reads and writes block
``b`` as flat row ``l*NB + b``, so attention gets the flat pool and
``tables + l*NB`` (layer ``l``'s trash block is row ``l*NB + trash``).
The pool as a scanned input and stacked output was sliced apart and
restacked every step: five pool-sized passes, and a second pool in
memory, because stacked outputs cannot alias scanned inputs. On a chip
the kernel takes the carried pool as an aliased operand and writes the
one tile that holds the new entry from the buffer it attends over. The
``jnp`` write (the CPU, shapes the kernel refuses) is a whole-block
read-modify-write (``write_block_rows``: gather the S current blocks,
put the new row in, scatter the blocks back): a scatter whose window is
every trailing dimension leaves the pool's layout row-major from
parameter to result, so the donated buffer is updated in place; the row
scatter ``at[fb, :, off]`` made XLA relayout the whole pool around the
loop. ``tests/test_chip_compile.py`` holds both compiled programs to
this (aliased pool, small temporaries, no pool-shaped copy, no gathered
block set in the kernel's program).

Scatter/gather safety: table-row padding and released rows point at
the reserved trash block, so pad-entry writes land in garbage, and the
length mask keeps garbage reads at exactly-zero softmax weight — the
same recycled-slot invariant as a contiguous cache, at block granularity.

The signatures, the sampling tail (``sampling=True`` threads per-slot
seeds / temps / top-k / top-p through both programs; greedy is the
default) and the slots' bookkeeping are ``shell.py``'s, as every
model's are; what is here is the GPT's two bodies, embed to head.

``attn_kernel`` is the decode program's attention: True = the Pallas
paged kernel (ops.paged_attention) that reads each slot's LIVE K/V
blocks in place from the carried flat pool, False = the XLA gather of
every slot's whole table row (``cached_paged_attention``: the CPU's
path, the path of shapes the kernel refuses, and the parity oracle). A
trace-time branch, so the program key, its signature and the
zero-steady-state-compile contract are the same on either. The engine
chooses once at build time from ``kernel_viable``; nobody sets it by
hand, and there is no second, quiet fallback here. The prefill
program's kernel is chosen the same way, once, when a bucket's program
is traced, from the bucket's shape and the backend
(``ops.attention.causal_attention_lse``: the flash forward kernel from
256 positions on in steps of 128, ``jnp`` for the bucket of 128, odd
chunk widths and the CPU).
"""
from ...profiler import device_scope


def build_paged_fns(cfg, num_slots, block_size, num_blocks,
                    blocks_per_slot, sampling=False, attn_kernel=False):
    """(paged_prefill, paged_decode) for a GPT decode config. Pure and
    shape-stable; the engine AOT-compiles them (decode once, prefill
    once per tail bucket)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ...ops import attention as attn_ops
    from ...ops import paged_attention as paged_attn_ops
    from ...text.models import _decode_forward_builder
    from .pool import TRASH_BLOCK
    from .shell import build_paged_programs, flat

    nh = cfg.num_heads
    hd = cfg.hidden_size // nh
    hidden = cfg.hidden_size
    ln, _ = _decode_forward_builder(nh, hd, hidden)
    L = cfg.num_layers
    BS = int(block_size)
    MB = int(blocks_per_slot)

    def mlp(x, p):
        with device_scope("mlp"):
            h2 = ln(x, p["ln2_w"], p["ln2_b"])
            m = jax.nn.gelu(h2 @ p["fc1_w"] + p["fc1_b"], approximate=True)
            return x + (m @ p["fc2_w"] + p["fc2_b"])

    def prefill_body(params, tokens, tail_len, start, slot, bt_row, cache):
        # tokens [1, B] right-padded tail; start = cached prefix length
        kc, vc = cache
        B = tokens.shape[1]
        NB = kc.shape[1]
        with device_scope("embed"):
            at = start + jnp.arange(B, dtype=jnp.int32)
            x = params["wemb"][tokens[0]] + params["pemb"][
                jnp.minimum(at, params["pemb"].shape[0] - 1)]   # [B, h]
        # where the run's keys and values go: the table entries from
        # start // BS on, one more than the run's own blocks because a
        # chunk may start inside a block. A column past the row goes to
        # the trash block like the row's own padding
        nW = (B + 2 * BS - 2) // BS
        off = start % jnp.int32(BS)
        wcol = start // jnp.int32(BS) + jnp.arange(nW, dtype=jnp.int32)
        wblk = jnp.where(wcol < MB, bt_row[jnp.minimum(wcol, MB - 1)],
                         jnp.int32(TRASH_BLOCK))
        # of those blocks' rows only the run's REAL positions change:
        # what lies below start (another chunk's, never a shared block:
        # a prefix is whole blocks) and the bucket's padding keep what
        # the pool holds, so trash is written back as it was read
        wrow = jnp.arange(nW * BS, dtype=jnp.int32)
        mine = ((wrow >= off) & (wrow < off + tail_len)).reshape(
            nW, 1, BS, 1)

        def blocks(new):
            # [nh, B, hd] -> [nW, nh, BS, hd], row t at flat row off + t
            buf = lax.dynamic_update_slice(
                jnp.zeros((nW * BS, nh, hd), new.dtype),
                new.transpose(1, 0, 2), (off, jnp.int32(0), jnp.int32(0)))
            return buf.reshape(nW, BS, nh, hd).transpose(0, 2, 1, 3)

        def body(carry, inp):
            x, kf, vf = carry
            p, layer = inp
            base = layer * jnp.int32(NB)
            with device_scope("attn"):
                h_ = ln(x, p["ln1_w"], p["ln1_b"])
                qkv = (h_ @ p["qkv_w"] + p["qkv_b"]).reshape(
                    B, 3, nh, hd).transpose(1, 2, 0, 3)   # [3, nh, B, hd]
                # attention sees keys and values as the cache holds them
                q, k, v = qkv[0], qkv[1].astype(kf.dtype), \
                    qkv[2].astype(vf.dtype)
                o = attn_ops.paged_prefill_attention(
                    q, k, v, kf, vf, base + bt_row, start)
                with device_scope("kv_write"):
                    rows = base + wblk
                    kf = kf.at[rows].set(jnp.where(
                        mine, blocks(k), kf[rows]))
                    vf = vf.at[rows].set(jnp.where(
                        mine, blocks(v), vf[rows]))
                o = o.transpose(1, 0, 2).reshape(B, hidden)
                x = x + (o @ p["out_w"] + p["out_b"])
            return (mlp(x, p), kf, vf), None

        # the pool rides the layer loop as CARRIED state, flat, as in
        # the decode program: layer l's block b is row l*NB + b
        (x, kf, vf), _ = lax.scan(
            body, (x, flat(kc), flat(vc)),
            (params["stacked"], jnp.arange(L, dtype=jnp.int32)))
        # back in the pool's shape in front of the head, where this
        # program has always had it (the shell's own reshape is then
        # nothing, and the pinned jaxpr stays as it is)
        kc, vc = kf.reshape(kc.shape), vf.reshape(vc.shape)
        with device_scope("lm_head"):
            # ONE row through the head, as a [1, h] matmul (as a vector
            # the product is elementwise and the head is upcast whole)
            row = lax.dynamic_slice_in_dim(x, tail_len - 1, 1, axis=0)
            last = (ln(row, params["lnf_w"], params["lnf_b"])
                    @ params["head"])[0]                       # [vocab]
        return last, (kc, vc)

    def decode_body(params, toks, pos, tables, cache, state):
        kc, vc = cache
        S = toks.shape[0]
        with device_scope("embed"):
            x = params["wemb"][toks] + params["pemb"][
                jnp.minimum(pos, params["pemb"].shape[0] - 1)]  # [S, h]
        # the pool rides the layer loop as CARRIED state, flat: layer
        # l's block b is row l*NB + b (module docstring)
        NB = kc.shape[1]
        kf, vf = flat(kc), flat(vc)
        # what attention may read of a slot: its positions so far, and
        # never more than the blocks its table row holds. A released
        # slot's position keeps counting while its row is all trash:
        # nothing of it is live (length 0), and the kernel, whose work
        # follows this length, passes it by instead of attending over a
        # full row of trash
        held = jnp.sum((tables != TRASH_BLOCK).astype(jnp.int32), axis=1)
        lengths = jnp.minimum(pos + 1, held * jnp.int32(BS))
        with device_scope("kv_write"):
            # where the step's entry goes: a parked or released slot's
            # nowhere anybody reads
            wpos = paged_attn_ops.live_write_pos(pos, lengths)

        def body(carry, inp):
            x, kf, vf = carry
            p, layer = inp
            base = layer * jnp.int32(NB)
            with device_scope("attn"):
                h_ = ln(x, p["ln1_w"], p["ln1_b"])
                qkv = h_ @ p["qkv_w"] + p["qkv_b"]
                qkv = qkv.reshape(S, 3, nh, hd).transpose(1, 0, 2, 3)
                q, k, v = qkv[0], qkv[1], qkv[2]          # [S, nh, hd]
                # the step's new entry, as the pool holds it; the
                # kernel places it (ops.paged_attention), the gather
                # path writes its block first
                with device_scope("kv_write"):
                    new = (k.astype(kf.dtype), v.astype(vf.dtype))
                # layer l's trash: l*NB + trash
                o, (kf, vf) = paged_attn_ops.paged_write_attention(
                    q, new, (kf, vf), tables + base, wpos, lengths,
                    attn_kernel)
                o = o.reshape(S, hidden)                  # concat heads
                x = x + (o @ p["out_w"] + p["out_b"])
            return (mlp(x, p), kf, vf), None

        (x, kf, vf), _ = lax.scan(
            body, (x, kf, vf),
            (params["stacked"], jnp.arange(L, dtype=jnp.int32)))
        # in front of the head, as in the prefill program
        kc, vc = kf.reshape(kc.shape), vf.reshape(vc.shape)
        with device_scope("lm_head"):
            logits = ln(x, params["lnf_w"], params["lnf_b"]) \
                @ params["head"]                          # [S, vocab]
        return logits, (kc, vc), state

    # an interior chunk parks the slot at the row's last addressable
    # position
    return build_paged_programs(prefill_body, decode_body, cfg.vocab_size,
                                sampling, park=MB * BS - 1)
