"""AOT-compilable k-token verify program (speculative decoding,
Leviathan et al.): ONE more program that runs the
decode forward over ``[slots, k+1]`` positions in a single dispatch —
the slot's last accepted token plus its k drafted continuations — so
the HBM-bound parameter + KV read every decode dispatch pays is
amortized over up to k+1 emitted tokens.

  ``paged_spec_verify(params, toks [S], pos [S], drafts [S, k],
                      dlen [S], tables [S, MB], kc, vc)``
      -> (out [S, k+1], accepted [S], toks', pos', kc, vc)

Shapes are FIXED: drafts pad to width k and ``dlen`` carries each
slot's real draft length (0 = this slot behaves exactly like a plain
decode step inside the verify program — the per-slot fallback costs
no extra program). ``out[s, i]`` is the greedy argmax after consuming
input position i; draft i is accepted iff it equals ``out[s, i]`` and
every earlier draft was accepted (longest-accepted-prefix), so
``accepted = sum(cumprod(match))`` on device, the next chained token
is the "bonus" ``out[s, accepted]``, and positions advance by
``accepted + 1`` — toks'/pos' chain device-side exactly like the
plain decode step, and the engine reads (out, accepted) back at
harvest to emit 1..k+1 tokens.

Greedy parity with generate() is by construction: query i attends
(per-query causal mask, ops.attention.cached_paged_block_attention)
over the live prefix plus candidates 0..i only, so its logits are
conditioned purely on tokens that are accepted whenever position i's
output is harvested. Rejected-tail K/V rows land in the cache but are
invisible and then legitimately overwritten: the next dispatch writes
its rows before attending (the same recycled-slot/parked-row
invariant the chunked-prefill program pins).

Write discipline: PR 7's whole-position ``wpos`` clamp per candidate
row, with rows past the slot's addressable range routed to the
reserved trash block (index 0), so a parked/overflowing slot's stray
rows land in garbage instead of cycling over live blocks.
"""


def build_paged_spec_verify_fn(cfg, num_slots, block_size, num_blocks,
                               blocks_per_slot, k):
    """The verify program for a GPT decode config (key
    ``("paged_spec_verify",)``). Pure and shape-stable; the engine
    AOT-compiles it ONCE alongside the plain decode. The cache is
    addressed through the fixed-shape block table with candidate rows
    scattered straight into each slot's privately owned blocks (decode positions are never inside shared-prefix
    blocks) and overflow rows trash-routed."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ...ops import attention as attn_ops
    from ...text.models import _decode_forward_builder

    nh = cfg.num_heads
    hd = cfg.hidden_size // nh
    hidden = cfg.hidden_size
    ln, _ = _decode_forward_builder(nh, hd, hidden)
    BS = int(block_size)
    MB = int(blocks_per_slot)
    C = MB * BS
    t = int(k) + 1
    assert 1 <= t <= C, f"spec_k+1 ({t}) must fit the slot row ({C})"

    def paged_spec_verify(params, toks, pos, drafts, dlen, tables, kc,
                          vc):
        S = toks.shape[0]
        tok_blk = jnp.concatenate([toks[:, None], drafts], axis=1)
        qpos = pos[:, None] + jnp.arange(t)[None, :]     # [S, t]
        x = params["wemb"][tok_blk] + params["pemb"][
            jnp.minimum(qpos, params["pemb"].shape[0] - 1)]
        # PR-7 wpos discipline, per candidate row: clamp the WHOLE
        # position, then route rows past the slot's addressable range
        # to the trash block so parked/overflowing slots never touch a
        # live block (plain decode pins to the private last entry; with
        # t rows that would collide, so garbage goes to garbage)
        valid = qpos <= jnp.int32(C - 1)                 # [S, t]
        wpos = jnp.minimum(qpos, jnp.int32(C - 1))
        col = wpos // jnp.int32(BS)
        bidx = jnp.take_along_axis(tables, col, axis=1)  # [S, t]
        bidx = jnp.where(valid, bidx, jnp.int32(0))
        off = wpos % jnp.int32(BS)

        def body(carry, inp):
            x = carry
            p, kcl, vcl = inp
            h_ = ln(x, p["ln1_w"], p["ln1_b"])
            qkv = h_ @ p["qkv_w"] + p["qkv_b"]
            qkv = qkv.reshape(S, t, 3, nh, hd).transpose(2, 0, 3, 1, 4)
            q, k_, v = qkv[0], qkv[1], qkv[2]     # [S, nh, t, hd]
            # advanced-index scatter: [S, t] block rows x offsets take
            # [S, t, nh, hd] values
            kcl = kcl.at[bidx, :, off].set(k_.transpose(0, 2, 1, 3))
            vcl = vcl.at[bidx, :, off].set(v.transpose(0, 2, 1, 3))
            o = attn_ops.cached_paged_block_attention(q, kcl, vcl,
                                                      tables, qpos)
            o = o.transpose(0, 2, 1, 3).reshape(S, t, hidden)
            x = x + (o @ p["out_w"] + p["out_b"])
            h2 = ln(x, p["ln2_w"], p["ln2_b"])
            m = jax.nn.gelu(h2 @ p["fc1_w"] + p["fc1_b"],
                            approximate=True)
            return x + (m @ p["fc2_w"] + p["fc2_b"]), (kcl, vcl)

        x, (kc, vc) = lax.scan(body, x, (params["stacked"], kc, vc))
        logits = ln(x, params["lnf_w"], params["lnf_b"]) \
            @ params["head"]                       # [S, t, vocab]
        out = jnp.argmax(logits, -1).astype(jnp.int32)   # [S, t]
        return _accept(jnp, out, drafts, dlen, pos, kc, vc)

    return paged_spec_verify


def _accept(jnp, out, drafts, dlen, pos, kc, vc):
    """Device-side longest-accepted-prefix: draft i counts iff it is a
    real draft (i < dlen) AND matches the model's greedy choice AND
    every earlier draft counted; the chained next token is the bonus
    ``out[s, accepted]`` and positions advance by accepted + 1."""
    k = drafts.shape[1]
    m = (out[:, :k] == drafts) & \
        (jnp.arange(k)[None, :] < dlen[:, None])
    # x64 note: jnp.sum widens int32 reductions to int64 when x64 is
    # on (this package enables it); pos/toks must stay int32 so the
    # chained outputs feed the next dispatch's compiled signature
    accepted = jnp.sum(jnp.cumprod(m.astype(jnp.int32), axis=1),
                       axis=1).astype(jnp.int32)          # [S]
    nxt = jnp.take_along_axis(out, accepted[:, None], axis=1)[:, 0]
    return (out, accepted, nxt,
            (pos + accepted + 1).astype(jnp.int32), kc, vc)
