"""Plain reference of the ``ouro`` family's forward pass: a LOOPED
language model ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741; ``model_type: ouro``).

Straightforward ``jax.numpy`` in float32 with ``precision=HIGHEST``
matmuls, over ONE whole sequence: no cache, no kernel, no batching, and
no import of the program under test. Written from the keys of the
published ``config.json`` (in ``code`` below); what is NOT in a key is
marked † and listed again at the end.

  ``h = E[ids]``, ``E [vocab_size, hidden_size]``; a head ``W_out
  [hidden_size, vocab_size]`` of its own (``tie_word_embeddings``
  false).
  For pass ``r`` in ``0 .. total_ut_steps - 1``, for layer ``l`` in ``0
  .. num_hidden_layers - 1``, with the SAME weights of layer ``l`` in
  every pass:
    ``a = rms(h; n1_l)``; ``q, k, v = a Wq_l, a Wk_l, a Wv_l`` as
    ``num_attention_heads`` / ``num_key_value_heads`` heads of
    ``head_dim``, no bias†; query head ``i`` reads KV head ``i //
    (heads / kv heads)``. Rotary positions over the WHOLE head in
    half-split pairs† ``(i, i + head_dim / 2)``, turned by ``t *
    rope_theta**(-2i / head_dim)`` (``rope_scaling`` null). Causal
    ``softmax(q k^T / sqrt(head_dim)) v`` as ONE ``[T, T]`` matrix a
    head under the mask ``j <= t``, over the keys and values of THIS
    pass of this layer (every ``layer_types`` entry is
    ``full_attention``; ``sliding_window`` null, ``use_sliding_window``
    false; ``max_window_layers`` unread);
    ``h = h + rms(attn Wo_l; n2_l)``†; ``m = rms(h; n3_l)``;
    ``h = h + rms((silu(m Wg_l) * (m Wu_l)) Wd_l; n4_l)``†
    (``hidden_act`` silu, ``intermediate_size``, RMS norm with a gain,
    eps ``rms_norm_eps``);
  after the last layer of EVERY pass: ``h = rms(h; n_f)``†, the model's
  final norm, and the normed state is what the next pass starts from†;
  ``g_r = sigmoid(h . w_gate + b_gate)``† (the exit gate, ``[hidden_size
  -> 1]`` with a bias).
  Exit†: ``p_r = g_r prod_{j<r} (1 - g_j)`` for ``r`` below the last
  pass, the last pass takes the rest of the mass; a token is read from
  the first pass whose cumulative ``p`` reaches ``early_exit_threshold``
  (published: 1, so every token is read from the last pass), else from
  the last. ``logits = h_exit W_out`` (the norm is already in
  ``h_exit``).

† NOT in a key of ``config.json`` (the configuration file lists these
under ``assumed``); from the family's paper and its published
``modeling_ouro.py``:
  * FOUR norms a layer ("sandwich"): one before each sublayer and one on
    its output before the residual add;
  * the final norm is applied after EVERY pass, and the next pass starts
    from the normed state;
  * the exit gate is one linear map of the normed state to a scalar,
    with a bias, through a sigmoid, and the exit distribution and rule
    above;
  * no projection bias; rotary pairs half-split (which lanes pair is a
    permutation of the columns of Wq and Wk alike, which seeded random
    weights cannot tell apart).

Departure, on purpose: computed in blocks so that 2.67 B parameters and
2.5 k positions fit beside nothing else on one chip: a layer's weights
are upcast one layer at a time (``lax.scan`` over the stacked leaves),
attention runs over blocks of query rows (each against ALL keys under
the mask), the head over blocks of rows; the numbers are those of the
unblocked formulas.

Weights: ``benchmarks/weights_ouro.py`` (``wqkv``'s output axis is (q
heads | k heads | v heads) x ``head_dim``).

``precision`` rounds every matmul operand to a lower type first
(products still accumulate in float32): ``"float32"`` is the reference;
``"bfloat16"`` what the configuration states; ``"float8"`` (e4m3,
scaled per tensor) the control, the nearest precision below it.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def _scaled_cast(x, dtype, largest):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _round_to(precision):
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        return lambda x: _scaled_cast(x, jnp.float8_e4m3fn, 448.0)
    raise ValueError(f"unknown precision {precision!r}")


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _check(cfg):
    kinds = set(cfg.get("layer_types") or ()) - {"full_attention"}
    bad = [k for k, on in (
        ("rope_scaling", cfg.get("rope_scaling") is not None),
        ("sliding_window", cfg.get("sliding_window") is not None),
        ("use_sliding_window", cfg.get("use_sliding_window", False)),
        ("layer_types", bool(kinds)),
        ("hidden_act", cfg.get("hidden_act", "silu") != "silu"),
        ("tie_word_embeddings", cfg.get("tie_word_embeddings", False)),
    ) if on]
    if bad:
        raise NotImplementedError(f"the reference does not cover {bad}")


def _rope(x, pos, theta):
    """x ``[T, n, d]``: the whole head turned, pairs (i, i + d/2)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv        # [T,1,d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _layer(x, p, cfg, mm, row_block):
    """One layer over x ``[T, h]`` with ONE layer's weights ``p``."""
    T = x.shape[0]
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(T, dtype=jnp.int32)
    a = _rms(x, _f32(p["n1"]), eps)
    qkv = mm(a, _f32(p["wqkv"]))
    q = _rope(qkv[:, :nq * hd].reshape(T, nq, hd), pos, theta)
    k = _rope(qkv[:, nq * hd:(nq + nkv) * hd].reshape(T, nkv, hd), pos,
              theta)
    v = qkv[:, (nq + nkv) * hd:].reshape(T, nkv, hd)
    # the KV heads repeated for their query heads
    kt = jnp.repeat(k, nq // nkv, axis=1).transpose(1, 2, 0)  # [nq,hd,T]
    vt = jnp.repeat(v, nq // nkv, axis=1).transpose(1, 0, 2)  # [nq,T,hd]

    def rows(args):
        qb, pb = args                                 # [rb, nq, hd], [rb]
        s = mm(qb.transpose(1, 0, 2), kt) * hd ** -0.5         # [nq,rb,T]
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s,
                      -jnp.inf)
        return mm(jax.nn.softmax(s, -1), vt).transpose(1, 0, 2)

    rb = row_block if T % row_block == 0 else T
    o = lax.map(rows, (q.reshape(T // rb, rb, nq, hd),
                       pos.reshape(T // rb, rb))).reshape(T, nq * hd)
    x = x + _rms(mm(o, _f32(p["wo"])), _f32(p["n2"]), eps)
    m = _rms(x, _f32(p["n3"]), eps)
    y = mm(_silu(mm(m, _f32(p["wg"]))) * mm(m, _f32(p["wu"])),
           _f32(p["wd"]))
    return x + _rms(y, _f32(p["n4"]), eps)


def exit_distribution(gates):
    """``p [R, T]`` from the gates ``[R, T]`` of every pass."""
    p, left = [], jnp.ones_like(gates[0])
    for r in range(gates.shape[0] - 1):
        p.append(gates[r] * left)
        left = left * (1.0 - gates[r])
    return jnp.stack(p + [left])


def hidden_states(w, ids, cfg, precision="float32", row_block=256):
    """(``h_exit [T, h]``: every position's state at the pass it is read
    from, final norm in it; ``p [R, T]``: the exit distribution; the
    pass read ``[T]``) of one sequence ``ids [T]``, all float32."""
    _check(cfg)
    q = _round_to(precision)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=_HI)

    x = _f32(w["wemb"][ids])
    hs, gates = [], []
    for _ in range(cfg["total_ut_steps"]):
        x, _ = lax.scan(
            lambda x, p: (_layer(x, p, cfg, mm, row_block), None), x,
            w["layers"])
        x = _rms(x, _f32(w["norm_f"]), cfg["rms_norm_eps"])
        hs.append(x)
        gates.append(jax.nn.sigmoid(
            jnp.sum(x * _f32(w["w_gate"]), -1) + _f32(w["b_gate"])[0]))
    p = exit_distribution(jnp.stack(gates))
    short = jnp.cumsum(p, 0)[:-1] < cfg.get("early_exit_threshold", 1.0)
    at = jnp.sum(short, 0).astype(jnp.int32)
    h = jnp.take_along_axis(jnp.stack(hs), at[None, :, None], 0)[0]
    return h, p, at


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _score(w, ids, probe, cfg, precision):
    cfg = dict(cfg)
    q = _round_to(precision)
    h, _, _ = hidden_states(w, ids, cfg, precision)
    T = ids.shape[0]
    head = q(_f32(w["head"]))
    rb = 512 if T % 512 == 0 else T

    def rows(args):
        hb, pb = args
        lg = jnp.matmul(q(hb), head, precision=_HI)            # [rb, V]
        at = jnp.take_along_axis(lg, pb[:, None], axis=1)[:, 0]
        return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)

    best, at, first = lax.map(rows, (h.reshape(T // rb, rb, -1),
                                     probe.reshape(T // rb, rb)))
    return best.reshape(T), at.reshape(T), first.reshape(T)


def _hashable(cfg):
    """The configuration as a static argument: scalars as they are, the
    per-layer list as a tuple, a group (``rope_scaling``) as its items."""
    def frozen(v):
        if isinstance(v, dict):
            return tuple(sorted((k, frozen(x)) for k, x in v.items()))
        return tuple(v) if isinstance(v, list) else v
    return tuple(sorted((k, frozen(v)) for k, v in cfg.items()))


def score(w, ids, probe, cfg, precision="float32"):
    """For one sequence ``ids [T]`` and probe tokens ``[T]``: at each
    position the best next-token logit, the logit of ``probe[t]`` and
    the best token (the caller aligns ``probe[t]`` with the token that
    followed position t)."""
    return _score(w, ids, probe, _hashable(cfg), precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _logits(w, ids, cfg, precision):
    cfg = dict(cfg)
    q = _round_to(precision)
    h, p, at = hidden_states(w, ids, cfg, precision)
    return jnp.matmul(q(h), q(_f32(w["head"])), precision=_HI), p, at


def logits(w, ids, cfg, precision="float32"):
    """(``[T, vocab]`` logits, the exit distribution ``[R, T]``, the
    pass read ``[T]``) of one sequence (for the tests at small sizes)."""
    return _logits(w, ids, _hashable(cfg), precision)
