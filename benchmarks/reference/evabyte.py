"""Plain reference of the ``evabyte`` family's forward pass.

Straightforward ``jax.numpy`` in float32 with ``precision=HIGHEST``
matmuls, over ONE whole sequence, BY MASKS: no cache, no compaction of
anything, no kernel, no batching, and no import of the program under
test. The layer, all alike (``d`` the head size, ``W = window_size``,
``C = chunk_size``, positions ``t`` from 0):

  ``h = rms(x) (1 + g1)``; ``q, k, v = h Wq, h Wk, h Wv`` as heads of
  ``d``; ``q, k`` rotated over the whole head by half-split pairs
  (lanes ``i`` and ``i + d/2``) at base ``rope_theta``, position ``t``.
  For every chunk ``c`` (positions ``C c .. C c + C - 1``), per head with
  its learned vectors ``mu``, ``phi``:
      ``kbar_c = sum_j softmax_j(<k_j, mu> / sqrt(d)) k_j``
      ``vbar_c = sum_j softmax_j(<k_j, phi> / sqrt(d)) v_j``.
  A query at ``t`` in window ``w = t // W`` sees the positions ``s`` with
  ``W w <= s <= t`` and the chunks ``c < (W / C) w``, in ONE softmax of
  ``q.k_s / sqrt(d)`` and ``q.kbar_c / sqrt(d)`` over the values ``v_s``
  and ``vbar_c``. Then ``x <- x + o Wo``; ``h = rms(x) (1 + g2)``;
  ``x <- x + (silu(h Wgate) * (h Wup)) Wdown``.
After the last layer ``rms(x) (1 + g)`` and ``num_pred_heads`` heads:
head ``i`` (columns ``[i V, (i + 1) V)`` of ``head``) scores the byte
``i + 1`` positions on; head 0 is the next byte.

Not in the published ``config.json``, so DEPARTURES TO CHECK against the
released ``eva.py`` when a network is at hand (the configuration file
lists the same under ``assumed``):

* the pooling form above and its ``1 / sqrt(d)`` (EVA's control variates
  with the released code's learned ``adaptive_mu_k`` / ``adaptive_phi``);
* a window's summaries become visible when the NEXT window begins: the
  query's own window is never summarised to it;
* rotary positions by half-split pairs (``rotate_half``), absolute
  position ``t``, keys rotated BEFORE they are pooled;
* the heads' order in the head matrix (head-major);
* ``mu``, ``phi`` drawn ``clip(N(0, 1), -1, 1) / sqrt(d)``, weights ``N(0,
  init_std)``, norm gains ``N(0, 0.02)`` about 0 under the unit offset
  (``weights_evabyte.py``);
* ``fp32_skip_add``, ``fp32_logits``, ``mixedp_attn``, ``fp32_ln`` say in
  which precision the released code computes; a float32 reference has
  only one;
* computed in blocks so that 30 k positions fit beside the weights: one
  layer's weights are upcast at a time, rows go through the projections,
  the feed-forward part and the heads a block at a time, and a block of
  queries multiplies only the keys of its own window and every chunk's
  summary, under the masks above; the numbers are those of the unblocked
  formulas.

Weights: ``benchmarks/weights_evabyte.py``. ``precision`` rounds every
matmul operand to a lower type first (products still accumulate in
float32): ``"float32"`` is the reference; ``"bfloat16"`` what the
configuration states; ``"float8"`` (e4m3, scaled per tensor) the
control, the nearest precision below it.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_ROWS = 2048       # rows a block of the per-token parts
_QUERIES = 256     # query rows a block of attention


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


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _check(cfg):
    bad = [k for k, on in (
        ("attention_class", cfg.get("attention_class", "eva") != "eva"),
        ("num_chunks", cfg.get("num_chunks") is not None),
        ("window_size % chunk_size",
         cfg["window_size"] % cfg["chunk_size"] != 0),
        ("num_key_value_heads", cfg.get(
            "num_key_value_heads", cfg["num_attention_heads"])
         != cfg["num_attention_heads"]),
        ("rope_scaling", cfg.get("rope_scaling") is not None),
        ("attention_bias", cfg.get("attention_bias", False)),
        ("tie_word_embeddings", cfg.get("tie_word_embeddings", False)),
        ("hidden_act", cfg.get("hidden_act", "silu") != "silu"),
        ("norm_add_unit_offset", not cfg.get("norm_add_unit_offset",
                                             True)),
    ) if on]
    if bad:
        raise NotImplementedError(f"the reference does not cover {bad}")


def _rope(x, pos, theta):
    """x ``[T, H, d]``: lanes ``i`` and ``i + d/2`` turned by ``pos *
    theta**(-2i/d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _blocks(fn, rows, *arrays):
    """``fn`` over ``arrays`` (leading axis T, a multiple of the block)
    a block of rows at a time."""
    T = arrays[0].shape[0]
    rb = rows if T % rows == 0 else T
    out = lax.map(lambda a: fn(*a), tuple(
        a.reshape((T // rb, rb) + a.shape[1:]) for a in arrays))
    return jax.tree_util.tree_map(
        lambda o: o.reshape((T,) + o.shape[2:]), out)


def _attention(x, p, cfg, mm):
    T = x.shape[0]                       # a multiple of the window
    H = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // H
    W, C = cfg["window_size"], cfg["chunk_size"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(T, dtype=jnp.int32)
    wq, wk, wv, g1 = (_f32(p[n]) for n in ("wq", "wk", "wv", "norm1"))

    def qkv(xb, pb):
        h = _rms(xb, g1, eps)
        return _rope(mm(h, wq).reshape(-1, H, d), pb, theta), \
            _rope(mm(h, wk).reshape(-1, H, d), pb, theta), \
            mm(h, wv).reshape(-1, H, d)

    q, k, v = _blocks(qkv, _ROWS, x, pos)                  # [T, H, d]
    # every chunk's pooled pair, whether or not anybody may see it
    kc = k.reshape(T // C, C, H, d)
    vc = v.reshape(T // C, C, H, d)
    scale = d ** -0.5
    wk_ = jax.nn.softmax(jnp.einsum(
        "cjhd,hd->cjh", kc, _f32(p["mu"]), precision=_HI) * scale, axis=1)
    wv_ = jax.nn.softmax(jnp.einsum(
        "cjhd,hd->cjh", kc, _f32(p["phi"]), precision=_HI) * scale, axis=1)
    kbar = jnp.sum(wk_[..., None] * kc, axis=1)            # [T/C, H, d]
    vbar = jnp.sum(wv_[..., None] * vc, axis=1)
    chunk = jnp.arange(T // C, dtype=jnp.int32)
    kbar_t, vbar_t = kbar.transpose(1, 2, 0), vbar.transpose(1, 0, 2)

    def queries(qb, pb):
        # the block lies in one window (its size divides the window's)
        w0 = (pb[0] // W) * W
        kw = lax.dynamic_slice_in_dim(k, w0, W, axis=0)     # [W, H, d]
        vw = lax.dynamic_slice_in_dim(v, w0, W, axis=0)
        spos = w0 + jnp.arange(W, dtype=jnp.int32)
        qh = qb.transpose(1, 0, 2)                          # [H, rb, d]
        s_raw = mm(qh, kw.transpose(1, 2, 0)) * scale       # [H, rb, W]
        s_sum = mm(qh, kbar_t) * scale                    # [H, rb, T/C]
        s_raw = jnp.where(spos[None, None, :] <= pb[None, :, None],
                          s_raw, -jnp.inf)
        s_sum = jnp.where(chunk[None, None, :]
                          < ((pb // W) * (W // C))[None, :, None],
                          s_sum, -jnp.inf)
        pr = jax.nn.softmax(jnp.concatenate([s_raw, s_sum], -1), -1)
        o = mm(pr[..., :W], vw.transpose(1, 0, 2)) \
            + mm(pr[..., W:], vbar_t)
        return o.transpose(1, 0, 2).reshape(-1, H * d)

    rb = _QUERIES if W % _QUERIES == 0 else W
    o = _blocks(queries, rb, q, pos)
    wo = _f32(p["wo"])
    return x + _blocks(lambda ob: mm(ob, wo), _ROWS, o)


def _mlp(x, p, cfg, mm):
    g2 = _f32(p["norm2"])
    gate, up, down = (_f32(p[n]) for n in ("gate", "up", "down"))

    def rows(xb):
        h = _rms(xb, g2, cfg["rms_norm_eps"])
        a = mm(h, gate)
        return mm(a / (1.0 + jnp.exp(-a)) * mm(h, up), down)

    return x + _blocks(rows, _ROWS, x)


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def hidden_states(w, ids, cfg, precision="float32"):
    """Final-norm hidden states ``[T, h]`` (float32) of one sequence
    ``ids [T]``."""
    _check(cfg)
    q = _round_to(precision)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=_HI)

    T, W = ids.shape[0], cfg["window_size"]
    Tp = -(-T // W) * W
    # padded to whole windows: a later position is seen by nobody
    x = _f32(w["wemb"][jnp.pad(ids, (0, Tp - T))])
    for i in range(cfg["num_hidden_layers"]):
        p = _layer(w["layers"], i)
        x = _mlp(_attention(x, p, cfg, mm), p, cfg, mm)
    return _rms(x, _f32(w["norm_f"]), cfg["rms_norm_eps"])[:T]


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _score(w, ids, probe, cfg, precision):
    cfg = dict(cfg)
    q = _round_to(precision)
    h = hidden_states(w, ids, cfg, precision)
    head = q(_f32(w["head"][:, :cfg["vocab_size"]]))

    def rows(hb, pb):
        lg = jnp.matmul(q(hb), head, precision=_HI)            # [rb, V]
        at = jnp.take_along_axis(lg, pb[:, None], axis=1)[:, 0]
        return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)

    return _blocks(rows, 1024, h, probe)


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


def score(w, ids, probe, cfg, precision="float32"):
    """For one sequence ``ids [T]`` and probe tokens ``[T]``: at each
    position the best next-byte logit (head 0), the logit of
    ``probe[t]`` and the best byte (the caller aligns ``probe[t]`` with
    the byte that followed position t)."""
    _check(cfg)
    return _score(w, ids, probe, _hashable(cfg), precision)


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _logits(w, ids, cfg, precision):
    cfg = dict(cfg)
    q = _round_to(precision)
    h = hidden_states(w, ids, cfg, precision)
    lg = jnp.matmul(q(h), q(_f32(w["head"])), precision=_HI)
    return lg.reshape(ids.shape[0], -1, cfg["vocab_size"])


def logits(w, ids, cfg, precision="float32"):
    """``[T, heads, vocab]`` logits of one sequence, every prediction
    head (for the tests at small sizes)."""
    _check(cfg)
    return _logits(w, ids, _hashable(cfg), precision)
