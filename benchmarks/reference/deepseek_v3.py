"""Plain reference of the ``deepseek_v3`` family's forward pass.

Straightforward ``jax.numpy`` in float32 with ``precision=HIGHEST``
matmuls, over ONE whole sequence: no cache, no kernel, the EXPANDED
attention form (keys and values rebuilt from the latent for every
position), experts as a loop over the experts with the tokens that
chose them, and no import of the program under test. Follows the
published description (DeepSeek-V3 technical report, section 2.1; Hugging
Face ``modeling_deepseek_v3.py``) at the sizes of a ``config.json``:

  attention   ``x' = rms(x)``; ``q = x' W_q`` -> per head ``[q_nope,
              q_pe]``; ``c_full = x' W_kva``; ``c = rms(c_full[:rank])``,
              ``k_pe = rope(c_full[rank:])`` (one rotary key for all
              heads), ``q_pe = rope(q_pe)``; ``[k_nope_h, v_h] = c
              W_kvb[h]``; causal softmax of ``(q_nope.k_nope + q_pe.k_pe)
              / sqrt(dn + dr)``; ``y = x + concat_h(o_h) W_o``.
  dense MLP   ``y = x + W_down(silu(W_gate x') * W_up x')`` in the first
              ``first_k_dense_replace`` layers.
  experts     ``s = sigmoid(x' W_g)``; the ``k`` largest of ``s + b``
              are chosen; weights ``s`` (without ``b``), normalised
              (+1e-20) and scaled by ``routed_scaling_factor``; ``y = x
              + sum_k w_k E_k(x') + S(x')``, ``S`` one SwiGLU of width
              ``n_shared_experts * moe_intermediate_size``.

Departures from the published description, each on purpose:

* rotary pairs are lanes ``(2i, 2i+1)`` turned in place
  (``rope_interleave``); Hugging Face permutes them into halves first,
  which moves queries and keys alike and leaves every score unchanged;
* computed in blocks so that 3.8 B parameters and 16k-token sequences
  fit one 16 GB chip next to the bf16 weights: one layer's (and one
  expert's) weights are upcast at a time, attention runs over blocks of
  query rows, the head over blocks of rows; the numbers are those of the
  unblocked formulas;
* the experts' loop visits, for each expert, the tokens that chose it in
  runs of ``run`` rows taken from the pairs sorted by expert (a run
  that is short is padded with a zero row): the same sum in another
  order of additions;
* ``n_group = topk_group = 1`` (no group step), no rope scaling, no
  query compression: the catalogued model has none, and ``score`` refuses
  a config that does;
* the cache and the router see float32, not bfloat16, operands.

Weights (``benchmarks/weights_deepseek_v3.py``): linear weights are
``[in, out]``; ``wq``'s output axis is ``(heads, dn + dr)``; ``wkva``'s
is ``(rank + dr)``; ``wkvb`` is ``[rank, heads, dn + dv]``; per-layer
leaves are stacked in the groups ``dense`` and ``moe``; the experts'
matrices are stacked flat as ``[expert layers * experts, ., .]``.

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


def rope_pairs(x, pos, theta):
    """x ``[T, ..., d]``, pos ``[T]``: lanes (2i, 2i+1) turned by ``pos *
    theta**(-2i/d)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [T, d/2]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    ra = a * jnp.cos(ang) - b * jnp.sin(ang)
    rb = b * jnp.cos(ang) + a * jnp.sin(ang)
    return jnp.stack([ra, rb], -1).reshape(x.shape)


def _check(cfg):
    if cfg.get("q_lora_rank") is not None or cfg.get("rope_scaling") \
            or cfg.get("n_group", 1) != 1 \
            or cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError("the reference covers no query "
                                  "compression, rope scaling, expert "
                                  "groups or softmax scores")


def _attention(x, p, cfg, mm, row_block):
    T = x.shape[0]
    nh = cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    pos = jnp.arange(T, dtype=jnp.int32)
    xn = _rms(x, _f32(p["norm1"]), eps)
    q = mm(xn, _f32(p["wq"])).reshape(T, nh, dn + dr)
    q_nope, q_pe = q[..., :dn], rope_pairs(q[..., dn:], pos, theta)
    c_full = mm(xn, _f32(p["wkva"]))
    c = _rms(c_full[:, :r], _f32(p["kv_norm"]), eps)
    k_pe = rope_pairs(c_full[:, r:], pos, theta)                # [T, dr]
    kv = mm(c, _f32(p["wkvb"]).reshape(r, nh * (dn + dv))) \
        .reshape(T, nh, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, None], (T, nh, dr))], -1)
    v = kv[..., dn:]
    qf = jnp.concatenate([q_nope, q_pe], -1)                    # [T,nh,dq]
    kt = k.transpose(1, 2, 0)                                   # [nh,dq,T]
    vt = v.transpose(1, 0, 2)                                   # [nh,T,dv]
    scale = (dn + dr) ** -0.5

    def rows(args):
        qb, pb = args                                 # [rb, nh, dq], [rb]
        s = mm(qb.transpose(1, 0, 2), kt) * scale              # [nh,rb,T]
        s = jnp.where(pos[None, None, :] <= pb[None, :, None], s,
                      -jnp.inf)
        return mm(jax.nn.softmax(s, -1), vt).transpose(1, 0, 2)

    rb = row_block if T % row_block == 0 else T
    o = lax.map(rows, (qf.reshape(T // rb, rb, nh, dn + dr),
                       pos.reshape(T // rb, rb))).reshape(T, nh * dv)
    return x + mm(o, _f32(p["wo"]))


def _swiglu(xn, wg, wu, wd, mm):
    return mm(_silu(mm(xn, _f32(wg))) * mm(xn, _f32(wu)), _f32(wd))


def _experts(xn, idx, w, experts, layer_m, cfg, mm, run):
    """``sum_k w_k E_k(x')``: for each expert in turn, the tokens that
    chose it (from the pairs sorted by expert), ``run`` rows at a
    time."""
    T, h = xn.shape
    E, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    eid = idx.reshape(-1)
    order = jnp.argsort(eid, stable=True)
    tok = jnp.concatenate([(order // k).astype(jnp.int32),
                           jnp.full((run,), T, jnp.int32)])
    wt = jnp.concatenate([w.reshape(-1)[order],
                          jnp.zeros((run,), jnp.float32)])
    count = jnp.zeros((E,), jnp.int32).at[eid].add(1)
    first = jnp.cumsum(count) - count
    xz = jnp.concatenate([xn, jnp.zeros((1, h), jnp.float32)])
    lane = jnp.arange(run, dtype=jnp.int32)

    def one_expert(e, acc):
        row = layer_m * E + e
        wg, wu, wd = (lax.dynamic_index_in_dim(experts[n], row,
                                               keepdims=False)
                      for n in ("gate", "up", "down"))

        def one_run(i, acc):
            at = first[e] + i * run
            live = (i * run + lane) < count[e]
            t = jnp.where(live, lax.dynamic_slice_in_dim(tok, at, run), T)
            ww = jnp.where(live, lax.dynamic_slice_in_dim(wt, at, run),
                           0.0)
            y = _swiglu(xz[t], wg, wu, wd, mm) * ww[:, None]
            return acc.at[t].add(y)

        return lax.fori_loop(0, (count[e] + run - 1) // run, one_run, acc)

    acc = lax.fori_loop(0, E, one_expert,
                        jnp.zeros((T + 1, h), jnp.float32))
    return acc[:T]


def _expert_layer(x, p, experts, layer_m, cfg, mm, run):
    eps = cfg["rms_norm_eps"]
    xn = _rms(x, _f32(p["norm2"]), eps)
    s = jax.nn.sigmoid(mm(xn, _f32(p["router_w"])))
    _, idx = lax.top_k(s + _f32(p["router_b"])[None, :],
                       cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg.get("routed_scaling_factor", 1.0)
    routed = _experts(xn, idx.astype(jnp.int32), w, experts, layer_m,
                      cfg, mm, run)
    shared = _swiglu(xn, p["sh_gate"], p["sh_up"], p["sh_down"], mm)
    return x + routed + shared, idx


def hidden_states(w, ids, cfg, precision="float32", row_block=256,
                  run=512):
    """Final-norm hidden states ``[T, h]`` (float32) of one sequence
    ``ids [T]``, and each expert layer's chosen experts ``[m, T, k]``."""
    _check(cfg)
    q = _round_to(precision)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=_HI)

    x = _f32(w["wemb"][ids])
    k = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    m = cfg["num_hidden_layers"] - k
    eps = cfg["rms_norm_eps"]
    run = min(run, max(8, ids.shape[0]))

    def dense(x, p):
        x = _attention(x, p, cfg, mm, row_block)
        xn = _rms(x, _f32(p["norm2"]), eps)
        return x + _swiglu(xn, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                           mm), None

    def moe(x, inp):
        p, layer_m = inp
        x = _attention(x, p, cfg, mm, row_block)
        return _expert_layer(x, p, w["experts"], layer_m, cfg, mm, run)

    chosen = None
    if k:
        x, _ = lax.scan(dense, x, w["dense"])
    if m:
        x, chosen = lax.scan(moe, x, (w["moe"],
                                      jnp.arange(m, dtype=jnp.int32)))
    return _rms(x, _f32(w["norm_f"]), eps), chosen


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _score(w, ids, probe, cfg, precision):
    cfg = dict(cfg)
    q = _round_to(precision)
    h, _ = hidden_states(w, ids, cfg, precision)
    T = ids.shape[0]
    head = q(_f32(w["head"]))
    rb = 1024 if T % 1024 == 0 else T

    def rows(args):
        hb, pb = args
        lg = jnp.matmul(q(hb), head, precision=_HI)            # [rb, V]
        at = jnp.take_along_axis(lg, pb[:, None], axis=1)[:, 0]
        return lg.max(-1), at, jnp.argmax(lg, -1).astype(jnp.int32)

    best, at, first = lax.map(rows, (h.reshape(T // rb, rb, -1),
                                     probe.reshape(T // rb, rb)))
    return best.reshape(T), at.reshape(T), first.reshape(T)


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


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
    h, chosen = hidden_states(w, ids, cfg, precision)
    return jnp.matmul(q(h), q(_f32(w["head"])), precision=_HI), chosen


def logits(w, ids, cfg, precision="float32"):
    """``[T, vocab]`` logits of one sequence and the chosen experts (for
    the tests at small sizes)."""
    return _logits(w, ids, _hashable(cfg), precision)
