"""Plain reference of the ``mimo_v2_flash`` family's forward pass.

Straightforward ``jax.numpy`` in float32 with ``precision=HIGHEST``
matmuls, over ONE whole sequence: no cache, no ring, no kernel, no
batching, and no import of the program under test. Written from the
keys of the published ``config.json`` (``model_type: mimo_v2_flash``).
Layer ``i`` is ``x = x + Attn_i(rms(x))``, ``x = x + FFN_i(rms(x))``
(RMS norm with a gain, eps ``layernorm_epsilon``); a final RMS norm and
an untied head.

  attention  ``hybrid_layer_pattern[i]``: 0 full, 1 window. ``q = x' Wq``
             as ``num_attention_heads`` heads of ``head_dim``; ``k = x'
             Wk`` as ``n_kv`` heads of ``head_dim``; ``v =
             attention_value_scale * (x' Wv)`` as ``n_kv`` heads of
             ``v_head_dim``; ``n_kv`` is ``num_key_value_heads`` in a
             full layer, ``swa_num_key_value_heads`` in a window layer;
             query head ``h`` reads KV head ``h // (heads / n_kv)`` (the
             KV heads REPEATED for their query heads). Rotary positions
             on the first ``r = int(head_dim * partial_rotary_factor)``
             lanes of q and k: lanes ``(i, i + r/2)`` are one pair,
             turned by ``t * theta**(-2i/r)``, ``theta = rope_theta``
             (full) or ``swa_rope_theta`` (window); the other lanes are
             not rotated. Scores ``q_t . k_j / sqrt(head_dim)`` as ONE
             ``[T, T]`` matrix a head under a MASK: ``j <= t`` in a full
             layer; ``t - sliding_window < j <= t`` in a window layer.
             With ``add_swa_attention_sink_bias`` a window layer's head
             ``h`` has a scalar ``b_h`` that joins the softmax as one
             more column and carries no value: ``p[t, j] = exp(s[t, j])
             / (exp(b_h) + sum_j' exp(s[t, j']))``. ``o = concat_h(sum_j
             p[t, j] v_j) Wo``.
  FFN        ``moe_layer_freq[i]``: 0 a dense SwiGLU of width
             ``intermediate_size``; 1 experts: ``s = sigmoid(x' Wr)``;
             the ``num_experts_per_tok`` largest of ``s + b`` are chosen
             (``noaux_tc``, one group); weights ``s`` (without ``b``),
             normalised (+1e-20) when ``norm_topk_prob`` and scaled by
             ``routed_scaling_factor`` (null: 1); ``sum_k w_k
             down_k(silu(gate_k x') * up_k x')``, as a loop over the
             experts. No shared expert.

Where a key names a thing without fixing its layout (the configuration
file lists these under ``assumed``): the value scale is applied to the
values (being linear it is the same on the output); the ROTATED lanes
are a head's first ``r`` in half-split pairs (a permutation of the
columns of Wq and Wk alike, which seeded random weights cannot tell
apart); ``sliding_window`` counts the position itself; the key
``attention_chunk_size`` is unread.

Departures, each on purpose:

* THE HELD SHARE: ``n_routed_experts`` of the configuration counts the
  experts held on this chip, ``first_held_expert..`` of the router's
  ``router_experts``; the router scores all of them and the layer's
  output is the held experts' part of the sum. What the absent experts
  would add is left out, here as in the program (``model-configs``
  guide, section 4);
* the multi-token-prediction layers are not built (the config has no
  key for them); ``score`` refuses what it does not cover;
* computed in blocks so that long sequences fit beside the weights:
  attention runs over blocks of query rows (each against ALL keys under
  the mask: never a band, never a ring), one expert's weights are upcast
  at a time, the head over blocks of rows; the numbers are those of the
  unblocked formulas.

Weights: ``benchmarks/weights_mimo_v2.py``.

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
    bad = [k for k, on in (
        ("n_group", cfg.get("n_group", 1) != 1),
        ("topk_group", cfg.get("topk_group", 1) != 1),
        ("n_shared_experts", bool(cfg.get("n_shared_experts"))),
        ("attention_bias", cfg.get("attention_bias", False)),
        ("add_full_attention_sink_bias",
         cfg.get("add_full_attention_sink_bias", False)),
        ("hidden_act", cfg.get("hidden_act", "silu") != "silu"),
        ("scoring_func", cfg.get("scoring_func", "sigmoid") != "sigmoid"),
        ("topk_method", cfg.get("topk_method", "noaux_tc") != "noaux_tc"),
        ("swa_head_dim", cfg.get("swa_head_dim", cfg["head_dim"])
         != cfg["head_dim"]),
        ("swa_v_head_dim", cfg.get("swa_v_head_dim", cfg["v_head_dim"])
         != cfg["v_head_dim"]),
        ("swa_num_attention_heads",
         cfg.get("swa_num_attention_heads", cfg["num_attention_heads"])
         != cfg["num_attention_heads"]),
        ("sliding_window_size",
         cfg.get("sliding_window_size", cfg["sliding_window"])
         != cfg["sliding_window"]),
    ) if on]
    if bad:
        raise NotImplementedError(f"the reference does not cover {bad}")


def _rope(x, pos, r, theta):
    """x ``[T, n, d]``: the first ``r`` lanes turned, pairs (i, i+r/2)."""
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = pos.astype(jnp.float32)[:, None, None] * inv        # [T,1,r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., r:]], axis=-1)


def _attention(x, p, window, cfg, mm, row_block):
    """``window``: None in a full layer, else the window layer's width."""
    T = x.shape[0]
    nq, hd, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                  cfg["v_head_dim"])
    swa = window is not None
    nkv = cfg.get("swa_num_key_value_heads", cfg["num_key_value_heads"]) \
        if swa else cfg["num_key_value_heads"]
    theta = cfg.get("swa_rope_theta", cfg["rope_theta"]) if swa \
        else cfg["rope_theta"]
    r = int(hd * cfg.get("partial_rotary_factor", 1.0))
    pos = jnp.arange(T, dtype=jnp.int32)
    xn = _rms(x, _f32(p["norm"]), cfg["layernorm_epsilon"])
    q = _rope(mm(xn, _f32(p["wq"])).reshape(T, nq, hd), pos, r,
              float(theta))
    k = _rope(mm(xn, _f32(p["wk"])).reshape(T, nkv, hd), pos, r,
              float(theta))
    v = cfg.get("attention_value_scale", 1.0) \
        * mm(xn, _f32(p["wv"])).reshape(T, nkv, dv)
    # the KV heads repeated for their query heads
    kt = jnp.repeat(k, nq // nkv, axis=1).transpose(1, 2, 0)  # [nq,hd,T]
    vt = jnp.repeat(v, nq // nkv, axis=1).transpose(1, 0, 2)  # [nq,T,dv]
    sink = _f32(p["sink"]) if swa and cfg.get(
        "add_swa_attention_sink_bias", False) else None

    def rows(args):
        qb, pb = args                                 # [rb, nq, hd], [rb]
        s = mm(qb.transpose(1, 0, 2), kt) * hd ** -0.5         # [nq,rb,T]
        seen = pos[None, None, :] <= pb[None, :, None]
        if swa:
            seen = seen & (pos[None, None, :] > pb[None, :, None] - window)
        s = jnp.where(seen, s, -jnp.inf)
        if sink is None:
            pr = jax.nn.softmax(s, -1)
        else:
            # one more column that carries no value
            full = jax.nn.softmax(jnp.concatenate(
                [s, jnp.broadcast_to(sink[:, None, None],
                                     s.shape[:2] + (1,))], -1), -1)
            pr = full[..., :-1]
        return mm(pr, vt).transpose(1, 0, 2)

    rb = row_block if T % row_block == 0 else T
    o = lax.map(rows, (q.reshape(T // rb, rb, nq, hd),
                       pos.reshape(T // rb, rb))).reshape(T, nq * dv)
    return x + mm(o, _f32(p["wo"]))


def _swiglu(xn, gate, up, down, mm):
    return mm(_silu(mm(xn, _f32(gate))) * mm(xn, _f32(up)), _f32(down))


def _experts(xn, idx, w, experts, layer_m, held, cfg, mm, run):
    """The held experts' part of ``sum_k w_k E_k(x')``: for each held
    expert in turn, the tokens that chose it (from the pairs sorted by
    expert), ``run`` rows at a time."""
    T, h = xn.shape
    first_held, count_held = held
    E = cfg.get("router_experts", cfg["n_routed_experts"])
    k = cfg["num_experts_per_tok"]
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

    def one_expert(j, acc):
        e = first_held + j
        row = layer_m * count_held + j
        gate, up, down = (lax.dynamic_index_in_dim(experts[n], row,
                                                   keepdims=False)
                          for n in ("gate", "up", "down"))

        def one_run(i, acc):
            at = first[e] + i * run
            live = (i * run + lane) < count[e]
            t = jnp.where(live, lax.dynamic_slice_in_dim(tok, at, run), T)
            ww = jnp.where(live, lax.dynamic_slice_in_dim(wt, at, run),
                           0.0)
            return acc.at[t].add(_swiglu(xz[t], gate, up, down, mm)
                                 * ww[:, None])

        return lax.fori_loop(0, (count[e] + run - 1) // run, one_run, acc)

    acc = lax.fori_loop(0, count_held, one_expert,
                        jnp.zeros((T + 1, h), jnp.float32))
    return acc[:T]


def expert_layer(xn, p, experts, layer_m, cfg, mm, run, held=None):
    """The expert layer without its norm and residual over xn ``[T, h]``
    (normed): (the held experts' part, chosen experts ``[T, k]``)."""
    if held is None:
        held = (cfg.get("first_held_expert", 0), cfg["n_routed_experts"])
    s = jax.nn.sigmoid(mm(xn, _f32(p["router_w"])))
    _, idx = lax.top_k(s + _f32(p["router_b"])[None, :],
                       cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    scale = cfg.get("routed_scaling_factor")
    w = w * (1.0 if scale is None else scale)
    return _experts(xn, idx.astype(jnp.int32), w, experts, layer_m, held,
                    cfg, mm, run), idx


def _layer(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def hidden_states(w, ids, cfg, precision="float32", row_block=256,
                  run=512):
    """Final-norm hidden states ``[T, h]`` (float32) of one sequence
    ``ids [T]``, and each expert layer's chosen experts ``[m, T, k]``."""
    _check(cfg)
    q = _round_to(precision)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=_HI)

    x = _f32(w["wemb"][ids])
    eps = cfg["layernorm_epsilon"]
    run = min(run, max(8, ids.shape[0]))
    seen = {"full": 0, "win": 0, "dense": 0, "moe": 0}
    chosen = []
    for swa, moe in zip(cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]):
        kind, ffn = ("win" if swa else "full"), ("moe" if moe else "dense")
        x = _attention(x, _layer(w[kind], seen[kind]),
                       cfg["sliding_window"] if swa else None, cfg, mm,
                       row_block)
        p = _layer(w[ffn], seen[ffn])
        xn = _rms(x, _f32(p["norm"]), eps)
        if moe:
            y, idx = expert_layer(xn, p, w["experts"], seen[ffn], cfg, mm,
                                  run)
            chosen.append(idx)
        else:
            y = _swiglu(xn, p["gate"], p["up"], p["down"], mm)
        x = x + y
        seen[kind] += 1
        seen[ffn] += 1
    return _rms(x, _f32(w["norm_f"]), eps), \
        (jnp.stack(chosen) if chosen else None)


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
    """The configuration as a static argument: scalars as they are, the
    two per-layer lists as tuples."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, list)) or v is None))


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
