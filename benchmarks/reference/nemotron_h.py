"""Plain reference of the ``nemotron_h`` family's forward pass.

Straightforward ``jax.numpy`` in float32 with ``precision=HIGHEST``
matmuls, over ONE whole sequence: no cache, no kernel, no batching, and
no import of the program under test. Follows the published description
(Hugging Face ``modeling_nemotron_h.py``; the Nemotron-H report) at the
sizes of a ``config.json``. Layer ``i`` is ``x = x + mixer_i(rms(x))``
with the mixer named by ``hybrid_override_pattern[i]``:

  ``M``  ``[z | xBC | dt] = x' W_in``; ``xBC = silu(conv(xBC) + b)``
         (causal, depthwise, ``conv_kernel`` taps); ``[xs | B | C] =
         xBC``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
         for every head ``h`` (group ``h // (H / G)``), as the DEFINING
         RECURRENCE in a sequential scan over time (not the chunked
         form): ``S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T``, ``y_t =
         S_t C_t + D xs_t``; ``y = group_rms(y * silu(z)) W_out`` (gate
         BEFORE the norm, groups of ``d_inner / G`` channels).
  ``*``  ``q, k, v = x' W_q, x' W_k, x' W_v``; causal softmax of ``q.k /
         sqrt(head_dim)``, query head ``h`` on KV head ``h // (nq /
         nkv)`` (the KV heads REPEATED for their query heads);
         ``concat_h(o_h) W_o``.
  ``E``  ``s = sigmoid(x' W_r)``; the ``k`` largest of ``s + b`` are
         chosen; weights ``s`` (without ``b``), normalised (+1e-20) and
         scaled by ``routed_scaling_factor``; ``sum_k w_k down_k(relu(
         up_k x')^2) + down_s(relu(up_s x')^2)``, the routed part as a
         loop over the experts.

Departures from the published description, each on purpose:

* NO positional rotation in attention: ``NemotronHAttention`` applies
  none (the report: "no position embeddings"); ``rope_theta`` and
  ``partial_rotary_factor`` of the config are unread;
* ``time_step_limit`` is (0, inf): ``dt`` is not clamped
  (``time_step_min/max/floor`` initialise ``dt_bias`` only);
* the recurrent state is float32 whatever the model's dtype (there is
  no other in a float32 reference; the program keeps it so in bfloat16
  serving too, as NVIDIA's serving instructions for the family do);
* THE HELD SHARE: ``n_routed_experts`` of the configuration counts the
  experts held on this chip, ``first_held_expert..`` of the router's
  ``router_experts``; the router scores all of them, and the layer's
  output is the held experts' part of the sum plus the shared expert's.
  What the absent experts would add is left out, here as in the program
  (``model-configs`` guide, section 4);
* ``n_group = topk_group = 1`` (no group step), no biases on
  projections, no sliding window: the catalogued model has none, and
  ``score`` refuses a config that does;
* computed in blocks so that long sequences fit beside the weights: one
  layer's (one expert's) weights are upcast at a time, attention runs
  over blocks of query rows, the head over blocks of rows; the numbers
  are those of the unblocked formulas.

Weights: ``benchmarks/weights_nemotron_h.py`` (an expert's two matrices
are both ``[f, h]``).

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
        ("mamba_proj_bias", cfg.get("mamba_proj_bias", False)),
        ("use_bias", cfg.get("use_bias", False)),
        ("mlp_bias", cfg.get("mlp_bias", False)),
        ("attention_bias", cfg.get("attention_bias", False)),
        ("sliding_window", cfg.get("sliding_window") is not None),
        ("mlp_hidden_act", cfg.get("mlp_hidden_act", "relu2") != "relu2"),
    ) if on]
    if bad or set(cfg["hybrid_override_pattern"]) - set("ME*"):
        raise NotImplementedError(f"the reference does not cover {bad} / "
                                  f"{cfg['hybrid_override_pattern']!r}")


def _mamba(x, p, cfg, mm):
    T = x.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    d, eps = H * P, cfg["layer_norm_epsilon"]
    cd = d + 2 * G * N
    zxd = mm(_rms(x, _f32(p["norm"]), eps), _f32(p["in_proj"]))
    z, u, dt = zxd[:, :d], zxd[:, d:d + cd], zxd[:, d + cd:]
    # causal depthwise convolution: tap K-1 on the newest input
    pad = jnp.concatenate([jnp.zeros((K - 1, cd), jnp.float32), u])
    cw = _f32(p["conv_w"])
    u = _silu(sum(pad[k:k + T] * cw[k][None, :] for k in range(K))
              + _f32(p["conv_b"])[None, :])
    xs = u[:, :d].reshape(T, H, P)
    B = jnp.repeat(u[:, d:d + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(u[:, d + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"])[None, :])      # [T, H]
    A = -jnp.exp(_f32(p["A_log"]))

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp            # [H,P] [H] [H,N] [H,N]
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)

    _, y = lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                    (xs, dt, B, C))
    y = (y + _f32(p["D"])[None, :, None] * xs).reshape(T, d)
    y = (y * _silu(z)).reshape(T, G, d // G)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    return x + mm(y.reshape(T, d) * _f32(p["gnorm"]), _f32(p["out_proj"]))


def _attention(x, p, cfg, mm, row_block):
    T = x.shape[0]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    pos = jnp.arange(T, dtype=jnp.int32)
    xn = _rms(x, _f32(p["norm"]), cfg["layer_norm_epsilon"])
    q = mm(xn, _f32(p["wq"])).reshape(T, nq, hd)
    k = mm(xn, _f32(p["wk"])).reshape(T, nkv, hd)
    v = mm(xn, _f32(p["wv"])).reshape(T, nkv, hd)
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
    return x + mm(o, _f32(p["wo"]))


def _relu2(xn, up_t, down, mm):
    r = jnp.maximum(mm(xn, _f32(up_t).T), 0.0)
    return mm(r * r, _f32(down))


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
        up_t, down = (lax.dynamic_index_in_dim(experts[n], row,
                                               keepdims=False)
                      for n in ("up_t", "down"))

        def one_run(i, acc):
            at = first[e] + i * run
            live = (i * run + lane) < count[e]
            t = jnp.where(live, lax.dynamic_slice_in_dim(tok, at, run), T)
            ww = jnp.where(live, lax.dynamic_slice_in_dim(wt, at, run),
                           0.0)
            return acc.at[t].add(_relu2(xz[t], up_t, down, mm)
                                 * ww[:, None])

        return lax.fori_loop(0, (count[e] + run - 1) // run, one_run, acc)

    acc = lax.fori_loop(0, count_held, one_expert,
                        jnp.zeros((T + 1, h), jnp.float32))
    return acc[:T]


def expert_layer(xn, p, experts, layer_m, cfg, mm, run, held=None,
                 with_shared=True):
    """The expert layer without its norm and residual over xn ``[T, h]``
    (normed): (the held experts' part [+ the shared expert's], chosen
    experts ``[T, k]``)."""
    if held is None:
        held = (cfg.get("first_held_expert", 0), cfg["n_routed_experts"])
    s = jax.nn.sigmoid(mm(xn, _f32(p["router_w"])))
    _, idx = lax.top_k(s + _f32(p["router_b"])[None, :],
                       cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if cfg.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = w * cfg.get("routed_scaling_factor", 1.0)
    y = _experts(xn, idx.astype(jnp.int32), w, experts, layer_m, held,
                 cfg, mm, run)
    if with_shared:
        y = y + _relu2(xn, p["sh_up_t"], p["sh_down"], mm)
    return y, idx


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
    eps = cfg["layer_norm_epsilon"]
    run = min(run, max(8, ids.shape[0]))
    seen = {"M": 0, "E": 0, "*": 0}
    chosen = []
    for letter in cfg["hybrid_override_pattern"]:
        i = seen[letter]
        seen[letter] += 1
        if letter == "M":
            x = _mamba(x, _layer(w["mamba"], i), cfg, mm)
        elif letter == "*":
            x = _attention(x, _layer(w["attn"], i), cfg, mm, row_block)
        else:
            p = _layer(w["moe"], i)
            y, idx = expert_layer(_rms(x, _f32(p["norm"]), eps), p,
                                  w["experts"], i, cfg, mm, run)
            x = x + y
            chosen.append(idx)
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
