"""Plain reference of the ``falcon_h1`` family's forward pass.

Straightforward ``jax.numpy`` in float32 with ``precision=HIGHEST``
matmuls, over ONE whole sequence: no cache, no kernel, no batching, no
chunked scan, and no import of the program under test. Follows the
published code (Hugging Face ``transformers``
``models/falcon_h1/modeling_falcon_h1.py``: ``FalconH1DecoderLayer
.forward``, ``FalconH1Mixer.torch_forward``, ``compute_mup_vector``,
``FalconH1MLP``, ``FalconH1Attention``; ``tests/test_falcon_h1.py``
holds this file to that code's own logits on the same weights) at the
sizes of a ``config.json``. With ``h`` the residual stream, EVERY layer
is::

    u      = rms(h; norm_in)
    zxbcdt = ((ssm_in_multiplier * u) W_in) * mup      # z | x | B | C | dt
             # mup: ssm_multipliers[0..4] on those five segments
    xBC    = silu(conv(x|B|C) + b)      # causal, depthwise, K taps
    dt     = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t    = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      # head h in group
    y_t    = S_t C_t + D x_t                           # h // (H / G)
    m      = ssm_out_multiplier * (group_rms(y * silu(z)) W_out)
    a_in   = attention_in_multiplier * u
    q, k, v = a_in W_q, key_multiplier * (a_in W_k), a_in W_v
    q, k   = rope(q), rope(k)         # rotate-half over the whole head
    a      = attention_out_multiplier * (softmax(q k^T / sqrt(hd)) v W_o)
    h      = h + m + a
    f      = rms(h; norm_ff)
    h      = h + mlp_multipliers[1] * ((f W_u * silu(mlp_multipliers[0]
                                                     * f W_g)) W_d)

the recurrence as the DEFINING sequential scan over positions in the
``[H, P, N]`` layout, the gate BEFORE the group norm
(``mamba_norm_before_gate`` false), the KV heads REPEATED for their
query heads; ``h_0 = embedding_multiplier * E[ids]``, ``logits =
lm_head_multiplier * (rms(h_L; norm_f) W_head)``, the head untied.

Read of the config, each on purpose:

* ``mamba_use_mlp`` is read nowhere in the published code and
  ``attn_layer_indices`` is null: every block has both mixers and the
  feed-forward part;
* ``time_step_limit`` is (0, inf) in the published mixer: ``dt`` is not
  clamped;
* the recurrent state is float32 whatever the model's dtype (there is
  no other in a float32 reference; the program keeps it so in bfloat16
  serving too);
* no biases on projections, no ``rope_scaling``: the catalogued model
  has none, and ``score`` refuses a config that does;
* computed in blocks so that 6,144 positions fit beside 10.5 GB of
  weights: ONE layer's weights are upcast at a time (a scan over the
  layers), attention and the feed-forward run over blocks of rows, the
  head over blocks of the vocabulary with a running best; the numbers
  are those of the unblocked formulas.

Weights: ``benchmarks/weights_falcon_h1.py`` (linear weights ``[in,
out]``, ``wqkv`` = q | k | v, ``conv_w`` ``[taps, channels]`` with the
last tap on the newest input).

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
        ("attention_bias", cfg.get("attention_bias", False)),
        ("mamba_proj_bias", cfg.get("mamba_proj_bias", False)),
        ("mlp_bias", cfg.get("mlp_bias", False)),
        ("projectors_bias", cfg.get("projectors_bias", False)),
        ("mamba_conv_bias", not cfg.get("mamba_conv_bias", True)),
        ("mamba_rms_norm", not cfg.get("mamba_rms_norm", True)),
        ("mamba_norm_before_gate",
         cfg.get("mamba_norm_before_gate", False)),
        ("mamba_use_mlp", not cfg.get("mamba_use_mlp", True)),
        ("attn_layer_indices", cfg.get("attn_layer_indices") is not None),
        ("rope_scaling", cfg.get("rope_scaling") is not None),
        ("hidden_act", cfg.get("hidden_act", "silu") != "silu"),
        ("tie_word_embeddings", cfg.get("tie_word_embeddings", False)),
    ) if on]
    if bad:
        raise NotImplementedError(f"the reference does not cover {bad}")


def _sizes(cfg):
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def mup_vector(cfg):
    """``compute_mup_vector``: the five ``ssm_multipliers`` over the
    segments z | x | B | C | dt of the in-projection's output."""
    H, _, G, N, d, _ = _sizes(cfg)
    z, x, b, c, dt = cfg["ssm_multipliers"]
    return jnp.concatenate([
        jnp.full((d,), z), jnp.full((d,), x), jnp.full((G * N,), b),
        jnp.full((G * N,), c), jnp.full((H,), dt)]).astype(jnp.float32)


def _mamba(u, p, cfg, mm):
    T = u.shape[0]
    H, P, G, N, d, cd = _sizes(cfg)
    K, eps = cfg["mamba_d_conv"], cfg["rms_norm_eps"]
    zxd = mm(cfg["ssm_in_multiplier"] * u, _f32(p["in_proj"])) \
        * mup_vector(cfg)
    z, xbc, dt = zxd[:, :d], zxd[:, d:d + cd], zxd[:, d + cd:]
    # causal depthwise convolution: tap K-1 on the newest input
    pad = jnp.concatenate([jnp.zeros((K - 1, cd), jnp.float32), xbc])
    cw = _f32(p["conv_w"])
    xbc = _silu(sum(pad[k:k + T] * cw[k][None, :] for k in range(K))
                + _f32(p["conv_b"])[None, :])
    xs = xbc[:, :d].reshape(T, H, P)
    B = jnp.repeat(xbc[:, d:d + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xbc[:, d + G * N:].reshape(T, G, N), H // G, axis=1)
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
    return cfg["ssm_out_multiplier"] * mm(
        y.reshape(T, d) * _f32(p["gnorm"]), _f32(p["out_proj"]))


def _rope(x, pos, theta):
    """Rotate-half over the whole head: lanes (i, i + d/2) are one pair
    turned by ``pos * theta ** (-2i / d)``; angles in float32."""
    d = x.shape[-1]
    inv = 1.0 / (jnp.float32(theta) ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(u, p, cfg, mm, row_block):
    T = u.shape[0]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    pos = jnp.arange(T, dtype=jnp.int32)
    a_in = cfg["attention_in_multiplier"] * u
    w = _f32(p["wqkv"])
    q = mm(a_in, w[:, :nq * hd]).reshape(T, nq, hd)
    k = cfg["key_multiplier"] * mm(
        a_in, w[:, nq * hd:(nq + nkv) * hd]).reshape(T, nkv, hd)
    v = mm(a_in, w[:, (nq + nkv) * hd:]).reshape(T, nkv, hd)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos,
                                                   cfg["rope_theta"])
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
    return cfg["attention_out_multiplier"] * mm(o, _f32(p["wo"]))


def _mlp(f, p, cfg, mm, row_block):
    gate_m, down_m = cfg["mlp_multipliers"]
    wg, wu, wd = _f32(p["wg"]), _f32(p["wu"]), _f32(p["wd"])

    def rows(fb):
        return down_m * mm(mm(fb, wu) * _silu(gate_m * mm(fb, wg)), wd)

    T = f.shape[0]
    rb = row_block if T % row_block == 0 else T
    return lax.map(rows, f.reshape(T // rb, rb, -1)).reshape(T, -1)


def _norm_of(x):
    return jnp.sqrt(jnp.mean(x * x))


def hidden_states(w, ids, cfg, precision="float32", row_block=256):
    """(final-norm hidden states ``[T, h]`` float32 of one sequence
    ``ids [T]``, ``[L, 4]``: the RMS of ``h`` going into each layer and
    of the three terms the layer adds to it: ``m``, ``a`` and the
    feed-forward's)."""
    q = _round_to(precision)
    eps = cfg["rms_norm_eps"]

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=_HI)

    def layer(h, i):
        p = jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False),
            w["layers"])
        u = _rms(h, _f32(p["norm_in"]), eps)
        m = _mamba(u, p, cfg, mm)
        a = _attention(u, p, cfg, mm, row_block)
        mid = h + m + a
        y = _mlp(_rms(mid, _f32(p["norm_ff"]), eps), p, cfg, mm,
                 4 * row_block)
        return mid + y, jnp.stack([_norm_of(h), _norm_of(m), _norm_of(a),
                                   _norm_of(y)])

    h = cfg["embedding_multiplier"] * _f32(w["wemb"][ids])
    h, terms = lax.scan(layer, h, jnp.arange(cfg["num_hidden_layers"],
                                             dtype=jnp.int32))
    return _rms(h, _f32(w["norm_f"]), eps), terms


def _vocab_blocks(vocab):
    """Blocks the head is walked in: 8 where the vocabulary is large
    enough for its float32 logits to matter."""
    return 8 if vocab % 8 == 0 and vocab >= 8192 else 1


@functools.partial(jax.jit, static_argnames=("cfg", "precision"))
def _score(w, ids, probe, cfg, precision):
    cfg = dict(cfg)
    q = _round_to(precision)
    h, _ = hidden_states(w, ids, cfg, precision)
    hq = q(h)
    T, V = ids.shape[0], w["head"].shape[1]
    nb = _vocab_blocks(V)
    vb = V // nb

    def block(j, carry):
        best, at, first = carry
        head = q(_f32(lax.dynamic_slice_in_dim(w["head"], j * vb, vb, 1)))
        lg = cfg["lm_head_multiplier"] * jnp.matmul(hq, head,
                                                    precision=_HI)
        here = probe - j * vb
        mine = jnp.take_along_axis(
            lg, jnp.clip(here, 0, vb - 1)[:, None], axis=1)[:, 0]
        at = jnp.where(jnp.logical_and(here >= 0, here < vb), mine, at)
        top = lg.max(-1)
        # strictly better: the first of equal logits wins, as argmax's
        first = jnp.where(top > best, jnp.argmax(lg, -1).astype(jnp.int32)
                          + j * vb, first)
        return jnp.maximum(best, top), at, first

    return lax.fori_loop(
        0, nb, block, (jnp.full((T,), -jnp.inf, jnp.float32),
                       jnp.zeros((T,), jnp.float32),
                       jnp.zeros((T,), jnp.int32)))


def _hashable(cfg):
    _check(cfg)

    def leaf(v):
        return tuple(v) if isinstance(v, (list, tuple)) else v
    return tuple(sorted(
        (k, leaf(v)) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, list, tuple))
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
    h, terms = hidden_states(w, ids, cfg, precision)
    return cfg["lm_head_multiplier"] * jnp.matmul(
        q(h), q(_f32(w["head"])), precision=_HI), terms


def logits(w, ids, cfg, precision="float32"):
    """``[T, vocab]`` logits of one sequence and the ``[L, 4]`` table of
    ``hidden_states`` (for the tests at small sizes)."""
    return _logits(w, ids, _hashable(cfg), precision)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _branch_rms(w, ids, cfg):
    return hidden_states(w, ids, dict(cfg))[1]


def branch_rms(w, ids, cfg):
    """``[L, 4]`` (RMS of h, m, a, the feed-forward's term) a layer, of
    one sequence: how the seeded weights balance the three branches."""
    return _branch_rms(w, ids, _hashable(cfg))
