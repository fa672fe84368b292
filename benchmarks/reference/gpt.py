"""Plain GPT reference: forward, loss, gradients and an AdamW step.

Straightforward ``jax.numpy`` in float32 with
``precision=HIGHEST`` matmuls (on a TPU a float32 matmul otherwise runs
in bfloat16 passes). No cache, no kernel, no batching tricks, and no
import of the program under test. Follows GPT-2/GPT-3: learned position
embeddings, pre-norm blocks, LayerNorm eps 1e-5, tanh-approximated GELU
(``gelu_new``), causal softmax attention scaled by 1/sqrt(head_dim), tied
input/output embedding. Weight layout: see ``benchmarks/weights.py``.

``precision`` rounds every matmul operand to a lower type first (the
products are still accumulated in float32). ``"float32"`` is the
reference itself; ``"bfloat16"`` is what the configurations state;
``"float8"`` (e4m3 operands forward, e5m2 gradients backward, each scaled
per tensor to its largest magnitude) is the control of "How correct is
decided": the nearest precision below bf16.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST


def _scaled_cast(x, dtype, largest):
    """x rounded to ``dtype`` after scaling its largest magnitude to
    the type's largest finite value (per-tensor scaling, as float8
    recipes do), returned in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / largest
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _float8(x):
    return _scaled_cast(x, jnp.float8_e4m3fn, 448.0)


# the usual float8 recipe: e4m3 operands forward, e5m2 gradients back
_float8.defvjp(lambda x: (_float8(x), None),
               lambda _, g: (_scaled_cast(g, jnp.float8_e5m2, 57344.0),))


def _round_to(precision):
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        return _float8
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


_LAYER_LEAVES = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
                 "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")


def hidden_states(w, ids, n_heads, precision="float32", remat=False):
    """Final-norm hidden states [B, T, H] (float32) for token ids
    [B, T]."""
    q = _round_to(precision)

    def mm(a, b):
        return jnp.matmul(q(a), q(b), precision=_HI)

    B, T = ids.shape
    H = w["wemb"].shape[1]
    hd = H // n_heads
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["wemb"])[ids] + f32(w["pemb"])[:T][None]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(x, p):
        p = {k: f32(v) for k, v in p.items()}
        h = _layer_norm(x, p["ln1_w"], p["ln1_b"])
        qkv = mm(h, p["qkv_w"]) + p["qkv_b"]
        qkv = qkv.reshape(B, T, 3, n_heads, hd).transpose(2, 0, 3, 1, 4)
        qh, kh, vh = qkv[0], qkv[1], qkv[2]          # [B, nh, T, hd]
        s = mm(qh, kh.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal, s, -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), vh)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H)
        x = x + mm(o, p["out_w"]) + p["out_b"]
        h = _layer_norm(x, p["ln2_w"], p["ln2_b"])
        m = _gelu_tanh(mm(h, p["fc1_w"]) + p["fc1_b"])
        return x + mm(m, p["fc2_w"]) + p["fc2_b"], None

    if remat:
        block = jax.checkpoint(block)
    x, _ = lax.scan(block, x, {k: w[k] for k in _LAYER_LEAVES})
    return _layer_norm(x, f32(w["lnf_w"]), f32(w["lnf_b"]))


def logits(w, ids, n_heads, precision="float32"):
    q = _round_to(precision)
    h = hidden_states(w, ids, n_heads, precision)
    return jnp.matmul(q(h), q(w["wemb"].astype(jnp.float32)).T,
                      precision=_HI)


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def score(w, ids, probe, n_heads, precision="float32"):
    """For one sequence ``ids`` [T] and probe tokens [T]: at each
    position t the best next-token logit, the logit of ``probe[t]`` and
    the best token. (The caller aligns ``probe[t]`` with the token that
    followed position t.)"""
    lg = logits(w, ids[None], n_heads, precision)[0]      # [T, V]
    best = lg.max(-1)
    at = jnp.take_along_axis(lg, probe[:, None], axis=1)[:, 0]
    return best, at, jnp.argmax(lg, -1).astype(jnp.int32)


def loss_sum(w, x, y, n_heads, precision="float32"):
    """Summed next-token cross-entropy over every position of x [B, T]
    against labels y [B, T]."""
    q = _round_to(precision)
    h = hidden_states(w, x, n_heads, precision, remat=True)
    lg = jnp.matmul(q(h), q(w["wemb"].astype(jnp.float32)).T,
                    precision=_HI)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return (lse - picked).sum()


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def _loss_and_grad_sum(w, x, y, n_heads, precision):
    return jax.value_and_grad(loss_sum)(w, x, y, n_heads, precision)


def loss_and_grads(w, x, y, n_heads, precision="float32", row_block=4):
    """Mean loss over all tokens of the batch and its gradients, taken
    ``row_block`` rows at a time so that float32 activations fit."""
    total, grads = 0.0, None
    for i in range(0, x.shape[0], row_block):
        l, g = _loss_and_grad_sum(w, x[i:i + row_block],
                                  y[i:i + row_block], n_heads, precision)
        total = total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = x.shape[0] * x.shape[1]
    return total / n, jax.tree.map(lambda g: g / n, grads)


@jax.jit
def adamw_step(w, g, m, v, step, lr, beta1, beta2, eps, wd):
    """Decoupled-weight-decay Adam (Loshchilov & Hutter), decay on every
    leaf; ``step`` counts from 1."""
    new_w, new_m, new_v = {}, {}, {}
    for k, p in w.items():
        m_k = beta1 * m[k] + (1.0 - beta1) * g[k]
        v_k = beta2 * v[k] + (1.0 - beta2) * g[k] * g[k]
        m_hat = m_k / (1.0 - beta1 ** step)
        v_hat = v_k / (1.0 - beta2 ** step)
        new_w[k] = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)
        new_m[k], new_v[k] = m_k, v_k
    return new_w, new_m, new_v


@jax.jit
def leaf_norms(tree):
    """L2 norm of every leaf; stacked per-layer leaves give one norm
    per layer (their leading axis)."""
    def one(name, a):
        a = a.astype(jnp.float32)
        if name in _LAYER_LEAVES:
            return jnp.sqrt((a * a).reshape(a.shape[0], -1).sum(-1))
        return jnp.sqrt((a * a).sum())
    return {k: one(k, a) for k, a in tree.items()}


def train_steps(w0, batches, n_heads, hp, precision="float32",
                row_block=4):
    """Follow ``len(batches)`` AdamW steps from ``w0`` (float32 leaves).
    Returns the loss of each step, the per-leaf norm of the first
    gradient and the per-leaf norm of the parameters' change."""
    w = w0
    m = jax.tree.map(jnp.zeros_like, w0)
    v = jax.tree.map(jnp.zeros_like, w0)
    losses, first_grad = [], None
    for step, (x, y) in enumerate(batches, start=1):
        loss, g = loss_and_grads(w, x, y, n_heads, precision, row_block)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = leaf_norms(g)
        w, m, v = adamw_step(w, g, m, v, jnp.float32(step), hp["lr"],
                             hp["beta1"], hp["beta2"], hp["eps"],
                             hp["weight_decay"])
    delta = leaf_norms(jax.tree.map(jnp.subtract, w, w0))
    return {"losses": losses, "grad_norms": first_grad,
            "delta_norms": delta}
