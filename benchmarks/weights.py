"""Seeded GPT weights, made on the device in one jitted call.

The benchmark owns the weights: the program under test is handed them
(``planes/*`` writes them into its parameters) and the plain reference
builds the same ones from the same seed, so neither takes anything the
other has made. Layout conventions (part of the model definition, as a
checkpoint format would be): linear weights are ``[in, out]``; the fused
qkv output axis is ordered ``(3, heads, head_dim)``; per-layer leaves are
stacked on a leading layer axis.
"""
import functools

import jax
import jax.numpy as jnp

# leaf -> (shape builder, kind); kind: "w" N(0, std), "b" N(0, std),
# "g" 1 + N(0, std).  Biases and norm gains are random too, so that a
# path which drops one of them cannot agree with the reference.
_STD = 0.02


def leaf_shapes(model):
    """name -> (shape, kind) for a GPT with the sizes in ``model``."""
    v, h = model["vocab_size"], model["hidden_size"]
    n, f = model["num_hidden_layers"], model["intermediate_size"]
    p = model["max_position_embeddings"]
    return {
        "wemb": ((v, h), "w"), "pemb": ((p, h), "w"),
        "ln1_w": ((n, h), "g"), "ln1_b": ((n, h), "b"),
        "qkv_w": ((n, h, 3 * h), "w"), "qkv_b": ((n, 3 * h), "b"),
        "out_w": ((n, h, h), "w"), "out_b": ((n, h), "b"),
        "ln2_w": ((n, h), "g"), "ln2_b": ((n, h), "b"),
        "fc1_w": ((n, h, f), "w"), "fc1_b": ((n, f), "b"),
        "fc2_w": ((n, f, h), "w"), "fc2_b": ((n, h), "b"),
        "lnf_w": ((h,), "g"), "lnf_b": ((h,), "b"),
    }


def seed_key(seed):
    """A jax PRNG key from any whole number (the driver's seeds pass
    2**31, more than a signed 32-bit key seed holds)."""
    seed = abs(int(seed))
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    out = {}
    for i, (name, shape, kind) in enumerate(shapes):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32) * _STD
        if kind == "g":
            x = x + 1.0
        out[name] = x.astype(dtype)
    return out


def gpt_weights(seed, model, dtype):
    """All leaves of a GPT, on the default device, in ``dtype``."""
    shapes = tuple((k, s, kind)
                   for k, (s, kind) in sorted(leaf_shapes(model).items()))
    return _make(seed_key(seed), shapes, jnp.dtype(dtype).name)


def count_params(model):
    total = 0
    for shape, _ in leaf_shapes(model).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total
