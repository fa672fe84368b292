"""Seeded weights of an ``evabyte`` configuration, made on the device in
one jitted call, in the dtype they are served in.

The benchmark owns the weights: the program under test is handed them
(``planes/evabyte_program.py`` gives them to the model class) and the
plain reference (``reference/evabyte.py``) builds the same ones from the
same seed. Layout (part of the model's definition, as a checkpoint
format would be): linear weights are ``[in, out]``; the output axis of
``wq``, ``wk``, ``wv`` and ``wo``'s input axis are ordered ``(heads,
head_dim)``; ``mu`` and ``phi`` are ``[heads, head_dim]``; the prediction heads share one matrix ``[hidden, heads *
vocab]``, head ``i`` in columns ``[i V, (i + 1) V)``; per-layer leaves
are stacked on a leading layer axis under ``layers``.

Kinds (none of them is in the published ``config.json`` beyond
``init_std``; the configuration file lists them under ``assumed``):
``w`` N(0, ``init_std``); ``g`` a norm's gain N(0, 0.02) ABOUT 0 (the
norm multiplies by ``1 + g``; random, so that a path which drops a gain
cannot agree with the reference); ``m`` the pooling vectors,
``clip(N(0, 1), -1, 1) / sqrt(head_dim)``.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key


def leaf_shapes(model):
    """(group, leaf) or (leaf,) -> (shape, kind)."""
    h, v = model["hidden_size"], model["vocab_size"]
    H = model["num_attention_heads"]
    d, f = h // H, model["intermediate_size"]
    L, P = model["num_hidden_layers"], model.get("num_pred_heads", 1)
    layer = {"norm1": ((h,), "g"), "wq": ((h, H * d), "w"),
             "wk": ((h, H * d), "w"), "wv": ((h, H * d), "w"),
             "wo": ((H * d, h), "w"), "mu": ((H, d), "m"),
             "phi": ((H, d), "m"), "norm2": ((h,), "g"),
             "gate": ((h, f), "w"), "up": ((h, f), "w"),
             "down": ((f, h), "w")}
    out = {("wemb",): ((v, h), "w"), ("norm_f",): ((h,), "g"),
           ("head",): ((h, P * v), "w")}
    for name, (shape, kind) in layer.items():
        out[("layers", name)] = ((L,) + shape, kind)
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype", "std"))
def _make(key, shapes, dtype, std):
    """Leaves with three axes are drawn one layer at a time
    (``lax.map``), so that the float32 draw of a leaf never exists whole
    beside the weights it is part of."""
    out = {}
    for i, (path, shape, kind) in enumerate(shapes):
        k = jax.random.fold_in(key, i)

        def draw(k, shape=shape[1:] if len(shape) > 2 else shape,
                 kind=kind):
            x = jax.random.normal(k, shape, jnp.float32)
            if kind == "m":
                x = jnp.clip(x, -1.0, 1.0) / math.sqrt(shape[-1])
            else:
                x = x * (0.02 if kind == "g" else std)
            return x.astype(dtype)

        out[path] = jax.lax.map(draw, jax.random.split(k, shape[0])) \
            if len(shape) > 2 else draw(k)
    return out


def make(seed, model, dtype):
    """All leaves as a nested dict (``w["layers"]["wq"]``,
    ``w["wemb"]``), on the default device, in ``dtype``."""
    shapes = tuple((p, s, kind)
                   for p, (s, kind) in sorted(leaf_shapes(model).items()))
    flat = _make(seed_key(seed), shapes, jnp.dtype(dtype).name,
                 float(model.get("init_std", 0.02)))
    tree = {}
    for path, a in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = a
    return tree


def count_params(model):
    return sum(math.prod(shape) for shape, _ in leaf_shapes(model).values())
