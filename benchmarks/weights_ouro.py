"""Seeded weights of an ``ouro`` configuration, made on the device in one
jitted call, in the dtype they are served in.

The benchmark owns the weights: the program under test is handed them
(``planes/ouro_program.py`` gives them to the model class) and the plain
reference (``reference/ouro.py``) builds the same ones from the same
seed. Layout (part of the model's definition, as a checkpoint format
would be): linear weights are ``[in, out]``; a layer's three attention
projections are ONE leaf ``wqkv`` whose output axis is (q heads | k
heads | v heads) x ``head_dim``, a head's lanes in half-split rotary
pairs; per-layer leaves are stacked on a leading axis of
``num_hidden_layers``: ONE stack, which every pass of
``total_ut_steps`` reads again.

Kinds: ``w`` N(0, 0.02) (the exit gate's ``w_gate`` among them); ``g``
the norms' gains, 1; ``z`` the gate's bias ``b_gate``, 0, float32. The
CPU tests perturb the gains and the bias themselves, so that a path
which drops or swaps one cannot agree with the reference. Nothing here
is routed, so no draw has to be balanced against the seed: a decode
step's bytes are the same whatever the weights.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

_STD = 0.02


def leaf_shapes(model):
    """(group, leaf) or (leaf,) -> (shape, kind)."""
    h, v = model["hidden_size"], model["vocab_size"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, f = model["head_dim"], model["intermediate_size"]
    L = model["num_hidden_layers"]
    out = {("wemb",): ((v, h), "w"), ("norm_f",): ((h,), "g"),
           ("head",): ((h, v), "w"), ("w_gate",): ((h,), "w"),
           ("b_gate",): ((1,), "z")}
    for leaf, shape in (("wqkv", (h, (nq + 2 * nkv) * hd)),
                        ("wo", (nq * hd, h)), ("wg", (h, f)),
                        ("wu", (h, f)), ("wd", (f, h))):
        out[("layers", leaf)] = ((L,) + shape, "w")
    for leaf in ("n1", "n2", "n3", "n4"):
        out[("layers", leaf)] = ((L, h), "g")
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    """Leaves with three axes are drawn one layer at a time
    (``lax.map``), so that the float32 draw of a 0.55 B-value leaf never
    exists whole beside the 5.3 GB it is part of."""
    out = {}
    for i, (path, shape, kind) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if kind == "z":
            out[path] = jnp.zeros(shape, jnp.float32)
        elif kind == "g":
            out[path] = jnp.ones(shape, dtype)
        else:
            def draw(k, shape=shape[1:] if len(shape) > 2 else shape):
                return (jax.random.normal(k, shape, jnp.float32)
                        * _STD).astype(dtype)
            out[path] = jax.lax.map(draw, jax.random.split(k, shape[0])) \
                if len(shape) > 2 else draw(k)
    return out


def make(seed, model, dtype):
    """All leaves as a nested dict (``w["layers"]["wqkv"]``,
    ``w["wemb"]``), on the default device, in ``dtype`` (kind ``z``:
    float32)."""
    shapes = tuple((p, s, kind)
                   for p, (s, kind) in sorted(leaf_shapes(model).items()))
    flat = _make(seed_key(seed), shapes, jnp.dtype(dtype).name)
    tree = {}
    for path, a in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = a
    return tree


def count_params(model):
    return sum(math.prod(shape) for shape, _ in leaf_shapes(model).values())
