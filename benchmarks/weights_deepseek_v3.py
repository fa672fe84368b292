"""Seeded weights of a ``deepseek_v3`` configuration, made on the device
in one jitted call, in the dtype they are served in.

The benchmark owns the weights: the program under test is handed them
(``planes/deepseek_v3_program.py`` gives them to the model class) and
the plain reference (``reference/deepseek_v3.py``) builds the same ones
from the same seed. Layout (part of the model's definition, as a
checkpoint format would be): linear weights are ``[in, out]``; ``wq``'s
output axis is ``(heads, nope + rope)``; ``wkva``'s is ``(rank + rope)``;
``wkvb`` is ``[rank, heads, nope + v]``; per-layer leaves are stacked on
a leading axis within the group ``dense`` (the first
``first_k_dense_replace`` layers) or ``moe`` (the rest); the routed
experts' matrices are stacked flat, ``[expert layers * experts, ., .]``.

Every weight is N(0, 0.02), every norm gain 1 + N(0, 0.02) (a path that
drops a gain cannot agree with the reference), the router's score
correction bias 0 in float32 (the published checkpoint's values are not
fetched).
"""
import functools

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

_STD = 0.02


def leaf_shapes(model):
    """(group, leaf) or (leaf,) -> (shape, kind); kind "w", "g" or "z"."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    r, dn = model["kv_lora_rank"], model["qk_nope_head_dim"]
    dr, dv = model["qk_rope_head_dim"], model["v_head_dim"]
    i, f = model["intermediate_size"], model["moe_intermediate_size"]
    e, v = model["n_routed_experts"], model["vocab_size"]
    fs = f * model["n_shared_experts"]
    n = model["num_hidden_layers"]
    k = min(model["first_k_dense_replace"], n)
    m = n - k
    attn = {"norm1": ((h,), "g"), "wq": ((h, nh * (dn + dr)), "w"),
            "wkva": ((h, r + dr), "w"), "kv_norm": ((r,), "g"),
            "wkvb": ((r, nh, dn + dv), "w"), "wo": ((nh * dv, h), "w"),
            "norm2": ((h,), "g")}
    out = {("wemb",): ((v, h), "w"), ("norm_f",): ((h,), "g"),
           ("head",): ((h, v), "w")}
    if k:
        for name, (shape, kind) in dict(
                attn, mlp_gate=((h, i), "w"), mlp_up=((h, i), "w"),
                mlp_down=((i, h), "w")).items():
            out[("dense", name)] = ((k,) + shape, kind)
    if m:
        for name, (shape, kind) in dict(
                attn, router_w=((h, e), "w"), router_b=((e,), "z"),
                sh_gate=((h, fs), "w"), sh_up=((h, fs), "w"),
                sh_down=((fs, h), "w")).items():
            out[("moe", name)] = ((m,) + shape, kind)
        for name, shape in (("gate", (h, f)), ("up", (h, f)),
                            ("down", (f, h))):
            out[("experts", name)] = ((m * e,) + shape, "w")
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    """Leaves with three or more axes are drawn one leading index at a
    time (``lax.map``), so that the float32 draw of a 1.0 B-value leaf
    never exists whole beside the 7.6 GB it is part of."""
    out = {}
    for i, (path, shape, kind) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if kind == "z":
            out[path] = jnp.zeros(shape, jnp.float32)
            continue

        def draw(k, shape=shape[1:] if len(shape) > 2 else shape,
                 kind=kind):
            x = jax.random.normal(k, shape, jnp.float32) * _STD
            return (x + 1.0 if kind == "g" else x).astype(dtype)

        out[path] = jax.lax.map(draw, jax.random.split(k, shape[0])) \
            if len(shape) > 2 else draw(k)
    return out


def make(seed, model, dtype):
    """All leaves as a nested dict (``w["moe"]["wq"]``, ``w["wemb"]``),
    on the default device, in ``dtype``."""
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
    total = 0
    for shape, _ in leaf_shapes(model).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total
