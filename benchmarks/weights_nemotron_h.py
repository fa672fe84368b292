"""Seeded weights of a ``nemotron_h`` configuration, made on the device
in one jitted call, in the dtype they are served in.

The benchmark owns the weights: the program under test is handed them
(``planes/nemotron_h_program.py`` gives them to the model class) and the
plain reference (``reference/nemotron_h.py``) builds the same ones from
the same seed. Layout (part of the model's definition, as a checkpoint
format would be): linear weights are ``[in, out]``; a state-space layer's
``in_proj`` output axis is ``(gate d_inner | conv channels d_inner + 2 G
N | dt H)``, its ``conv_w`` is ``[taps, channels]`` with the LAST tap on
the newest input; an expert's two matrices are BOTH ``[f, h]`` (``up``
transposed: a width that is no multiple of 128 rides the sublanes);
per-layer leaves are stacked on a leading axis BY KIND of layer
(``mamba``, ``attn``, ``moe``); the routed experts' matrices are stacked
flat, ``[expert layers * held experts, f, h]``, and only the HELD
experts exist.

Kinds: ``w`` N(0, 0.02); ``o`` the same with every output column's mean
over the INPUT axis taken off (below); ``g`` 1 + N(0, 0.02) (a path that
drops a gain cannot agree with the reference); ``c`` the convolution's taps, U(-1/2,
1/2) (``1/sqrt(taps)``, the published initialisation: with 0.02 the
channels would vanish under the gated norm); ``z`` the float32 leaves,
each drawn as its name says, so that ``exp(dt A)`` spans what a trained
model's does instead of sitting at 0 or 1:
  ``A_log``    log U(1, 16)                      (published init)
  ``dt_bias``  softplus^-1 of exp U(log 1e-3, log 1e-1), floor 1e-4
               (published init: ``time_step_min/max/floor``)
  ``D``        1 + N(0, 0.02)
  ``router_b`` N(0, 0.01): the score correction bias NOT zero, so that
               choice (with it) and weight (without it) differ, and
               small beside the scores' own spread over tokens (about
               0.2), as a bias trained to BALANCE the load is: drawn at
               N(0, 0.1) it decided the routing by itself (half of a
               step's tokens chose one expert, a third of the held
               experts were never hit: my chip run, PR 35).

Kind ``o`` is the three projections that read an activation with a mean
of its own: an expert's and the shared expert's ``down`` (``relu(.)^2``
is never negative) and a state-space layer's ``out_proj`` (its input
carries ``D * silu(.)``). A random projection turns that mean into ONE
vector that every token's residual stream shares, and at these widths it
outgrows what tells tokens apart (the embedding is N(0, 0.02)); every
router then sees nearly the same input, a third of a step's tokens chose
one expert, 94.5-95.3 % of the held experts were hit a step depending on
the seed, and the step's time followed the seed by 1.4 % (12 seeds, my
chip runs, PR 35: the refused check's spread). Trained weights route
evenly (the score correction bias is trained for it), and 768 choices a
step over 128 experts leave a held expert unhit 0.25 % of the time. A
column that sums to zero over its inputs passes the fluctuation and
drops the shared mean; each weight moves by about 2 % of its spread.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

_STD = 0.02


def sizes(model):
    """The derived sizes of a configuration's model keys."""
    pattern = model["hybrid_override_pattern"]
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    gn = model["n_groups"] * model["ssm_state_size"]
    return {"pattern": pattern, "d_inner": H * P,
            "conv_dim": H * P + 2 * gn,
            "held": model["n_routed_experts"],
            "router": model.get("router_experts",
                                model["n_routed_experts"]),
            "first": model.get("first_held_expert", 0),
            "n": {c: pattern.count(c) for c in "ME*"}}


def leaf_shapes(model):
    """(group, leaf) or (leaf,) -> (shape, kind)."""
    s = sizes(model)
    h, v = model["hidden_size"], model["vocab_size"]
    H, d, cd = model["mamba_num_heads"], s["d_inner"], s["conv_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, K = model["head_dim"], model["conv_kernel"]
    f = model["moe_intermediate_size"]
    fs = model["moe_shared_expert_intermediate_size"]
    groups = {
        "mamba": {"norm": ((h,), "g"), "in_proj": ((h, d + cd + H), "w"),
                  "conv_w": ((K, cd), "c"), "conv_b": ((cd,), "w"),
                  "dt_bias": ((H,), "z"), "A_log": ((H,), "z"),
                  "D": ((H,), "z"), "gnorm": ((d,), "g"),
                  "out_proj": ((d, h), "o")},
        "attn": {"norm": ((h,), "g"), "wq": ((h, nq * hd), "w"),
                 "wk": ((h, nkv * hd), "w"), "wv": ((h, nkv * hd), "w"),
                 "wo": ((nq * hd, h), "w")},
        "moe": {"norm": ((h,), "g"), "router_w": ((h, s["router"]), "w"),
                "router_b": ((s["router"],), "z"),
                "sh_up_t": ((fs, h), "w"), "sh_down": ((fs, h), "o")},
        "experts": {"up_t": ((f, h), "w"), "down": ((f, h), "o")},
    }
    out = {("wemb",): ((v, h), "w"), ("norm_f",): ((h,), "g"),
           ("head",): ((h, v), "w")}
    m = s["n"]["E"]
    for group, n in (("mamba", s["n"]["M"]), ("attn", s["n"]["*"]),
                     ("moe", m), ("experts", m * s["held"])):
        if n:
            for name, (shape, kind) in groups[group].items():
                out[(group, name)] = ((n,) + shape, kind)
    return out


def _draw_f32(name, k, shape):
    u = jax.random.uniform(k, shape, jnp.float32)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":
        dt = jnp.maximum(jnp.exp(math.log(1e-3) + u * (
            math.log(1e-1) - math.log(1e-3))), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    n = jax.random.normal(k, shape, jnp.float32)
    return 1.0 + _STD * n if name == "D" else 0.01 * n


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    """Leaves with three or more axes are drawn one leading index at a
    time (``lax.map``), so that the float32 draw of a 1.6 B-value leaf
    never exists whole beside the 8.6 GB it is part of."""
    out = {}
    for i, (path, shape, kind) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if kind == "z":
            out[path] = _draw_f32(path[-1], k, shape)
            continue

        def draw(k, shape=shape[1:] if len(shape) > 2 else shape,
                 kind=kind):
            if kind == "c":
                return (jax.random.uniform(k, shape, jnp.float32) - 0.5
                        ).astype(dtype)
            x = jax.random.normal(k, shape, jnp.float32) * _STD
            if kind == "o":     # [in, out]: columns sum to zero
                x = x - x.mean(axis=0, keepdims=True)
            return (x + 1.0 if kind == "g" else x).astype(dtype)

        out[path] = jax.lax.map(draw, jax.random.split(k, shape[0])) \
            if len(shape) > 2 else draw(k)
    return out


def make(seed, model, dtype):
    """All leaves as a nested dict (``w["mamba"]["in_proj"]``,
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
