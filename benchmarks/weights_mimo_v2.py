"""Seeded weights of a ``mimo_v2`` configuration, made on the device in
one jitted call, in the dtype they are served in.

The benchmark owns the weights: the program under test is handed them
(``planes/mimo_v2_program.py`` gives them to the model class) and the
plain reference (``reference/mimo_v2.py``) builds the same ones from the
same seed. Layout (part of the model's definition, as a checkpoint
format would be): linear weights are ``[in, out]``; ``wq``'s and
``wk``'s output axis is ``(heads, head_dim)`` and a head's lanes are
(rotated ``int(head_dim * partial_rotary_factor)`` | not rotated), the
rotated ones in half-split pairs; ``wv``'s is ``(heads, v_head_dim)``;
per-layer leaves are stacked on a leading axis BY KIND (``full`` and
``win`` attention, ``dense`` and ``moe`` feed-forward); the routed
experts' three matrices are stacked flat, ``[expert layers * held
experts, ., .]``, and only the HELD experts exist.

Kinds: ``w`` N(0, 0.02); ``g`` 1 + N(0, 0.02) (a path that drops a gain
cannot agree with the reference); ``r`` the router's columns, N(0, 0.02)
in ANTITHETIC PAIRS (below); ``z`` the float32 leaves, each drawn as its
name says:
  ``router_b`` N(0, 0.01) in ANTITHETIC PAIRS (below): the score
               correction bias NOT zero, so that choice (with it) and
               weight (without it) differ, and small beside the scores'
               own spread over tokens, as a bias trained to balance the
               load is (``weights_nemotron_h.py`` has the reading
               behind the size);
  ``sink``     N(0, 1): a window layer's learned sink logits NOT zero
               and of the scores' own size, so that a path which leaves
               the sink out moves every window layer's weights by an
               amount the check sees.

The pairs: expert ``2i + 1``'s router column AND its bias are minus
expert ``2i``'s. They are there so that the EXPERTS HIT A STEP, and with
them the step's time, do not follow the seed. With 48 tokens a step, 8
of 256 experts each and 16 held, a layer's held experts see 24 pairs a
step and 12 of the 16 are hit: the one regime in the benchmark in which
a step's bytes follow the held share's load (the other two expert cells
hold every expert, or see 384 pairs on 64). Drawn independently, each
expert's popularity follows its own bias and column: the eight chosen
have scores near 0.92, where the sigmoid has flattened a logit 13 times
over, so a bias of 0.01 is 0.1 of the logits' spread and makes its
expert 1.2 times as popular or as rare; a mean that a router's input
carries (3-11 % of its energy: 384 tokens at the published widths, CPU,
PR 42) does as much through ``mu . w_e``. Single experts were 1.8-1.9
times as popular as the mean (``moe_load_imbalance``), the held 16's
load followed the seed by -12 to +5 % a layer (the same for a seed
whatever the embedding's size, and with token streams that never repeat:
my chip runs, PR 42), the experts hit a step by 59.2-61.9 of 80, and
``serve_tokens_per_s`` by 1.0-1.2 % over six seeds, the same seed giving
the same count of steps to 1 in 3,500, where half the bound is 0.7 %. A
trained router does not do that: its bias is trained to balance the
load. In antithetic pairs the two of a pair are moved by opposite
amounts, so the load of any share made of whole pairs (the held 16 are
eight) no longer follows the draw to first order. A pair's two experts
are never both among a token's eight; every width, the bias's size and
the choice by ``s + b`` are as published.

A SwiGLU's middle activation ``silu(g) * u`` has no mean of its own
(``u`` is symmetric about 0 and independent of ``g``), so no projection
here needs its columns centred as the relu-squared experts' do.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

_STD = 0.02


def sizes(model):
    """The derived sizes of a configuration's model keys."""
    kinds = ["win" if a else "full" for a in model["hybrid_layer_pattern"]]
    ffns = ["moe" if f else "dense" for f in model["moe_layer_freq"]]
    return {"kinds": kinds, "ffns": ffns,
            "n": {k: (kinds + ffns).count(k)
                  for k in ("full", "win", "dense", "moe")},
            "kv": {"full": model["num_key_value_heads"],
                   "win": model.get("swa_num_key_value_heads",
                                    model["num_key_value_heads"])},
            "rot": int(model["head_dim"]
                       * model.get("partial_rotary_factor", 1.0)),
            "held": model["n_routed_experts"],
            "router": model.get("router_experts",
                                model["n_routed_experts"]),
            "first": model.get("first_held_expert", 0)}


def leaf_shapes(model):
    """(group, leaf) or (leaf,) -> (shape, kind)."""
    s = sizes(model)
    if s["router"] % 2:
        raise ValueError("router columns are drawn in pairs: "
                         f"{s['router']} experts")
    h, v = model["hidden_size"], model["vocab_size"]
    nq, hd = model["num_attention_heads"], model["head_dim"]
    dv = model["v_head_dim"]
    f, fd = model["moe_intermediate_size"], model["intermediate_size"]

    def attn(nkv):
        return {"norm": ((h,), "g"), "wq": ((h, nq * hd), "w"),
                "wk": ((h, nkv * hd), "w"), "wv": ((h, nkv * dv), "w"),
                "wo": ((nq * dv, h), "w")}
    win = attn(s["kv"]["win"])
    if model.get("add_swa_attention_sink_bias", False):
        win["sink"] = ((nq,), "z")
    groups = {
        "full": attn(s["kv"]["full"]), "win": win,
        "dense": {"norm": ((h,), "g"), "gate": ((h, fd), "w"),
                  "up": ((h, fd), "w"), "down": ((fd, h), "w")},
        "moe": {"norm": ((h,), "g"), "router_w": ((h, s["router"]), "r"),
                "router_b": ((s["router"],), "z")},
        "experts": {"gate": ((h, f), "w"), "up": ((h, f), "w"),
                    "down": ((f, h), "w")},
    }
    out = {("wemb",): ((v, h), "w"), ("norm_f",): ((h,), "g"),
           ("head",): ((h, v), "w")}
    n = dict(s["n"], experts=s["n"]["moe"] * s["held"])
    for group, leaves in groups.items():
        if n[group]:
            for name, (shape, kind) in leaves.items():
                out[(group, name)] = ((n[group],) + shape, kind)
    return out


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    """Leaves with three or more axes are drawn one leading index at a
    time (``lax.map``), so that the float32 draw of a 2.0 B-value leaf
    never exists whole beside the 5.9 GB it is part of."""
    out = {}
    for i, (path, shape, kind) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if kind == "z":
            if path[-1] == "sink":
                out[path] = jax.random.normal(k, shape, jnp.float32)
            else:               # [n, E]: bias 2i + 1 = -bias 2i
                b = jax.random.normal(k, (shape[0], shape[1] // 2),
                                      jnp.float32) * 0.01
                out[path] = jnp.stack([b, -b], axis=-1).reshape(shape)
            continue

        def draw(k, shape=shape[1:] if len(shape) > 2 else shape,
                 kind=kind):
            if kind == "r":     # [h, E]: column 2i + 1 = -column 2i
                x = jax.random.normal(k, (shape[0], shape[1] // 2),
                                      jnp.float32) * _STD
                return jnp.stack([x, -x], axis=-1).reshape(shape
                                                           ).astype(dtype)
            x = jax.random.normal(k, shape, jnp.float32) * _STD
            return (x + 1.0 if kind == "g" else x).astype(dtype)

        out[path] = jax.lax.map(draw, jax.random.split(k, shape[0])) \
            if len(shape) > 2 else draw(k)
    return out


def make(seed, model, dtype):
    """All leaves as a nested dict (``w["win"]["wq"]``, ``w["wemb"]``),
    on the default device, in ``dtype`` (kind ``z``: float32)."""
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
