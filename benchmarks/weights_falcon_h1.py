"""Seeded weights of a ``falcon_h1`` configuration, made on the device in
one jitted call, in the dtype they are served in.

The benchmark owns the weights: the program under test is handed them
(``planes/falcon_h1_program.py`` gives them to the model class) and the
plain reference (``reference/falcon_h1.py``) builds the same ones from
the same seed. Layout (part of the model's definition, as a checkpoint
format would be): linear weights are ``[in, out]``; ``in_proj``'s output
axis is (z ``d_ssm`` | x ``d_ssm`` | B | C ``G x N`` each | dt ``H``);
a layer's three attention projections are ONE leaf ``wqkv`` whose output
axis is (q heads | k heads | v heads) x ``head_dim``, a head's lanes in
half-split rotary pairs; ``conv_w`` is ``[taps, channels]`` with the
LAST tap on the newest input; per-layer leaves are stacked on a leading
axis of ``num_hidden_layers``.

Kinds: ``w`` N(0, 0.02 r / m), with ``m`` THE PRODUCT OF THE PUBLISHED
MULTIPLIERS THE MATRIX'S OUTPUT MEETS (``multipliers``): the embedding
meets ``embedding_multiplier``, the head ``lm_head_multiplier``,
``in_proj``'s five column segments ``ssm_in_multiplier`` x their entry
of ``ssm_multipliers``, ``out_proj`` ``ssm_out_multiplier``, ``wqkv``'s
columns ``attention_in_multiplier`` (x ``key_multiplier`` for the
keys'), ``wo`` ``attention_out_multiplier``, ``wg`` and ``wd`` the two
``mlp_multipliers``, ``wu`` none. So each product of a matrix and its
multipliers is N(0, 0.02 r), and the three branches add to the residual
stream what they add in a standard-parametrised model. The multipliers
belong to weights TRAINED under them (muP); at N(0, 0.02) throughout
``key_multiplier`` 0.011 would flatten every softmax,
``attention_out_multiplier`` 0.0375 and ``mlp_multipliers[1]`` 0.011
would put both branches far under the embedding's 5.66 x, and
``lm_head_multiplier`` 0.0078 would make every logit gap pass any
limit: a fault in a mixer would not reach the served logits.
``r`` is 1 but for the three matrices that write a branch into the
residual stream (``fan_in_gain``): ``out_proj``, ``wo`` and ``wd`` are
drawn at ``r = sqrt(hidden / fan_in)``, so that each branch adds 0.02
sqrt(hidden) x the RMS of what it projects, whatever its width. With
``r = 1`` throughout the feed-forward's term (fan-in 21,504) stood 10 x
over attention's (fan-in 2,560, its values averaged over the positions
a random softmax spreads on) on the chip: 3.80 against 0.38-0.42 at
1,024 positions, the state-space mixer's 1.28 between them (my chip
run, PR 48, the ``branch balance`` lines of call A); with it the three
lie within a factor of 4 (the set-up logs them every run). ``g`` the
norms' gains, 1 + N(0, 0.02) (a path that drops a gain cannot agree
with the reference); ``c`` the convolution's taps, U(-1/2, 1/2)
(``1/sqrt(taps)``, the published initialisation); ``z`` the float32
leaves, each drawn as its name says, as ``weights_nemotron_h.py`` draws
them:
  ``A_log``    log U(1, 16)
  ``dt_bias``  softplus^-1 of exp U(log 1e-3, log 1e-1), floor 1e-4
               (the published mixer's ``time_step_min/max``)
  ``D``        1 + N(0, 0.02)
Nothing here is routed, so no draw has to be balanced against the seed:
a decode step's bytes are the same whatever the weights.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.weights import seed_key

_STD = 0.02
# a leaf of more values than this is drawn a part at a time, so that
# its float32 draw never exists whole beside the 10.5 GB it is part of
_PART = 1 << 28


def sizes(model):
    H, P = model["mamba_n_heads"], model["mamba_d_head"]
    gn = model["mamba_n_groups"] * model["mamba_d_state"]
    return {"d": H * P, "gn": gn, "conv_dim": H * P + 2 * gn, "H": H}


def leaf_shapes(model):
    """(group, leaf) or (leaf,) -> (shape, kind)."""
    s = sizes(model)
    h, v = model["hidden_size"], model["vocab_size"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, f = model["head_dim"], model["intermediate_size"]
    L, K = model["num_hidden_layers"], model["mamba_d_conv"]
    d, cd, H = s["d"], s["conv_dim"], s["H"]
    out = {("wemb",): ((v, h), "w"), ("norm_f",): ((h,), "g"),
           ("head",): ((h, v), "w")}
    for leaf, shape, kind in (
            ("norm_in", (h,), "g"), ("in_proj", (h, d + cd + H), "w"),
            ("conv_w", (K, cd), "c"), ("conv_b", (cd,), "w"),
            ("dt_bias", (H,), "z"), ("A_log", (H,), "z"), ("D", (H,), "z"),
            ("gnorm", (d,), "g"), ("out_proj", (d, h), "w"),
            ("wqkv", (h, (nq + 2 * nkv) * hd), "w"),
            ("wo", (nq * hd, h), "w"), ("norm_ff", (h,), "g"),
            ("wg", (h, f), "w"), ("wu", (h, f), "w"), ("wd", (f, h), "w")):
        out[("layers", leaf)] = ((L,) + shape, kind)
    return out


def multipliers(model):
    """leaf -> ((columns, multiplier), ...) over the matrix's output
    axis: what its product meets in the published forward pass. A leaf
    that is not here meets none."""
    s = sizes(model)
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["head_dim"]
    sm, si = model["ssm_multipliers"], model["ssm_in_multiplier"]
    ai = model["attention_in_multiplier"]
    gate, down = model["mlp_multipliers"]
    return {
        "wemb": ((model["hidden_size"], model["embedding_multiplier"]),),
        "head": ((model["vocab_size"], model["lm_head_multiplier"]),),
        "in_proj": ((s["d"], si * sm[0]), (s["d"], si * sm[1]),
                    (s["gn"], si * sm[2]), (s["gn"], si * sm[3]),
                    (s["H"], si * sm[4])),
        "out_proj": ((model["hidden_size"], model["ssm_out_multiplier"]),),
        "wqkv": ((nq * hd, ai), (nkv * hd, ai * model["key_multiplier"]),
                 (nkv * hd, ai)),
        "wo": ((model["hidden_size"],
                model["attention_out_multiplier"]),),
        "wg": ((model["intermediate_size"], gate),),
        "wd": ((model["hidden_size"], down),),
    }


def fan_in_gain(model):
    """leaf -> ``sqrt(hidden / fan_in)`` for the matrices that write a
    branch into the residual stream."""
    s, h = sizes(model), model["hidden_size"]
    wide = {"out_proj": s["d"],
            "wo": model["num_attention_heads"] * model["head_dim"],
            "wd": model["intermediate_size"]}
    return {leaf: math.sqrt(h / n) for leaf, n in wide.items()}


def _draw_f32(name, k, shape):
    u = jax.random.uniform(k, shape, jnp.float32)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":
        dt = jnp.maximum(jnp.exp(math.log(1e-3) + u * (
            math.log(1e-1) - math.log(1e-3))), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    return 1.0 + _STD * jax.random.normal(k, shape, jnp.float32)   # D


def _parts(shape):
    """(parts, the shape of one): a stacked leaf a layer at a time, a
    large matrix in 8 runs of rows."""
    if len(shape) > 2:
        return shape[0], shape[1:]
    if len(shape) == 2 and math.prod(shape) > _PART and shape[0] % 8 == 0:
        return 8, (shape[0] // 8, shape[1])
    return 1, shape


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _make(key, shapes, dtype):
    out = {}
    for i, (path, shape, kind, gain, segments) in enumerate(shapes):
        k = jax.random.fold_in(key, i)
        if kind == "z":
            out[path] = _draw_f32(path[-1], k, shape)
            continue
        n, part = _parts(shape)
        std = _STD * gain if not segments else jnp.concatenate(
            [jnp.full((cols,), _STD * gain / m, jnp.float32)
             for cols, m in segments])

        def draw(k, part=part, kind=kind, std=std):
            if kind == "c":
                return (jax.random.uniform(k, part, jnp.float32) - 0.5
                        ).astype(dtype)
            x = jax.random.normal(k, part, jnp.float32) * std
            return (x + 1.0 if kind == "g" else x).astype(dtype)

        out[path] = jax.lax.map(draw, jax.random.split(k, n)).reshape(
            shape) if n > 1 else draw(k)
    return out


def make(seed, model, dtype):
    """All leaves as a nested dict (``w["layers"]["in_proj"]``,
    ``w["wemb"]``), on the default device, in ``dtype`` (kind ``z``:
    float32)."""
    mult, gain = multipliers(model), fan_in_gain(model)
    shapes = tuple(
        (p, s, kind, gain.get(p[-1], 1.0),
         tuple(mult.get(p[-1], ())) if kind == "w" else ())
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
