"""What the causal LMs that are built FOR SERVING share
(``text.deepseek_v3``, ``text.nemotron_h``, ``text.mimo_v2``, ``text.ouro``,
``text.falcon_h1``): parameters held stacked per
group in the serving dtype, exactly as the compiled programs take them,
a ready tree of arrays adopted without a copy, a small cache of jitted
eager programs, and the refusal by name of engine options the model has
no program for.
"""
import collections
import re
import sys

import jax
import jax.numpy as jnp

from ..profiler import device_scope
from .. import nn


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + jnp.float32(eps))
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def project_heads(x, w, heads):
    """``x @ w`` (``w`` ``[in, heads * d]``) split into heads: ``[...,
    heads, d]`` in x's dtype. A 2-D matmul with an f32 result, rounded
    at once to x's dtype (where a bf16 dot rounds its own) and split
    into heads AFTER that. With the split folded into the dot XLA wants
    the contracted axis minor-most: it staged a stacked layer's weight
    in VMEM and relaid it there, every layer of every decode step; in
    this form it reads the weight from HBM inside the matmul, as it does
    for an output projection (PERF.md section 6, PR 45)."""
    y = jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)
    return y.reshape(x.shape[:-1] + (heads, -1))


def block_of(cfg):
    """The module that holds a configuration's block: the one its class
    is defined in (``text.nemotron_h`` for a ``NemotronHConfig``,
    ``text.falcon_h1`` for a ``FalconH1Config``). The paged programs
    and the eager paths that two families share call the block through
    it."""
    return sys.modules[type(cfg).__module__]


def lm_head(cfg, params, x):
    """Final norm + head over x ``[..., h]``: logits in f32."""
    with device_scope("lm_head"):
        return jnp.dot(rms_norm(x, params["norm_f"], cfg.rms_norm_eps),
                       params["head"], preferred_element_type=jnp.float32)


def count_routing(counts, layer, tokens):
    """Add one decode step's routing in expert layer ``layer`` to
    ``counts`` (``[expert layers, held + 2]`` int32: tokens per held
    expert, distinct experts hit, steps)."""
    row = jnp.concatenate([
        tokens, jnp.sum(tokens > 0, dtype=jnp.int32)[None],
        jnp.ones((1,), jnp.int32)])
    return counts.at[layer].add(row)


def layer_plan(pattern):
    """A pattern of one letter a layer as runs ``[(unit, repeats),
    ...]``: at each position the repeated unit (up to 4 letters) that
    covers most layers, or the single layer. A run is one ``lax.scan``
    of a model's layer loop."""
    plan, i = [], 0
    while i < len(pattern):
        best = (pattern[i], 1)
        for u in range(1, 5):
            unit = pattern[i:i + u]
            reps = len(re.match(f"(?:{re.escape(unit)})*",
                                pattern[i:]).group(0)) // u
            if reps > 1 and u * reps > len(best[0]) * best[1]:
                best = (unit, reps)
        plan.append(best)
        i += len(best[0]) * best[1]
    return plan


def take_layer(tree, i):
    """Layer ``i`` of a kind's stacked weights."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _init_leaf(key, shape, kind, dtype, std):
    if kind == "g":
        return jnp.ones(shape, dtype)
    if kind == "z":
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            * jnp.float32(std)).astype(dtype)


_init_leaf_jit = jax.jit(_init_leaf, static_argnums=(1, 2, 3, 4))


def greedy_or_sampled(greedy, top_k):
    """``pick(logits [b, V], key, temp) -> [b]`` int32: the argmax, or a
    temperature sample over the ``top_k`` logits (0 = all)."""
    def pick(logits, key, temp):
        if greedy:
            return jnp.argmax(logits, -1).astype(jnp.int32)
        lg = logits / temp
        if top_k > 0:
            kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
            lg = jnp.where(lg < kth, jnp.float32(-1e30), lg)
        return jax.random.categorical(key, lg).astype(jnp.int32)
    return pick


class StackedCausalLM(nn.Layer):
    """``shapes``: {path tuple: (shape, kind, dtype name)}; kind "w"
    N(0, ``cfg.initializer_range``), "g" ones, "z" zeros.
    ``export_decode_params()`` hands out the arrays the model holds (no
    second copy), and ``weights=`` adopts a ready tree of arrays without
    initialising anything."""

    def __init__(self, cfg, shapes, weights=None, seed=0):
        super().__init__()
        self.cfg = cfg
        self._paths = {}
        key = jax.random.PRNGKey(int(seed))
        for i, (path, (shape, kind, dt)) in enumerate(
                sorted(shapes.items())):
            if weights is not None:
                a = weights
                for part in path:
                    a = a[part]
                if tuple(a.shape) != shape or jnp.dtype(a.dtype) != \
                        jnp.dtype(dt):
                    raise ValueError(
                        f"{'.'.join(path)}: got {a.dtype}{a.shape}, "
                        f"the config says {dt}{shape}")
            else:
                a = _init_leaf_jit(jax.random.fold_in(key, i), shape,
                                   kind, dt, cfg.initializer_range)
            name = "_".join(path)
            from ..core.tensor import Parameter
            self.add_parameter(name, Parameter(a, name=name,
                                               trainable=False))
            self._paths[path] = name
        self._decode_cache = collections.OrderedDict()

    def export_decode_params(self):
        """The parameter tree of the compiled programs, BY REFERENCE:
        the arrays the model holds, as of this call."""
        from ..core.lazy import concrete
        tree = {}
        for path, name in self._paths.items():
            node = tree
            for part in path[:-1]:
                node = node.setdefault(part, {})
            node[path[-1]] = concrete(self._parameters[name].value)
        return tree

    def check_serving_config(self, config):
        """Refuse, by name, an engine option this model has no program
        for (the engine calls this at construction)."""
        bad = [name for name, on in (
            ("speculative", config.speculative),
            (f"role={config.role!r}", config.role != "monolithic"),
        ) if on]
        if bad:
            raise ValueError(
                f"{type(self).__name__} is served greedy or with "
                f"sampling=True; no program for: {', '.join(bad)}")

    def _no_program(self, what):
        raise NotImplementedError(
            f"{type(self).__name__} has no {what} program: only "
            f"prefill and decode (greedy or sampling=True) are brought")

    def build_paged_spec_verify_fn(self, *a, **k):
        self._no_program("speculative verify")

    def _ids(self, input_ids):
        from ..core.lazy import concrete
        return jnp.asarray(concrete(getattr(input_ids, "value", input_ids)),
                           jnp.int32)

    def _jitted(self, key, fn):
        cache = self._decode_cache
        got = cache.get(key)
        if got is None:
            got = cache[key] = jax.jit(fn)
            while len(cache) > 8:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return got
