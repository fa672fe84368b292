"""The ``nemotron_h`` family of causal LMs on the served path: a stack
in which every layer is ONE mixer with its norm and its residual, ``x =
x + mixer(rms_norm(x))``, the mixer named by a letter of
``hybrid_override_pattern``:

  ``M``  a Mamba-2 state-space mixer (``ops.ssm``): in-projection to a
         gate, convolution channels and step sizes; a causal depthwise
         convolution; the recurrence over a state ``[P, N]`` a head with
         ``B`` and ``C`` shared by the heads of a group; a gated group
         norm; out-projection;
  ``*``  causal softmax attention with grouped queries (``num_heads``
         query heads over ``num_key_value_heads`` KV heads) and NO
         positional rotation (the published block applies none);
  ``E``  a dropless expert layer: sigmoid scores, bias-corrected top-k,
         NON-GATED experts ``down(relu(up x)^2)`` (``ops.moe_experts``:
         the relu-squared form) and one shared expert of the same form.

**The block is written once, here**: ``mamba_mixer``, ``attention`` and
``expert_mixer`` are called by the eager ``forward``, by ``generate()``
and by the engine's paged programs (``serving/paged/hybrid_programs.py``).
What differs between the callers is how a layer reaches what it keeps of
a sequence, and that is an ACCESS object over a tuple of cache arrays it
does not own. Two kinds of state live side by side: keys and values a
TOKEN owns (attention layers only), and a convolution window and a
recurrent state a SLOT owns (state-space layers only):

  ``attn_prefill(state, li, start, k, v) -> state, (k_view, v_view)``
      write a run of new rows at positions ``start..``, give back
      position-ordered views ``[b, nkv, C, hd]`` that include them;
  ``attn_decode(state, li, pos, q, k, v) -> state, o``
      write one row a sequence and attend over what is live;
  ``ssm_init(state, mi, start, b) -> window, S``  what the run starts
      from (S packed, ``ops.ssm``): zeros when ``start == 0`` whatever
      the slot held;
  ``ssm_commit(state, mi, window, S) -> state``  what it ends with;
  ``ssm_decode(state, mi, pos, ...) -> state, xs, y``  one step.

``li`` counts attention layers, ``mi`` state-space layers.
``SeqAccess`` (no cache: eager forward) and ``ContigAccess``
(``generate()``) are here; ``PagedAccess`` is beside the programs.

**The layer loop** walks ``layer_plan(pattern)``: the pattern cut into
runs of a repeated unit (``MEMEM*EMEMEM*`` is ``ME`` x 2, ``M``, ``*``,
``EM`` x 3, ``*``). A run is one ``lax.scan`` over its repeats with the
cache state in the carry; every layer indexes the stacked weights of its
kind (``[6, ...]``, ``[5, ...]``, ``[2, ...]``) by its count within the
kind. So a body is traced once a run, not once a layer, and 52 layers
cost what 13 do.

A chip may hold a contiguous share of each layer's routed experts:
``n_routed_experts`` is then the number HELD, ``router_experts`` the
router's (published) width and ``first_held_expert`` where the share
starts; the layer returns its own experts' part of the sum plus the
shared expert's.

Shared with ``text.falcon_h1`` (a state-space mixer AND attention in
EVERY layer): the state-space mixer between its two projections
(``split_projection``, ``ssm_core``), the access objects, and the model
class's serving and eager paths (``HybridCausalLM``), which call the
block of the configuration's own module (``stacked_lm.block_of``).

Not brought by this module: training, sharding over a mesh, expert
groups (``n_group > 1``), projection biases, a sliding window,
speculative decoding, a disaggregated role, KV hand-off.
"""
import jax
import jax.numpy as jnp

from ..profiler import device_scope
from ..ops import attention as attn_ops
from ..ops import moe_experts as moe_ops
from ..ops import ssm as ssm_ops
from .stacked_lm import (  # noqa: F401 - parts of this block
    StackedCausalLM, block_of, count_routing, greedy_or_sampled,
    layer_plan, lm_head, rms_norm, take_layer as _take)

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


class NemotronHConfig:
    """Sizes of one model, from the keys of a Hugging Face
    ``config.json`` of ``model_type: nemotron_h`` (``from_hf``)."""

    def __init__(self, vocab_size, hidden_size, hybrid_override_pattern,
                 num_attention_heads, num_key_value_heads, head_dim,
                 mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
                 moe_intermediate_size, moe_shared_expert_intermediate_size,
                 n_routed_experts, num_experts_per_tok,
                 num_hidden_layers=None, conv_kernel=4, chunk_size=128,
                 n_shared_experts=1, routed_scaling_factor=1.0,
                 norm_topk_prob=True, layer_norm_epsilon=1e-5,
                 max_position_embeddings=4096, n_group=1, topk_group=1,
                 mamba_proj_bias=False, use_bias=False, mlp_bias=False,
                 attention_bias=False, use_conv_bias=True,
                 sliding_window=None, mlp_hidden_act="relu2",
                 mamba_hidden_act="silu", router_experts=None,
                 first_held_expert=0, initializer_range=0.02,
                 dtype="float32", **ignored):
        for name, on in (("n_group > 1 (expert groups)", int(n_group) != 1
                          or int(topk_group) != 1),
                         ("mamba_proj_bias", mamba_proj_bias),
                         ("use_bias", use_bias), ("mlp_bias", mlp_bias),
                         ("attention_bias", attention_bias),
                         ("sliding_window", sliding_window is not None),
                         ("use_conv_bias=False", not use_conv_bias),
                         (f"mlp_hidden_act={mlp_hidden_act!r}",
                          mlp_hidden_act != "relu2"),
                         (f"mamba_hidden_act={mamba_hidden_act!r}",
                          mamba_hidden_act != "silu"),
                         ("n_shared_experts != 1",
                          int(n_shared_experts) != 1)):
            if on:
                raise NotImplementedError(
                    f"nemotron_h: {name} is not brought")
        self.pattern = str(hybrid_override_pattern)
        if set(self.pattern) - set(KINDS):
            raise NotImplementedError(
                f"nemotron_h: layer kinds {sorted(set(self.pattern) - set(KINDS))} "
                f"in {self.pattern!r}; only M, E and * are brought")
        if num_hidden_layers is not None \
                and int(num_hidden_layers) != len(self.pattern):
            raise ValueError(
                f"num_hidden_layers {num_hidden_layers} != "
                f"len({self.pattern!r})")
        self.num_layers = len(self.pattern)
        self.plan = layer_plan(self.pattern)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_heads = int(num_attention_heads)
        self.num_kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.num_heads % self.num_kv_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        self.mamba_heads = int(mamba_num_heads)
        self.mamba_head_dim = int(mamba_head_dim)
        self.state_size = int(ssm_state_size)
        self.n_groups = int(n_groups)
        if self.mamba_heads % self.n_groups:
            raise ValueError("n_groups must divide mamba_num_heads")
        self.conv_kernel = int(conv_kernel)
        self.chunk_size = int(chunk_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.shared_intermediate_size = int(
            moe_shared_expert_intermediate_size)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_norm_eps = float(layer_norm_epsilon)
        self.max_seq_len = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        # keys, values and the convolution window in the model's dtype;
        # the recurrence and the router's scores never below float32
        self.dtype = self.cache_dtype = jnp.dtype(dtype).name
        self.state_dtype = self.router_dtype = "float32"
        count = int(n_routed_experts)
        self.router_experts = int(router_experts or count)
        first = int(first_held_expert)
        if first < 0 or count < 1 or first + count > self.router_experts:
            raise ValueError(
                f"experts {first}..{first + count} are not a share of "
                f"the router's {self.router_experts}")
        self.held = (first, count)

    @classmethod
    def from_hf(cls, config, **overrides):
        return cls(**{**config, **overrides})

    def count(self, letter):
        return self.pattern.count(letter)

    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.state_size


# ------------------------------------------------------------ the block
def group_rms_norm(x, w, groups, eps):
    """RMS norm over each of ``groups`` equal runs of the last axis."""
    xf = x.astype(jnp.float32)
    xg = xf.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, axis=-1, keepdims=True)
                            + jnp.float32(eps))
    return xg.reshape(x.shape) * w.astype(jnp.float32)


def split_channels(cfg, act):
    """The activated convolution channels ``[..., conv_dim]`` as (xs
    ``[..., H, P]``, B, C ``[..., G, N]``)."""
    d, gn = cfg.d_inner, cfg.n_groups * cfg.state_size
    lead = act.shape[:-1]
    return (act[..., :d].reshape(lead + (cfg.mamba_heads,
                                         cfg.mamba_head_dim)),
            act[..., d:d + gn].reshape(lead + (cfg.n_groups,
                                               cfg.state_size)),
            act[..., d + gn:].reshape(lead + (cfg.n_groups,
                                              cfg.state_size)))


def split_projection(cfg, p, zxd):
    """A state-space in-projection's output ``[..., d + conv_dim + H]``
    as (gate z, convolution inputs u, step sizes dt in float32 after
    the softplus, A ``[H]``)."""
    d = cfg.d_inner
    f32 = jnp.float32
    z = zxd[..., :d]
    u = zxd[..., d:d + cfg.conv_dim]
    dt = jax.nn.softplus(zxd[..., d + cfg.conv_dim:].astype(f32)
                         + p["dt_bias"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    return z, u, dt, A


def ssm_core(cfg, p, z, u, dt, A, positions, access, state, mi, start,
             mode, length, kernel):
    """A state-space mixer between its two projections: convolution,
    recurrence, ``D`` skip and the gated group norm. "prefill": u ``[b,
    T, conv_dim]``, the first ``length`` rows are the run; "decode": u
    ``[S, conv_dim]``. Returns (y ``[..., d]`` f32, state)."""
    f32 = jnp.float32
    if mode == "decode":
        with device_scope("ssm/scan"):
            state, xs, y = access.ssm_decode(
                state, mi, positions, u, dt, A, p["conv_w"], p["conv_b"],
                kernel)
    else:
        T = u.shape[1]
        with device_scope("ssm/conv"):
            window, S0 = access.ssm_init(state, mi, start, u.shape[0])
            act, window = jax.vmap(
                lambda uu, ww: ssm_ops.conv_prefill(
                    uu, ww, p["conv_w"], p["conv_b"], length))(u, window)
            xs, B, C = split_channels(cfg, act)
        with device_scope("ssm/scan"):
            # rows past the run leave the state as it was
            dt = jnp.where((jnp.arange(T) < length)[None, :, None], dt,
                           f32(0))
            y, S = jax.vmap(
                lambda a, b, c, e, s: ssm_ops.ssd_prefill(
                    a, b, A, c, e, s, cfg.chunk_size))(xs, dt, B, C, S0)
            state = access.ssm_commit(state, mi, window, S)
    with device_scope("ssm/out"):
        y = y + p["D"].astype(f32)[:, None] * xs.astype(f32)
        y = y.reshape(u.shape[:-1] + (cfg.d_inner,))
        return group_rms_norm(y * jax.nn.silu(z.astype(f32)), p["gnorm"],
                              cfg.n_groups, cfg.rms_norm_eps), state


def mamba_mixer(cfg, p, x, positions, access, state, mi, start, mode,
                length, kernel):
    """One state-space layer with its norm and residual. "prefill": x
    ``[b, T, h]``, the first ``length`` rows are the run; "decode": x
    ``[S, h]``."""
    with device_scope("ssm/in_proj"):
        xn = rms_norm(x, p["norm"], cfg.rms_norm_eps)
        z, u, dt, A = split_projection(cfg, p, jnp.dot(xn, p["in_proj"]))
    y, state = ssm_core(cfg, p, z, u, dt, A, positions, access, state, mi,
                        start, mode, length, kernel)
    with device_scope("ssm/out"):
        return x + jnp.dot(y.astype(x.dtype), p["out_proj"]), state


def attention(cfg, p, x, positions, access, state, li, start, mode,
              kernel):
    """One attention layer with its norm and residual; no positional
    rotation. "prefill": x ``[b, T, h]``, positions ``[b, T]``;
    "decode": x ``[S, h]``, positions ``[S]``."""
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = x.shape[:-1]
    cdt = jnp.dtype(cfg.cache_dtype)
    with device_scope("attn/qkv"):
        xn = rms_norm(x, p["norm"], cfg.rms_norm_eps)
        q = jnp.dot(xn, p["wq"]).reshape(lead + (nq, hd)).astype(cdt)
        k = jnp.dot(xn, p["wk"]).reshape(lead + (nkv, hd)).astype(cdt)
        v = jnp.dot(xn, p["wv"]).reshape(lead + (nkv, hd)).astype(cdt)
    with device_scope("attn/paged"):
        if mode == "decode":
            state, o = access.attn_decode(state, li, positions, q, k, v,
                                          kernel)
        else:
            state, (kv_, vv_) = access.attn_prefill(state, li, start, k, v)
            o = jax.vmap(attn_ops.grouped_causal_attention)(
                q, kv_, vv_, positions)
    with device_scope("attn/out"):
        return x + jnp.dot(o.astype(x.dtype).reshape(lead + (nq * hd,)),
                           p["wo"]), state


def expert_layer(cfg, p, experts, xn, ei, mode, kernel=False,
                 with_shared=True, held=None):
    """The expert layer WITHOUT its norm and residual: xn ``[T, h]``
    (normed). Routes over all ``router_experts``, computes the part of
    the sum that the held experts give (``held = (first, count)``,
    default the config's) plus, when ``with_shared``, the shared
    expert's. ``experts`` holds the held experts' matrices of every
    expert layer, stacked flat; ``ei`` counts expert layers. Returns (y
    ``[T, h]`` f32, tokens per held expert ``[count]``)."""
    first, count = held if held is not None else cfg.held
    base = jnp.asarray(ei, jnp.int32) * jnp.int32(count)
    with device_scope("moe/router"):
        idx, w = moe_ops.route_sigmoid(
            xn, p["router_w"], p["router_b"], cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scaling_factor,
            jnp.dtype(cfg.router_dtype))
        tokens = moe_ops.expert_counts(idx, first, count)
    with device_scope("moe/experts"):
        up, down = experts["up_t"], experts["down"]
        if mode == "decode":
            cw = moe_ops.combine_matrix(idx, w, first, count)
            fn = moe_ops.moe_experts_relu2_decode if kernel \
                else moe_ops.moe_experts_relu2_jnp
            y = fn(xn, up, down, cw, base)
        else:
            y = moe_ops.moe_experts_grouped_relu2(xn, up, down, idx, w,
                                                  first, count, base)
    if with_shared:
        with device_scope("moe/shared"):
            y = y + moe_ops.relu2_mlp(xn, p["sh_up_t"], p["sh_down"])
    return y, tokens


def expert_mixer(cfg, p, experts, x, ei, mode, kernel, counts):
    """Norm + expert layer + residual over x ``[..., h]``; a decode step
    adds its routing to ``counts`` (``[expert layers, count + 2]``
    int32: tokens per held expert, distinct experts hit, steps)."""
    lead = x.shape[:-1]
    xn = rms_norm(x, p["norm"], cfg.rms_norm_eps).reshape(
        -1, x.shape[-1])
    y, tokens = expert_layer(cfg, p, experts, xn, ei, mode, kernel)
    if counts is not None and mode == "decode":
        counts = count_routing(counts, ei, tokens)
    return x + y.astype(x.dtype).reshape(lead + (x.shape[-1],)), counts


def embed(cfg, params, ids):
    """The residual stream's first state: the tokens' rows."""
    return params["wemb"][ids]


def run_layers(cfg, params, x, positions, access, state, start=0,
               mode="prefill", kernel=False, counts=None, length=None):
    """Every layer over x, run by run of ``cfg.plan`` (module
    docstring), with the cache state (and the counters) in the carry.
    ``length``: rows of a prefill that are the run (default all).
    Returns (x, state, counts)."""
    have_counts = counts is not None
    if not have_counts:
        counts = jnp.zeros((max(cfg.count("E"), 1), cfg.held[1] + 2),
                           jnp.int32)
    if length is None:
        length = x.shape[-2]

    def layer(letter, carry, i):
        x, state, counts = carry
        p = _take(params[KINDS[letter]], i)
        if letter == "M":
            x, state = mamba_mixer(cfg, p, x, positions, access, state, i,
                                   start, mode, length, kernel)
        elif letter == "*":
            x, state = attention(cfg, p, x, positions, access, state, i,
                                 start, mode, kernel)
        else:
            x, counts = expert_mixer(cfg, p, params["experts"], x, i,
                                     mode, kernel, counts)
        return x, state, counts

    seen = dict.fromkeys(KINDS, 0)
    carry = (x, state, counts)
    for unit, reps in cfg.plan:
        def body(carry, j, unit=unit, base=dict(seen)):
            at = dict(base)
            for letter in unit:
                carry = layer(letter, carry,
                              j * jnp.int32(unit.count(letter))
                              + jnp.int32(at[letter]))
                at[letter] += 1
            return carry, None

        if reps == 1:
            carry, _ = body(carry, jnp.int32(0))
        else:
            carry, _ = jax.lax.scan(body, carry,
                                    jnp.arange(reps, dtype=jnp.int32))
        for letter in unit:
            seen[letter] += reps
    x, state, counts = carry
    return x, state, (counts if have_counts else None)


# ------------------------------------------------------- cache accesses
def zero_slot_state(cfg, b):
    """(window ``[b, (K-1) * conv_dim]``, packed state ``[b, H / q, N,
    q * P]``) of a sequence that starts."""
    conv, ssm = slot_state_shapes(cfg)
    return (jnp.zeros((b,) + conv, jnp.dtype(cfg.dtype)),
            jnp.zeros((b,) + ssm, jnp.dtype(cfg.state_dtype)))


class SeqAccess:
    """No cache: the sequence's own rows are the view, every state-space
    layer starts from zeros (eager forward)."""

    def __init__(self, cfg):
        self.cfg = cfg

    def attn_prefill(self, state, li, start, k, v):
        return state, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    def ssm_init(self, state, mi, start, b):
        return zero_slot_state(self.cfg, b)

    def ssm_commit(self, state, mi, window, S):
        return state


class ContigAccess(SeqAccess):
    """``generate()``'s cache: keys and values ``[La, b, nkv, total,
    hd]``, every sequence at the same position, and the slot state of a
    batch of ``b``: conv ``[Lm, b, .]``, state ``[Lm * b, ...]`` packed
    (``ops.ssm``)."""

    def __init__(self, cfg, b):
        super().__init__(cfg)
        self.b = b

    def attn_prefill(self, state, li, start, k, v):
        kc, vc, conv, ssm = state
        z = jnp.int32(0)
        at = (li, z, z, start, z)
        kc = jax.lax.dynamic_update_slice(
            kc, k.transpose(0, 2, 1, 3)[None], at)
        vc = jax.lax.dynamic_update_slice(
            vc, v.transpose(0, 2, 1, 3)[None], at)
        return (kc, vc, conv, ssm), (kc[li], vc[li])

    def attn_decode(self, state, li, pos, q, k, v, kernel):
        kc, vc, conv, ssm = state
        z = jnp.int32(0)
        at = (li, z, z, pos[0], z)
        kc = jax.lax.dynamic_update_slice(kc, k[None, :, :, None], at)
        vc = jax.lax.dynamic_update_slice(vc, v[None, :, :, None], at)
        o = attn_ops.cached_slot_attention(q, kc[li], vc[li], pos + 1)
        return (kc, vc, conv, ssm), o

    def ssm_commit(self, state, mi, window, S):
        kc, vc, conv, ssm = state
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, window.astype(conv.dtype), mi, axis=0)
        ssm = jax.lax.dynamic_update_slice_in_dim(
            ssm, S.astype(ssm.dtype), mi * jnp.int32(self.b), axis=0)
        return kc, vc, conv, ssm

    def ssm_decode(self, state, mi, pos, u, dt, A, conv_w, conv_b, kernel):
        kc, vc, conv, ssm = state
        conv, ssm, xs, y = ssm_ops.ssm_decode_step(
            conv, ssm, mi, u, dt, A,
            lambda act: split_channels(self.cfg, act), conv_w, conv_b,
            self.b, jnp.ones((self.b,), bool), kernel)
        return (kc, vc, conv, ssm), xs, y


# ------------------------------------------------------------ the model
def slot_state_shapes(cfg):
    """(conv window, recurrent state) a slot keeps in ONE state-space
    layer, as the cache arrays hold them (``ops.ssm``)."""
    return (((cfg.conv_kernel - 1) * cfg.conv_dim,),
            ssm_ops.packed_shape(cfg.mamba_heads, cfg.mamba_head_dim,
                                 cfg.state_size, cfg.n_groups))


def hybrid_cache_spec(cfg):
    """A token owns keys and values in the attention layers only; a slot
    owns a convolution window and a recurrent state in the state-space
    layers only; the decode program carries the expert-routing counters
    beside them."""
    from ..serving.paged.cache_spec import CacheSpec
    la, lm = cfg.count("*"), cfg.count("M")
    conv, ssm = slot_state_shapes(cfg)
    return CacheSpec(
        la,
        [("k", (cfg.num_kv_heads,), (cfg.head_dim,), cfg.cache_dtype),
         ("v", (cfg.num_kv_heads,), (cfg.head_dim,), cfg.cache_dtype)],
        state=[("moe_counts", (max(cfg.count("E"), 1), cfg.held[1] + 2),
                "int32")],
        slot=[("conv", lm, conv, cfg.dtype),
              ("ssm", lm, ssm, cfg.state_dtype)])


def _leaf_shapes(cfg):
    """group -> leaf -> (shape without the layer axis, kind, dtype or
    None for the model's); kind "w" N(0, range), "g" ones, "z" zeros.
    The experts' leading axis is (expert layers x held experts), flat;
    an expert's two matrices are BOTH ``[f, h]`` (``ops.moe_experts``)."""
    h, d, H = cfg.hidden_size, cfg.d_inner, cfg.mamba_heads
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    f, fs = cfg.moe_intermediate_size, cfg.shared_intermediate_size
    e = cfg.router_experts
    f32 = "float32"
    return {
        "mamba": {"norm": ((h,), "g", None),
                  "in_proj": ((h, d + cfg.conv_dim + H), "w", None),
                  "conv_w": ((cfg.conv_kernel, cfg.conv_dim), "w", None),
                  "conv_b": ((cfg.conv_dim,), "z", None),
                  "dt_bias": ((H,), "z", f32), "A_log": ((H,), "z", f32),
                  "D": ((H,), "g", f32), "gnorm": ((d,), "g", None),
                  "out_proj": ((d, h), "w", None)},
        "attn": {"norm": ((h,), "g", None), "wq": ((h, nq * hd), "w", None),
                 "wk": ((h, nkv * hd), "w", None),
                 "wv": ((h, nkv * hd), "w", None),
                 "wo": ((nq * hd, h), "w", None)},
        "moe": {"norm": ((h,), "g", None), "router_w": ((h, e), "w", None),
                "router_b": ((e,), "z", f32),
                "sh_up_t": ((fs, h), "w", None),
                "sh_down": ((fs, h), "w", None)},
        "experts": {"up_t": ((f, h), "w", None),
                    "down": ((f, h), "w", None)},
    }


def param_shapes(cfg):
    """The parameter tree's shapes: {path tuple: (shape, kind, dtype
    name)}. Per-layer leaves are stacked on a leading axis BY KIND;
    ``router_b`` (the score correction bias), ``dt_bias``, ``A_log`` and
    ``D`` are float32."""
    out = {("wemb",): ((cfg.vocab_size, cfg.hidden_size), "w", cfg.dtype),
           ("norm_f",): ((cfg.hidden_size,), "g", cfg.dtype),
           ("head",): ((cfg.hidden_size, cfg.vocab_size), "w", cfg.dtype)}
    m = cfg.count("E")
    for group, n in (("mamba", cfg.count("M")), ("attn", cfg.count("*")),
                     ("moe", m), ("experts", m * cfg.held[1])):
        if not n:
            continue
        for leaf, (shape, kind, dt) in _leaf_shapes(cfg)[group].items():
            out[(group, leaf)] = ((n,) + shape, kind, dt or cfg.dtype)
    return out


class HybridCausalLM(StackedCausalLM):
    """A causal LM whose layers keep keys and values a token owns AND a
    convolution window and a recurrent state a slot owns, for serving:
    what ``NemotronHForCausalLM`` and ``text.falcon_h1
    .FalconH1ForCausalLM`` share. The block (``embed``, ``run_layers``,
    ``lm_head``, ``hybrid_cache_spec``) is the one in the module of the
    configuration's class (``stacked_lm.block_of``)."""

    # -------------------------------------------------- what serving takes
    def cache_spec(self):
        return block_of(self.cfg).hybrid_cache_spec(self.cfg)

    def build_paged_serving_fns(self, num_slots, block_size, num_blocks,
                                blocks_per_slot, sampling=False):
        """(paged_prefill, paged_decode) over the hybrid pool, with the
        engine's signatures (``serving/paged/hybrid_programs.py``). The
        decode program's kernels are not an option: on a backend that
        has Mosaic they are the only path and a shape they cannot take
        is refused here; the CPU runs the ``jnp`` formulations."""
        from ..serving.paged.hybrid_programs import build_paged_hybrid_fns
        return build_paged_hybrid_fns(
            self.cfg, num_slots, block_size, num_blocks, blocks_per_slot,
            sampling=sampling)

    # ------------------------------------------------------------ eager
    def forward(self, input_ids):
        """Logits ``[b, T, vocab]`` (f32) of whole sequences, through
        the same block as the serving programs, no cache. Inference
        only: nothing is taped."""
        from ..core.tensor import Tensor
        ids = self._ids(input_ids)
        fn = self._jitted(("forward",) + ids.shape, self._forward_fn)
        return Tensor(fn(self.export_decode_params(), ids))

    def _forward_fn(self, params, ids):
        cfg, block = self.cfg, block_of(self.cfg)
        b, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        x, _, _ = block.run_layers(cfg, params,
                                   block.embed(cfg, params, ids), pos,
                                   SeqAccess(cfg), (), 0, "prefill")
        return block.lm_head(cfg, params, x)

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, seed=0):
        """Prefill + one decode step a token over a contiguous cache of
        keys, values and slot state, as one jitted program. Greedy when
        ``temperature <= 0`` or ``top_k == 1``, else temperature
        sampling over the ``top_k`` logits (0 = all)."""
        from ..core.tensor import Tensor
        cfg, block = self.cfg, block_of(self.cfg)
        ids = self._ids(input_ids)
        b, s0 = ids.shape
        n_new = int(max_new_tokens)
        if s0 + n_new > cfg.max_seq_len:
            raise ValueError(f"prompt {s0} + max_new_tokens {n_new} "
                             f"exceeds max_seq_len {cfg.max_seq_len}")
        if n_new <= 0:
            return Tensor(ids.astype(jnp.int64))
        greedy = temperature <= 0 or top_k == 1
        kk = min(int(top_k), cfg.vocab_size)
        total = s0 + n_new
        access = ContigAccess(cfg, b)
        pick = greedy_or_sampled(greedy, kk)
        la, lm = cfg.count("*"), cfg.count("M")
        conv_shape, ssm_shape = slot_state_shapes(cfg)

        def decode(params, ids, key, temp):
            cdt = jnp.dtype(cfg.cache_dtype)
            kv = (max(la, 1), b, cfg.num_kv_heads, total, cfg.head_dim)
            state = (jnp.zeros(kv, cdt), jnp.zeros(kv, cdt),
                     jnp.zeros((max(lm, 1), b) + conv_shape,
                               jnp.dtype(cfg.dtype)),
                     jnp.zeros((max(lm, 1) * b,) + ssm_shape,
                               jnp.dtype(cfg.state_dtype)))
            pos = jnp.broadcast_to(jnp.arange(s0, dtype=jnp.int32),
                                   (b, s0))
            x, state, _ = block.run_layers(
                cfg, params, block.embed(cfg, params, ids), pos, access,
                state, jnp.int32(0), "prefill")
            key, sub = jax.random.split(key)
            first = pick(block.lm_head(cfg, params, x[:, -1]), sub, temp)

            def step(carry, _):
                tok, p, state, key = carry
                x, state, _ = block.run_layers(
                    cfg, params, block.embed(cfg, params, tok),
                    jnp.broadcast_to(p, (b,)), access, state,
                    mode="decode")
                key, sub = jax.random.split(key)
                nxt = pick(block.lm_head(cfg, params, x), sub, temp)
                return (nxt, p + 1, state, key), nxt

            _, rest = jax.lax.scan(
                step, (first, jnp.int32(s0), state, key), None,
                length=n_new - 1)
            gen = jnp.concatenate([first[:, None], rest.T], axis=1)
            return jnp.concatenate([ids, gen], axis=1)

        fn = self._jitted(("generate", b, s0, n_new, greedy, kk), decode)
        out = fn(self.export_decode_params(), ids,
                 jax.random.PRNGKey(int(seed)),
                 jnp.float32(max(float(temperature), 1e-6)))
        return Tensor(out.astype(jnp.int64))


class NemotronHForCausalLM(HybridCausalLM):
    """Causal LM of the family, for serving. Parameters are held
    STACKED by kind of layer, in ``cfg.dtype``, exactly as the compiled
    programs take them (``stacked_lm.StackedCausalLM``)."""

    def __init__(self, cfg, weights=None, seed=0):
        super().__init__(cfg, param_shapes(cfg), weights, seed)

    def moe_counter_layout(self):
        """Which layers and experts the rows and columns of
        ``moe_counts`` stand for (``ServingMetrics.set_moe_counters``)."""
        cfg = self.cfg
        return {"layers": [i for i, c in enumerate(cfg.pattern)
                           if c == "E"],
                "first": cfg.held[0], "count": cfg.held[1]}
