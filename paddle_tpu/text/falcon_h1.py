"""The ``falcon_h1`` family of causal LMs on the served path: a PARALLEL
hybrid. EVERY layer runs a Mamba-2 state-space mixer AND grouped-query
softmax attention side by side on the same normed input and adds both to
the residual stream, then a gated SiLU feed-forward; every branch
carries published multipliers (muP). With ``h`` the residual stream and
``RMS`` an RMS norm with gain, a layer is::

    u      = RMS_in(h)
    zxbcdt = in_proj(ssm_in_multiplier * u) * mup
             # mup: ssm_multipliers[0..4] on the segments z | x | B | C |
             # dt of the projection's output, in that order
    xBC    = silu(causal_depthwise_conv(x|B|C) + conv_bias)
    dt     = softplus(dt + dt_bias);   A = -exp(A_log)
    S_t    = exp(dt A) S_{t-1} + dt x_t B_t^T;   y_t = S_t C_t + D x_t
    m      = ssm_out_multiplier * out_proj(group_RMS(y * silu(z)))
    a_in   = attention_in_multiplier * u
    q, k, v = q_proj(a_in), key_multiplier * k_proj(a_in), v_proj(a_in)
    q, k   = rope(q), rope(k)       # rotate-half over the whole head
    a      = attention_out_multiplier * o_proj(softmax(q k^T / sqrt(hd)) v)
    h      = h + m + a
    f      = RMS_ff(h)
    h      = h + mlp_multipliers[1] * down(up(f) * silu(mlp_multipliers[0]
                                                        * gate(f)))

and around the layers ``h_0 = embedding_multiplier * E[token]``,
``logits = lm_head_multiplier * head(RMS_final(h_L))``, the head untied.

**Both kinds of cache in every layer.** Layer ``l`` is at once attention
layer ``l`` (keys and values a TOKEN owns, paged) and state-space layer
``l`` (a convolution window and a recurrent state a SLOT owns): the two
cache indices advance together, and ``cfg.count`` answers ``num_layers``
for both kinds. The state-space mixer between its projections
(``ssm_core``), the access objects, the packed state (``ops.ssm``) and
the model class's serving and eager paths are ``text.nemotron_h``'s, and
the paged programs are ``serving/paged/hybrid_programs.py``'s, which
take the block from the configuration (``stacked_lm.block_of``): ONE
``PagedAccess`` and one pair of bodies for both families.

**Where the multipliers are applied.** The published code scales the
activations in the model's dtype; here every multiplier is applied to a
matmul's float32 result before it is rounded (``ssm_in_multiplier`` is
folded into ``mup``; ``attention_in_multiplier`` scales q, k and v;
``key_multiplier`` scales k before the rotation, which commutes with
it). In float32 the two orders agree to rounding; in bfloat16 this one
rounds once where the published one rounds twice. The embedding's rows
are scaled in float32 and rounded to the model's dtype.

**The layer loop** is ONE ``lax.scan`` over the layers (they are all
alike), the cache state in the carry, the stacked weights indexed by the
layer's number. The shared pre-norm is staged with the state-space
branch's in-projection (scope ``ssm/in_proj``, as ``nemotron_h``'s is);
each branch has an enclosing scope (``branch/ssm``, ``branch/attn``) and
the sum into the residual stream one (``branch/mix``).

Not brought by this module: training, sharding over a mesh, projection
biases, attention in only some layers (``attn_layer_indices``), a block
without its feed-forward part, ``rope_scaling``, the gate after the norm
(``mamba_norm_before_gate``), speculative decoding, a disaggregated
role, KV hand-off.
"""
import jax
import jax.numpy as jnp
import numpy as np

from ..profiler import device_scope
from ..ops import attention as attn_ops
from ..ops.eva import rope_half
from . import stacked_lm
from .nemotron_h import (  # noqa: F401 - parts of this block
    HybridCausalLM, slot_state_shapes, split_channels, split_projection,
    ssm_core)
from .stacked_lm import rms_norm, take_layer


class FalconH1Config:
    """Sizes and multipliers of one model, from the keys of a Hugging
    Face ``config.json`` of ``model_type: falcon_h1`` (``from_hf``):
    every key is read or refused by name, and a key this class does not
    know is an error."""

    def __init__(self, vocab_size, hidden_size, intermediate_size,
                 num_hidden_layers, num_attention_heads,
                 num_key_value_heads, head_dim, mamba_n_heads,
                 mamba_d_head, mamba_d_state, mamba_n_groups,
                 mamba_d_ssm=None, mamba_expand=2, mamba_d_conv=4,
                 mamba_chunk_size=128, mamba_conv_bias=True,
                 mamba_proj_bias=False, mamba_rms_norm=True,
                 mamba_norm_before_gate=False, mamba_use_mlp=True,
                 attention_bias=False, mlp_bias=False,
                 projectors_bias=False, attn_layer_indices=None,
                 hidden_act="silu", rms_norm_eps=1e-5,
                 rope_theta=1e11, rope_scaling=None,
                 max_position_embeddings=4096, tie_word_embeddings=False,
                 mlp_expansion_factor=None, num_logits_to_keep=1,
                 attention_in_multiplier=1.0,
                 attention_out_multiplier=1.0, key_multiplier=1.0,
                 ssm_in_multiplier=1.0, ssm_out_multiplier=1.0,
                 ssm_multipliers=(1.0,) * 5, mlp_multipliers=(1.0, 1.0),
                 embedding_multiplier=1.0, lm_head_multiplier=1.0,
                 model_type="falcon_h1", initializer_range=0.02,
                 dtype="float32"):
        for name, on in (
                (f"model_type={model_type!r}", model_type != "falcon_h1"),
                ("attention_bias", attention_bias),
                ("mamba_proj_bias", mamba_proj_bias),
                ("mlp_bias", mlp_bias),
                ("projectors_bias", projectors_bias),
                ("mamba_conv_bias=False", not mamba_conv_bias),
                ("mamba_rms_norm=False", not mamba_rms_norm),
                ("mamba_norm_before_gate", mamba_norm_before_gate),
                ("mamba_use_mlp=False", not mamba_use_mlp),
                ("attn_layer_indices", attn_layer_indices is not None),
                ("rope_scaling", rope_scaling is not None),
                (f"hidden_act={hidden_act!r}", hidden_act != "silu"),
                ("tie_word_embeddings", tie_word_embeddings)):
            if on:
                raise NotImplementedError(
                    f"falcon_h1: {name} is not brought")
        self.num_layers = int(num_hidden_layers)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_heads = int(num_attention_heads)
        self.num_kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads; head_dim is even")
        # the state-space sizes under nemotron_h's names: its mixer
        # core and its access objects read them
        self.mamba_heads = int(mamba_n_heads)
        self.mamba_head_dim = int(mamba_d_head)
        self.state_size = int(mamba_d_state)
        self.n_groups = int(mamba_n_groups)
        if self.mamba_heads % self.n_groups:
            raise ValueError("mamba_n_groups must divide mamba_n_heads")
        d_ssm = int(mamba_expand * self.hidden_size) \
            if mamba_d_ssm is None else int(mamba_d_ssm)
        if d_ssm != self.d_inner:
            raise ValueError(
                f"mamba_d_ssm {d_ssm} != mamba_n_heads x mamba_d_head "
                f"{self.d_inner}")
        # mlp_expansion_factor and num_logits_to_keep are read and
        # unused: intermediate_size is given, one row goes to the head
        self.conv_kernel = int(mamba_d_conv)
        self.chunk_size = int(mamba_chunk_size)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        self.attention_in_multiplier = float(attention_in_multiplier)
        self.attention_out_multiplier = float(attention_out_multiplier)
        self.key_multiplier = float(key_multiplier)
        self.ssm_in_multiplier = float(ssm_in_multiplier)
        self.ssm_out_multiplier = float(ssm_out_multiplier)
        self.ssm_multipliers = tuple(float(m) for m in ssm_multipliers)
        self.mlp_multipliers = tuple(float(m) for m in mlp_multipliers)
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers has 5 entries (z, x, B, C, "
                             "dt), mlp_multipliers 2 (gate, down)")
        self.embedding_multiplier = float(embedding_multiplier)
        self.lm_head_multiplier = float(lm_head_multiplier)
        # keys, values and the convolution window in the model's dtype;
        # the recurrence never below float32
        self.dtype = self.cache_dtype = jnp.dtype(dtype).name
        self.state_dtype = "float32"

    @classmethod
    def from_hf(cls, config, **overrides):
        return cls(**{**config, **overrides})

    def count(self, letter):
        """Layers that own a cache of a kind (``nemotron_h``'s letters):
        every layer is an attention layer ``*`` AND a state-space layer
        ``M``; none has experts."""
        return self.num_layers if letter in ("*", "M") else 0

    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.state_size

    def mup_vector(self):
        """``[d + conv_dim + H]`` float32: what the in-projection's
        output is scaled by, ``ssm_in_multiplier`` folded in."""
        d, gn = self.d_inner, self.n_groups * self.state_size
        z, x, b, c, dt = self.ssm_multipliers
        return self.ssm_in_multiplier * np.concatenate([
            np.full(d, z), np.full(d, x), np.full(gn, b), np.full(gn, c),
            np.full(self.mamba_heads, dt)]).astype(np.float32)


# ------------------------------------------------------------ the block
def embed(cfg, params, ids):
    """``embedding_multiplier * E[token]``, scaled in float32."""
    rows = params["wemb"][ids]
    return (rows.astype(jnp.float32)
            * jnp.float32(cfg.embedding_multiplier)).astype(rows.dtype)


def lm_head(cfg, params, x):
    """``lm_head_multiplier * head(RMS_final(x))``: logits in f32."""
    logits = stacked_lm.lm_head(cfg, params, x)
    with device_scope("lm_head"):
        return logits * jnp.float32(cfg.lm_head_multiplier)


def ssm_branch(cfg, p, u, positions, access, state, mi, start, mode,
               length, kernel):
    """The state-space mixer over the normed input u: ``m`` in f32."""
    f32 = jnp.float32
    with device_scope("ssm/in_proj"):
        zxd = jnp.dot(u, p["in_proj"], preferred_element_type=f32) \
            * cfg.mup_vector()
        z, xbc, dt, A = split_projection(cfg, p, zxd)
        xbc = xbc.astype(u.dtype)      # the window keeps the model's dtype
    y, state = ssm_core(cfg, p, z, xbc, dt, A, positions, access, state,
                        mi, start, mode, length, kernel)
    with device_scope("ssm/out"):
        return jnp.dot(y.astype(u.dtype), p["out_proj"],
                       preferred_element_type=f32) \
            * f32(cfg.ssm_out_multiplier), state


def attn_branch(cfg, p, u, positions, access, state, li, start, mode,
                kernel):
    """Grouped-query attention with rotary positions over the normed
    input u: ``a`` in f32. "prefill": u ``[b, T, h]``, positions ``[b,
    T]``; "decode": u ``[S, h]``, positions ``[S]``."""
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = u.shape[:-1]
    f32 = jnp.float32
    cdt = jnp.dtype(cfg.cache_dtype)
    with device_scope("attn/qkv"):
        qkv = jnp.dot(u, p["wqkv"], preferred_element_type=f32) \
            * f32(cfg.attention_in_multiplier)
        q = qkv[..., :nq * hd].reshape(lead + (nq, hd))
        k = qkv[..., nq * hd:(nq + nkv) * hd].reshape(lead + (nkv, hd)) \
            * f32(cfg.key_multiplier)
        v = qkv[..., (nq + nkv) * hd:].reshape(lead + (nkv, hd))
        at = positions[..., None]
        q = rope_half(q, at, cfg.rope_theta).astype(cdt)
        k = rope_half(k, at, cfg.rope_theta).astype(cdt)
        v = v.astype(cdt)
    with device_scope("attn/paged"):
        if mode == "decode":
            state, o = access.attn_decode(state, li, positions, q, k, v,
                                          kernel)
        else:
            state, (kv_, vv_) = access.attn_prefill(state, li, start, k, v)
            o = jax.vmap(attn_ops.grouped_causal_attention)(
                q, kv_, vv_, positions)
    with device_scope("attn/out"):
        return jnp.dot(o.astype(u.dtype).reshape(lead + (nq * hd,)),
                       p["wo"], preferred_element_type=f32) \
            * f32(cfg.attention_out_multiplier), state


def mlp(cfg, p, x):
    """The gated SiLU feed-forward with its norm, its two multipliers
    and its residual."""
    f32 = jnp.float32
    gate_m, down_m = cfg.mlp_multipliers
    with device_scope("mlp"):
        f = rms_norm(x, p["norm_ff"], cfg.rms_norm_eps)
        g = jnp.dot(f, p["wg"], preferred_element_type=f32) * f32(gate_m)
        up = jnp.dot(f, p["wu"], preferred_element_type=f32)
        y = jnp.dot((jax.nn.silu(g) * up).astype(x.dtype), p["wd"],
                    preferred_element_type=f32) * f32(down_m)
        return (x.astype(f32) + y).astype(x.dtype)


def layer(cfg, p, x, positions, access, state, i, start, mode, length,
          kernel):
    """One layer: cache index ``i`` of BOTH kinds."""
    with device_scope("branch/ssm"):
        with device_scope("ssm/in_proj"):
            u = rms_norm(x, p["norm_in"], cfg.rms_norm_eps)
        m, state = ssm_branch(cfg, p, u, positions, access, state, i,
                              start, mode, length, kernel)
    with device_scope("branch/attn"):
        a, state = attn_branch(cfg, p, u, positions, access, state, i,
                               start, mode, kernel)
    with device_scope("branch/mix"):
        x = (x.astype(jnp.float32) + m + a).astype(x.dtype)
    return mlp(cfg, p, x), state


def run_layers(cfg, params, x, positions, access, state, start=0,
               mode="prefill", kernel=False, counts=None, length=None):
    """Every layer over x as one scan, the cache state in the carry.
    ``length``: rows of a prefill that are the run (default all).
    Returns (x, state, counts): ``nemotron_h.run_layers``'s signature,
    ``counts`` (no experts here) handed back as it came."""
    if length is None:
        length = x.shape[-2]

    def body(carry, i):
        x, state = carry
        return layer(cfg, take_layer(params["layers"], i), x, positions,
                     access, state, i, start, mode, length, kernel), None

    (x, state), _ = jax.lax.scan(
        body, (x, state), jnp.arange(cfg.num_layers, dtype=jnp.int32))
    return x, state, counts


# ------------------------------------------------------------ the model
def hybrid_cache_spec(cfg):
    """In EVERY layer a token owns a key and a value and a slot owns a
    convolution window and a recurrent state; the decode program carries
    nothing beside them."""
    from ..serving.paged.cache_spec import CacheSpec
    L = cfg.num_layers
    conv, ssm = slot_state_shapes(cfg)
    return CacheSpec(
        L,
        [("k", (cfg.num_kv_heads,), (cfg.head_dim,), cfg.cache_dtype),
         ("v", (cfg.num_kv_heads,), (cfg.head_dim,), cfg.cache_dtype)],
        slot=[("conv", L, conv, cfg.dtype),
              ("ssm", L, ssm, cfg.state_dtype)])


def param_shapes(cfg):
    """The parameter tree's shapes: {path tuple: (shape, kind, dtype
    name)}. Per-layer leaves are stacked on a leading axis of
    ``num_hidden_layers``. Linear weights are ``[in, out]``;
    ``in_proj``'s output axis is (z ``d_ssm`` | x ``d_ssm`` | B | C ``G
    x N`` each | dt ``H``); ``wqkv``'s is (q heads | k heads | v heads)
    x ``head_dim``; ``conv_w`` is ``[taps, channels]`` with the LAST
    tap on the newest input; ``dt_bias``, ``A_log`` and ``D`` are
    float32."""
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    d, cd, H = cfg.d_inner, cfg.conv_dim, cfg.mamba_heads
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt, f32 = cfg.dtype, "float32"
    out = {("wemb",): ((cfg.vocab_size, h), "w", dt),
           ("norm_f",): ((h,), "g", dt),
           ("head",): ((h, cfg.vocab_size), "w", dt)}
    for leaf, shape, kind, ldt in (
            ("norm_in", (h,), "g", dt), ("in_proj", (h, d + cd + H), "w", dt),
            ("conv_w", (cfg.conv_kernel, cd), "w", dt),
            ("conv_b", (cd,), "z", dt), ("dt_bias", (H,), "z", f32),
            ("A_log", (H,), "z", f32), ("D", (H,), "g", f32),
            ("gnorm", (d,), "g", dt), ("out_proj", (d, h), "w", dt),
            ("wqkv", (h, (nq + 2 * nkv) * hd), "w", dt),
            ("wo", (nq * hd, h), "w", dt), ("norm_ff", (h,), "g", dt),
            ("wg", (h, f), "w", dt), ("wu", (h, f), "w", dt),
            ("wd", (f, h), "w", dt)):
        out[("layers", leaf)] = ((L,) + shape, kind, ldt)
    return out


class FalconH1ForCausalLM(HybridCausalLM):
    """Causal LM of the family, for serving. Parameters are held as ONE
    stack of layers in ``cfg.dtype``, exactly as the compiled programs
    take them (``stacked_lm.StackedCausalLM``); the serving and eager
    paths are ``nemotron_h.HybridCausalLM``'s over this module's
    block."""

    def __init__(self, cfg, weights=None, seed=0):
        super().__init__(cfg, param_shapes(cfg), weights, seed)
