"""The ``deepseek_v3`` family of causal LMs on the served path: RMS
norm, rotary positions, multi-head latent attention (no query
compression), a gated dense MLP in the first ``first_k_dense_replace``
layers and a dropless expert layer (sigmoid scores, bias-corrected
top-k, shared experts) in the rest.

**The block is written once, here**: ``attention``, ``dense_mlp`` and
``expert_layer`` are called by the eager ``forward``, by ``generate()``
and by the engine's paged prefill and decode programs
(``serving/paged/latent_programs.py``). What differs between the
callers is how a layer reaches its cache, and that is an ACCESS object
with two methods over a tuple of cache arrays it does not own:

  ``prefill(state, layer, start, c, k_pe) -> state, (c_view, pe_view)``
      write a run of new rows at positions ``start..`` and give back
      position-ordered views that include them (EXPANDED attention
      form: keys and values are rebuilt from the latent);
  ``decode(state, layer, pos, c, k_pe, q_lat, q_pe, scale)
      -> state, o_lat``
      write one row a sequence and attend in the ABSORBED form: the
      latent is read once for all heads and never expanded.

``SeqAccess`` (the sequence is its own cache: eager forward) and
``ContigAccess`` (``[L, b, total, .]``: ``generate()``) are here;
``PagedAccess`` (block tables over the engine's flat pool, whole-block
in-place writes, the Pallas kernel) is beside the programs.

A token's cache entry is its normed latent and its rotary key:
``cache_spec()`` says so, and ``serving.paged.PagedKVPool`` allocates
what it says.

Not brought by this module: training (no autograd through the block),
tensor or expert parallel sharding over a mesh (``held`` only says which
experts THIS program holds), query compression (``q_lora_rank``), rope
scaling, expert groups (``n_group > 1``).
"""
import jax
import jax.numpy as jnp

from ..profiler import device_scope
from ..ops import mla_attention as mla_ops
from ..ops import moe_experts as moe_ops
from .stacked_lm import (  # noqa: F401 - parts of this block
    StackedCausalLM, count_routing, greedy_or_sampled, lm_head,
    project_heads, rms_norm)


class DeepseekV3Config:
    """Sizes of one model, from the keys of a Hugging Face
    ``config.json`` of ``model_type: deepseek_v3`` (``from_hf``)."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, intermediate_size,
                 moe_intermediate_size, n_routed_experts,
                 n_shared_experts, num_experts_per_tok,
                 first_k_dense_replace=1, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, q_lora_rank=None,
                 max_position_embeddings=4096, rms_norm_eps=1e-6,
                 rope_theta=10000.0, rope_interleave=True,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 scoring_func="sigmoid", topk_method="noaux_tc",
                 n_group=1, topk_group=1, rope_scaling=None,
                 initializer_range=0.02, dtype="float32",
                 cache_dtype=None, router_dtype="float32", held=None,
                 **ignored):
        if q_lora_rank is not None:
            raise NotImplementedError("q_lora_rank: query compression "
                                      "is not brought")
        if rope_scaling is not None:
            raise NotImplementedError("rope_scaling is not brought")
        if scoring_func != "sigmoid" or topk_method != "noaux_tc":
            raise NotImplementedError(
                f"router {scoring_func}/{topk_method}: only "
                f"sigmoid/noaux_tc")
        if int(n_group) != 1 or int(topk_group) != 1:
            raise NotImplementedError("expert groups (n_group > 1)")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_hidden_layers)
        self.num_heads = int(num_attention_heads)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        self.n_shared_experts = int(n_shared_experts)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.first_k_dense = min(int(first_k_dense_replace),
                                 self.num_layers)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.max_seq_len = int(max_position_embeddings)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.rope_interleave = bool(rope_interleave)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.initializer_range = float(initializer_range)
        self.dtype = jnp.dtype(dtype).name
        self.cache_dtype = jnp.dtype(cache_dtype or dtype).name
        self.router_dtype = jnp.dtype(router_dtype).name
        first, count = held if held is not None \
            else (0, self.n_routed_experts)
        if first < 0 or count < 1 \
                or first + count > self.n_routed_experts:
            raise ValueError(f"held={held!r} is not a share of "
                             f"{self.n_routed_experts} experts")
        self.held = (int(first), int(count))

    @classmethod
    def from_hf(cls, config, **overrides):
        return cls(**{**config, **overrides})

    @property
    def num_moe_layers(self):
        return self.num_layers - self.first_k_dense

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_scale(self):
        return self.qk_head_dim ** -0.5


# ------------------------------------------------------------ the block
def rope_interleaved(x, pos, theta):
    """Rotary positions in the checkpoint's interleaved layout: lanes
    ``(2i, 2i+1)`` are one pair, turned by ``pos * theta**(-2i/d)``.
    x ``[..., d]``, pos broadcastable to ``x.shape[:-1]``. (Hugging Face
    first permutes the pairs into halves; scores are the same, since
    queries and keys are permuted alike.)"""
    d = x.shape[-1]
    inv = jnp.float32(theta) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / jnp.float32(d))
    ang = pos.astype(jnp.float32)[..., None] * inv       # [..., d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def attention(cfg, p, x, positions, access, state, layer, start, mode):
    """One layer's attention with its residual. ``mode`` "prefill": x
    ``[B, T, h]``, positions ``[B, T]``, expanded form over the views the
    access returns; "decode": x ``[S, h]``, positions ``[S]``, absorbed
    form through ``access.decode``."""
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r, dv = cfg.kv_lora_rank, cfg.v_head_dim
    lead = x.shape[:-1]
    cdt = jnp.dtype(cfg.cache_dtype)
    with device_scope("mla/q_absorb"):
        xn = rms_norm(x, p["norm1"], cfg.rms_norm_eps)
        q = project_heads(xn, p["wq"], nh)
        q_nope = q[..., :dn]
        q_pe = rope_interleaved(q[..., dn:], positions[..., None],
                                cfg.rope_theta)
        ckv = jnp.dot(xn, p["wkva"])
        c = rms_norm(ckv[..., :r], p["kv_norm"],
                     cfg.rms_norm_eps).astype(cdt)
        k_pe = rope_interleaved(ckv[..., r:], positions,
                                cfg.rope_theta).astype(cdt)
        if mode == "decode":
            q_lat = jnp.einsum("shd,rhd->shr", q_nope,
                               p["wkvb"][..., :dn]).astype(cdt)
    with device_scope("mla/attn"):
        if mode == "decode":
            state, o_lat = access.decode(state, layer, positions, c, k_pe,
                                         q_lat, q_pe.astype(cdt),
                                         cfg.attn_scale)
        else:
            state, (cv, pv) = access.prefill(state, layer, start, c, k_pe)
            o = jax.vmap(
                lambda qn, qp, cc, pp, ps: mla_ops.expanded_attention(
                    qn, qp, cc, pp, p["wkvb"].astype(cc.dtype), ps,
                    cfg.attn_scale))(
                q_nope.astype(cdt), q_pe.astype(cdt), cv, pv, positions)
    with device_scope("mla/out"):
        if mode == "decode":
            o = jnp.einsum("shr,rhd->shd", o_lat.astype(x.dtype),
                           p["wkvb"][..., dn:])
        y = x + jnp.dot(o.astype(x.dtype).reshape(lead + (nh * dv,)),
                        p["wo"])
    return y, state


def dense_mlp(cfg, p, x):
    with device_scope("mlp"):
        xn = rms_norm(x, p["norm2"], cfg.rms_norm_eps)
        y = moe_ops.swiglu(xn, p["mlp_gate"], p["mlp_up"], p["mlp_down"])
        return x + y.astype(x.dtype)


def expert_layer(cfg, p, experts, xn, layer_m, mode, kernel=False,
                 with_shared=True, held=None):
    """The expert layer WITHOUT its norm and residual: xn ``[T, h]``
    (normed). Routes over all ``n_routed_experts``, computes the part of
    the sum that the held experts give (``held = (first, count)``,
    default the config's) plus, when ``with_shared``, the shared
    expert's. ``experts`` holds the held experts' matrices of every
    expert layer, stacked flat; ``layer_m`` counts expert layers.
    Returns (y ``[T, h]`` f32, tokens per held expert ``[count]``)."""
    first, count = held if held is not None else cfg.held
    base = jnp.asarray(layer_m, jnp.int32) * jnp.int32(count)
    with device_scope("moe/router"):
        idx, w = moe_ops.route_sigmoid(
            xn, p["router_w"], p["router_b"], cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scaling_factor,
            jnp.dtype(cfg.router_dtype))
        tokens = moe_ops.expert_counts(idx, first, count)
    with device_scope("moe/experts"):
        wg, wu, wd = experts["gate"], experts["up"], experts["down"]
        if mode == "decode":
            cw = moe_ops.combine_matrix(idx, w, first, count)
            fn = moe_ops.moe_experts_swiglu_decode if kernel \
                else moe_ops.moe_experts_swiglu_jnp
            y = fn(xn, wg, wu, wd, cw, base)
        else:
            y = moe_ops.moe_experts_grouped(xn, wg, wu, wd, idx, w,
                                            first, count, base)
    if with_shared:
        with device_scope("moe/shared"):
            y = y + moe_ops.swiglu(xn, p["sh_gate"], p["sh_up"],
                                   p["sh_down"])
    return y, tokens


def moe_mlp(cfg, p, experts, x, layer_m, mode, kernel, counts):
    """Norm + expert layer + residual over x ``[..., h]``; a decode
    step adds its routing to ``counts`` (``[expert layers, count + 2]``
    int32: tokens per held expert, distinct experts hit, steps)."""
    lead = x.shape[:-1]
    xn = rms_norm(x, p["norm2"], cfg.rms_norm_eps).reshape(
        -1, x.shape[-1])
    y, tokens = expert_layer(cfg, p, experts, xn, layer_m, mode, kernel)
    if counts is not None and mode == "decode":
        counts = count_routing(counts, layer_m, tokens)
    return x + y.astype(x.dtype).reshape(lead + (x.shape[-1],)), counts


def run_layers(cfg, params, x, positions, access, state, start=0,
               mode="prefill", kernel=False, counts=None):
    """Every layer over x: the dense group, then the expert group, each
    a ``lax.scan`` over its stacked weights with the cache state (and
    the counters) in the carry. Returns (x, state, counts)."""
    k = cfg.first_k_dense
    have_counts = counts is not None
    if not have_counts:
        counts = jnp.zeros((max(cfg.num_moe_layers, 1),
                            cfg.held[1] + 2), jnp.int32)

    def dense_body(carry, inp):
        x, state, counts = carry
        p, layer = inp
        x, state = attention(cfg, p, x, positions, access, state, layer,
                             start, mode)
        return (dense_mlp(cfg, p, x), state, counts), None

    def moe_body(carry, inp):
        x, state, counts = carry
        p, layer = inp
        x, state = attention(cfg, p, x, positions, access, state, layer,
                             start, mode)
        x, counts = moe_mlp(cfg, p, params["experts"], x, layer - k, mode,
                            kernel, counts)
        return (x, state, counts), None

    carry = (x, state, counts)
    if k:
        carry, _ = jax.lax.scan(
            dense_body, carry,
            (params["dense"], jnp.arange(k, dtype=jnp.int32)))
    if cfg.num_moe_layers:
        carry, _ = jax.lax.scan(
            moe_body, carry,
            (params["moe"], jnp.arange(k, cfg.num_layers,
                                       dtype=jnp.int32)))
    x, state, counts = carry
    return x, state, (counts if have_counts else None)


# ------------------------------------------------------- cache accesses
class SeqAccess:
    """No cache: the sequence's own rows are the view (eager forward)."""

    def prefill(self, state, layer, start, c, k_pe):
        return state, (c, k_pe)


class ContigAccess:
    """``generate()``'s cache: (c ``[L, b, total, rank]``, k_pe ``[L, b,
    total, dr]``), every sequence at the same position."""

    def prefill(self, state, layer, start, c, k_pe):
        cc, pc = state
        z = jnp.int32(0)
        cc = jax.lax.dynamic_update_slice(cc, c[None], (layer, z, start, z))
        pc = jax.lax.dynamic_update_slice(pc, k_pe[None],
                                          (layer, z, start, z))
        return (cc, pc), (cc[layer], pc[layer])

    def decode(self, state, layer, pos, c, k_pe, q_lat, q_pe, scale):
        cc, pc = state
        z = jnp.int32(0)
        cc = jax.lax.dynamic_update_slice(cc, c[None, :, None],
                                          (layer, z, pos[0], z))
        pc = jax.lax.dynamic_update_slice(pc, k_pe[None, :, None],
                                          (layer, z, pos[0], z))
        o = mla_ops.mla_decode_attn_jnp(q_lat, q_pe, cc[layer], pc[layer],
                                        pos + 1, scale)
        return (cc, pc), o


# ------------------------------------------------------------ the model
def latent_cache_spec(cfg):
    """A token owns, in each layer, its normed latent and its rotary key
    (no head axis); the decode program carries the expert-routing
    counters beside them."""
    from ..serving.paged.cache_spec import CacheSpec
    return CacheSpec(
        cfg.num_layers,
        [("c", (), (cfg.kv_lora_rank,), cfg.cache_dtype),
         # blocks of the rotary key are kept transposed, tokens on the
         # minor axis (ops.mla_attention)
         ("k_pe", (cfg.qk_rope_head_dim,), (), cfg.cache_dtype)],
        state=[("moe_counts", (max(cfg.num_moe_layers, 1),
                               cfg.held[1] + 2), "int32")])


def _leaf_shapes(cfg):
    """group -> leaf -> (shape without the layer axis, kind); kind "w"
    N(0, range), "g" ones, "z" zeros. The experts' leading axis is
    (expert layers x held experts), flat."""
    h, nh = cfg.hidden_size, cfg.num_heads
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    dr, dv = cfg.qk_rope_head_dim, cfg.v_head_dim
    attn = {"norm1": ((h,), "g"), "wq": ((h, nh * (dn + dr)), "w"),
            "wkva": ((h, r + dr), "w"), "kv_norm": ((r,), "g"),
            "wkvb": ((r, nh, dn + dv), "w"), "wo": ((nh * dv, h), "w"),
            "norm2": ((h,), "g")}
    i, f = cfg.intermediate_size, cfg.moe_intermediate_size
    fs = f * cfg.n_shared_experts
    e = cfg.n_routed_experts
    return {
        "dense": dict(attn, mlp_gate=((h, i), "w"), mlp_up=((h, i), "w"),
                      mlp_down=((i, h), "w")),
        "moe": dict(attn, router_w=((h, e), "w"), router_b=((e,), "z"),
                    sh_gate=((h, fs), "w"), sh_up=((h, fs), "w"),
                    sh_down=((fs, h), "w")),
        "experts": {"gate": ((h, f), "w"), "up": ((h, f), "w"),
                    "down": ((f, h), "w")},
    }


def param_shapes(cfg):
    """The decode parameter tree's shapes: {path tuple: (shape, kind,
    dtype name)}. Per-layer leaves are stacked on a leading axis within
    their group; ``router_b`` (the score correction bias) is float32."""
    groups = _leaf_shapes(cfg)
    k, m = cfg.first_k_dense, cfg.num_moe_layers
    out = {("wemb",): ((cfg.vocab_size, cfg.hidden_size), "w", cfg.dtype),
           ("norm_f",): ((cfg.hidden_size,), "g", cfg.dtype),
           ("head",): ((cfg.hidden_size, cfg.vocab_size), "w", cfg.dtype)}
    for group, n in (("dense", k), ("moe", m),
                     ("experts", m * cfg.held[1])):
        if not n:
            continue
        for leaf, (shape, kind) in groups[group].items():
            dt = "float32" if leaf == "router_b" else cfg.dtype
            out[(group, leaf)] = ((n,) + shape, kind, dt)
    return out


class DeepseekV3ForCausalLM(StackedCausalLM):
    """Causal LM of the family, for serving. Parameters are held
    STACKED per group, in ``cfg.dtype``, exactly as the compiled
    programs take them (``stacked_lm.StackedCausalLM``)."""

    def __init__(self, cfg, weights=None, seed=0):
        super().__init__(cfg, param_shapes(cfg), weights, seed)

    # -------------------------------------------------- what serving takes
    def cache_spec(self):
        return latent_cache_spec(self.cfg)

    def moe_counter_layout(self):
        """Which layers and experts the rows and columns of
        ``moe_counts`` stand for (``ServingMetrics.set_moe_counters``)."""
        cfg = self.cfg
        return {"layers": list(range(cfg.first_k_dense, cfg.num_layers)),
                "first": cfg.held[0], "count": cfg.held[1]}

    def build_paged_serving_fns(self, num_slots, block_size, num_blocks,
                                blocks_per_slot, sampling=False):
        """(paged_prefill, paged_decode) over the latent pool, with the
        engine's signatures (``serving/paged/latent_programs.py``). The
        decode program's kernels are not an option: on a backend that
        has Mosaic they are the only path and a shape they cannot take
        is refused here; the CPU runs the ``jnp`` formulations."""
        from ..serving.paged.latent_programs import build_paged_latent_fns
        return build_paged_latent_fns(
            self.cfg, num_slots, block_size, num_blocks, blocks_per_slot,
            sampling=sampling)

    # ------------------------------------------------------------ eager
    def forward(self, input_ids):
        """Logits ``[b, T, vocab]`` (f32) of whole sequences, through
        the same block as the serving programs, expanded attention
        form, no cache. Inference only: nothing is taped."""
        from ..core.tensor import Tensor
        ids = self._ids(input_ids)
        fn = self._jitted(("forward",) + ids.shape, self._forward_fn)
        return Tensor(fn(self.export_decode_params(), ids))

    def _forward_fn(self, params, ids):
        cfg = self.cfg
        b, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        x, _, _ = run_layers(cfg, params, params["wemb"][ids], pos,
                             SeqAccess(), (), 0, "prefill")
        return lm_head(cfg, params, x)

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, seed=0):
        """Prefill (expanded form) + one decode step a token (absorbed
        form) over a contiguous latent cache, as one jitted program.
        Greedy when ``temperature <= 0`` or ``top_k == 1``, else
        temperature sampling over the ``top_k`` logits (0 = all)."""
        from ..core.tensor import Tensor
        cfg = self.cfg
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
        access = ContigAccess()
        pick = greedy_or_sampled(greedy, kk)

        def decode(params, ids, key, temp):
            cdt = jnp.dtype(cfg.cache_dtype)
            state = (jnp.zeros((cfg.num_layers, b, total,
                                cfg.kv_lora_rank), cdt),
                     jnp.zeros((cfg.num_layers, b, total,
                                cfg.qk_rope_head_dim), cdt))
            pos = jnp.broadcast_to(jnp.arange(s0, dtype=jnp.int32),
                                   (b, s0))
            x, state, _ = run_layers(cfg, params, params["wemb"][ids],
                                     pos, access, state, jnp.int32(0),
                                     "prefill")
            key, sub = jax.random.split(key)
            first = pick(lm_head(cfg, params, x[:, -1]), sub, temp)

            def step(carry, _):
                tok, p, state, key = carry
                x, state, _ = run_layers(
                    cfg, params, params["wemb"][tok],
                    jnp.broadcast_to(p, (b,)), access, state,
                    mode="decode")
                key, sub = jax.random.split(key)
                nxt = pick(lm_head(cfg, params, x), sub, temp)
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
