"""The ``mimo_v2_flash`` family of causal LMs on the served path: a
stack in which FULL and WINDOW attention layers alternate by a published
list, over a dense SwiGLU or a sparse expert layer by a second list.
Every layer is ``x = x + Attn(rms(x))``, ``x = x + FFN(rms(x))``.

  attention   ``q = x Wq`` as ``num_attention_heads`` heads of
              ``head_dim``, ``k = x Wk`` as ``n_kv`` heads of
              ``head_dim``, ``v = attention_value_scale * (x Wv)`` as
              ``n_kv`` heads of ``v_head_dim`` (a value NARROWER than its
              key); ``n_kv`` is ``num_key_value_heads`` in a full layer
              and ``swa_num_key_value_heads`` in a window layer (two KV
              head counts in one model). Rotary positions on the FIRST
              ``int(head_dim * partial_rotary_factor)`` lanes of q and
              k, half-split pairs (``ops.eva.rope_half``), base
              ``rope_theta`` / ``swa_rope_theta``; scores over the whole
              ``head_dim``. A full layer sees ``j <= t``; a window layer
              ``t - sliding_window < j <= t`` and, with
              ``add_swa_attention_sink_bias``, a learned scalar a query
              head that joins the softmax as one more column and carries
              no value.
  ``D``       dense SwiGLU of width ``intermediate_size``;
  ``E``       a dropless expert layer: sigmoid scores, bias-corrected
              top-k (``noaux_tc``), SwiGLU experts, NO shared expert.

A layer is one letter of ``cfg.pattern``: ``a`` full + dense, ``b`` full
+ experts, ``c`` window + dense, ``d`` window + experts; the layer loop
walks ``stacked_lm.layer_plan(pattern)`` run by run (one ``lax.scan`` a
run) and every layer indexes the stacked weights of its KINDS (``full``,
``win``, ``dense``, ``moe``) by its count within each.

**What a layer keeps of a sequence differs by kind**, and that is what
the ACCESS object is for (the block is written once, here; the callers
differ in how a layer reaches its cache):

  a full layer keeps every position: the key's un-rotated lanes and the
  value a position in blocks, the key's rotated lanes in a third array
  stored transposed (``ops.paged_attention``: nothing is padded);
  a window layer keeps a RING of ``sliding_window`` entries a slot,
  position ``t`` at entry ``t % W``, and nothing else: a per-slot array
  that does not grow with the position (``cache_spec``: ``slot=``,
  ``ring=``).

  ``full_prefill(state, li, start, q, k, v, positions, length)``,
  ``full_decode(state, li, pos, q, k, v)``,
  ``win_prefill(state, wi, start, q, k, v, positions, length, sink)``,
  ``win_decode(state, wi, pos, q, k, v, sink)``  each ``-> state, o``.

``SeqAccess`` (no cache: the eager forward) is here; ``PagedAccess`` is
beside the programs (``serving/paged/mixed_programs.py``).

A chip may hold a contiguous share of each layer's routed experts:
``n_routed_experts`` is then the number HELD, ``router_experts`` the
router's (published) width and ``first_held_expert`` where the share
starts; the layer returns its own experts' part of the sum.

Not brought by this module: training, sharding over a mesh, expert
groups (``n_group > 1``), a shared expert, projection biases, a sink in
the full layers, the multi-token-prediction layers, ``generate()``,
speculative decoding, a disaggregated role, KV hand-off, prefix sharing
(a ring is a slot's own).
"""
import jax
import jax.numpy as jnp

from ..profiler import device_scope
from ..ops import attention as attn_ops
from ..ops import moe_experts as moe_ops
from ..ops.eva import rope_half
from .stacked_lm import (  # noqa: F401 - parts of this block
    StackedCausalLM, count_routing, layer_plan, lm_head, rms_norm,
    take_layer)

# letter -> (attention kind, FFN kind)
LAYERS = {"a": ("full", "dense"), "b": ("full", "moe"),
          "c": ("win", "dense"), "d": ("win", "moe")}
_LETTER = {v: k for k, v in LAYERS.items()}


class MimoV2Config:
    """Sizes of one model, from the keys of a Hugging Face
    ``config.json`` of ``model_type: mimo_v2_flash`` (``from_hf``)."""

    def __init__(self, vocab_size, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, v_head_dim,
                 hybrid_layer_pattern, moe_layer_freq, intermediate_size,
                 moe_intermediate_size, n_routed_experts,
                 num_experts_per_tok, sliding_window,
                 swa_num_key_value_heads=None, num_hidden_layers=None,
                 swa_num_attention_heads=None, swa_head_dim=None,
                 swa_v_head_dim=None, sliding_window_size=None,
                 partial_rotary_factor=1.0, rope_theta=10000.0,
                 swa_rope_theta=None, attention_value_scale=1.0,
                 add_swa_attention_sink_bias=False,
                 add_full_attention_sink_bias=False,
                 attention_bias=False, hidden_act="silu",
                 scoring_func="sigmoid", topk_method="noaux_tc",
                 n_group=1, topk_group=1, n_shared_experts=None,
                 norm_topk_prob=True, routed_scaling_factor=None,
                 layernorm_epsilon=1e-5, max_position_embeddings=4096,
                 router_experts=None, first_held_expert=0,
                 initializer_range=0.02, dtype="float32", **ignored):
        heads, hd, dv = (int(num_attention_heads), int(head_dim),
                         int(v_head_dim))
        for name, on in (
                ("n_group > 1 (expert groups)",
                 int(n_group) != 1 or int(topk_group) != 1),
                ("n_shared_experts", bool(n_shared_experts)),
                ("attention_bias", attention_bias),
                ("add_full_attention_sink_bias",
                 add_full_attention_sink_bias),
                (f"hidden_act={hidden_act!r}", hidden_act != "silu"),
                (f"scoring_func={scoring_func!r}",
                 scoring_func != "sigmoid"),
                (f"topk_method={topk_method!r}",
                 topk_method != "noaux_tc"),
                ("swa_num_attention_heads != num_attention_heads",
                 int(swa_num_attention_heads or heads) != heads),
                ("swa_head_dim != head_dim",
                 int(swa_head_dim or hd) != hd),
                ("swa_v_head_dim != v_head_dim",
                 int(swa_v_head_dim or dv) != dv),
                ("sliding_window_size != sliding_window",
                 int(sliding_window_size or sliding_window)
                 != int(sliding_window))):
            if on:
                raise NotImplementedError(
                    f"mimo_v2: {name} is not brought")
        kinds, ffns = list(hybrid_layer_pattern), list(moe_layer_freq)
        if len(kinds) != len(ffns) or (
                num_hidden_layers is not None
                and int(num_hidden_layers) != len(kinds)):
            raise ValueError(
                f"hybrid_layer_pattern ({len(kinds)}), moe_layer_freq "
                f"({len(ffns)}) and num_hidden_layers "
                f"({num_hidden_layers}) disagree")
        if set(kinds) - {0, 1} or set(ffns) - {0, 1}:
            raise NotImplementedError(
                "mimo_v2: hybrid_layer_pattern is 0 (full) or 1 "
                "(window), moe_layer_freq 0 (dense) or 1 (experts)")
        self.pattern = "".join(
            _LETTER[("win" if a else "full", "moe" if f else "dense")]
            for a, f in zip(kinds, ffns))
        self.num_layers = len(self.pattern)
        self.plan = layer_plan(self.pattern)
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_heads, self.head_dim, self.v_head_dim = heads, hd, dv
        self.kv_heads = {"full": int(num_key_value_heads),
                         "win": int(swa_num_key_value_heads
                                    or num_key_value_heads)}
        for n in self.kv_heads.values():
            if heads % n:
                raise ValueError("the KV head counts must divide "
                                 "num_attention_heads")
        # rotated lanes come first and in half-split pairs; the key's
        # other lanes are as wide as a value where the two differ (what
        # the paged cache's two key arrays hold)
        self.rot_dim = int(hd * float(partial_rotary_factor))
        if self.rot_dim % 2 or not 0 < self.rot_dim <= hd:
            raise ValueError(f"rotary width {self.rot_dim} of {hd}")
        self.theta = {"full": float(rope_theta),
                      "win": float(swa_rope_theta or rope_theta)}
        self.window = int(sliding_window)
        self.sink = bool(add_swa_attention_sink_bias)
        self.value_scale = float(attention_value_scale)
        self.intermediate_size = int(intermediate_size)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = 1.0 if routed_scaling_factor is None \
            else float(routed_scaling_factor)
        self.rms_norm_eps = float(layernorm_epsilon)
        self.max_seq_len = int(max_position_embeddings)
        self.initializer_range = float(initializer_range)
        # keys, values and rings in the model's dtype; the router's
        # scores and the softmax never below float32
        self.dtype = self.cache_dtype = jnp.dtype(dtype).name
        self.router_dtype = "float32"
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

    def count(self, kind):
        """Layers of an attention or FFN kind (``full``, ``win``,
        ``dense``, ``moe``)."""
        return sum(kind in LAYERS[c] for c in self.pattern)

    @property
    def nope_dim(self):
        return self.head_dim - self.rot_dim


# ------------------------------------------------------------ the block
def attention(cfg, kind, p, x, positions, access, state, i, start, mode,
              length):
    """One attention layer of ``kind`` with its norm and residual.
    "prefill": x ``[b, T, h]``, positions ``[b, T]``; "decode": x ``[S,
    h]``, positions ``[S]``."""
    nq, nkv = cfg.num_heads, cfg.kv_heads[kind]
    hd, dv, rd = cfg.head_dim, cfg.v_head_dim, cfg.rot_dim
    lead = x.shape[:-1]
    cdt = jnp.dtype(cfg.cache_dtype)
    with device_scope("attn/qkv"):
        xn = rms_norm(x, p["norm"], cfg.rms_norm_eps)
        # the products stay [.., heads * head_dim] until they are split:
        # where the split reaches the matmul, XLA makes a head_dim that
        # is no multiple of 128 lanes a bitcast by computing q and k
        # TRANSPOSED, and transposes wq and wk for it in every step
        # (1.2 GB of copies a decode step: AOT and my chip run, PR 42)
        q, k = jax.lax.optimization_barrier(
            (jnp.dot(xn, p["wq"]), jnp.dot(xn, p["wk"])))
        q = q.reshape(lead + (nq, hd))
        k = k.reshape(lead + (nkv, hd))
        v = (jnp.dot(xn, p["wv"], preferred_element_type=jnp.float32)
             * jnp.float32(cfg.value_scale)).reshape(lead + (nkv, dv))
        at = positions[..., None]
        q, k = (jnp.concatenate(
            [rope_half(a[..., :rd], at, cfg.theta[kind]), a[..., rd:]],
            axis=-1).astype(cdt) for a in (q, k))
        v = v.astype(cdt)
    with device_scope("attn/paged"):
        if kind == "full":
            state, o = access.full_decode(state, i, positions, q, k, v) \
                if mode == "decode" \
                else access.full_prefill(state, i, start, q, k, v,
                                         positions, length)
        else:
            sink = p["sink"] if cfg.sink else None
            state, o = access.win_decode(state, i, positions, q, k, v,
                                         sink) if mode == "decode" \
                else access.win_prefill(state, i, start, q, k, v,
                                        positions, length, sink)
    with device_scope("attn/out"):
        return x + jnp.dot(o.astype(x.dtype).reshape(lead + (nq * dv,)),
                           p["wo"]), state


def dense_mlp(cfg, p, x):
    with device_scope("mlp"):
        xn = rms_norm(x, p["norm"], cfg.rms_norm_eps)
        y = moe_ops.swiglu(xn, p["gate"], p["up"], p["down"])
        return x + y.astype(x.dtype)


def expert_layer(cfg, p, experts, xn, ei, mode, kernel=False, held=None):
    """The expert layer WITHOUT its norm and residual: xn ``[T, h]``
    (normed). Routes over all ``router_experts`` and computes the part
    of the sum that the held experts give (``held = (first, count)``,
    default the config's). ``experts`` holds the held experts' matrices
    of every expert layer, stacked flat; ``ei`` counts expert layers.
    Returns (y ``[T, h]`` f32, tokens per held expert ``[count]``)."""
    first, count = held if held is not None else cfg.held
    base = jnp.asarray(ei, jnp.int32) * jnp.int32(count)
    with device_scope("moe/router"):
        idx, w = moe_ops.route_sigmoid(
            xn, p["router_w"], p["router_b"], cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scaling_factor,
            jnp.dtype(cfg.router_dtype))
        tokens = moe_ops.expert_counts(idx, first, count)
    with device_scope("moe/experts"):
        mats = experts["gate"], experts["up"], experts["down"]
        if mode == "decode":
            cw = moe_ops.combine_matrix(idx, w, first, count)
            fn = moe_ops.moe_experts_swiglu_decode if kernel \
                else moe_ops.moe_experts_swiglu_jnp
            y = fn(xn, *mats, cw, base)
        else:
            y = moe_ops.moe_experts_grouped(xn, *mats, idx, w, first,
                                            count, base)
    return y, tokens


def expert_mlp(cfg, p, experts, x, ei, mode, kernel, counts):
    """Norm + expert layer + residual over x ``[..., h]``; a decode step
    adds its routing to ``counts`` (``stacked_lm.count_routing``)."""
    lead = x.shape[:-1]
    xn = rms_norm(x, p["norm"], cfg.rms_norm_eps).reshape(
        -1, x.shape[-1])
    y, tokens = expert_layer(cfg, p, experts, xn, ei, mode, kernel)
    if counts is not None and mode == "decode":
        counts = count_routing(counts, ei, tokens)
    return x + y.astype(x.dtype).reshape(lead + (x.shape[-1],)), counts


def run_layers(cfg, params, x, positions, access, state, start=0,
               mode="prefill", kernel=False, counts=None, length=None):
    """Every layer over x, run by run of ``cfg.plan`` (module
    docstring), with the cache state (and the counters) in the carry.
    ``length``: rows of a prefill that are the run (default all).
    Returns (x, state, counts)."""
    have_counts = counts is not None
    if not have_counts:
        counts = jnp.zeros((max(cfg.count("moe"), 1), cfg.held[1] + 2),
                           jnp.int32)
    if length is None:
        length = x.shape[-2]

    def layer(letter, carry, at):
        x, state, counts = carry
        kind, ffn = LAYERS[letter]
        x, state = attention(
            cfg, kind, take_layer(params[kind], at[kind]), x, positions,
            access, state, at[kind], start, mode, length)
        p = take_layer(params[ffn], at[ffn])
        if ffn == "dense":
            x = dense_mlp(cfg, p, x)
        else:
            x, counts = expert_mlp(cfg, p, params["experts"], x, at[ffn],
                                   mode, kernel, counts)
        return x, state, counts

    seen = dict.fromkeys(("full", "win", "dense", "moe"), 0)
    carry = (x, state, counts)
    for unit, reps in cfg.plan:
        per = {k: sum(k in LAYERS[c] for c in unit) for k in seen}

        def body(carry, j, unit=unit, base=dict(seen), per=per):
            at = {k: j * jnp.int32(per[k]) + jnp.int32(base[k])
                  for k in base}
            for letter in unit:
                carry = layer(letter, carry, at)
                for k in LAYERS[letter]:
                    at[k] = at[k] + jnp.int32(1)
            return carry, None

        if reps == 1:
            carry, _ = body(carry, jnp.int32(0))
        else:
            carry, _ = jax.lax.scan(body, carry,
                                    jnp.arange(reps, dtype=jnp.int32))
        for k in seen:
            seen[k] += reps * per[k]
    x, state, counts = carry
    return x, state, (counts if have_counts else None)


# ------------------------------------------------------- cache accesses
def ring_positions(last, window):
    """The position each of a ring's ``window`` entries holds once
    position ``last`` (``[...]`` int32) is in it: the latest ``p <=
    last`` with ``p % window == entry``; NEGATIVE where the sequence has
    not reached the entry yet, whatever a slot's last owner left there.
    -> ``[..., window]``."""
    j = jnp.arange(window, dtype=jnp.int32)
    last = jnp.asarray(last, jnp.int32)[..., None]
    return last - (last - j) % jnp.int32(window)


class SeqAccess:
    """No cache: the sequence's own rows are the view (eager forward)."""

    def __init__(self, cfg):
        self.cfg = cfg

    def _attend(self, q, k, v, positions, window, sink):
        return jax.vmap(
            lambda q, k, v, pos: attn_ops.grouped_causal_attention(
                q, k.transpose(1, 0, 2), v.transpose(1, 0, 2), pos,
                k_pos=pos, window=window, sink=sink))(q, k, v, positions)

    def full_prefill(self, state, li, start, q, k, v, positions, length):
        return state, self._attend(q, k, v, positions, None, None)

    def win_prefill(self, state, wi, start, q, k, v, positions, length,
                    sink):
        return state, self._attend(q, k, v, positions, self.cfg.window,
                                   sink)


# ------------------------------------------------------------ the model
def mixed_cache_spec(cfg):
    """A token owns a key (in two arrays: the un-rotated lanes as wide
    as a value, the rotated ones transposed) and a value in the FULL
    layers only; a slot owns a key ring and a value ring of
    ``sliding_window`` entries in the WINDOW layers only; the decode
    program carries the expert-routing counters beside them."""
    from ..serving.paged.cache_spec import CacheSpec
    lf, lw = cfg.count("full"), cfg.count("win")
    nf, nw, W = cfg.kv_heads["full"], cfg.kv_heads["win"], cfg.window
    dt = cfg.cache_dtype
    return CacheSpec(
        cfg.num_layers,
        [("k", (nf,), (cfg.nope_dim,), dt, lf),
         ("kr", (nf, cfg.rot_dim), (), dt, lf),
         ("v", (nf,), (cfg.v_head_dim,), dt, lf)],
        state=[("moe_counts", (max(cfg.count("moe"), 1),
                               cfg.held[1] + 2), "int32")],
        slot=[("kring", lw, (nw, cfg.head_dim, W), dt),
              ("vring", lw, (nw, W, cfg.v_head_dim), dt)],
        ring=W)


def _leaf_shapes(cfg):
    """group -> leaf -> (shape without the layer axis, kind, dtype or
    None for the model's); kind "w" N(0, range), "g" ones, "z" zeros.
    The experts' leading axis is (expert layers x held experts), flat."""
    h, nq, hd, dv = (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
                     cfg.v_head_dim)
    f, fd, e = (cfg.moe_intermediate_size, cfg.intermediate_size,
                cfg.router_experts)

    def attn(nkv):
        return {"norm": ((h,), "g", None), "wq": ((h, nq * hd), "w", None),
                "wk": ((h, nkv * hd), "w", None),
                "wv": ((h, nkv * dv), "w", None),
                "wo": ((nq * dv, h), "w", None)}
    win = attn(cfg.kv_heads["win"])
    if cfg.sink:
        win["sink"] = ((nq,), "z", "float32")
    return {
        "full": attn(cfg.kv_heads["full"]), "win": win,
        "dense": {"norm": ((h,), "g", None), "gate": ((h, fd), "w", None),
                  "up": ((h, fd), "w", None), "down": ((fd, h), "w", None)},
        "moe": {"norm": ((h,), "g", None), "router_w": ((h, e), "w", None),
                "router_b": ((e,), "z", "float32")},
        "experts": {"gate": ((h, f), "w", None), "up": ((h, f), "w", None),
                    "down": ((f, h), "w", None)},
    }


def param_shapes(cfg):
    """The parameter tree's shapes: {path tuple: (shape, kind, dtype
    name)}. Per-layer leaves are stacked on a leading axis BY KIND;
    ``router_b`` (the score correction bias) and ``sink`` are float32."""
    out = {("wemb",): ((cfg.vocab_size, cfg.hidden_size), "w", cfg.dtype),
           ("norm_f",): ((cfg.hidden_size,), "g", cfg.dtype),
           ("head",): ((cfg.hidden_size, cfg.vocab_size), "w", cfg.dtype)}
    m = cfg.count("moe")
    for group, n in (("full", cfg.count("full")), ("win", cfg.count("win")),
                     ("dense", cfg.count("dense")), ("moe", m),
                     ("experts", m * cfg.held[1])):
        if not n:
            continue
        for leaf, (shape, kind, dt) in _leaf_shapes(cfg)[group].items():
            out[(group, leaf)] = ((n,) + shape, kind, dt or cfg.dtype)
    return out


class MimoV2ForCausalLM(StackedCausalLM):
    """Causal LM of the family, for serving. Parameters are held
    STACKED by kind of layer, in ``cfg.dtype``, exactly as the compiled
    programs take them (``stacked_lm.StackedCausalLM``)."""

    def __init__(self, cfg, weights=None, seed=0):
        super().__init__(cfg, param_shapes(cfg), weights, seed)

    # -------------------------------------------------- what serving takes
    def cache_spec(self):
        return mixed_cache_spec(self.cfg)

    def moe_counter_layout(self):
        """Which layers and experts the rows and columns of
        ``moe_counts`` stand for (``ServingMetrics.set_moe_counters``)."""
        cfg = self.cfg
        return {"layers": [i for i, c in enumerate(cfg.pattern)
                           if LAYERS[c][1] == "moe"],
                "first": cfg.held[0], "count": cfg.held[1]}

    def build_paged_serving_fns(self, num_slots, block_size, num_blocks,
                                blocks_per_slot, sampling=False):
        """(paged_prefill, paged_decode) over the mixed pool, with the
        engine's signatures (``serving/paged/mixed_programs.py``). The
        decode program's kernels are not an option: on a backend that
        has Mosaic they are the only path and a shape they cannot take
        is refused here; the CPU runs the ``jnp`` formulations."""
        from ..serving.paged.mixed_programs import build_paged_mixed_fns
        return build_paged_mixed_fns(
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
        cfg = self.cfg
        b, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        x, _, _ = run_layers(cfg, params, params["wemb"][ids], pos,
                             SeqAccess(cfg), (), 0, "prefill")
        return lm_head(cfg, params, x)
