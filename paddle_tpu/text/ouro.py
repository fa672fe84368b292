"""The ``ouro`` family of causal LMs on the served path: a LOOPED
language model. ONE stack of ``num_hidden_layers`` layers is run
``total_ut_steps`` times over the same weights; the state a pass ends
with, through the model's final norm, is what the next pass starts from,
and an exit gate after every pass says from which pass a token is read.

  a layer    ``a = rms(h; n1)``; ``q, k, v = a Wqkv`` as
             ``num_attention_heads`` / ``num_key_value_heads`` heads of
             ``head_dim``; rotary positions over the whole head in
             half-split pairs (``ops.eva.rope_half``) at base
             ``rope_theta``; causal softmax attention over the keys and
             values OF THIS PASS of this layer; ``h = h + rms(attn Wo;
             n2)``; ``m = rms(h; n3)``; ``h = h + rms((silu(m Wg) * m
             Wu) Wd; n4)``: four norms a layer, one before each sublayer
             and one on its output before the residual add;
  a pass     every layer in order, then ``h = rms(h; norm_f)`` and the
             gate ``g_r = sigmoid(h . w_gate + b_gate)``;
  the exit   ``p_r = g_r prod_{j<r} (1 - g_j)`` below the last pass, the
             last pass takes the rest of the mass; a token is read from
             the first pass whose cumulative ``p`` reaches
             ``early_exit_threshold``, else from the last (the published
             threshold is 1: every token is read from the last pass);
             ``logits = h_exit W_out`` (the norm is already in ``h``).

**A cache entry for every pass of every layer**: pass ``r`` of layer
``l`` keeps its own keys and values, entry ``r * num_hidden_layers + l``
of ``total_ut_steps * num_hidden_layers``. So the cache's layers are NOT
the weights' layers: ``cache_spec().num_layers`` is their product, a
cached position costs that many (k, v) pairs, and a decode step reads
the layers' weights once a PASS.

**The block is written once, here**: ``attention`` and ``mlp`` are
called by the eager ``forward``, by ``generate()`` and by the engine's
paged programs (``serving/paged/looped_programs.py``) through
``run_passes``: a ``lax.scan`` over the layers inside a ``lax.scan``
over the passes, one set of stacked weights, the cache state in the
carry of both. A layer reaches its cache through the access objects of
``text.nemotron_h`` and ``serving/paged/hybrid_programs.py``
(``attn_prefill`` / ``attn_decode`` over keys and values a token owns,
indexed by ENTRY); of their state ``(k, v, conv, ssm)`` this model has
the first two, the others are ``None``.

Not brought by this module: training (its loss is an expectation over
the exit distribution), sharding over a mesh, ``rope_scaling``, a
sliding window, projection biases, a token that LEAVES EARLY
(``early_exit_threshold < 1``: it would still owe later tokens its later
passes' entries; the eager ``forward`` reads it from its exit pass, the
cached paths refuse it by name), speculative decoding, a disaggregated
role, KV hand-off.
"""
import jax
import jax.numpy as jnp

from ..profiler import device_scope
from ..ops import attention as attn_ops
from ..ops import moe_experts as moe_ops
from ..ops.eva import rope_half
from .nemotron_h import ContigAccess, SeqAccess
from .stacked_lm import (  # noqa: F401 - parts of this block
    StackedCausalLM, greedy_or_sampled, rms_norm, take_layer)


class OuroConfig:
    """Sizes of one model, from the keys of a Hugging Face
    ``config.json`` of ``model_type: ouro`` (``from_hf``): every key is
    read or refused by name, and a key this class does not know is an
    error."""

    def __init__(self, vocab_size, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, intermediate_size,
                 num_hidden_layers, total_ut_steps,
                 early_exit_threshold=1.0, layer_types=None,
                 max_position_embeddings=4096, max_window_layers=None,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 sliding_window=None, use_sliding_window=False,
                 hidden_act="silu", tie_word_embeddings=False,
                 model_type="ouro", initializer_range=0.02,
                 dtype="float32"):
        kinds = set(layer_types or ()) - {"full_attention"}
        for name, on in (
                (f"model_type={model_type!r}", model_type != "ouro"),
                ("rope_scaling", rope_scaling is not None),
                ("sliding_window", sliding_window is not None
                 or use_sliding_window),
                (f"layer_types {sorted(kinds)}", bool(kinds)),
                (f"hidden_act={hidden_act!r}", hidden_act != "silu"),
                ("tie_word_embeddings", tie_word_embeddings)):
            if on:
                raise NotImplementedError(f"ouro: {name} is not brought")
        self.num_layers = int(num_hidden_layers)
        if layer_types is not None and len(layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types ({len(layer_types)}) and num_hidden_layers "
                f"({self.num_layers}) disagree")
        self.num_passes = int(total_ut_steps)
        self.exit_threshold = float(early_exit_threshold)
        if self.num_passes < 1 or not 0 < self.exit_threshold <= 1:
            raise ValueError(
                f"total_ut_steps {total_ut_steps} >= 1 and 0 < "
                f"early_exit_threshold {early_exit_threshold} <= 1")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_heads = int(num_attention_heads)
        self.num_kv_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        if self.num_heads % self.num_kv_heads or self.head_dim % 2:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads; head_dim is even")
        self.intermediate_size = int(intermediate_size)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.max_seq_len = int(max_position_embeddings)
        # max_window_layers is read and unused: it bounds the layers
        # that MAY use a window, and no layer does
        self.initializer_range = float(initializer_range)
        # keys and values in the model's dtype; the residual stream, the
        # norms, the gate and the softmax never below float32
        self.dtype = self.cache_dtype = jnp.dtype(dtype).name

    @classmethod
    def from_hf(cls, config, **overrides):
        return cls(**{**config, **overrides})

    @property
    def cache_layers(self):
        """Cache entries a position owns: one a pass of every layer."""
        return self.num_passes * self.num_layers


# ------------------------------------------------------------ the block
def attention(cfg, p, x, positions, access, state, entry, start, mode,
              kernel):
    """One layer's attention with both its norms and its residual, over
    cache entry ``entry`` (pass * layers + layer). "prefill": x ``[b, T,
    h]``, positions ``[b, T]``; "decode": x ``[S, h]``, positions
    ``[S]``."""
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = x.shape[:-1]
    cdt = jnp.dtype(cfg.cache_dtype)
    wdt = p["wqkv"].dtype
    with device_scope("attn/qkv"):
        a = rms_norm(x, p["n1"], cfg.rms_norm_eps).astype(wdt)
        qkv = jnp.dot(a, p["wqkv"], preferred_element_type=jnp.float32)
        q = qkv[..., :nq * hd].reshape(lead + (nq, hd))
        k = qkv[..., nq * hd:(nq + nkv) * hd].reshape(lead + (nkv, hd))
        v = qkv[..., (nq + nkv) * hd:].reshape(lead + (nkv, hd))
        at = positions[..., None]
        q = rope_half(q, at, cfg.rope_theta).astype(cdt)
        k = rope_half(k, at, cfg.rope_theta).astype(cdt)
        v = v.astype(cdt)
    with device_scope("attn/paged"):
        if mode == "decode":
            state, o = access.attn_decode(state, entry, positions, q, k,
                                          v, kernel)
        else:
            state, (kv_, vv_) = access.attn_prefill(state, entry, start,
                                                    k, v)
            o = jax.vmap(attn_ops.grouped_causal_attention)(
                q, kv_, vv_, positions)
    with device_scope("attn/out"):
        y = jnp.dot(o.astype(wdt).reshape(lead + (nq * hd,)), p["wo"],
                    preferred_element_type=jnp.float32)
        return x + rms_norm(y, p["n2"], cfg.rms_norm_eps), state


def mlp(cfg, p, x):
    """The SwiGLU with both its norms and its residual."""
    with device_scope("mlp"):
        m = rms_norm(x, p["n3"], cfg.rms_norm_eps).astype(p["wg"].dtype)
        y = moe_ops.swiglu(m, p["wg"], p["wu"], p["wd"])
        return x + rms_norm(y, p["n4"], cfg.rms_norm_eps)


def exit_distribution(gates):
    """``p [R, ...]`` from the gates ``[R, ...]`` (f32) of every pass:
    ``p_r = g_r prod_{j<r} (1 - g_j)`` below the last pass, which takes
    what is left. Sums to 1 over the passes."""
    stay = jnp.cumprod(1.0 - gates, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([(gates * before)[:-1], before[-1:]])


def exit_pass(p, threshold):
    """The pass ``[...]`` (int32) a token is read from: the first whose
    cumulative ``p`` reaches ``threshold``, else the last."""
    short = jnp.cumsum(p, axis=0)[:-1] < jnp.float32(threshold)
    return jnp.sum(short, axis=0, dtype=jnp.int32)


def run_passes(cfg, params, x, positions, access, state, start=0,
               mode="prefill", kernel=False):
    """Every pass of every layer over x, the cache state in the carry.
    THE RESIDUAL STREAM IS FLOAT32 whatever the weights' dtype: it takes
    ``2 x passes x layers`` additions of a sublayer's normed output, and
    in bfloat16 each of them rounds the whole stream (a mean logit gap
    of 0.07-0.08 against 0.002-0.03 on the chip at 192 layer
    applications: PERF.md, PR 44); a sublayer's matmuls take their input
    in the weights' dtype and accumulate in float32.
    Returns (``hs [R, ..., h]`` f32: the normed state after each pass,
    ``p [R, ...]`` f32: the exit distribution, state)."""
    L = cfg.num_layers
    layers = params["layers"]
    w_gate = params["w_gate"].astype(jnp.float32)
    x = x.astype(jnp.float32)

    def one_pass(carry, r):
        def one_layer(carry, l):
            x, state = carry
            p = take_layer(layers, l)
            x, state = attention(cfg, p, x, positions, access, state,
                                 r * jnp.int32(L) + l, start, mode,
                                 kernel)
            return (mlp(cfg, p, x), state), None

        (x, state), _ = jax.lax.scan(one_layer, carry,
                                     jnp.arange(L, dtype=jnp.int32))
        with device_scope("loop/norm"):
            x = rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
        with device_scope("loop/gate"):
            g = jax.nn.sigmoid(jnp.sum(x * w_gate, axis=-1)
                               + params["b_gate"][0])
        return (x, state), (x, g)

    (_, state), (hs, gates) = jax.lax.scan(
        one_pass, (x, state),
        jnp.arange(cfg.num_passes, dtype=jnp.int32))
    with device_scope("loop/gate"):
        p = exit_distribution(gates)
    return hs, p, state


def read_exit(cfg, hs, p):
    """(``h_exit [..., h]``, exit pass ``[...]``) of every row."""
    with device_scope("loop/gate"):
        at = exit_pass(p, cfg.exit_threshold)
        h = jnp.take_along_axis(hs, at[None, ..., None], axis=0)[0]
    return h, at


def head(params, x):
    """Logits in f32 of x ``[..., h]`` that the final norm is already
    in."""
    with device_scope("lm_head"):
        return jnp.dot(x.astype(params["head"].dtype), params["head"],
                       preferred_element_type=jnp.float32)


# ------------------------------------------------------------ the model
def looped_cache_spec(cfg):
    """A token owns a key and a value in every PASS of every layer; the
    decode program carries the loop's counters beside them: ``[R + 1]``
    int32 (tokens by the pass they were read from, passes run) and
    ``[R]`` float32 (the exit distribution's summed mass a pass)."""
    from ..serving.paged.cache_spec import CacheSpec
    R = cfg.num_passes
    return CacheSpec(
        cfg.cache_layers,
        [("k", (cfg.num_kv_heads,), (cfg.head_dim,), cfg.cache_dtype),
         ("v", (cfg.num_kv_heads,), (cfg.head_dim,), cfg.cache_dtype)],
        state=[("loop_counts", (R + 1,), "int32"),
               ("loop_gate_mass", (R,), "float32")])


def param_shapes(cfg):
    """The parameter tree's shapes: {path tuple: (shape, kind, dtype
    name)}. Per-layer leaves are stacked on a leading axis of
    ``num_hidden_layers`` (ONE stack, whatever the passes); ``wqkv``'s
    output axis is (q heads | k heads | v heads) x ``head_dim``;
    ``b_gate`` is float32."""
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nq, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.dtype
    out = {("wemb",): ((cfg.vocab_size, h), "w", dt),
           ("norm_f",): ((h,), "g", dt),
           ("head",): ((h, cfg.vocab_size), "w", dt),
           ("w_gate",): ((h,), "w", dt),
           ("b_gate",): ((1,), "z", "float32")}
    for leaf, shape in (("wqkv", (h, (nq + 2 * nkv) * hd)),
                        ("wo", (nq * hd, h)), ("wg", (h, f)),
                        ("wu", (h, f)), ("wd", (f, h))):
        out[("layers", leaf)] = ((L,) + shape, "w", dt)
    for leaf in ("n1", "n2", "n3", "n4"):
        out[("layers", leaf)] = ((L, h), "g", dt)
    return out


class OuroForCausalLM(StackedCausalLM):
    """Causal LM of the family, for serving. Parameters are held as ONE
    stack of layers in ``cfg.dtype``, exactly as the compiled programs
    take them (``stacked_lm.StackedCausalLM``)."""

    def __init__(self, cfg, weights=None, seed=0):
        super().__init__(cfg, param_shapes(cfg), weights, seed)

    # -------------------------------------------------- what serving takes
    def cache_spec(self):
        return looped_cache_spec(self.cfg)

    def loop_counter_layout(self):
        """What ``loop_counts`` and ``loop_gate_mass`` stand for
        (``ServingMetrics.set_loop_counters``)."""
        return {"passes": self.cfg.num_passes,
                "cache_passes": self.cfg.cache_layers
                // self.cfg.num_layers}

    def _no_early_exit(self, what):
        if self.cfg.exit_threshold < 1:
            self._no_program(
                f"early exit (early_exit_threshold "
                f"{self.cfg.exit_threshold} < 1) {what}")

    def build_paged_serving_fns(self, num_slots, block_size, num_blocks,
                                blocks_per_slot, sampling=False):
        """(paged_prefill, paged_decode) over the pool of ``passes x
        layers`` entries a position, with the engine's signatures
        (``serving/paged/looped_programs.py``). The decode program's
        kernel is not an option: on a backend that has Mosaic it is the
        only path and a shape it cannot take is refused here; the CPU
        runs the ``jnp`` formulation."""
        from ..serving.paged.looped_programs import build_paged_looped_fns
        self._no_early_exit("serving")
        return build_paged_looped_fns(
            self.cfg, num_slots, block_size, num_blocks, blocks_per_slot,
            sampling=sampling)

    # ------------------------------------------------------------ eager
    def forward(self, input_ids):
        """Logits ``[b, T, vocab]`` (f32) of whole sequences, through
        the same block as the serving programs, no cache; every token
        read from its exit pass. Inference only: nothing is taped."""
        from ..core.tensor import Tensor
        ids = self._ids(input_ids)
        fn = self._jitted(("forward",) + ids.shape, self._forward_fn)
        return Tensor(fn(self.export_decode_params(), ids)[0])

    def exit_distribution(self, input_ids):
        """``p [R, b, T]`` (f32): the mass each pass takes of every
        token."""
        ids = self._ids(input_ids)
        fn = self._jitted(("forward",) + ids.shape, self._forward_fn)
        return fn(self.export_decode_params(), ids)[1]

    def _forward_fn(self, params, ids):
        cfg = self.cfg
        b, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        hs, p, _ = run_passes(cfg, params, params["wemb"][ids], pos,
                              SeqAccess(cfg), (), 0, "prefill")
        return head(params, read_exit(cfg, hs, p)[0]), p

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, seed=0):
        """Prefill + one decode step a token over a contiguous cache of
        ``passes x layers`` entries, as one jitted program. Greedy when
        ``temperature <= 0`` or ``top_k == 1``, else temperature
        sampling over the ``top_k`` logits (0 = all)."""
        from ..core.tensor import Tensor
        cfg = self.cfg
        self._no_early_exit("generate")
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

        def decode(params, ids, key, temp):
            kv = (cfg.cache_layers, b, cfg.num_kv_heads, total,
                  cfg.head_dim)
            cdt = jnp.dtype(cfg.cache_dtype)
            state = (jnp.zeros(kv, cdt), jnp.zeros(kv, cdt), None, None)
            pos = jnp.broadcast_to(jnp.arange(s0, dtype=jnp.int32),
                                   (b, s0))
            hs, p, state = run_passes(cfg, params, params["wemb"][ids],
                                      pos, access, state, jnp.int32(0),
                                      "prefill")
            key, sub = jax.random.split(key)
            first = pick(head(params, read_exit(
                cfg, hs[:, :, -1], p[:, :, -1])[0]), sub, temp)

            def step(carry, _):
                tok, at, state, key = carry
                hs, p, state = run_passes(
                    cfg, params, params["wemb"][tok],
                    jnp.broadcast_to(at, (b,)), access, state,
                    mode="decode")
                key, sub = jax.random.split(key)
                nxt = pick(head(params, read_exit(cfg, hs, p)[0]), sub,
                           temp)
                return (nxt, at + 1, state, key), nxt

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
