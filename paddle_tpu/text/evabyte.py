"""The ``evabyte`` family of byte-level causal LMs on the served path:
RMS norm with a unit offset (``rms(x) * (1 + g)``), a float32 residual
stream, rotary positions by half-split pairs, EVA attention, SwiGLU, and
``num_pred_heads`` prediction heads of which head 0 is the next byte.

EVA attention (Zheng et al., ICLR 2023; ``ops/eva.py`` has the
equations): a query at position ``t`` in window ``w = t // W`` attends,
in one softmax, to the positions of its own window up to ``t`` and to
ONE pooled key and value for every chunk of ``C`` positions of every
window before ``w``. A sequence's cache is therefore not one entry a
position: it is ``(W / C) w`` summaries and then the raw keys and values
of the current window, ``entries(t) = (W / C) (t // W) + t % W`` before
position ``t`` is written, and when a window ends its ``W`` raw entries
are COMPACTED into ``W / C`` in place. ``cache_spec()`` says so
(``CacheSpec.window``), the pool counts capacity in entries and the
engine takes a compacted window's blocks back while the slot lives.

**The block is written once, here**: ``attention`` and ``mlp`` are
called by the eager ``forward``, by ``generate()`` and by the engine's
paged programs (``serving/paged/eva_programs.py``). What differs is how
a layer reaches its cache, an ACCESS object over arrays it does not own:

  ``summaries(state, layer, start) -> (ks, vs [b, H, R, d], n)``
      the pooled pairs a run that starts at window boundary ``start``
      may see, the first ``n`` of them live;
  ``store(state, layer, start, k, v, kbar, vbar, length) -> state``
      keep a run's entries: of each window it FILLS the summaries, of a
      last partial window the raw rows;
  ``decode(state, layer, pos, q, k, v, kernel) -> state, o``
      write one position a sequence at its entry and attend over the
      entries so far.

``SeqAccess`` (no cache: eager forward), ``ContigAccess`` (``[L, b, E,
H, d]`` entries, compacted inside the jitted loop: ``generate()``);
``PagedAccess`` is beside the programs.

Not brought: the model's multibyte self-speculative decoding (heads
1.. are held and computed by ``forward_heads`` only; ``speculative`` is
refused by name), training, sharding over a mesh, ``num_chunks`` (a
fixed number of chunks a window), ``rope_scaling``, biases, tied
embeddings, grouped key-value heads.
"""
import jax
import jax.numpy as jnp

from ..profiler import device_scope
from ..ops import eva as eva_ops
from ..ops import moe_experts as moe_ops
from .stacked_lm import (StackedCausalLM, greedy_or_sampled,
                         project_heads, rms_norm)


class EvaByteConfig:
    """Sizes of one model, from the keys of a Hugging Face
    ``config.json`` of ``model_type: evabyte`` (``from_hf``)."""

    def __init__(self, vocab_size, hidden_size, num_hidden_layers,
                 num_attention_heads, intermediate_size,
                 num_key_value_heads=None, window_size=2048,
                 chunk_size=16, num_pred_heads=1,
                 max_position_embeddings=32768, rms_norm_eps=1e-5,
                 rope_theta=100000.0, attention_class="eva",
                 num_chunks=None, rope_scaling=None, attention_bias=False,
                 tie_word_embeddings=False, hidden_act="silu",
                 norm_add_unit_offset=True, fp32_skip_add=True,
                 fp32_logits=True, fp32_ln=False, init_std=0.02,
                 dtype="float32", cache_dtype=None, **ignored):
        nkv = num_attention_heads if num_key_value_heads is None \
            else num_key_value_heads
        bad = [name for name, on in (
            (f"attention_class={attention_class!r}",
             attention_class != "eva"),
            ("num_chunks", num_chunks is not None),
            (f"window_size % chunk_size ({window_size} % {chunk_size})",
             int(window_size) % int(chunk_size) != 0),
            (f"num_key_value_heads={nkv} != num_attention_heads="
             f"{num_attention_heads}", int(nkv) != int(num_attention_heads)),
            ("rope_scaling", rope_scaling is not None),
            ("attention_bias", bool(attention_bias)),
            ("tie_word_embeddings", bool(tie_word_embeddings)),
            (f"hidden_act={hidden_act!r}", hidden_act != "silu"),
            ("fp32_ln", bool(fp32_ln)),
        ) if on]
        if bad:
            raise NotImplementedError(
                f"evabyte: not brought: {', '.join(bad)}")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_layers = int(num_hidden_layers)
        self.num_heads = int(num_attention_heads)
        self.head_dim = self.hidden_size // self.num_heads
        self.intermediate_size = int(intermediate_size)
        self.window_size = int(window_size)
        self.chunk_size = int(chunk_size)
        self.num_pred_heads = int(num_pred_heads)
        self.max_seq_len = int(max_position_embeddings)
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        self.unit_offset = bool(norm_add_unit_offset)
        self.fp32_skip_add = bool(fp32_skip_add)
        self.fp32_logits = bool(fp32_logits)
        self.initializer_range = float(init_std)
        self.dtype = jnp.dtype(dtype).name
        self.cache_dtype = jnp.dtype(cache_dtype or dtype).name

    @classmethod
    def from_hf(cls, config, **overrides):
        return cls(**{**config, **overrides})

    @property
    def summaries_per_window(self):
        return self.window_size // self.chunk_size

    def entries(self, positions):
        """Cache entries before position ``positions`` is written (also
        the entry it is written at); jnp or int."""
        W = self.window_size
        return (positions // W) * self.summaries_per_window \
            + positions % W


# ------------------------------------------------------------ the block
def norm(cfg, x, g):
    """``rms(x) * (1 + g)`` (``norm_add_unit_offset``) in the compute
    dtype, whatever the residual stream's."""
    g = g.astype(jnp.float32)
    return rms_norm(x.astype(jnp.dtype(cfg.dtype)),
                    g + 1.0 if cfg.unit_offset else g, cfg.rms_norm_eps)


def prefill_attention(cfg, q, k, v, mu, phi, ks0, vs0, n0):
    """A run of ``T`` positions from a window's start, ``b`` sequences:
    q, k, v ``[b, T, H, d]``; ks0, vs0 ``[b, H, R0, d]`` the summaries
    before it (``n0`` live). Window by window (a static split): each
    sees the summaries before the run and those of the run's earlier
    windows. Returns (o ``[b, T, H, d]`` f32, kbar, vbar ``[b, H,
    (T // W) S, d]``: the pooled pairs of the run's windows of full
    width)."""
    W, C = cfg.window_size, cfg.chunk_size
    T = q.shape[1]
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))  # [b,H,T,d]
    full = T // W
    kbar, vbar = eva_ops.window_compact(
        kt[:, :, :full * W], vt[:, :, :full * W], mu, phi, C)
    S = cfg.summaries_per_window
    given = jnp.arange(ks0.shape[2], dtype=jnp.int32) < n0
    outs = []
    for i in range(-(-T // W)):
        sl = slice(i * W, min((i + 1) * W, T))
        # the run's own earlier windows sit behind the ones it was given
        ks = jnp.concatenate([ks0, kbar[:, :, :i * S]], axis=2)
        vs = jnp.concatenate([vs0, vbar[:, :, :i * S]], axis=2)
        live = jnp.concatenate([given, jnp.ones((i * S,), bool)])
        outs.append(jax.vmap(
            lambda qq, kk, vv, a, c: eva_ops.window_attention(
                qq, kk, vv, a, c, live))(
            qt[:, :, sl], kt[:, :, sl], vt[:, :, sl], ks, vs))
    o = jnp.concatenate(outs, axis=2).transpose(0, 2, 1, 3)
    return o, kbar, vbar


def attention(cfg, p, x, positions, access, state, layer, start, mode,
              kernel=False, length=None):
    """One layer's attention with its residual. ``mode`` "prefill": x
    ``[b, T, h]``, positions ``[b, T]``, a run from window boundary
    ``start`` of which ``length`` rows are real (None: all);
    "decode": x ``[S, h]``, positions ``[S]``."""
    H, d = cfg.num_heads, cfg.head_dim
    lead = x.shape[:-1]
    cdt = jnp.dtype(cfg.cache_dtype)
    with device_scope("eva/qkv"):
        h = norm(cfg, x, p["norm1"])
        # three matmuls, each reading its layer's [h, h] matrix inside
        # the dot like wo below. As jnp.dot(h, w).reshape(heads) XLA
        # staged and relaid each in VMEM (0.86 of a 13.49 ms step, ledger,
        # PR 44); a fused [h, 3h] one it copied (16.1 ms for 14.4, PR 37)
        q, k, v = (project_heads(h, p[n], H)
                   for n in ("wq", "wk", "wv"))
        q = eva_ops.rope_half(q, positions[..., None],
                              cfg.rope_theta).astype(cdt)
        k = eva_ops.rope_half(k, positions[..., None],
                              cfg.rope_theta).astype(cdt)
        v = v.astype(cdt)
    with device_scope("eva/attn"):
        if mode == "decode":
            state, o = access.decode(state, layer, positions, q, k, v,
                                     kernel)
        else:
            ks0, vs0, n0 = access.summaries(state, layer, start)
            o, kbar, vbar = prefill_attention(
                cfg, q, k, v, p["mu"], p["phi"], ks0, vs0, n0)
            state = access.store(state, layer, start, k, v, kbar, vbar,
                                 length)
    with device_scope("eva/out"):
        y = jnp.dot(o.astype(h.dtype).reshape(lead + (H * d,)), p["wo"],
                    preferred_element_type=jnp.float32)
    return x + y.astype(x.dtype), state


def mlp(cfg, p, x):
    with device_scope("mlp"):
        y = moe_ops.swiglu(norm(cfg, x, p["norm2"]), p["gate"], p["up"],
                           p["down"])
        return x + y.astype(x.dtype)


def run_layers(cfg, params, x, positions, access, state, start=0,
               mode="prefill", kernel=False, length=None):
    """Every layer over x in ONE ``lax.scan`` over the stacked weights,
    the cache state in the carry. Returns (x, state)."""
    def body(carry, inp):
        x, state = carry
        p, layer = inp
        x, state = attention(cfg, p, x, positions, access, state, layer,
                             start, mode, kernel, length)
        return (mlp(cfg, p, x), state), None

    (x, state), _ = jax.lax.scan(
        body, (x, state),
        (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    return x, state


def embed(cfg, params, ids):
    with device_scope("embed"):
        x = params["wemb"][ids]
        return x.astype(jnp.float32) if cfg.fp32_skip_add else x


def lm_head(cfg, params, x, heads=1):
    """Final norm + the first ``heads`` prediction heads over x ``[...,
    h]``: logits ``[..., heads * vocab]`` in float32 (``fp32_logits``:
    operands too). Head ``i`` is columns ``[i V, (i + 1) V)``; head 0 is
    the next byte, what serving samples."""
    with device_scope("lm_head"):
        xn = norm(cfg, x, params["norm_f"])
        w = params["head"][:, :heads * cfg.vocab_size]
        if cfg.fp32_logits:
            xn, w = xn.astype(jnp.float32), w.astype(jnp.float32)
        return jnp.dot(xn, w, preferred_element_type=jnp.float32)


# ------------------------------------------------------- cache accesses
class SeqAccess:
    """No cache: a whole sequence from position 0 is its own history
    (eager forward)."""

    def __init__(self, cfg, batch):
        self.cfg, self.b = cfg, batch

    def summaries(self, state, layer, start):
        H, d = self.cfg.num_heads, self.cfg.head_dim
        z = jnp.zeros((self.b, H, 0, d), jnp.dtype(self.cfg.cache_dtype))
        return z, z, jnp.int32(0)

    def store(self, state, layer, start, k, v, kbar, vbar, length):
        return state


class ContigAccess:
    """``generate()``'s cache: entries (ke, ve ``[L, b, E, H, d]``),
    every sequence at the same position. The prompt's full windows are
    stored as their summaries, its last partial window raw; a decode
    step that ends a window compacts it under a condition."""

    def __init__(self, cfg, p_mu, p_phi):
        self.cfg, self.mu, self.phi = cfg, p_mu, p_phi   # [L, H, d]

    def summaries(self, state, layer, start):
        ke = state[0]
        z = jnp.zeros((ke.shape[1], ke.shape[3], 0, ke.shape[4]), ke.dtype)
        return z, z, jnp.int32(0)

    def store(self, state, layer, start, k, v, kbar, vbar, length):
        cfg = self.cfg
        T = k.shape[1]
        full = (T // cfg.window_size) * cfg.window_size
        out = []
        for cache, bar, raw in zip(state, (kbar, vbar), (k, v)):
            rows = jnp.concatenate(
                [bar.transpose(0, 2, 1, 3), raw[:, full:]], axis=1)
            z = jnp.int32(0)
            out.append(jax.lax.dynamic_update_slice(
                cache, rows[None].astype(cache.dtype), (layer, z, z, z, z)))
        return tuple(out)

    def decode(self, state, layer, pos, q, k, v, kernel):
        cfg = self.cfg
        W, S = cfg.window_size, cfg.summaries_per_window
        ke, ve = state
        t = pos[0]
        e = cfg.entries(t)
        z = jnp.int32(0)
        ke = jax.lax.dynamic_update_slice(ke, k[None, :, None].astype(
            ke.dtype), (layer, z, e, z, z))
        ve = jax.lax.dynamic_update_slice(ve, v[None, :, None].astype(
            ve.dtype), (layer, z, e, z, z))
        o = eva_ops.entry_attention(
            q, ke[layer], ve[layer],
            jnp.broadcast_to(e + 1, pos.shape))

        def compact(kv):
            ke, ve = kv
            base = (t // W) * S
            kw = jax.lax.dynamic_slice_in_dim(ke[layer], base, W, axis=1)
            vw = jax.lax.dynamic_slice_in_dim(ve[layer], base, W, axis=1)
            kb, vb = eva_ops.window_compact(
                kw.transpose(0, 2, 1, 3), vw.transpose(0, 2, 1, 3),
                self.mu[layer], self.phi[layer], cfg.chunk_size)
            return tuple(jax.lax.dynamic_update_slice(
                c, bar.transpose(0, 2, 1, 3)[None], (layer, z, base, z, z))
                for c, bar in ((ke, kb), (ve, vb)))

        ke, ve = jax.lax.cond(t % W == W - 1, compact, lambda kv: kv,
                              (ke, ve))
        return (ke, ve), o


# ------------------------------------------------------------ the model
def eva_cache_spec(cfg):
    """An ENTRY owns, in each layer, a key and a value over all heads:
    a position of the current window, or the pooled pair of a chunk of a
    window that is over (``CacheSpec.window``)."""
    from ..serving.paged.cache_spec import CacheSpec
    return CacheSpec(
        cfg.num_layers,
        [("k", (cfg.num_heads,), (cfg.head_dim,), cfg.cache_dtype),
         ("v", (cfg.num_heads,), (cfg.head_dim,), cfg.cache_dtype)],
        window=(cfg.window_size, cfg.chunk_size))


def param_shapes(cfg):
    """The decode parameter tree's shapes: {path tuple: (shape, kind,
    dtype name)}; per-layer leaves stacked on a leading axis. Kinds for
    the class's own initialisation: gains are about 0 under the unit
    offset."""
    h, H, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    f, L = cfg.intermediate_size, cfg.num_layers
    g = "z" if cfg.unit_offset else "g"
    layer = {"norm1": ((h,), g), "wq": ((h, H * d), "w"),
             "wk": ((h, H * d), "w"), "wv": ((h, H * d), "w"),
             "wo": ((H * d, h), "w"), "mu": ((H, d), "w"),
             "phi": ((H, d), "w"), "norm2": ((h,), g),
             "gate": ((h, f), "w"), "up": ((h, f), "w"),
             "down": ((f, h), "w")}
    out = {("wemb",): ((cfg.vocab_size, h), "w", cfg.dtype),
           ("norm_f",): ((h,), g, cfg.dtype),
           ("head",): ((h, cfg.num_pred_heads * cfg.vocab_size), "w",
                       cfg.dtype)}
    for leaf, (shape, kind) in layer.items():
        out[("layers", leaf)] = ((L,) + shape, kind, cfg.dtype)
    return out


class EvaByteForCausalLM(StackedCausalLM):
    """Causal LM of the family, for serving. Parameters are held
    STACKED, in ``cfg.dtype``, exactly as the compiled programs take
    them (``stacked_lm.StackedCausalLM``)."""

    def __init__(self, cfg, weights=None, seed=0):
        super().__init__(cfg, param_shapes(cfg), weights, seed)

    # -------------------------------------------------- what serving takes
    def cache_spec(self):
        return eva_cache_spec(self.cfg)

    def build_paged_serving_fns(self, num_slots, block_size, num_blocks,
                                blocks_per_slot, sampling=False):
        """(paged_prefill, paged_decode) over the entry pool, with the
        engine's signatures (``serving/paged/eva_programs.py``)."""
        from ..serving.paged.eva_programs import build_paged_eva_fns
        return build_paged_eva_fns(
            self.cfg, num_slots, block_size, num_blocks, blocks_per_slot,
            sampling=sampling)[:2]

    def build_paged_compact_fn(self, num_slots, block_size, num_blocks,
                               blocks_per_slot):
        """``paged_compact``: one slot's finished window pooled in
        place, the program the step loop dispatches between two decode
        steps when a slot's window ends."""
        from ..serving.paged.eva_programs import build_paged_eva_fns
        return build_paged_eva_fns(
            self.cfg, num_slots, block_size, num_blocks,
            blocks_per_slot)[2]

    # ------------------------------------------------------------ eager
    def forward(self, input_ids):
        """Next-byte logits ``[b, T, vocab]`` (f32) of whole sequences
        through the same block as the serving programs, no cache."""
        return self._eager(input_ids, 1)

    def forward_heads(self, input_ids):
        """All prediction heads: ``[b, T, heads, vocab]`` (f32); head
        ``i`` predicts the byte ``i + 1`` positions on."""
        from ..core.tensor import Tensor
        cfg = self.cfg
        out = self._eager(input_ids, cfg.num_pred_heads).value
        return Tensor(out.reshape(out.shape[:2] + (cfg.num_pred_heads,
                                                   cfg.vocab_size)))

    def _eager(self, input_ids, heads):
        from ..core.tensor import Tensor
        ids = self._ids(input_ids)
        cfg = self.cfg

        def fn(params, ids):
            b, t = ids.shape
            pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
            x, _ = run_layers(cfg, params, embed(cfg, params, ids), pos,
                              SeqAccess(cfg, b), (), 0, "prefill")
            return lm_head(cfg, params, x, heads)

        return Tensor(self._jitted(("forward", heads) + ids.shape, fn)(
            self.export_decode_params(), ids))

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, seed=0):
        """Prefill by windows + one decode step a token over a
        contiguous ENTRY cache, as one jitted program; a window that
        ends is compacted inside the loop. Greedy when ``temperature <=
        0`` or ``top_k == 1``, else temperature sampling over the
        ``top_k`` logits (0 = all). Head 0 only."""
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
        spec = self.cache_spec()
        # room for a whole window's raw entries, whatever the length
        E = max(spec.capacity(s0 + n_new), cfg.window_size)
        pick = greedy_or_sampled(greedy, kk)

        def decode(params, ids, key, temp):
            cdt = jnp.dtype(cfg.cache_dtype)
            access = ContigAccess(cfg, params["layers"]["mu"],
                                  params["layers"]["phi"])
            shape = (cfg.num_layers, b, E, cfg.num_heads, cfg.head_dim)
            state = (jnp.zeros(shape, cdt), jnp.zeros(shape, cdt))
            pos = jnp.broadcast_to(jnp.arange(s0, dtype=jnp.int32),
                                   (b, s0))
            x, state = run_layers(cfg, params, embed(cfg, params, ids),
                                  pos, access, state, jnp.int32(0),
                                  "prefill")
            key, sub = jax.random.split(key)
            first = pick(lm_head(cfg, params, x[:, -1]), sub, temp)

            def step(carry, _):
                tok, p, state, key = carry
                x, state = run_layers(
                    cfg, params, embed(cfg, params, tok),
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
