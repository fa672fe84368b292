"""BERT / GPT model families.

Reference anchors: BERT-base pretraining and GPT-3 1.3B hybrid-parallel
configs (reference TP layers
fleet/meta_parallel/parallel_layers/mp_layers.py). Models are built from
paddle_tpu.nn layers; when a hybrid mesh is active, linear/embedding
layers use the tensor-parallel variants so GSPMD shards them over 'mp'.
"""
import math

import jax

from ..profiler import device_scope
from .. import nn
from ..core import trace as trace_mod
from ..ops import creation, manipulation, math as math_ops, nn_ops
from ..distributed import topology
from ..distributed.fleet.meta_parallel.mp_layers import (
    VocabParallelEmbedding, ColumnParallelLinear, RowParallelLinear,
    shard_constraint,
)


class TransformerLMConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=None, max_seq_len=1024,
                 dropout=0.1, use_mp=False, tie_embeddings=True,
                 use_flash_attention=True, initializer_range=0.02,
                 recompute=False, use_sp=False, sp_mode="ring"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size or hidden_size * 4
        self.max_seq_len = max_seq_len
        self.dropout = dropout
        self.use_mp = use_mp
        self.tie_embeddings = tie_embeddings
        self.use_flash_attention = use_flash_attention
        self.initializer_range = initializer_range
        self.recompute = recompute
        # sequence/context parallelism over the 'sp' mesh axis:
        # attention runs ring (K/V stream the ICI ring, O(S/sp) HBM per
        # chip) or ulysses (head all-to-all) and activations are
        # sequence-sharded — the lever that trains long contexts the
        # chip's HBM cannot hold whole
        self.use_sp = use_sp
        assert sp_mode in ("ring", "ulysses")
        self.sp_mode = sp_mode


def _mp_active():
    mesh = topology.get_mesh()
    return mesh is not None and int(mesh.shape.get("mp", 1)) > 1


def _sp_active():
    mesh = topology.get_mesh()
    return mesh is not None and int(mesh.shape.get("sp", 1)) > 1


class SelfAttention(nn.Layer):
    """Fused-QKV attention; column-parallel QKV + row-parallel output when
    TP is active (the Megatron split, reference mp_layers.py)."""

    def __init__(self, cfg, causal):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        self.causal = causal
        self.dropout = cfg.dropout
        self.use_flash = cfg.use_flash_attention
        self.use_sp = getattr(cfg, "use_sp", False)
        self.sp_mode = getattr(cfg, "sp_mode", "ring")
        use_mp = cfg.use_mp and _mp_active()
        if use_mp:
            self.qkv = ColumnParallelLinear(h, 3 * h, gather_output=False)
            self.out = RowParallelLinear(h, h, input_is_parallel=True)
        else:
            self.qkv = nn.Linear(h, 3 * h)
            self.out = nn.Linear(h, h)

    def forward(self, x, attn_mask=None):
        from ..ops import attention as attn_ops
        b, s, h = x.shape
        qkv = self.qkv(x)
        mesh = topology.get_mesh()
        if attn_mask is None and (mesh is None or mesh.size == 1) \
                and attn_ops.packed_qkv_viable(qkv.shape, qkv.dtype,
                                               self.num_heads):
            # the kernels read q, k, v from the projection's output
            # where it lies and write o as the output projection reads
            # it: no head split, forward or backward
            o = attn_ops.flash_attention_qkv(qkv, self.num_heads,
                                             causal=self.causal)
            return self._project_out(o)
        qkv = manipulation.reshape(qkv, (b, s, 3, self.num_heads,
                                         self.head_dim))
        qkv = manipulation.transpose(qkv, (2, 0, 3, 1, 4))
        q, k, v = manipulation.unbind(qkv, axis=0)
        if self.use_sp and attn_mask is None and _sp_active():
            # sequence-parallel kernel over the 'sp' mesh axis (falls
            # back to dense/flash when the mesh has no sp axis); custom
            # masks need the gathered scores and keep the dense path
            from ..distributed.fleet.meta_parallel.sequence_parallel \
                import ring_attention, ulysses_attention
            sp_fn = (ring_attention if self.sp_mode == "ring"
                     else ulysses_attention)
            o = sp_fn(q, k, v, causal=self.causal)
        else:
            o = attn_ops.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, is_causal=self.causal)
        o = manipulation.transpose(o, (0, 2, 1, 3))
        return self._project_out(manipulation.reshape(o, (b, s, h)))

    def _project_out(self, o):
        o = self.out(o)
        if self.dropout:
            o = nn_ops.dropout(o, p=self.dropout, training=self.training)
        return o


class MLP(nn.Layer):
    def __init__(self, cfg, activation="gelu"):
        super().__init__()
        h, inter = cfg.hidden_size, cfg.intermediate_size
        use_mp = cfg.use_mp and _mp_active()
        if use_mp:
            self.fc1 = ColumnParallelLinear(h, inter, gather_output=False)
            self.fc2 = RowParallelLinear(inter, h, input_is_parallel=True)
        else:
            self.fc1 = nn.Linear(h, inter)
            self.fc2 = nn.Linear(inter, h)
        self.act = activation
        self.dropout = cfg.dropout

    def forward(self, x):
        x = self.fc1(x)
        x = nn_ops.gelu(x, approximate=True) if self.act == "gelu" else \
            nn_ops.relu(x)
        x = self.fc2(x)
        if self.dropout:
            x = nn_ops.dropout(x, p=self.dropout, training=self.training)
        return x


class Block(nn.Layer):
    def __init__(self, cfg, causal, pre_norm=True):
        super().__init__()
        self.pre_norm = pre_norm
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = SelfAttention(cfg, causal)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.mlp = MLP(cfg)

    def forward(self, x, attn_mask=None):
        # the scopes name the staged ops in the device trace (each
        # half with its norm and its residual add); eager, they do
        # nothing
        if self.pre_norm:  # GPT style
            with device_scope("block/attn"):
                x = math_ops.add(x, self.attn(self.ln1(x), attn_mask))
            with device_scope("block/mlp"):
                x = math_ops.add(x, self.mlp(self.ln2(x)))
        else:  # BERT style post-norm
            with device_scope("block/attn"):
                x = self.ln1(math_ops.add(x, self.attn(x, attn_mask)))
            with device_scope("block/mlp"):
                x = self.ln2(math_ops.add(x, self.mlp(x)))
        return x


class _TransformerCore(nn.Layer):
    def __init__(self, cfg, causal, pre_norm, with_token_type=False):
        super().__init__()
        self.cfg = cfg
        use_mp = cfg.use_mp and _mp_active()
        # reference init (BERT/GPT initializer_range=0.02): with tied
        # embeddings, N(0,1) rows would give logits of scale
        # sqrt(hidden) and an untrainable initial loss
        from ..nn import initializer as init_mod
        emb_attr = init_mod.ParamAttr(
            initializer=init_mod.Normal(0.0, cfg.initializer_range))
        if use_mp:
            self.word_embeddings = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size, weight_attr=emb_attr)
        else:
            self.word_embeddings = nn.Embedding(cfg.vocab_size,
                                                cfg.hidden_size,
                                                weight_attr=emb_attr)
        self.position_embeddings = nn.Embedding(cfg.max_seq_len,
                                                cfg.hidden_size,
                                                weight_attr=emb_attr)
        self.token_type_embeddings = nn.Embedding(
            2, cfg.hidden_size, weight_attr=emb_attr) \
            if with_token_type else None
        self.blocks = nn.LayerList(
            [Block(cfg, causal, pre_norm) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        self.pre_norm = pre_norm

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        s = input_ids.shape[1]
        with device_scope("embed"):
            pos = creation.arange(0, s, dtype="int64")
            x = self.word_embeddings(input_ids)
            x = math_ops.add(x, self.position_embeddings(pos))
            if self.token_type_embeddings is not None \
                    and token_type_ids is not None:
                x = math_ops.add(
                    x, self.token_type_embeddings(token_type_ids))
            if self.cfg.dropout:
                x = nn_ops.dropout(x, p=self.cfg.dropout,
                                   training=self.training)
        if getattr(self.cfg, "use_sp", False) and _sp_active():
            # sequence-shard the activations: every elementwise op /
            # LayerNorm / MLP between attentions holds only S/sp of the
            # sequence per chip (GSPMD propagates the layout; the
            # attention kernel reshards to its ring/all-to-all form)
            from ..distributed.fleet.meta_parallel.mp_layers import \
                shard_constraint
            mesh = topology.get_mesh()
            bspec = "dp" if "dp" in mesh.axis_names else None
            x = shard_constraint(x, (bspec, "sp", None))
        use_rc = (getattr(self.cfg, "recompute", False) and self.training
                  and not x.stop_gradient)
        if use_rc:
            from ..distributed.utils_recompute import recompute as _rc
        for blk in self.blocks:
            # per-block activation recompute (reference: fleet recompute
            # over transformer layers) — trades one extra forward per
            # block for O(layers) less live activation memory; the lever
            # that fits seq-4096 training batches on one chip
            x = _rc(blk, x, attn_mask) if use_rc else blk(x, attn_mask)
        if self.pre_norm:
            x = self.ln_f(x)
        return x


class GPTModel(_TransformerCore):
    """Decoder-only causal LM core (GPT-3 style: pre-norm)."""

    def __init__(self, cfg):
        super().__init__(cfg, causal=True, pre_norm=True)


def _decode_forward_builder(num_heads, head_dim, hidden_size):
    """Pure-jax KV-cache decode math shared by generate() AND the
    serving engine (paddle_tpu.serving) — one definition, so the
    continuous-batching engine's greedy tokens match generate() by
    construction. Returns (ln, forward_t):

      forward_t(params, tok [bb, t], pos, kc, vc) -> (logits, kc, vc)

    with kc/vc [L, bb, nh, total, hd]; writes the new K/V at
    pos..pos+t and attends causally over the cache (positions beyond
    the live prefix are masked to exact-zero softmax weight, so stale
    slot contents are invisible)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    nh, hd = num_heads, head_dim

    def ln(x, w, bias):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * w + bias

    def block(x, p, kc, vc, pos):
        # x [bb, t, h]; kc/vc [bb, nh, total, hd]; writes at
        # pos..pos+t (bb = batch OR batch*beams OR one pool slot)
        bb, t = x.shape[0], x.shape[1]
        total = kc.shape[2]
        with device_scope("attn"):
            h_ = ln(x, p["ln1_w"], p["ln1_b"])
            qkv = h_ @ p["qkv_w"] + p["qkv_b"]
            qkv = qkv.reshape(bb, t, 3, nh, hd).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]
            z = jnp.int32(0)  # index dtypes must all match under x64
            with device_scope("kv_write"):
                kc = lax.dynamic_update_slice(kc, k, (z, z, pos, z))
                vc = lax.dynamic_update_slice(vc, v, (z, z, pos, z))
            s = jnp.einsum("bhtd,bhsd->bhts", q, kc) / jnp.sqrt(
                jnp.float32(hd))
            kpos = jnp.arange(total)[None, None, None, :]
            qpos = pos + jnp.arange(t)[None, None, :, None]
            s = jnp.where(kpos <= qpos, s, jnp.float32(-1e30))
            # f32 scores/softmax; the output returns to the residual
            # stream's dtype (a no-op for f32, keeps a bf16 model bf16)
            o = jnp.einsum("bhts,bhsd->bhtd",
                           jax.nn.softmax(s, axis=-1), vc).astype(x.dtype)
            o = o.transpose(0, 2, 1, 3).reshape(bb, t, hidden_size)
            x = x + (o @ p["out_w"] + p["out_b"])
        with device_scope("mlp"):
            h2 = ln(x, p["ln2_w"], p["ln2_b"])
            m = jax.nn.gelu(h2 @ p["fc1_w"] + p["fc1_b"],
                            approximate=True)
            return x + (m @ p["fc2_w"] + p["fc2_b"]), kc, vc

    def forward_t(pr, tok, pos, kc, vc):
        # tok [bb, t] int32; kc/vc [L, bb, nh, total, hd]
        t = tok.shape[1]
        with device_scope("embed"):
            x = pr["wemb"][tok] + pr["pemb"][pos + jnp.arange(t)]

        def body(carry, inp):
            x = carry
            p, kcl, vcl = inp
            x, kcl, vcl = block(x, p, kcl, vcl, pos)
            return x, (kcl, vcl)

        x, (kc, vc) = lax.scan(body, x, (pr["stacked"], kc, vc))
        with device_scope("lm_head"):
            logits = ln(x, pr["lnf_w"], pr["lnf_b"]) @ pr["head"]
        return logits, kc, vc

    return ln, forward_t


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     bias_attr=False)

    def forward(self, input_ids, labels=None):
        h = self.gpt(input_ids)
        return self._head_loss(h, labels)

    def _head_loss(self, h, labels=None):
        mesh = topology.get_mesh()
        mesh_trivial = mesh is None or all(
            int(d) == 1 for d in mesh.shape.values())
        # only the compiled step spans the mesh: a shard_map in an eager
        # phase would turn the whole lazily-fused eager step into a
        # multi-device program
        in_step = trace_mod.in_compiled_step()
        eager_mp = self.cfg.use_mp and mesh is not None and not in_step
        if labels is not None and self.cfg.tie_embeddings \
                and ((not self.cfg.use_mp and mesh_trivial) or eager_mp):
            # head and loss as ONE op (ops/fused_ce.py): by default
            # plain jnp whose f32 [tokens, vocab] logits DO live in HBM,
            # once, the softmax's sum taken out of the dx matmul (three
            # logits-sized passes a step); its Pallas kernels, which
            # never hold the logits, are off on one chip (they lost on
            # time). Also the one-device head of a TP model's eager
            # phases.
            from ..ops.fused_ce import fused_linear_cross_entropy
            # head and loss are one kernel here: one scope, both names
            with device_scope("lm_head"), device_scope("loss"):
                flat = manipulation.reshape(labels, (-1,))
                per_tok = fused_linear_cross_entropy(
                    manipulation.reshape(h, (-1, self.cfg.hidden_size)),
                    self.gpt.word_embeddings.weight, flat)
                # mean over NON-IGNORED tokens, matching
                # cross_entropy's reduction='mean' (a plain mean would
                # scale loss/grads by the valid fraction on padded
                # batches)
                valid = (flat != -100).astype("float32").sum()
                return per_tok.sum() / valid.clip(min=1.0)
        if labels is not None and self.cfg.tie_embeddings \
                and self.cfg.use_mp and mesh is not None and in_step:
            # TP: the vocab-sharded fused kernel — each mp shard
            # streams its LOCAL vocab tile through VMEM, then
            # pmax/psum combine the per-shard logsumexp (the
            # c_softmax_with_cross_entropy_op.cu scheme; pp>1 keeps
            # the composition — stages slice the program before the
            # head)
            from ..ops.fused_ce import (fused_linear_cross_entropy_tp,
                                        tp_fused_applicable)
            t = 1
            for d in h.shape[:-1]:
                t *= int(d)
            if tp_fused_applicable(mesh, t, self.cfg.hidden_size,
                                   self.cfg.vocab_size):
                with device_scope("lm_head"), device_scope("loss"):
                    flat = manipulation.reshape(labels, (-1,))
                    per_tok = fused_linear_cross_entropy_tp(
                        manipulation.reshape(
                            h, (-1, self.cfg.hidden_size)),
                        self.gpt.word_embeddings.weight, flat, mesh)
                    valid = (flat != -100).astype("float32").sum()
                    return per_tok.sum() / valid.clip(min=1.0)
        with device_scope("lm_head"):
            if self.cfg.tie_embeddings:
                logits = math_ops.matmul(
                    h, self.gpt.word_embeddings.weight, transpose_y=True)
            else:
                logits = self.lm_head(h)
        if labels is None:
            return logits
        with device_scope("loss"):
            loss = nn_ops.cross_entropy(
                manipulation.reshape(logits, (-1, self.cfg.vocab_size)),
                manipulation.reshape(labels, (-1,)))
        return loss

    def export_decode_params(self):
        """Weights as the stacked pytree the jitted decode programs
        consume (generate() and the serving engine): per-layer tensors
        stacked on a leading layer axis for lax.scan, plus embeddings
        and the (tied or separate) head. Values are concrete jax
        arrays snapshotted NOW — serving engines built from this see
        the weights as of this call."""
        import jax.numpy as jnp

        from ..core.lazy import concrete

        cfg = self.cfg

        def W(t):
            return concrete(t.value)

        stacked = {}
        per_layer = []
        for blk in self.gpt.blocks:
            per_layer.append({
                "ln1_w": W(blk.ln1.weight), "ln1_b": W(blk.ln1.bias),
                "qkv_w": W(blk.attn.qkv.weight),
                "qkv_b": W(blk.attn.qkv.bias),
                "out_w": W(blk.attn.out.weight),
                "out_b": W(blk.attn.out.bias),
                "ln2_w": W(blk.ln2.weight), "ln2_b": W(blk.ln2.bias),
                "fc1_w": W(blk.mlp.fc1.weight),
                "fc1_b": W(blk.mlp.fc1.bias),
                "fc2_w": W(blk.mlp.fc2.weight),
                "fc2_b": W(blk.mlp.fc2.bias)})
        for k in per_layer[0]:
            stacked[k] = jnp.stack([p[k] for p in per_layer])
        wemb = W(self.gpt.word_embeddings.weight)
        pemb = W(self.gpt.position_embeddings.weight)
        head = wemb.T if cfg.tie_embeddings else W(self.lm_head.weight)
        return {"stacked": stacked, "wemb": wemb, "pemb": pemb,
                "lnf_w": W(self.gpt.ln_f.weight),
                "lnf_b": W(self.gpt.ln_f.bias), "head": head}

    def cache_spec(self):
        """What a token owns in a layer of the paged cache: K and V as
        the qkv projection computes them, per head, in the weights'
        dtype (``serving.paged.cache_spec``)."""
        from ..core.lazy import concrete
        from ..serving.paged.cache_spec import kv_pair_spec
        cfg = self.cfg
        return kv_pair_spec(
            cfg.num_layers, cfg.num_heads,
            cfg.hidden_size // cfg.num_heads,
            concrete(self.gpt.blocks[0].attn.qkv.weight.value).dtype)

    def build_paged_serving_fns(self, num_slots, block_size, num_blocks,
                                blocks_per_slot, sampling=False,
                                attn_kernel=False):
        """The continuous-batching engine's prefill and decode
        programs over the block-granular KV pool (serving.paged): the
        decode math of generate() (``forward_t`` of
        _decode_forward_builder is their parity oracle), cache
        addressed through a fixed-shape block table so shared-prefix
        blocks are reused instead of re-prefilled. Both thread the
        engine's rolling device state (toks/pos [S]) through, so
        consecutive steps chain on device —

          paged_prefill(params, tokens [1, B], tail_len, start, slot,
                        final, bt_row [MB], toks [S], pos [S], kc, vc)
              -> (first [1], toks', pos', kc, vc)
          paged_decode(params, toks [S], pos [S], tables [S, MB],
                       kc, vc)
              -> (next [S], pos + 1, kc, vc)

        with kc/vc [L, num_blocks, nh, block_size, hd]. Both are pure
        and shape-stable (start/tail_len/final are traced scalars, so
        prefix AND chunk variety costs zero compiles); the engine
        AOT-compiles them (decode once, prefill once per tail bucket).
        ``sampling=True`` appends per-slot sampling parameters to both
        signatures (serving.sched.sampling); ``attn_kernel`` is
        the decode attention, the Pallas paged kernel
        (ops.paged_attention) or the XLA gather: the engine's choice
        from ``kernel_viable``, with the same signatures either way."""
        from ..serving.paged.programs import build_paged_fns
        return build_paged_fns(self.cfg, num_slots, block_size,
                               num_blocks, blocks_per_slot,
                               sampling=sampling,
                               attn_kernel=attn_kernel)

    def build_paged_spec_verify_fn(self, num_slots, block_size,
                                   num_blocks, blocks_per_slot,
                                   spec_k):
        """The speculative k-token verify program
        (serving.spec.programs, ServingConfig(speculative=True)): one
        fixed-shape ``[S, k+1]``-position dispatch verifying each
        slot's k drafted continuations against the model's own greedy
        choices — longest-accepted-prefix on device, bit-exact with
        plain decode by construction. Candidate K/V rows scatter
        straight into each slot's privately-owned blocks under PR 7's
        whole-position clamp (overflow rows trash-routed), attention
        through the gathered block-table view."""
        from ..serving.spec.programs import build_paged_spec_verify_fn
        return build_paged_spec_verify_fn(
            self.cfg, num_slots, block_size, num_blocks,
            blocks_per_slot, spec_k)

    _DECODE_CACHE_MAX = 16

    @staticmethod
    def _decode_cache_get(cache, key, build):
        """LRU get-or-jit on the per-shape decode cache: each distinct
        call signature compiles its own executable, and serving loops
        with arbitrary prompt lengths must not retain unboundedly
        many."""
        import jax
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = jax.jit(build)
            while len(cache) > GPTForCausalLM._DECODE_CACHE_MAX:
                cache.popitem(last=False)
        else:
            cache.move_to_end(key)
        return fn

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, seed=0, num_beams=1):
        """TPU-native autoregressive decoding: prefill + per-token
        steps run as ONE jitted program — a `lax.scan` over positions
        with a static-shape KV cache ([L, b, heads, total, hd], write
        index advances; no dynamic shapes anywhere, so XLA compiles a
        single decode executable). Greedy when temperature<=0 or
        top_k==1; otherwise temperature sampling over the top_k logits
        (0 = full vocab). Reference analogue: the generation utilities
        the fluid-era GPT examples build per-step in Python — here the
        whole decode is compiler-scheduled.

        Works for TP-configured models too: parameters are FULL logical
        arrays (GSPMD shards activations inside the pjit'd train step,
        not the stored weights), so decode reads them directly and runs
        as a single-device program — correct for any model whose
        weights + caches fit one chip. Sharding the decode itself over
        the mesh (for models that NEED TP at inference) would add
        in_shardings over the head axis; not done here."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..core.lazy import concrete
        from ..core.tensor import Tensor

        cfg = self.cfg
        nh = cfg.num_heads
        hd = cfg.hidden_size // nh

        params = self.export_decode_params()
        ids = jnp.asarray(
            concrete(getattr(input_ids, "value", input_ids)), jnp.int32)
        b, s0 = ids.shape
        n_new = int(max_new_tokens)
        total = s0 + n_new
        if total > cfg.max_seq_len:
            raise ValueError(f"prompt {s0} + max_new_tokens "
                             f"{max_new_tokens} exceeds max_seq_len "
                             f"{cfg.max_seq_len}")
        if n_new <= 0:
            return Tensor(ids.astype(jnp.int64))
        L = cfg.num_layers
        greedy = temperature <= 0 or top_k == 1
        kk = min(int(top_k), cfg.vocab_size)  # top_k > vocab = full vocab

        # decode math shared with the serving engine — ONE definition
        # (parity between generate() and continuous batching holds by
        # construction, not by testing alone)
        _, forward_t = _decode_forward_builder(nh, hd, cfg.hidden_size)

        def pick(logits, key, temp):
            # logits [b, vocab]
            if greedy:
                return jnp.argmax(logits, -1).astype(jnp.int32)
            lg = logits / temp
            if kk > 0:
                kth = lax.top_k(lg, kk)[0][:, -1:]
                lg = jnp.where(lg < kth, jnp.float32(-1e30), lg)
            return jax.random.categorical(key, lg).astype(jnp.int32)

        def decode(pr, ids, key, temp):
            # the cache holds K/V as the qkv projection computes them:
            # in the weights' dtype (the serving engine's rule too)
            kc = jnp.zeros((L, b, nh, total, hd),
                           pr["stacked"]["qkv_w"].dtype)
            vc = jnp.zeros_like(kc)
            logits, kc, vc = forward_t(pr, ids, jnp.int32(0), kc, vc)
            key, sub = jax.random.split(key)
            first = pick(logits[:, -1], sub, temp)
            if n_new == 1:
                return jnp.concatenate([ids, first[:, None]], axis=1)

            def step(carry, _):
                tok, pos, kc, vc, key = carry
                logits, kc, vc = forward_t(pr, tok[:, None], pos, kc, vc)
                key, sub = jax.random.split(key)
                nxt = pick(logits[:, -1], sub, temp)
                return (nxt, pos + 1, kc, vc, key), nxt

            # n_new - 1 steps: the prefill already produced token 1
            _, rest = lax.scan(step, (first, jnp.int32(s0), kc, vc, key),
                               None, length=n_new - 1)
            gen = jnp.concatenate([first[:, None], rest.T], axis=1)
            return jnp.concatenate([ids, gen], axis=1)

        K = int(num_beams)

        def beam_decode(pr, ids):
            # deterministic beam search over cumulative log-prob
            # (reference analogue: fluid beam_search op + gather_tree —
            # here the whole search is one scanned program; beams are a
            # batch*K batch dim, caches re-gathered by beam each step)
            kc = jnp.zeros((L, b, nh, total, hd),
                           pr["stacked"]["qkv_w"].dtype)
            vc = jnp.zeros_like(kc)
            logits, kc, vc = forward_t(pr, ids, jnp.int32(0), kc, vc)
            lp0 = jax.nn.log_softmax(logits[:, -1])        # [b, V]
            scores, tok = lax.top_k(lp0, K)                # [b, K]
            tok = tok.astype(jnp.int32)
            kc = jnp.repeat(kc, K, axis=1)                 # beams join batch
            vc = jnp.repeat(vc, K, axis=1)
            seqs = jnp.zeros((b, K, n_new), jnp.int32)
            z = jnp.int32(0)
            seqs = lax.dynamic_update_slice(seqs, tok[:, :, None],
                                            (z, z, z))

            def step(carry, i):
                seqs, scores, tok, pos, kc, vc = carry
                logits, kc, vc = forward_t(pr, tok.reshape(b * K, 1),
                                           pos, kc, vc)
                V = logits.shape[-1]
                lp = jax.nn.log_softmax(logits[:, -1]).reshape(b, K, V)
                cand = scores[:, :, None] + lp
                scores, flat = lax.top_k(cand.reshape(b, K * V), K)
                beam = (flat // V).astype(jnp.int32)
                tok = (flat % V).astype(jnp.int32)
                kc = kc.reshape(L, b, K, nh, total, hd)
                vc = vc.reshape(L, b, K, nh, total, hd)
                idx = beam[None, :, :, None, None, None]
                kc = jnp.take_along_axis(kc, idx, axis=2) \
                    .reshape(L, b * K, nh, total, hd)
                vc = jnp.take_along_axis(vc, idx, axis=2) \
                    .reshape(L, b * K, nh, total, hd)
                seqs = jnp.take_along_axis(seqs, beam[:, :, None],
                                           axis=1)
                seqs = lax.dynamic_update_slice(
                    seqs, tok[:, :, None], (z, z, i))
                return (seqs, scores, tok, pos + jnp.int32(1),
                        kc, vc), None

            if n_new > 1:
                (seqs, scores, _, _, _, _), _ = lax.scan(
                    step, (seqs, scores, tok, jnp.int32(s0), kc, vc),
                    jnp.arange(1, n_new, dtype=jnp.int32))
            # top_k keeps beams sorted by score: beam 0 is the best
            return jnp.concatenate([ids, seqs[:, 0]], axis=1)

        # cache the jitted decode per call signature; weights arrive as
        # ARGUMENTS (not closure constants), so repeat calls — and
        # calls after further training — reuse the same executable.
        # Every distinct (batch, prompt_len, max_new_tokens) compiles
        # its own executable; an LRU cap keeps variable-length serving
        # loops from retaining unboundedly many (callers who want zero
        # recompiles should pad prompts to a fixed length themselves,
        # since padding here would let attention see the pad tokens).
        import collections
        cache = self.__dict__.setdefault("_decode_jit",
                                         collections.OrderedDict())
        if K < 1:
            raise ValueError(f"num_beams must be >= 1, got {num_beams}")
        if K > 1:
            if K > cfg.vocab_size:
                raise ValueError(f"num_beams {K} > vocab size "
                                 f"{cfg.vocab_size}")
            if temperature not in (1.0, 0.0) or top_k or seed:
                # beam search here is pure max-log-prob search; honoring
                # sampling args would be a different algorithm — reject
                # rather than silently ignore them
                raise ValueError(
                    "num_beams > 1 is deterministic beam search; "
                    "temperature/top_k/seed do not apply (use "
                    "num_beams=1 for sampling)")
            ck = ("beam", b, s0, n_new, K)
            fn = self._decode_cache_get(cache, ck, beam_decode)
            out = fn(params, ids)
        else:
            ck = (b, s0, n_new, greedy, kk)
            fn = self._decode_cache_get(cache, ck, decode)
            out = fn(params, ids, jax.random.PRNGKey(int(seed)),
                     jnp.float32(max(temperature, 1e-6)))
        return Tensor(out.astype(jnp.int64))

    def pp_segments(self):
        """Pipeline-parallel segmentation (see PipelineParallel): edge
        segments run GSPMD on the full mesh — which makes the tied
        embedding (used in pre AND post) trivially shared — and the
        transformer blocks are the pipelined homogeneous run."""
        core = self.gpt

        def pre(input_ids):
            s = input_ids.shape[1]
            pos = creation.arange(0, s, dtype="int64")
            x = core.word_embeddings(input_ids)
            x = math_ops.add(x, core.position_embeddings(pos))
            if core.cfg.dropout:
                x = nn_ops.dropout(x, p=core.cfg.dropout,
                                   training=core.training)
            return x

        def post(h, labels=None):
            h = core.ln_f(h)
            return self._head_loss(h, labels)

        return {"pre": pre, "blocks": list(core.blocks), "post": post}


class BertModel(_TransformerCore):
    """Encoder core (BERT style: post-norm, token types)."""

    def __init__(self, cfg):
        super().__init__(cfg, causal=False, pre_norm=False,
                         with_token_type=True)
        self.pooler = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attn_mask=None):
        h = super().forward(input_ids, token_type_ids, attn_mask)
        pooled = nn_ops.tanh(self.pooler(h[:, 0]))
        return h, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads (reference pretraining objective for config 3)."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.mlm_transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.mlm_ln = nn.LayerNorm(cfg.hidden_size)
        self.nsp_head = nn.Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, masked_lm_labels=None,
                next_sentence_labels=None):
        h, pooled = self.bert(input_ids, token_type_ids)
        t = nn_ops.gelu(self.mlm_transform(h), approximate=True)
        t = self.mlm_ln(t)
        logits = math_ops.matmul(t, self.bert.word_embeddings.weight,
                                 transpose_y=True)
        if masked_lm_labels is None:
            return logits
        mlm_loss = nn_ops.cross_entropy(
            manipulation.reshape(logits, (-1, self.cfg.vocab_size)),
            manipulation.reshape(masked_lm_labels, (-1,)),
            ignore_index=-1)
        if next_sentence_labels is not None:
            nsp_logits = self.nsp_head(pooled)
            nsp_loss = nn_ops.cross_entropy(
                nsp_logits, manipulation.reshape(next_sentence_labels, (-1,)))
            return math_ops.add(mlm_loss, nsp_loss)
        return mlm_loss


def bert_base(vocab_size=30522, max_seq_len=512, **kwargs):
    cfg = TransformerLMConfig(vocab_size=vocab_size, hidden_size=768,
                              num_layers=12, num_heads=12,
                              max_seq_len=max_seq_len, **kwargs)
    return BertForPretraining(cfg)


def gpt3_1p3b(vocab_size=50304, max_seq_len=1024, **kwargs):
    """GPT-3 1.3B: 24 layers, hidden 2048, 16 heads (the hybrid-parallel
    GPT config)."""
    cfg = TransformerLMConfig(vocab_size=vocab_size, hidden_size=2048,
                              num_layers=24, num_heads=16,
                              max_seq_len=max_seq_len, **kwargs)
    return GPTForCausalLM(cfg)
