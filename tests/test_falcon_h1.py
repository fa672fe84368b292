"""The ``falcon_h1`` family on the served path, at small sizes on the
CPU: the plain reference (``benchmarks/reference/falcon_h1.py``) against
the published code (``transformers``' own ``FalconH1ForCausalLM``, its
pure-``torch`` path) on the same weights; the eager model, prefill +
decode through the paged cache AND the per-slot state, and
``generate()`` against the plain reference; chunked prefill against
one-shot prefill; a parked slot; every branch and every multiplier shown
to reach the logits; both decode kernels in interpret mode at this
family's shapes (one head a row of the packed state, a query group of
5); every refusal by name.

The small size has what the catalogued model has: 2 state-space groups,
a head of 128 channels (``q = 1``: one head fills a row of the packed
state), 10 query heads over 2 KV heads (a group of 5), a ``head_dim``
that is NOT ``hidden_size / num_attention_heads``, and every multiplier
away from 1.

Tolerances. Everything here is float32 on both sides, so what differs is
the order of additions (the chunked scan, blocked attention, a
multiplier applied after a matmul instead of before it): logits of
magnitude ~1 agree to a few 1e-6; ``TOL`` = 2e-4 leaves room for other
BLAS builds. Against ``torch`` (another library's float32 kernels, the
chunked scan in its own order) the same ``TOL`` holds.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import weights_falcon_h1 as W  # noqa: E402
from benchmarks.reference import falcon_h1 as ref  # noqa: E402
from paddle_tpu.ops import attention as attn_ops  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.ops import ssm  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.text import falcon_h1 as fh  # noqa: E402
from paddle_tpu.text import nemotron_h as nh  # noqa: E402
from paddle_tpu.text.stacked_lm import block_of  # noqa: E402

TOL = 2e-4
HF = dict(vocab_size=96, hidden_size=160, intermediate_size=192,
          num_hidden_layers=3, num_attention_heads=10,
          num_key_value_heads=2, head_dim=24, mamba_n_heads=4,
          mamba_d_head=128, mamba_d_ssm=512, mamba_d_state=16,
          mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=16,
          mamba_expand=2, mamba_conv_bias=True, mamba_proj_bias=False,
          mamba_rms_norm=True, mamba_norm_before_gate=False,
          mamba_use_mlp=True, attention_bias=False, mlp_bias=False,
          projectors_bias=False, attn_layer_indices=None,
          hidden_act="silu", rms_norm_eps=1e-5, rope_theta=1e4,
          rope_scaling=None, max_position_embeddings=64,
          tie_word_embeddings=False, model_type="falcon_h1",
          attention_in_multiplier=1.3, attention_out_multiplier=0.6,
          key_multiplier=0.4, ssm_in_multiplier=0.7,
          ssm_out_multiplier=0.45,
          ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.36],
          mlp_multipliers=[0.3, 0.05], embedding_multiplier=5.6,
          lm_head_multiplier=0.03)
# the kernels' shapes: heads of 128 lanes for the paged kernel too
KERNEL = dict(head_dim=128)
SCALARS = ("attention_in_multiplier", "attention_out_multiplier",
           "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
           "embedding_multiplier", "lm_head_multiplier")


def _model(seed=3, **over):
    hf = dict(HF, **over)
    w = W.make(seed, hf, "float32")
    cfg = fh.FalconH1Config.from_hf(hf, dtype="float32")
    return fh.FalconH1ForCausalLM(cfg, weights=w), w, hf


def _ref_logits(w, ids, hf=HF):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32), hf)[0])


@pytest.fixture(scope="module")
def model_w():
    m, w, _ = _model()
    return m, w


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 96, size=(2, 37))


# ------------------------------------ the reference and the published code
def test_reference_matches_transformers_own_model(ids):
    """``transformers``' ``FalconH1ForCausalLM`` at the small size (its
    pure-``torch`` path: no CUDA here), the same seeded weights copied
    in, every multiplier away from 1: its logits are the reference's.
    So the equations of the reference (and of ISSUE 48) are tied to the
    published code and not to anyone's recall of it."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    if not hasattr(tf, "FalconH1ForCausalLM"):
        pytest.skip("this transformers has no falcon_h1")
    w = W.make(3, HF, "float32")
    hf_cfg = tf.FalconH1Config(**{k: v for k, v in HF.items()
                                  if k != "model_type"},
                               attn_implementation="eager")
    assert hf_cfg.head_dim == 24 != HF["hidden_size"] // 10
    net = tf.FalconH1ForCausalLM(hf_cfg).eval().float()

    def t(a, transpose=True):
        a = np.asarray(a, np.float32)
        return torch.from_numpy(np.ascontiguousarray(a.T if transpose
                                                     else a))
    nq, nkv, hd = 10, 2, 24
    state = {"model.embed_tokens.weight": t(w["wemb"], False),
             "model.final_layernorm.weight": t(w["norm_f"], False),
             "lm_head.weight": t(w["head"])}
    for i in range(HF["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], w["layers"])
        qkv = np.asarray(p["wqkv"])
        pre = f"model.layers.{i}."
        state.update({
            pre + "input_layernorm.weight": t(p["norm_in"], False),
            pre + "pre_ff_layernorm.weight": t(p["norm_ff"], False),
            pre + "mamba.in_proj.weight": t(p["in_proj"]),
            # [channels, 1, taps]: tap K-1 meets the newest input
            pre + "mamba.conv1d.weight": t(p["conv_w"])[:, None, :],
            pre + "mamba.conv1d.bias": t(p["conv_b"], False),
            pre + "mamba.dt_bias": t(p["dt_bias"], False),
            pre + "mamba.A_log": t(p["A_log"], False),
            pre + "mamba.D": t(p["D"], False),
            pre + "mamba.norm.weight": t(p["gnorm"], False),
            pre + "mamba.out_proj.weight": t(p["out_proj"]),
            pre + "self_attn.q_proj.weight": t(qkv[:, :nq * hd]),
            pre + "self_attn.k_proj.weight":
                t(qkv[:, nq * hd:(nq + nkv) * hd]),
            pre + "self_attn.v_proj.weight": t(qkv[:, (nq + nkv) * hd:]),
            pre + "self_attn.o_proj.weight": t(p["wo"]),
            pre + "feed_forward.gate_proj.weight": t(p["wg"]),
            pre + "feed_forward.up_proj.weight": t(p["wu"]),
            pre + "feed_forward.down_proj.weight": t(p["wd"]),
        })
    missing, unexpected = net.load_state_dict(state, strict=False)
    assert not unexpected and not [k for k in missing
                                   if "mup_vector" not in k], missing
    with torch.no_grad():
        got = net(torch.from_numpy(np.asarray(ids, np.int64))).logits \
            .numpy()
    for b in range(ids.shape[0]):
        want = _ref_logits(w, ids[b])
        assert np.abs(want).max() > 0.3
        assert np.abs(got[b] - want).max() < TOL


# ------------------------------------------------------ the whole model
def test_eager_logits_match_reference(model_w, ids):
    m, w = model_w
    got = np.asarray(m.forward(ids).value)
    for b in range(ids.shape[0]):
        assert np.abs(got[b] - _ref_logits(w, ids[b])).max() < TOL


def test_generate_greedy_matches_reference(model_w, ids):
    """Prefill + decode through ``generate()``'s contiguous cache and
    slot state: every generated token is the reference's best."""
    m, w = model_w
    out = np.asarray(m.generate(ids, max_new_tokens=7).value)
    for b in range(ids.shape[0]):
        lg = _ref_logits(w, out[b, :-1])
        gen = out[b, ids.shape[1]:]
        at = lg[np.arange(ids.shape[1] - 1, out.shape[1] - 1), gen]
        assert (lg[ids.shape[1] - 1:].max(-1) - at).max() < TOL


def test_the_block_is_taken_from_the_configuration(model_w):
    """One access object and one pair of bodies for two families: the
    block each runs is the module of its configuration's class, and
    both model classes share their serving and eager paths."""
    from tests.test_nemotron_h import _model as nemotron
    assert block_of(model_w[0].cfg) is fh
    assert block_of(nemotron()[0].cfg) is nh
    assert fh.FalconH1ForCausalLM.build_paged_serving_fns \
        is nh.NemotronHForCausalLM.build_paged_serving_fns
    # every layer is an attention layer AND a state-space layer
    cfg = model_w[0].cfg
    assert (cfg.count("*"), cfg.count("M"), cfg.count("E")) == (3, 3, 0)
    assert not hasattr(model_w[0], "moe_counter_layout")


ZEROED = {"state_space": "out_proj", "attention": "wo",
          "feed_forward": "wd"}


@pytest.mark.parametrize("branch", list(ZEROED))
def test_every_branch_reaches_the_logits(model_w, ids, branch):
    """With one branch's last matrix zeroed the program still agrees
    with the reference (on the same weights) and both leave the sound
    logits by far more than the comparison's tolerance."""
    _, w = model_w
    layers = dict(w["layers"],
                  **{ZEROED[branch]: jnp.zeros_like(
                      w["layers"][ZEROED[branch]])})
    cut = dict(w, layers=layers)
    m = fh.FalconH1ForCausalLM(
        fh.FalconH1Config.from_hf(HF, dtype="float32"), weights=cut)
    got = np.asarray(m.forward(ids[:1]).value)[0]
    assert np.abs(got - _ref_logits(cut, ids[0])).max() < TOL
    assert np.abs(got - _ref_logits(w, ids[0])).max() > 25 * TOL


MULTIPLIERS = [(k, None) for k in SCALARS] \
    + [("ssm_multipliers", i) for i in range(5)] \
    + [("mlp_multipliers", i) for i in range(2)]


@pytest.mark.parametrize("key,index", MULTIPLIERS,
                         ids=[k if i is None else f"{k}_{i}"
                              for k, i in MULTIPLIERS])
def test_every_multiplier_reaches_the_logits(model_w, ids, key, index):
    """No key is read and dropped: one multiplier tripled moves the
    program's logits and the reference's alike, and away from the sound
    ones by more than the comparison's tolerance."""
    _, w = model_w
    value = HF[key]
    if index is None:
        value = 3.0 * value
    else:
        value = [3.0 * v if i == index else v for i, v in enumerate(value)]
    hf = dict(HF, **{key: value})
    m = fh.FalconH1ForCausalLM(
        fh.FalconH1Config.from_hf(hf, dtype="float32"), weights=w)
    got = np.asarray(m.forward(ids[:1]).value)[0]
    assert np.abs(got - _ref_logits(w, ids[0], hf)).max() < TOL
    assert np.abs(got - _ref_logits(w, ids[0])).max() > 5 * TOL


# ----------------------------------------------------- through the engine
def _drive(engine, prompts, new):
    reqs = [engine.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    engine.run()
    return reqs


def _served_gap(w, prompt, req, hf=HF):
    served = np.asarray(req.generated)
    seq = np.concatenate([prompt, served])
    lg = _ref_logits(w, seq[:-1], hf)
    at = lg[np.arange(len(prompt) - 1, len(seq) - 1), served]
    return (lg[len(prompt) - 1:].max(-1) - at).max()


@pytest.mark.parametrize("chunk", [None, 16, 8],
                         ids=["whole", "chunk16", "chunk8"])
def test_paged_prefill_and_decode_match_reference(model_w, chunk):
    """Through ``ServingEngine`` over paged keys and values AND per-slot
    state in every layer: three slots, five requests of uneven lengths,
    so slots are released and taken again (a slot reused by a second
    request starts from ZERO state) and released slots keep stepping
    meanwhile; with ``prefill_chunk`` the long prompts prefill chunk by
    chunk, their state carried from chunk to chunk, and their slots are
    PARKED through the decode steps in between. Every served token's
    reference logit is the reference's best at its position (the gap
    that ``correct`` reads on the chip), and the tokens are
    ``generate()``'s."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=3, block_size=8, max_len=64,
                        buckets=[16, 32], prefill_chunk=chunk)
    rng = np.random.default_rng(1)
    lens, new = (5, 17, 9, 30, 12), (6, 9, 4, 12, 7)
    prompts = [rng.integers(0, 96, size=n) for n in lens]
    reqs = _drive(eng, prompts, new)
    assert eng.pool.reuse_count >= 2          # released slots came back
    for p, r, k in zip(prompts, reqs, new):
        assert len(r.generated) == k
        assert _served_gap(w, p, r) < TOL
        want = np.asarray(m.generate(p[None], max_new_tokens=k).value)[0]
        assert (np.asarray(r.output_ids) == want).all()
    text = eng.metrics.prometheus_text()
    per_token = 3 * 2 * 2 * 24 * 4     # layers x (k, v) x 2 heads x 24 f32
    per_slot = 3 * (3 * 576 + 4 * 128 * 16) * 4   # layers x (conv + ssm)
    assert f"serving_kv_bytes_per_token {per_token}" in text
    assert f"serving_state_bytes_per_slot {per_slot}" in text
    assert "serving_moe_expert_tokens_total" not in text


def test_deep_pipeline_over_slot_state(model_w):
    """``async_depth`` 12 as the benchmark cell keeps it: a slot is
    released, zeroed and prefilled again while older steps that still
    name it are queued on the device."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                        buckets=[16, 32], async_depth=12)
    rng = np.random.default_rng(5)
    lens, new = (5, 17, 9, 30, 12), (16, 9, 14, 12, 7)
    prompts = [rng.integers(0, 96, size=n) for n in lens]
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    deepest = 0
    while eng.step():
        deepest = max(deepest, len(eng._pending_steps))
    assert deepest == 12 and not eng._pending
    assert eng.pool.reuse_count >= 3
    for p, r in zip(prompts, reqs):
        assert _served_gap(w, p, r) < TOL


def test_a_common_prefix_is_not_shared(model_w):
    """A slot carries state beside its blocks, so a cached prefix
    without the state at its boundary would be a wrong answer: no hit,
    correct logits."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                        buckets=[32])
    rng = np.random.default_rng(2)
    common = rng.integers(0, 96, size=24)
    prompts = [np.concatenate([common, rng.integers(0, 96, size=n)])
               for n in (3, 5)]
    (a,) = _drive(eng, prompts[:1], [4])
    assert eng.pool.match_prefix(prompts[1]) == 0
    (b,) = _drive(eng, prompts[1:], [4])
    for p, r in zip(prompts, (a, b)):
        assert _served_gap(w, p, r) < TOL
    assert eng.metrics.snapshot()["prefix_cache"]["hits"] == 0


def test_sampling_program_runs_and_repeats(model_w):
    m, _ = model_w

    def once():
        eng = ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                            buckets=[16], sampling=True)
        r = eng.add_request(np.arange(7), max_new_tokens=6,
                            temperature=0.8, top_k=20, seed=5)
        eng.run()
        return list(r.generated)
    a = once()
    assert len(a) == 6 and a == once()


# ------------------------------------------ the two programs, called bare
def _programs(m, slots=2):
    from paddle_tpu.serving.paged import PagedKVPool
    from paddle_tpu.serving.paged.hybrid_programs import \
        build_paged_hybrid_fns
    pool = PagedKVPool(slots, max_len=32, block_size=8,
                       spec=m.cache_spec())
    prefill, decode = build_paged_hybrid_fns(
        m.cfg, slots, 8, pool.num_blocks, pool.blocks_per_slot)
    for name in "ab"[:slots]:
        alloc = pool.acquire(name, np.arange(11), 32, 0)
    return pool, jax.jit(prefill), jax.jit(decode), alloc.slot


def _prefill(m, pool, prefill, slot, arrays, tokens, start, final):
    row = np.zeros((1, 16), np.int32)
    row[0, :len(tokens)] = tokens
    i32 = np.int32
    out = prefill(m.export_decode_params(), row, i32(len(tokens)),
                  i32(start), i32(slot), i32(final), pool.table_row(slot),
                  jnp.zeros((pool.num_slots,), jnp.int32),
                  jnp.zeros((pool.num_slots,), jnp.int32), *arrays)
    return int(out[0][0]), int(out[2][slot]), list(out[3:])


def test_chunked_prefill_carries_the_state(model_w):
    """A prompt of 11 prefilled as chunks of 8 and 3 (the slot parked in
    between) leaves the first token, the keys and values, the window and
    the state that one prefill of 11 leaves, to the order of the scan's
    additions (a later layer's inputs carry the earlier layers')."""
    m, _ = model_w
    pool, prefill, _, slot = _programs(m)
    tokens = np.arange(11) * 7 % 96
    stale = [jnp.full(a.shape, 3.0, a.dtype) for a in pool.arrays]
    first, pos, whole = _prefill(m, pool, prefill, slot, stale, tokens,
                                 0, 1)
    assert pos == 11
    _, parked, mid = _prefill(m, pool, prefill, slot, stale, tokens[:8],
                              0, 0)
    assert parked == 31                       # capacity - 1: nobody's
    again, pos, parts = _prefill(m, pool, prefill, slot, mid, tokens[8:],
                                 8, 1)
    assert (again, pos) == (first, 11)
    rows = np.asarray(pool.table_row(slot))[:2]
    for a, b in zip(whole[:2], parts[:2]):        # k, v: 11 positions
        a, b = (np.asarray(x)[:, rows].transpose(0, 2, 1, 3, 4).reshape(
            3, 2, 16, 24)[:, :, :11] for x in (a, b))
        assert np.abs(a - b).max() < 1e-6
    assert np.abs(np.asarray(whole[2][:, slot])
                  - np.asarray(parts[2][:, slot])).max() < 1e-5
    s_whole, s_parts = (np.asarray(x[3][:, slot]) for x in (whole, parts))
    assert np.abs(s_whole).max() > 1e-3
    assert np.abs(s_whole - s_parts).max() < 1e-5 * np.abs(s_whole).max()
    # the other slot's state: untouched by either
    for out in (whole, parts):
        assert (np.asarray(out[2][:, 1 - slot]) == 3.0).all()
        assert (np.asarray(out[3][:, 1 - slot]) == 3.0).all()


def test_a_parked_slot_keeps_window_and_state_bit_for_bit(model_w):
    """A decode step with one slot live and one parked between the
    chunks of its prefill (``pos == capacity - 1``): the parked slot's
    window and state are EXACTLY what they were in every layer, the live
    slot's moved."""
    m, _ = model_w
    pool, prefill, decode, slot = _programs(m)
    rng = np.random.default_rng(3)
    arrays = [jnp.asarray(rng.normal(size=a.shape), a.dtype)
              for a in pool.arrays]
    live = 1 - slot
    pos = np.zeros((2,), np.int32)
    pos[slot], pos[live] = 31, 11
    out = decode(m.export_decode_params(), np.array([5, 9], np.int32),
                 pos, pool.block_tables, *arrays)
    conv, state = out[4], out[5]
    for new, old in ((conv, arrays[2]), (state, arrays[3])):
        np.testing.assert_array_equal(np.asarray(new[:, slot]),
                                      np.asarray(old[:, slot]))
        assert np.abs(np.asarray(new[:, live])
                      - np.asarray(old[:, live])).max() > 1e-3
    assert (np.asarray(out[1]) == pos + 1).all()


# ------------------------------------------------- kernels (interpret)
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(ssm, "_FORCE_INTERPRET", [True])
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", [True])


def test_the_real_state_is_exactly_the_kernels_limit():
    """32 heads x 128 x 256 in float32 is 4 MiB a slot a layer: the
    largest state the kernel takes (``<=``), one head a row of 128
    lanes, 16 rows a group; a 33rd head would not fit."""
    assert ssm.heads_per_row(32, 128, 2) == 1
    assert ssm.packed_shape(32, 128, 256, 2) == (32, 256, 128)
    assert 32 * 256 * 128 * 4 == ssm._SLOT_STATE_BYTES
    assert ssm.kernel_viable(32, 128, 256, 2) is True
    assert ssm.kernel_viable(34, 128, 256, 2) is False
    # nemotron_h's: two heads of 64 share a row
    assert ssm.packed_shape(64, 64, 128, 8) == (32, 128, 128)


def test_ssm_decode_kernel_one_head_a_row_two_groups(interpret):
    """``ssm_decode_step`` (interpret mode) where a head fills a row
    (``q = 1``) and the rows are two groups, against its ``jnp``
    formulation: layer 1 of 2, a slot passed by (``dt = 0``) keeps its
    state bit for bit, the other layer's rows are not touched."""
    S, H, P, G, N = 4, 4, 128, 2, 16
    rng = np.random.default_rng(5)
    f = jnp.float32
    xs = jnp.asarray(rng.normal(size=(S, H, P)), f)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        size=(S, H))), f)
    dt = dt.at[2].set(0.0)
    A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), f)
    B = jnp.asarray(rng.normal(size=(S, G, N)), f)
    C = jnp.asarray(rng.normal(size=(S, G, N)), f)
    assert ssm.heads_per_row(H, P, G) == 1
    state = jnp.asarray(rng.normal(
        size=(2 * S,) + ssm.packed_shape(H, P, N, G)), f)
    a, ya = ssm.ssm_state_step_jnp(state, 1, xs, dt, A, B, C, S)
    b, yb = ssm.ssm_state_step(state, 1, xs, dt, A, B, C, S)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5
    assert np.abs(np.asarray(ya) - np.asarray(yb)).max() < 1e-5
    assert np.abs(np.asarray(yb)).max() > 0.1
    np.testing.assert_array_equal(np.asarray(b[S + 2]),
                                  np.asarray(state[S + 2]))
    np.testing.assert_array_equal(np.asarray(b[:S]), np.asarray(state[:S]))
    # group 1's rows read group 1's B and C: with group 0's they differ
    c, _ = ssm.ssm_state_step(state, 1, xs, dt, A, B[:, ::-1], C, S)
    assert np.abs(np.asarray(c) - np.asarray(b)).max() > 1e-3


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_paged_kernel_at_a_query_group_of_five(interpret, dtype, tol):
    """The paged decode kernel (interpret mode) with 5 query heads to a
    KV head (the group rides in a tile of 8 rows, 3 of them padding)
    against its ``jnp`` twin: read only, and placing the step's new
    entry (a live slot, a parked one, a released one)."""
    rng = np.random.default_rng(0)
    S, nkv, g, hd, BS, MB, NB = 3, 2, 5, 128, 16, 4, 20
    q = jnp.asarray(rng.normal(size=(S, nkv * g, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(NB, nkv, BS, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(NB, nkv, BS, hd)), dtype)
    tables = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB),
                         jnp.int32)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)   # part, mid, full
    want = attn_ops.cached_paged_attention(q, k, v, tables, lengths)
    got = pa.paged_decode_attention(q, k, v, tables, lengths)
    assert got.shape == want.shape == (S, 10, hd)
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol
    new = tuple(jnp.asarray(rng.normal(size=(S, nkv, hd)), dtype)
                for _ in range(2))
    lengths = jnp.asarray([5, 33, 0], jnp.int32)
    wpos = pa.live_write_pos(jnp.asarray([4, 63, 70], jnp.int32), lengths)
    assert list(np.asarray(wpos)) == [4, -1, -1]
    got, got_pools = pa.paged_write_attention(q, new, (k, v), tables, wpos,
                                              lengths, True)
    want, want_pools = pa.paged_write_attention(q, new, (k, v), tables,
                                                wpos, lengths, False)
    live = np.asarray(lengths) > 0
    assert np.abs(np.asarray(got, np.float32)[live]
                  - np.asarray(want, np.float32)[live]).max() < tol
    blk = int(tables[0, 0])
    for have, oracle, entry in zip(got_pools, want_pools, new):
        np.testing.assert_array_equal(
            np.asarray(have[blk, :, 4], np.float32),
            np.asarray(entry[0], np.float32))
        np.testing.assert_array_equal(
            np.asarray(have[blk, :, :5], np.float32),
            np.asarray(oracle[blk, :, :5], np.float32))


def test_engine_with_both_kernels_in_interpret_mode(interpret):
    """The decode program with BOTH kernels in it (interpret mode) at
    this family's shapes (one head a row, a group of 5, heads of 128)
    serves the tokens of the ``jnp`` formulations: eight slots, a prompt
    prefilled in chunks (its slot parked in between), slots released and
    taken again."""
    m, w, hf = _model(seed=1, **KERNEL)
    rng = np.random.default_rng(43)
    lens = (5, 30, 9, 17, 12, 3, 7, 14, 6, 11)
    new = (9, 7, 10, 5, 6, 11, 3, 5, 8, 4)
    prompts = [rng.integers(0, 96, size=n) for n in lens]
    eng = ServingEngine(m, num_slots=8, block_size=8, max_len=64,
                        buckets=[16], prefill_chunk=16)
    reqs = _drive(eng, prompts, new)
    assert eng.pool.reuse_count >= 2
    for p, r, k in zip(prompts, reqs, new):
        assert _served_gap(w, p, r, hf) < TOL
        want = np.asarray(m.generate(p[None], max_new_tokens=k).value)[0]
        assert (np.asarray(r.output_ids) == want).all()


def test_the_builder_resolves_two_kernels(monkeypatch):
    """No expert kernel is asked for; where there is Mosaic, a shape a
    kernel cannot take is refused in the shell's words."""
    from paddle_tpu.serving.paged import hybrid_programs
    cfg = fh.FalconH1Config.from_hf(HF)
    assert hybrid_programs.decode_kernels(cfg, 3, 8) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"^paged_decode_attn cannot "
                       r"take \(.+\) = \(2, 24, 8, float32\): "
                       r"ops\.paged_attention\.kernel_viable$"):
        hybrid_programs.decode_kernels(cfg, 3, 8)


# ------------------------------------------------------ the cache's spec
def test_cache_spec_has_both_kinds_in_every_layer(model_w):
    from paddle_tpu.serving.paged import PagedKVPool
    spec = model_w[0].cache_spec()
    assert [a.name for a in spec.arrays] == ["k", "v", "conv", "ssm"]
    assert [a.per for a in spec.arrays] == ["token"] * 2 + ["slot"] * 2
    assert [a.layers for a in spec.arrays] == [3] * 4
    assert spec.state == () and not spec.shareable
    pool = PagedKVPool(3, max_len=64, block_size=8, spec=spec)
    assert [a.shape for a in pool.arrays] == [
        (3, 25, 2, 8, 24), (3, 25, 2, 8, 24), (3, 3, 1728),
        (3, 3, 4, 16, 128)]
    assert [str(a.dtype) for a in pool.arrays] == ["float32"] * 4
    bf16 = fh.hybrid_cache_spec(fh.FalconH1Config.from_hf(
        HF, dtype="bfloat16"))
    assert [a.dtype.name for a in bf16.arrays] == [
        "bfloat16", "bfloat16", "bfloat16", "float32"]


# ----------------------------------------------------- refusals, by name
@pytest.mark.parametrize("option", [
    {"speculative": True}, {"role": "prefill"}],
    ids=["speculative", "role"])
def test_engine_refuses_an_option_without_a_program(model_w, option):
    with pytest.raises(ValueError, match="no program for"):
        ServingEngine(model_w[0], num_slots=2, **option)


@pytest.mark.parametrize("key,value,name", [
    ("attention_bias", True, "attention_bias"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("projectors_bias", True, "projectors_bias"),
    ("mamba_conv_bias", False, "mamba_conv_bias"),
    ("mamba_rms_norm", False, "mamba_rms_norm"),
    ("mamba_norm_before_gate", True, "mamba_norm_before_gate"),
    ("mamba_use_mlp", False, "mamba_use_mlp"),
    ("attn_layer_indices", [0, 2], "attn_layer_indices"),
    ("rope_scaling", {"rope_type": "linear"}, "rope_scaling"),
    ("hidden_act", "gelu", "hidden_act"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("model_type", "falcon", "model_type")])
def test_config_refuses_what_it_has_no_equations_for(key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        fh.FalconH1Config.from_hf(dict(HF, **{key: value}))
    if key != "model_type":
        w = W.make(1, dict(HF, num_hidden_layers=1), "float32")
        with pytest.raises(NotImplementedError, match=name):
            ref.logits(w, jnp.zeros((4,), jnp.int32),
                       dict(HF, num_hidden_layers=1, **{key: value}))


def test_config_refuses_a_key_it_does_not_know_and_sizes_that_disagree():
    with pytest.raises(TypeError, match="sliding_window"):
        fh.FalconH1Config.from_hf(dict(HF, sliding_window=128))
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        fh.FalconH1Config.from_hf(dict(HF, mamba_d_ssm=500))
    with pytest.raises(ValueError, match="5 entries"):
        fh.FalconH1Config.from_hf(dict(HF, ssm_multipliers=[1.0, 1.0]))
    # mamba_d_ssm left out: mamba_expand x hidden_size
    cfg = fh.FalconH1Config.from_hf(dict(
        HF, mamba_d_ssm=None, mamba_n_heads=10, mamba_d_head=32))
    assert cfg.d_inner == 320 == 2 * HF["hidden_size"]
