"""The ``mimo_v2_flash`` family on the served path, at small sizes on the
CPU: the eager model and prefill + decode through the paged cache AND
the window layers' rings against the plain reference
(``benchmarks/reference/mimo_v2.py``) on seeded weights; the ring's
bookkeeping by position; the paged decode kernel with a key in two parts
in interpret mode against its ``jnp`` twin, and its jaxpr at the GPT's
and ``nemotron_h``'s shapes shown unchanged; the expert shares against
the uncut layer; every refusal by name; what the cache spec counts.

Tolerances. Everything here is float32 on both sides, so what differs is
the order of additions (blocked attention, the band, the experts' sorted
runs): logits of magnitude ~1 agree to a few 1e-6; ``TOL`` = 2e-4 leaves
room for other BLAS builds.
"""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import weights_mimo_v2 as W  # noqa: E402
from benchmarks.reference import mimo_v2 as ref  # noqa: E402
from paddle_tpu.ops import attention as attn_ops  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.text import mimo_v2 as mm  # noqa: E402

TOL = 2e-4
# both kinds of attention (2 and 4 KV heads for 8 query heads), keys of
# 24 (8 rotated) beside values of 16, a window of 8 with a sink, a dense
# layer and five expert layers holding experts 2-5 of 8
HF = dict(attention_value_scale=0.707, hidden_act="silu", hidden_size=64,
          intermediate_size=96, max_position_embeddings=512,
          num_attention_heads=8, head_dim=24, num_hidden_layers=6,
          num_key_value_heads=2, layernorm_epsilon=1e-5,
          rope_theta=5000000, vocab_size=128, partial_rotary_factor=0.334,
          sliding_window=8, swa_rope_theta=10000, attention_bias=False,
          v_head_dim=16, hybrid_layer_pattern=[0, 1, 1, 1, 1, 0],
          add_swa_attention_sink_bias=True,
          add_full_attention_sink_bias=False, sliding_window_size=8,
          attention_chunk_size=8, moe_layer_freq=[0, 1, 1, 1, 1, 1],
          moe_intermediate_size=32, n_routed_experts=4,
          n_shared_experts=None, num_experts_per_tok=2,
          norm_topk_prob=True, scoring_func="sigmoid", n_group=1,
          topk_group=1, topk_method="noaux_tc", routed_scaling_factor=None,
          swa_num_attention_heads=8, swa_num_key_value_heads=4,
          swa_head_dim=24, swa_v_head_dim=16, router_experts=8,
          first_held_expert=2)


def _model(seed=3, **over):
    hf = dict(HF, **over)
    w = W.make(seed, hf, "float32")
    cfg = mm.MimoV2Config.from_hf(hf, dtype="float32")
    return mm.MimoV2ForCausalLM(cfg, weights=w), w, hf


def _ref_logits(w, ids, hf=HF):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32), hf)[0])


@pytest.fixture(scope="module")
def model_w():
    m, w, _ = _model()
    return m, w


# ------------------------------------------------------ the whole model
def test_the_two_published_lists_become_one_plan():
    cfg = mm.MimoV2Config.from_hf(HF)
    assert cfg.pattern == "addddb"
    assert cfg.plan == [("a", 1), ("d", 4), ("b", 1)]
    assert [cfg.count(k) for k in ("full", "win", "dense", "moe")] \
        == [2, 4, 1, 5]
    assert (cfg.rot_dim, cfg.nope_dim) == (8, 16)
    assert cfg.kv_heads == {"full": 2, "win": 4}
    # the published 48 layers: 5 window layers to every full one
    full = mm.MimoV2Config.from_hf(dict(
        HF, num_hidden_layers=48,
        hybrid_layer_pattern=[0] + ([1] * 4 + [0]) + ([1] * 5 + [0]) * 7,
        moe_layer_freq=[0] + [1] * 47))
    assert full.pattern == "a" + "ddddb" + "dddddb" * 7
    assert sum(r * len(u) for u, r in full.plan) == 48


def test_eager_logits_match_reference(model_w):
    m, w = model_w
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 40))
    got = np.asarray(m(ids).value)
    for b in range(2):
        want = _ref_logits(w, ids[b])
        assert np.abs(got[b] - want).max() < TOL
    # the sink, the value scale and the window's lower edge each move
    # the reference by far more than that: the comparison would notice
    for over in (dict(add_swa_attention_sink_bias=False),
                 dict(attention_value_scale=1.0),
                 dict(sliding_window=16, sliding_window_size=16)):
        moved = _ref_logits(w, ids[0], dict(HF, **over))
        assert np.abs(moved - _ref_logits(w, ids[0])).max() > 50 * TOL


def test_ring_positions_are_the_latest_of_each_residue():
    got = np.asarray(mm.ring_positions(jnp.asarray([-1, 0, 5, 8, 21]), 8))
    assert (got[0] < 0).all()                   # nothing reached yet
    assert list(got[1]) == [0, -7, -6, -5, -4, -3, -2, -1]
    assert list(got[2]) == [0, 1, 2, 3, 4, 5, -2, -1]
    assert list(got[3]) == [8, 1, 2, 3, 4, 5, 6, 7]
    assert list(got[4]) == [16, 17, 18, 19, 20, 21, 14, 15]
    for row, last in zip(got[1:], (0, 5, 8, 21)):
        seen = row[row >= 0]
        assert sorted(seen) == list(range(max(0, last - 7), last + 1))


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


@pytest.mark.parametrize("chunk", [None, 16],
                         ids=["whole", "chunk16"])
def test_paged_prefill_and_decode_match_reference(model_w, chunk):
    """Through ``ServingEngine`` over paged keys and values (full
    layers) AND the rings (window layers): three slots, six requests of
    uneven lengths, so slots are released and taken again (a slot reused
    by a SHORTER sequence sees nothing of its last owner's ring) and
    released slots keep stepping meanwhile; sequences cross the window
    of 8 several times in prefill and in decode; with ``prefill_chunk``
    the long prompts prefill chunk by chunk (45 ends mid-chunk), their
    rings carried from chunk to chunk, and their slots are PARKED (ring
    untouched) through the decode steps in between. Every served token
    is the reference's best at its position, by the logit gap that
    ``correct`` reads on the chip."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=3, block_size=8, max_len=96,
                        buckets=[16, 32] if chunk is None else [16],
                        prefill_chunk=chunk)
    rng = np.random.default_rng(1)
    lens = (30, 17, 9, 5, 12, 31 if chunk is None else 45)
    new = (26, 29, 24, 32, 27, 20)
    prompts = [rng.integers(0, 128, size=n) for n in lens]
    reqs = _drive(eng, prompts, new)
    assert eng.pool.reuse_count >= 2          # released slots came back
    for p, r, k in zip(prompts, reqs, new):
        assert len(r.generated) == k
        assert _served_gap(w, p, r) < TOL
    snap = eng.metrics.snapshot()
    steps = snap["moe"]["layer_steps"]
    assert len(steps) == 5 and min(steps) == snap["decode_steps"] > 0
    text = eng.metrics.prometheus_text()
    per_token = 2 * 2 * (24 + 16) * 4        # full layers x kv x (k+v) f32
    per_slot = 4 * 4 * 8 * (24 + 16) * 4     # window layers x kv x W x .
    assert f"serving_kv_bytes_per_token {per_token}" in text
    assert f"serving_state_bytes_per_slot {per_slot}" in text
    rings = snap["cache_rings"]
    assert rings["cache_live_bytes"] > 0
    # every layer keeping every position: 6 layers' worth a position
    dense = per_token + per_slot // 8
    assert rings["cache_full_equiv_bytes"] % dense == 0
    positions = rings["cache_full_equiv_bytes"] // dense
    assert (rings["cache_live_bytes"] - positions * per_token) \
        % per_slot == 0


def test_deep_pipeline_over_the_rings(model_w):
    """``async_depth`` steps of results unread (the benchmark cell keeps
    12 in flight): a slot is released and prefilled again while older
    steps that still name it are queued on the device."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=96,
                        buckets=[16, 32], async_depth=12)
    rng = np.random.default_rng(5)
    lens, new = (5, 17, 9, 30, 12), (16, 19, 14, 22, 17)
    prompts = [rng.integers(0, 128, size=n) for n in lens]
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    deepest = 0
    while eng.step():
        deepest = max(deepest, len(eng._pending_steps))
    assert deepest == 12 and not eng._pending
    assert eng.pool.reuse_count >= 3
    for p, r in zip(prompts, reqs):
        assert _served_gap(w, p, r) < TOL


def _prefill_into(m, fill, n=5, start=0):
    """One prefill of ``n`` rows (a bucket of 16) into slot 1 of a pool
    whose arrays all hold ``fill``: (first token, the slot's rings)."""
    from paddle_tpu.serving.paged import PagedKVPool
    from paddle_tpu.serving.paged.mixed_programs import \
        build_paged_mixed_fns
    pool = PagedKVPool(2, max_len=32, block_size=8, spec=m.cache_spec())
    prefill, _ = build_paged_mixed_fns(m.cfg, 2, 8, pool.num_blocks,
                                       pool.blocks_per_slot)
    pool.acquire("a", np.arange(11), 32, 0)
    alloc = pool.acquire("b", np.arange(11), 32, 0)
    arrays = [jnp.full(a.shape, fill, a.dtype) for a in pool.arrays]
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :n] = np.arange(n) * 7 % 128
    i32 = np.int32
    first, _, pos, _, _, _, kring, vring = prefill(
        m.export_decode_params(), tokens, i32(n), i32(start),
        i32(alloc.slot), i32(1), pool.table_row(alloc.slot),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), *arrays)
    assert alloc.slot == 1 and int(pos[1]) == start + n
    assert (np.asarray(kring[:, 0]) == fill).all()   # slot 0: untouched
    return int(first[0]), np.asarray(kring[:, 1]), np.asarray(vring[:, 1])


def test_a_ring_entry_of_another_sequence_is_never_seen(model_w,
                                                        monkeypatch):
    """A prefill that STARTS a sequence shorter than the window gives
    the same first token and the same ring whatever the slot's last
    owner left behind; with every entry counted as the sequence's own
    (even a cleared one) the difference shows, so this test would
    notice."""
    m, _ = model_w
    clean = _prefill_into(m, 0.0)
    stale = _prefill_into(m, 3.0)
    assert clean[0] == stale[0]
    assert (clean[1] == stale[1]).all() and (clean[2] == stale[2]).all()
    assert np.abs(clean[1][..., :5]).max() > 0      # entries 0-4 written
    assert (clean[1][..., 5:] == 0).all()           # the rest cleared
    carried = _prefill_into(m, 3.0, start=16)       # a later chunk reads
    assert (carried[1][..., 5:] == 3.0).all()
    real = mm.ring_positions
    monkeypatch.setattr(mm, "ring_positions",
                        lambda last, W: jnp.abs(real(last, W)))
    broken = _prefill_into(m, 3.0)
    assert np.abs(broken[2] - clean[2])[..., :5, :].max() > 1e-3


def test_a_common_prefix_is_not_shared(model_w):
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                        buckets=[32])
    assert not eng.cache_spec.shareable
    rng = np.random.default_rng(2)
    common = rng.integers(0, 128, size=24)
    prompts = [np.concatenate([common, rng.integers(0, 128, size=n)])
               for n in (3, 5)]
    (a,) = _drive(eng, prompts[:1], [4])
    assert eng.pool.match_prefix(prompts[1]) == 0
    (b,) = _drive(eng, prompts[1:], [4])
    for p, r in zip(prompts, (a, b)):
        assert _served_gap(w, p, r) < TOL


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


@pytest.mark.parametrize("option", [
    dict(speculative=True), dict(role="prefill"), dict(role="decode")],
    ids=["speculative", "prefill_role", "decode_role"])
def test_engine_refuses_an_option_without_a_program(model_w, option):
    with pytest.raises((ValueError, NotImplementedError)):
        ServingEngine(model_w[0], num_slots=2, block_size=8, max_len=32,
                      buckets=[16], **option)


@pytest.mark.parametrize("key,value,name", [
    ("n_group", 2, "n_group"), ("n_shared_experts", 1, "n_shared_experts"),
    ("attention_bias", True, "attention_bias"),
    ("add_full_attention_sink_bias", True, "add_full_attention_sink_bias"),
    ("hidden_act", "gelu", "hidden_act"),
    ("scoring_func", "softmax", "scoring_func"),
    ("topk_method", "greedy", "topk_method"),
    ("swa_head_dim", 32, "swa_head_dim"),
    ("swa_v_head_dim", 8, "swa_v_head_dim"),
    ("swa_num_attention_heads", 4, "swa_num_attention_heads"),
    ("sliding_window_size", 4, "sliding_window_size")])
def test_config_refuses_what_it_has_no_equations_for(key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        mm.MimoV2Config.from_hf(dict(HF, **{key: value}))
    with pytest.raises(NotImplementedError, match=name):
        ref.logits(W.make(1, HF, "float32"), jnp.zeros((8,), jnp.int32),
                   dict(HF, **{key: value}))


def test_config_refuses_lists_that_disagree_and_a_share_outside():
    with pytest.raises(ValueError, match="disagree"):
        mm.MimoV2Config.from_hf(dict(HF, moe_layer_freq=[0, 1, 1]))
    with pytest.raises(ValueError, match="not a share"):
        mm.MimoV2Config.from_hf(dict(HF, first_held_expert=6))


# -------------------------------------------------------- expert shares
@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("shares", [1, 4, 16],
                         ids=["whole", "quarters", "sixteenths"])
def test_expert_shares_add_up_to_the_uncut_layer(shares, mode):
    """The parts that the 16 shares of a layer's experts give (one
    expert of 16 each, as the configuration's 16 expert-parallel chips
    hold 16 of 256) add up to the uncut reference's layer; there is no
    shared expert to count once."""
    E = 16
    whole = dict(HF, n_routed_experts=E, router_experts=E,
                 first_held_expert=0, num_experts_per_tok=4)
    m, w, _ = _model(**whole)
    cfg = m.cfg
    ei, n_moe = 1, cfg.count("moe")
    p = jax.tree.map(lambda a: a[ei], w["moe"])
    x = jax.random.normal(jax.random.PRNGKey(7), (16, 64), jnp.float32)
    xn = mm.rms_norm(x, p["norm"], cfg.rms_norm_eps)
    count = E // shares
    total, routed = 0.0, 0
    for i in range(shares):
        held = (i * count, count)
        rows = np.concatenate([np.arange(l * E + held[0],
                                         l * E + held[0] + count)
                               for l in range(n_moe)])
        mine = {k: v[rows] for k, v in w["experts"].items()}
        y, tokens = mm.expert_layer(cfg, p, mine, xn, ei, mode, held=held)
        assert tokens.shape == (count,)
        total, routed = total + y, routed + int(tokens.sum())
    assert routed == 16 * 4                  # every pair on some share

    def mat(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    want, _ = ref.expert_layer(xn, p, w["experts"], ei, whole, mat, 8)
    assert np.abs(np.asarray(total - want)).max() < 1e-4


# ------------------------------------------------- kernels (interpret)
@pytest.fixture
def interpret(monkeypatch):
    from paddle_tpu.ops import moe_experts as moe
    from paddle_tpu.ops import slot_ring_decode as ring
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", [True])
    monkeypatch.setattr(moe, "_FORCE_INTERPRET", [True])
    monkeypatch.setattr(ring, "_FORCE_INTERPRET", [True])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("BS,MB", [(16, 4), (128, 2)],
                         ids=["four_planes", "one_plane"])
def test_two_part_key_kernel_matches_its_jnp_twin(interpret, dtype, tol,
                                                  BS, MB):
    """The paged decode kernel at the full layers' shape (4 KV heads, 16
    query heads a group, keys of 128 + 64 transposed, values of 128)
    against the gather form: slots with part of a block, several chunks
    and nothing live."""
    rng = np.random.default_rng(0)
    S, nkv, g, dn, dr = 4, 4, 16, 128, 64
    NB = S * MB + 3
    q = jnp.asarray(rng.normal(size=(S, nkv * g, dn)), dtype)
    q2 = jnp.asarray(rng.normal(size=(S, nkv * g, dr)), dtype)
    k = jnp.asarray(rng.normal(size=(NB, nkv, BS, dn)), dtype)
    k2 = jnp.asarray(rng.normal(size=(NB, nkv, dr, BS)), dtype)
    v = jnp.asarray(rng.normal(size=(NB, nkv, BS, dn)), dtype)
    tables = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB),
                         jnp.int32)
    lengths = jnp.asarray([5, BS + 1, MB * BS, 0], jnp.int32)
    assert pa.blocks_per_chunk(nkv, dn, BS, MB, dtype, dr) >= 1
    want = attn_ops.cached_paged_attention(q, k, v, tables, lengths, q2,
                                           k2)
    got = pa.paged_decode_attention(q, k, v, tables, lengths, q2, k2)
    assert got.shape == want.shape == (S, nkv * g, dn)
    assert got.dtype == want.dtype
    live = np.asarray(lengths) > 0
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32))[live].max() < tol
    # the scores are scaled by the WHOLE key's width: without the second
    # part the same call differs
    alone = pa.paged_decode_attention(q, k, v, tables, lengths)
    assert np.abs(np.asarray(alone, np.float32)
                  - np.asarray(got, np.float32))[live].max() > 10 * tol


def test_the_two_part_gather_equals_one_wide_key():
    rng = np.random.default_rng(1)
    S, nkv, g, BS, MB, dn, dr = 2, 2, 4, 8, 3, 16, 8
    NB = S * MB + 1
    f = jnp.float32
    q = jnp.asarray(rng.normal(size=(S, nkv * g, dn + dr)), f)
    kw = jnp.asarray(rng.normal(size=(NB, nkv, BS, dn + dr)), f)
    v = jnp.asarray(rng.normal(size=(NB, nkv, BS, dn)), f)
    tables = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB),
                         jnp.int32)
    lengths = jnp.asarray([7, 24], jnp.int32)
    want = attn_ops.cached_paged_attention(q, kw, v, tables, lengths)
    got = attn_ops.cached_paged_attention(
        q[..., dr:], kw[..., dr:], v, tables, lengths, q[..., :dr],
        kw[..., :dr].transpose(0, 1, 3, 2))
    assert want.shape == (S, nkv * g, dn)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# jaxpr digests of the kernel's call at the GPT cell's, the nemotron_h
# cell's and an f32 test's shapes, taken on the parent commit (e0db585)
KERNEL_DIGESTS = {
    (24, 16, 16, 128, 16, 16, 400, "bfloat16"):
        "d726e0b54a157fb153ac16cca1210b20",
    (128, 32, 2, 128, 256, 48, 100, "bfloat16"):
        "30947221033d1cc988b80372cc661606",
    (4, 4, 4, 128, 16, 4, 20, "float32"):
        "92654421365d7d7e4141eab44bdcc802"}


@pytest.mark.parametrize("shape", list(KERNEL_DIGESTS),
                         ids=["gpt", "nemotron_h", "f32"])
def test_one_part_calls_keep_their_jaxpr(interpret, shape):
    """ONE kernel body: a call without the key's second part traces
    what it traced before there was one."""
    S, nq, nh, hd, BS, MB, NB, dt = shape
    q = jnp.zeros((S, nq, hd), dt)
    k = jnp.zeros((NB, nh, BS, hd), dt)
    bt, ln = jnp.zeros((S, MB), jnp.int32), jnp.zeros((S,), jnp.int32)
    text = str(jax.make_jaxpr(pa.paged_decode_attention)(q, k, k, bt, ln))
    assert hashlib.md5(text.encode()).hexdigest() == KERNEL_DIGESTS[shape]
    assert pa.blocks_per_chunk(16, 128, 16, 64, jnp.bfloat16) == 8
    assert pa.blocks_per_chunk(2, 128, 256, 48, jnp.bfloat16) == 4


def test_engine_with_kernels_in_interpret_mode(interpret):
    """The decode program with ALL THREE kernels in it (interpret mode,
    a key of 128 + 8 beside values of 128) serves the reference's
    tokens."""
    m, w, hf = _model(seed=1, head_dim=136, swa_head_dim=136,
                      v_head_dim=128, swa_v_head_dim=128,
                      partial_rotary_factor=0.06, hidden_size=128,
                      moe_intermediate_size=128)
    assert (m.cfg.rot_dim, m.cfg.nope_dim) == (8, 128)
    eng = ServingEngine(m, num_slots=8, block_size=8, max_len=32,
                        buckets=[16])
    p = np.arange(11) % 128
    (r,) = _drive(eng, [p], [9])
    assert _served_gap(w, p, r, hf) < TOL


def test_engine_kernel_places_entries_like_the_gather_path(monkeypatch):
    """All three kernels in the decode program, the full layers' one
    placing the step's new entry in all three pools, the window layers'
    one in the slot's rings (interpret mode, a key of 128 + 8 beside
    values of 128), against the ``jnp`` formulations with the block
    write and the ring select: eight slots, eleven requests, so
    slots are released and taken again while released ones keep
    stepping; a prompt of 45 prefilled in chunks of 16 (its slot parked
    in between); outputs of up to 20 tokens over blocks of 8. The same
    tokens on both, each the reference's best."""
    from paddle_tpu.ops import moe_experts as moe
    from paddle_tpu.ops import slot_ring_decode as ring
    m, w, hf = _model(seed=1, head_dim=136, swa_head_dim=136,
                      v_head_dim=128, swa_v_head_dim=128,
                      partial_rotary_factor=0.06, hidden_size=128,
                      moe_intermediate_size=128)
    rng = np.random.default_rng(43)
    lens = (5, 45, 9, 17, 12, 3, 7, 14, 6, 11, 4)
    new = (20, 7, 10, 18, 6, 11, 13, 5, 8, 14, 9)
    prompts = [rng.integers(0, 128, size=n) for n in lens]
    served = {}
    for kernel in (True, False):
        monkeypatch.setattr(pa, "_FORCE_INTERPRET", [kernel])
        monkeypatch.setattr(moe, "_FORCE_INTERPRET", [kernel])
        monkeypatch.setattr(ring, "_FORCE_INTERPRET", [kernel])
        eng = ServingEngine(m, num_slots=8, block_size=8, max_len=96,
                            buckets=[16], prefill_chunk=16)
        reqs = _drive(eng, prompts, new)
        assert eng.pool.reuse_count >= 2
        served[kernel] = [np.asarray(r.output_ids) for r in reqs]
        for p, r in zip(prompts, reqs):
            assert _served_gap(w, p, r, hf) < TOL
    for a, b in zip(served[True], served[False]):
        np.testing.assert_array_equal(a, b)


def test_ring_kernel_serves_the_jnp_path_tokens_at_depth_12(monkeypatch):
    """Through the engine with the kernels forced (interpret mode; the
    window layers' ``ring_decode_attn`` among them) at the cell's depth,
    12 steps of results unread, against the ``jnp`` path: eight slots,
    thirteen requests, so slots are released and prefilled again while
    older steps that still name them are queued, sequences cross the
    window of 8 several times, and a prompt of 37 is prefilled in
    chunks of 16 with its slot parked (ring untouched) in between. The
    same tokens, each the reference's best."""
    from paddle_tpu.ops import moe_experts as moe
    from paddle_tpu.ops import slot_ring_decode as ring
    from paddle_tpu.serving.paged import mixed_programs as mp
    m, w, hf = _model(seed=1, head_dim=136, swa_head_dim=136,
                      v_head_dim=128, swa_v_head_dim=128,
                      partial_rotary_factor=0.06, hidden_size=128,
                      moe_intermediate_size=128)
    rng = np.random.default_rng(46)
    lens = (5, 37, 9, 30, 12, 3, 7, 14, 6, 11, 4, 21, 8)
    new = (16, 19, 14, 22, 17, 25, 13, 15, 18, 24, 19, 9, 16)
    prompts = [rng.integers(0, 128, size=n) for n in lens]
    served = {}
    for kernel in (True, False):
        for op in (pa, moe, ring):
            monkeypatch.setattr(op, "_FORCE_INTERPRET", [kernel])
        assert mp.decode_kernels(m.cfg, 8, 8) == kernel
        eng = ServingEngine(m, num_slots=8, block_size=8, max_len=96,
                            buckets=[16], prefill_chunk=16, async_depth=12)
        reqs = [eng.add_request(p, max_new_tokens=k)
                for p, k in zip(prompts, new)]
        deepest = 0
        while eng.step():
            deepest = max(deepest, len(eng._pending_steps))
        assert deepest == 12 and eng.pool.reuse_count >= 3
        served[kernel] = [np.asarray(r.output_ids) for r in reqs]
        for p, r in zip(prompts, reqs):
            assert _served_gap(w, p, r, hf) < TOL
    for a, b in zip(served[True], served[False]):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- what the spec counts
def test_cache_spec_counts_blocks_and_rings(model_w):
    from paddle_tpu.serving.paged import PagedKVPool
    from paddle_tpu.serving.paged.cache_spec import CacheSpec
    spec = model_w[0].cache_spec()
    assert [a.name for a in spec.arrays] == ["k", "kr", "v", "kring",
                                             "vring"]
    assert [a.per for a in spec.arrays] == ["token"] * 3 + ["slot"] * 2
    assert [a.layers for a in spec.arrays] == [2, 2, 2, 4, 4]
    assert not spec.shareable and spec.ring == 8 and spec.window is None
    assert spec.bytes_per_token == 2 * 2 * (24 + 16) * 4
    assert spec.bytes_per_slot == 4 * 4 * 8 * (24 + 16) * 4
    # all six layers keeping every position
    assert spec.dense_bytes_per_token \
        == (2 * 2 + 4 * 4) * (24 + 16) * 4
    pool = PagedKVPool(3, max_len=64, block_size=8, spec=spec)
    assert [a.shape for a in pool.arrays] == [
        (2, 25, 2, 8, 16), (2, 25, 2, 8, 8), (2, 25, 2, 8, 16),
        (4, 3, 4, 24, 8), (4, 3, 4, 8, 16)]
    # a ring is per-slot arrays and nothing without them
    with pytest.raises(ValueError, match="ring"):
        CacheSpec(2, [("k", (2,), (16,), "float32")], ring=8)
    assert CacheSpec(2, [("k", (2,), (16,), "float32")]
                     ).dense_bytes_per_token is None
