"""The ``deepseek_v3`` family on the served path, at small sizes on the
CPU: the eager model, prefill + decode through the latent paged cache
and ``generate()`` against the plain reference
(``benchmarks/reference/deepseek_v3.py``) on seeded weights; the two
attention forms against each other; rotary pairs, the router and the
expert shares against hand calculations; both decode kernels in
interpret mode against their ``jnp`` formulations.

Tolerances. Everything here is float32 on both sides, so what differs is
the order of additions (blocked attention, the experts' sorted runs, the
absorbed form's regrouped products): logits of magnitude ~6 agree to a
few 1e-6; ``TOL`` = 2e-4 leaves room for other BLAS builds. A cache or a
router in bfloat16 moves logits by 1e-2 or more and must fail ``TOL``:
two tests hold the comparison to that.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.reference import deepseek_v3 as ref  # noqa: E402
from paddle_tpu.ops import mla_attention as mla  # noqa: E402
from paddle_tpu.ops import moe_experts as moe  # noqa: E402
from paddle_tpu.ops.paged_attention import live_write_pos  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.text import deepseek_v3 as ds  # noqa: E402

TOL = 2e-4
HF = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
          num_attention_heads=4, intermediate_size=128,
          moe_intermediate_size=32, n_routed_experts=8,
          n_shared_experts=2, num_experts_per_tok=2,
          first_k_dense_replace=1, kv_lora_rank=32, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
          max_position_embeddings=64, rms_norm_eps=1e-6,
          rope_theta=10000.0, rope_interleave=True,
          routed_scaling_factor=2.448, norm_topk_prob=True,
          scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
          topk_group=1, rope_scaling=None)


def _weights(cfg, seed=3):
    """Seeded weights with every part that could be dropped made
    visible: large N(0, 0.2) matrices, random gains, a random score
    correction bias."""
    w = ds.DeepseekV3ForCausalLM(cfg, seed=seed).export_decode_params()
    key = jax.random.PRNGKey(seed + 100)
    for i, g in enumerate(("dense", "moe")):
        for j, n in enumerate(("norm1", "norm2", "kv_norm")):
            k = jax.random.fold_in(key, 10 * i + j)
            w[g][n] = 1.0 + 0.1 * jax.random.normal(k, w[g][n].shape)
    w["norm_f"] = 1.0 + 0.1 * jax.random.normal(key, w["norm_f"].shape)
    w["moe"]["router_b"] = (0.1 * jax.random.normal(
        jax.random.fold_in(key, 99), w["moe"]["router_b"].shape)
    ).astype(jnp.float32)
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


def _model(**over):
    cfg = ds.DeepseekV3Config.from_hf(HF, initializer_range=0.2, **over)
    w = _weights(ds.DeepseekV3Config.from_hf(HF, initializer_range=0.2))
    return ds.DeepseekV3ForCausalLM(cfg, weights=w), w


def _ref_logits(w, ids):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32), HF)[0])


@pytest.fixture(scope="module")
def model_w():
    return _model()


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 96, size=(2, 24))


# ------------------------------------------------- against the reference
def test_eager_logits_match_reference(model_w, ids):
    m, w = model_w
    got = np.asarray(m(ids).value)
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(w, ids[b])).max() < TOL


@pytest.mark.parametrize("what", ["cache", "router"])
def test_lower_precision_fails_the_comparison(ids, what):
    """The same comparison with the cache, or the router, in bfloat16
    is outside ``TOL``: the tolerance would notice either."""
    over = {"cache_dtype": "bfloat16"} if what == "cache" \
        else {"router_dtype": "bfloat16"}
    m, w = _model(**over)
    got = np.asarray(m(ids).value)
    worst = max(np.abs(got[b] - _ref_logits(w, ids[b])).max()
                for b in range(2))
    assert worst > 10 * TOL, worst


def test_generate_greedy_matches_reference(model_w, ids):
    """Prefill (expanded) + decode steps (absorbed) over the contiguous
    latent cache pick, at every step, the reference's best token, and
    the logits the decode path produced are the reference's."""
    m, w = model_w
    out = np.asarray(m.generate(ids, max_new_tokens=8).value)
    assert (out[:, :24] == ids).all()
    for b in range(2):
        lg = _ref_logits(w, out[b])
        assert (np.argmax(lg, -1)[23:-1] == out[b, 24:]).all()


def test_absorbed_form_equals_expanded_form(model_w, ids):
    """One layer's attention for the LAST token of a sequence, computed
    in the absorbed form over a cache the expanded form filled, equals
    the expanded form's row for that token."""
    m, w = model_w
    cfg = m.cfg
    p = jax.tree.map(lambda a: a[0], w["dense"])
    x = w["wemb"][jnp.asarray(ids[:1])]                       # [1, T, h]
    T = x.shape[1]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    full, _ = ds.attention(cfg, p, x, pos, ds.SeqAccess(), (), 0, 0,
                           "prefill")
    access = ds.ContigAccess()
    state = (jnp.zeros((1, 1, T, cfg.kv_lora_rank), jnp.float32),
             jnp.zeros((1, 1, T, cfg.qk_rope_head_dim), jnp.float32))
    _, state = ds.attention(cfg, p, x[:, :T - 1], pos[:, :T - 1], access,
                            state, jnp.int32(0), jnp.int32(0), "prefill")
    last, _ = ds.attention(cfg, p, x[0, T - 1:], pos[0, T - 1:], access,
                           state, jnp.int32(0), 0, "decode")
    assert np.abs(np.asarray(last[0] - full[0, -1])).max() < 1e-5


def _drive(engine, prompts, new):
    reqs = [engine.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    engine.run()
    return reqs


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunked"])
def test_paged_prefill_and_decode_match_reference(model_w, chunk):
    """Through ``ServingEngine`` over the latent paged cache: three
    slots, five requests of uneven lengths (so slots are released and
    taken again, and released slots keep decoding into the trash block
    meanwhile); with ``prefill_chunk`` the long prompts prefill chunk by
    chunk and their slots are PARKED between chunks. Every served token
    is the reference's best at its position, by the logit gap that
    ``correct`` reads on the chip."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=3, block_size=8,
                        max_len=64, buckets=[16, 32],
                        prefill_chunk=chunk)
    rng = np.random.default_rng(1)
    lens, new = (5, 17, 9, 30, 12), (6, 9, 4, 12, 7)
    prompts = [rng.integers(0, 96, size=n) for n in lens]
    reqs = _drive(eng, prompts, new)
    assert eng.pool.reuse_count >= 2          # released slots came back
    for p, r, k in zip(prompts, reqs, new):
        served = np.asarray(r.generated)
        assert len(served) == k
        seq = np.concatenate([p, served])
        lg = _ref_logits(w, seq[:-1])
        at = lg[np.arange(len(p) - 1, len(seq) - 1), served]
        gap = lg[len(p) - 1:].max(-1) - at
        assert gap.max() < TOL, gap
        want = np.asarray(m.generate(p[None], max_new_tokens=k).value)[0]
        assert (np.asarray(r.output_ids) == want).all()
    snap = eng.metrics.snapshot()
    moe_ = snap["moe"]
    steps = moe_["layer_steps"]
    assert steps[0] == steps[1] == snap["decode_steps"] > 0
    for row, n in zip(moe_["expert_tokens"], steps):
        assert sum(row) == n * 3 * HF["num_experts_per_tok"]
    assert all(0 < h <= n * 8 for h, n in zip(moe_["experts_hit"], steps))
    text = eng.metrics.prometheus_text()
    per_token = 3 * (32 + 8) * 4                # layers x (c + k_pe) x f32
    assert f"serving_kv_bytes_per_token {per_token}" in text
    assert 'serving_moe_expert_tokens_total{layer="2",expert="7"}' in text


def test_sampling_program_runs_and_repeats(model_w):
    m, _ = model_w

    def once():
        eng = ServingEngine(m, num_slots=2, block_size=8,
                            max_len=64, buckets=[16], sampling=True)
        r = eng.add_request(np.arange(7), max_new_tokens=6,
                            temperature=0.8, top_k=20, seed=5)
        eng.run()
        return list(r.generated)
    a = once()
    assert len(a) == 6 and a == once()


@pytest.mark.parametrize("option", [
    {"speculative": True}, {"role": "prefill"}],
    ids=["speculative", "role"])
def test_engine_refuses_an_option_without_a_program(model_w, option):
    with pytest.raises(ValueError, match="no program for"):
        ServingEngine(model_w[0], num_slots=2, **option)


def test_engine_never_hands_the_latent_model_the_gpt_kernel(model_w):
    """The engine chooses the GPT's paged decode kernel from
    ``kernel_viable`` only where the cache is a (k, v) pair. Even where
    that guard would say yes to anything (forced interpret), a latent
    cache's programs are built without the choice: their builder has no
    such parameter."""
    import inspect
    from paddle_tpu.ops import paged_attention as pa
    assert "attn_kernel" not in inspect.signature(
        model_w[0].build_paged_serving_fns).parameters
    pa._FORCE_INTERPRET[0] = True
    try:
        eng = ServingEngine(model_w[0], num_slots=2,
                            block_size=8, max_len=64, buckets=[16])
    finally:
        pa._FORCE_INTERPRET[0] = False
    assert eng.paged_attn is False and eng.decode_layout == "paged_xla"


# ------------------------------------------- where a projection rounds
def test_query_projection_rounds_to_bf16_straight_after_the_dot():
    """``wq`` of a decode layer in bfloat16: the dot asks for float32
    (the form XLA streams a stacked weight in: ``stacked_lm.
    project_heads``) and its result is rounded to bfloat16 before the
    rotary, the absorbed einsum or anything else reads it, so the
    queries hold what ``jnp.dot(xn, wq)`` gave."""
    from jaxpr_check import assert_same_bf16_rounding, rounded_projections
    cfg = ds.DeepseekV3Config.from_hf(HF, initializer_range=0.2,
                                      dtype="bfloat16")
    w = ds.DeepseekV3ForCausalLM(cfg, seed=5).export_decode_params()
    p = {n: a[0] for n, a in w["dense"].items()}
    assert p["wq"].dtype == jnp.bfloat16
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(6, cfg.hidden_size)), jnp.bfloat16)
    pos = jnp.arange(40, 46, dtype=jnp.int32)

    class Access:
        def decode(self, state, layer, positions, c, k_pe, q_lat, q_pe,
                   scale):
            return state, q_lat.astype(jnp.float32)

    got, = rounded_projections(
        lambda p, x: ds.attention(cfg, p, x, pos, Access(), (), 0, 0,
                                  "decode")[0],
        (p, x), [p["wq"]])
    assert_same_bf16_rounding(
        got, ds.rms_norm(x, p["norm1"], cfg.rms_norm_eps), p["wq"])


# --------------------------------------------------- hand calculations
def test_rope_rotates_interleaved_pairs():
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0]], jnp.float32)
    pos, theta = 3, 100.0
    got = np.asarray(ds.rope_interleaved(x, jnp.asarray([pos]), theta))[0]
    want = []
    for i, (a, b) in enumerate([(1.0, 2.0), (3.0, 4.0)]):
        ang = pos * theta ** (-2 * i / 4)
        want += [a * np.cos(ang) - b * np.sin(ang),
                 b * np.cos(ang) + a * np.sin(ang)]
    assert np.allclose(got, want, atol=1e-6)
    assert np.allclose(np.asarray(ref.rope_pairs(
        x, jnp.asarray([pos]), theta))[0], want, atol=1e-6)


def test_router_chooses_by_biased_score_and_weighs_by_score():
    """Scores s = sigmoid(logit) = (0.9, 0.8, 0.7, 0.6); the bias lifts
    expert 3 over expert 1. Chosen: the two largest of s + b = {0, 3};
    weights: s WITHOUT b, normalised, times 2.448."""
    s = np.asarray([0.9, 0.8, 0.7, 0.6])
    logit = np.log(s / (1 - s))
    x = jnp.asarray([[1.0, 0.0]], jnp.float32)
    w_router = jnp.asarray(np.stack([logit, np.zeros(4)]), jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.25], jnp.float32)
    idx, w = moe.route_sigmoid(x, w_router, bias, 2, True, 2.448)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]
    got = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(w)[0]))
    assert np.isclose(got[0], 2.448 * 0.9 / 1.5, atol=1e-5)
    assert np.isclose(got[3], 2.448 * 0.6 / 1.5, atol=1e-5)
    cw = np.asarray(moe.combine_matrix(idx, w, 2, 2))   # holds {2, 3}
    assert cw[0, 0] == 0 and np.isclose(cw[0, 1], got[3])
    assert np.asarray(moe.expert_counts(idx, 0, 4)).tolist() == [1, 0, 0, 1]


@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("shares", [1, 2, 8],
                         ids=["whole", "halves", "eighths"])
def test_expert_shares_add_up_to_the_uncut_layer(model_w, shares, mode):
    """The parts that the shares of a layer's experts give, plus the
    shared expert counted ONCE, equal the uncut reference's layer."""
    m, w = model_w
    cfg, E = m.cfg, HF["n_routed_experts"]
    layer_m = 1
    p = jax.tree.map(lambda a: a[layer_m], w["moe"])
    x = jax.random.normal(jax.random.PRNGKey(7), (16, 64), jnp.float32)
    xn = ds.rms_norm(x, p["norm2"], cfg.rms_norm_eps)
    count = E // shares
    total = 0.0
    for i in range(shares):
        held = (i * count, count)
        rows = np.concatenate([np.arange(l * E + held[0],
                                         l * E + held[0] + count)
                               for l in range(cfg.num_moe_layers)])
        mine = {k: v[rows] for k, v in w["experts"].items()}
        y, tokens = ds.expert_layer(cfg, p, mine, xn, layer_m, mode,
                                    with_shared=(i == 0), held=held)
        assert tokens.shape == (count,)
        total = total + y

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    want, _ = ref._expert_layer(x, p, w["experts"], layer_m, HF, mm, 8)
    assert np.abs(np.asarray(x + total - want)).max() < 1e-4


# ------------------------------------------------- kernels (interpret)
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(mla, "_FORCE_INTERPRET", [True])
    monkeypatch.setattr(moe, "_FORCE_INTERPRET", [True])


# (block size, blocks a slot, blocks a chunk, per-slot lengths). The
# first is PR 28's case (blocks of 16 cannot sit side by side on the
# lanes: one block a chunk). The others walk chunks of G = 2 blocks of
# 128 over slots of 5 (capacity 640, chunks of 256, an odd count of
# chunks so that the buffers' halves swap from slot to slot): a released
# slot (0) between live ones and at either end (the hand-over of the
# prefetch), 1, a chunk's last position (256, 512), its first position
# + 1 (258, 514), a ragged last block (300, 130), full capacity and one
# past it (``pos + 1`` at the clamp in ``LatentAccess.decode``)
_MLA_WALKS = {
    "blocks16": (16, 4, 1, [5, 33, 64]),
    "chunks": (128, 5, 2, [300, 0, 1, 256, 258, 640, 641, 0, 0, 514]),
    "edges": (128, 5, 2, [0, 640, 0, 512, 130, 1, 0, 257, 641, 0]),
}


def _mla_case(monkeypatch, walk, dtype, rng):
    BS, MB, G, lengths = _MLA_WALKS[walk]
    S, nh, r, dr = len(lengths), 4, 128, 16
    NB = S * MB + 2       # the last two are in no table
    if G > 1:
        # the chunk budget of a test-sized block: room for G blocks
        monkeypatch.setattr(mla, "_CHUNK_VMEM_BYTES", 2 * G * BS * (r + dr)
                            * jnp.dtype(dtype).itemsize)
    assert mla.blocks_per_chunk(BS, r, dr, MB, dtype) == G
    assert G == 1 or MB > 2 * G
    q_lat = jnp.asarray(rng.normal(size=(S, nh, r)), dtype)
    q_pe = jnp.asarray(rng.normal(size=(S, nh, dr)), dtype)
    c = jnp.asarray(rng.normal(size=(NB, BS, r)), dtype)
    pe = jnp.asarray(rng.normal(size=(NB, dr, BS)), dtype)
    tables = jnp.asarray(rng.permutation(NB - 2)[:S * MB].reshape(S, MB),
                         jnp.int32)
    return q_lat, q_pe, c, pe, tables, jnp.asarray(lengths, jnp.int32)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("walk", list(_MLA_WALKS))
def test_mla_decode_kernel_matches_formulation(interpret, monkeypatch,
                                               walk, dtype, tol):
    args = _mla_case(monkeypatch, walk, dtype, np.random.default_rng(0))
    want = mla.mla_paged_decode_attn_jnp(*args, 0.1)
    got = mla.mla_paged_decode_attn(*args, 0.1)
    assert got.dtype == jnp.float32
    # a slot with nothing live: the oracle averages what it gathered,
    # the kernel writes zeros, nobody reads either
    live = np.asarray(args[-1]) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < tol
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("walk", ["blocks16", "chunks"])
def test_mla_decode_kernel_never_reads_a_dead_block(interpret, monkeypatch,
                                                    walk):
    """Table entries past a slot's live blocks point at a block of
    ``nan`` latent and ``inf`` rotary key (in the pool: the trash block,
    or a released slot's stale row): neither copied nor computed."""
    q_lat, q_pe, c, pe, tables, lengths = _mla_case(
        monkeypatch, walk, jnp.float32, np.random.default_rng(1))
    BS, MB = c.shape[1], tables.shape[1]
    bad = c.shape[0] - 1
    dead = np.arange(MB)[None, :] * BS >= np.asarray(lengths)[:, None]
    want = mla.mla_paged_decode_attn_jnp(q_lat, q_pe, c, pe, tables,
                                         lengths, 0.1)
    got = mla.mla_paged_decode_attn(
        q_lat, q_pe, c.at[bad].set(jnp.nan), pe.at[bad].set(jnp.inf),
        jnp.where(dead, bad, tables), lengths, 0.1)
    live = np.asarray(lengths) > 0
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < 2e-6


# the step's new entry placed by the kernel. Blocks of 256 (two lane
# tiles of the transposed rotary key), 5 a slot, chunks of G = 2 blocks:
# (length, write position) of the slot under test, None = length - 1
# (a live entry). It sits between two live slots, so that its first
# chunk is a hand-over and its write-back is waited for before the next
# slot's chunk lands
_BSW, _MBW = 256, 5
_MLA_WRITES = {
    "block_first_row": (_BSW + 1, None),
    "block_last_row": (_BSW, None),
    "mid_tile": (_BSW + 8, None),             # row 7 of a 16-row tile
    "lane_127": (128, None),
    "lane_128": (129, None),
    "chunk_first_block": (2 * _BSW + 5, None),
    "chunk_last_block": (4 * _BSW - 3, None),
    "one_live_block_chunk": (4 * _BSW + 10, None),
    "length_1": (1, None),
    "capacity": (_MBW * _BSW, None),
    "no_write": (300, -1),
    "parked": (2 * _BSW, -1),      # holds 2 blocks, position at the park
    "released": (0, -1),           # a row of trash
    "stale_position": (300, 17),   # not the last live position
    "past_capacity": (_MBW * _BSW + 1, _MBW * _BSW),
}


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(_MLA_WRITES))
def test_mla_decode_kernel_places_the_new_entry(interpret, monkeypatch,
                                                case, dtype, tol):
    """The kernel with ``new`` / ``write_pos`` against the ``jnp`` block
    write followed by ``mla_paged_decode_attn_jnp``: ``o_lat`` of every
    slot with something live as the oracle's; both pools BYTE-equal to
    what they were with the live entries at their positions and nothing
    else changed (not another row of the tile written back, not another
    lane of the rotary key's, not a parked or released slot's block, not
    the trash block), and byte-equal to the oracle's everywhere but the
    row's last entry of a slot that writes nothing, where the oracle
    pins its stray entry."""
    BS, MB, G = _BSW, _MBW, 2
    nh, r, dr = 4, 128, 16
    slots = [(700, None), _MLA_WRITES[case], (40, None)]
    lens = np.asarray([n for n, _ in slots], np.int32)
    wpos = np.asarray([n - 1 if w is None else w for n, w in slots],
                      np.int32)
    S = len(slots)
    NB = S * MB + 1                                   # the last: trash
    monkeypatch.setattr(mla, "_CHUNK_VMEM_BYTES", 2 * G * BS * (r + dr)
                        * jnp.dtype(dtype).itemsize)
    assert mla.blocks_per_chunk(BS, r, dr, MB, dtype) == G
    rng = np.random.default_rng(2)
    q_lat = jnp.asarray(rng.normal(size=(S, nh, r)), dtype)
    q_pe = jnp.asarray(rng.normal(size=(S, nh, dr)), dtype)
    pools = (jnp.asarray(rng.normal(size=(NB, BS, r)), dtype),
             jnp.asarray(rng.normal(size=(NB, dr, BS)), dtype))
    new = (jnp.asarray(rng.normal(size=(S, r)), dtype),
           jnp.asarray(rng.normal(size=(S, dr)), dtype))
    tables = rng.permutation(NB - 1).reshape(S, MB).astype(np.int32)
    # blocks a slot does not hold are the trash block
    tables[np.arange(MB)[None, :] * BS >= lens[:, None]] = NB - 1
    def run(wpos, kernel):
        return mla.latent_write_attention(
            q_lat, q_pe, new, pools, jnp.asarray(tables),
            jnp.asarray(wpos), jnp.asarray(lens), 0.1, kernel)
    # the kernel is handed the position as it came and must itself
    # write only a live one; the oracle what the program forms of it
    # (-1 where it is not the slot's last live position)
    got, got_pools = run(wpos, True)
    wpos = np.asarray(live_write_pos(jnp.asarray(wpos), jnp.asarray(lens)))
    placed = (wpos >= 0) & (wpos < MB * BS)
    if case != "past_capacity":      # no program forms it, no oracle
        want, want_pools = run(wpos, False)
        some = lens > 0
        assert np.abs(np.asarray(got) - np.asarray(want))[some].max() < tol
    assert np.isfinite(np.asarray(got)).all()
    for i, (pool, entry) in enumerate(zip(pools, new)):
        expect = np.array(pool)
        for s in np.nonzero(placed)[0]:
            blk, off = tables[s, wpos[s] // BS], wpos[s] % BS
            if i:
                expect[blk, :, off] = np.asarray(entry)[s]
            else:
                expect[blk, off] = np.asarray(entry)[s]
        np.testing.assert_array_equal(_bits(got_pools[i]), _bits(expect))
        if case == "past_capacity":
            continue
        same = np.ones(pool.shape, bool)
        for s in np.nonzero(~placed)[0]:
            # where the oracle pins a stray entry
            blk, off = tables[s, MB - 1], BS - 1
            same[(blk, slice(None), off) if i else (blk, off)] = False
        np.testing.assert_array_equal(_bits(got_pools[i])[same],
                                      _bits(want_pools[i])[same])


def test_mla_read_only_call_traces_no_write(interpret):
    """Without a new entry the call is what it was: one result, no
    aliased pool, no third scalar; with one, both pools are aliased
    onto results 1 and 2."""
    f32 = jnp.float32
    q_lat, q_pe, c, pe, tables, lens = (
        jnp.zeros((2, 4, 128), f32), jnp.zeros((2, 4, 16), f32),
        jnp.zeros((5, 16, 128), f32), jnp.zeros((5, 16, 16), f32),
        jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32))
    text = str(jax.make_jaxpr(
        lambda *a: mla.mla_paged_decode_attn(*a, 0.1))(
            q_lat, q_pe, c, pe, tables, lens))
    assert "input_output_aliases=()" in text
    wrote = str(jax.make_jaxpr(
        lambda *a: mla.mla_paged_decode_attn(
            *a, 0.1, new=(q_lat[:, 0], q_pe[:, 0]), write_pos=lens - 1))(
                q_lat, q_pe, c, pe, tables, lens))
    assert "input_output_aliases=((5, 1), (6, 2))" in wrote


@pytest.mark.parametrize("layer_m", [0, 1])
def test_moe_decode_kernel_matches_formulation(interpret, layer_m):
    """With experts no token chose (skipped by the kernel), at a row
    offset into the stacked matrices, against the formulation that
    computes every expert; the sorted grouped path gives the same."""
    rng = np.random.default_rng(0)
    T, h, f, E, Lm = 16, 128, 256, 8, 2
    x = jnp.asarray(rng.normal(size=(T, h)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(Lm * E, h, f)) * 0.1,
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(Lm * E, f, h)) * 0.1, jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, E)), jnp.float32)
    idx, w = moe.route_sigmoid(x, wr, jnp.zeros(E), 2, True, 2.448)
    idx = jnp.where(idx == 3, 4, idx)           # nobody chooses expert 3
    cw = moe.combine_matrix(idx, w, 0, E)
    base = jnp.int32(layer_m * E)
    want = np.asarray(moe.moe_experts_swiglu_jnp(x, wg, wu, wd, cw, base))
    got = moe.moe_experts_swiglu_decode(x, wg, wu, wd, cw, base)
    assert np.abs(np.asarray(got) - want).max() < 2e-5 * np.abs(want).max()
    grouped = moe.moe_experts_grouped(x, wg, wu, wd, idx, w, 0, E, base,
                                      tile=8)
    assert np.abs(np.asarray(grouped) - want).max() \
        < 2e-5 * np.abs(want).max()


def test_engine_with_kernels_in_interpret_mode(interpret, model_w):
    """The decode program with BOTH kernels in it (interpret mode)
    serves the tokens ``generate()`` picks."""
    # kernel shapes: 8 slots (a sublane tile of f32 tokens), lanes whole
    cfg = ds.DeepseekV3Config.from_hf(
        dict(HF, hidden_size=128, moe_intermediate_size=128,
             kv_lora_rank=128), initializer_range=0.2)
    m = ds.DeepseekV3ForCausalLM(cfg, seed=1)
    eng = ServingEngine(m, num_slots=8, block_size=8,
                        max_len=32, buckets=[16])
    p = np.arange(9) % 96
    (r,) = _drive(eng, [p], [5])
    want = np.asarray(m.generate(p[None], max_new_tokens=5).value)[0]
    assert (np.asarray(r.output_ids) == want).all()


# -------------------------------------------- the generalised pool (GPT)
def test_pool_from_gpt_cache_spec_is_todays_pool():
    """A pool built from the GPT's cache spec has the arrays today's
    constructor made (shape, dtype, names ``kc``/``vc``), and the GPT
    engine's decode program takes exactly today's arguments."""
    from paddle_tpu.serving.paged import PagedKVPool
    from paddle_tpu.serving.paged.cache_spec import kv_pair_spec
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig
    old = PagedKVPool(3, 2, 4, 64, 16, block_size=8, dtype=jnp.bfloat16)
    new = PagedKVPool(3, max_len=64, block_size=8,
                      spec=kv_pair_spec(2, 4, 16, jnp.bfloat16))
    for a, b in zip(old.arrays, new.arrays):
        assert a.shape == b.shape == (2, 25, 4, 8, 16)
        assert a.dtype == b.dtype == jnp.bfloat16
    assert new.kc is new.arrays[0] and new.vc is new.arrays[1]
    assert new.nbytes() == old.nbytes() == 2 * 2 * 25 * 4 * 8 * 16 * 2
    assert new.spec.bytes_per_token == 2 * 2 * 4 * 16 * 2
    with pytest.raises(ValueError, match="rebind"):
        new.rebind(new.kc)
    cfg = TransformerLMConfig(vocab_size=64, hidden_size=32, num_layers=2,
                              num_heads=2, max_seq_len=32, dropout=0.0)
    gpt = GPTForCausalLM(cfg)
    gpt.eval()
    eng = ServingEngine(gpt, num_slots=2, block_size=8)
    assert [a.name for a in eng.cache_spec.arrays] == ["k", "v"]
    args, donate = eng._decode_dispatch_args(eng.pool)
    assert donate == (2, 4, 5) and len(args) == 6
    assert args[4] is eng.pool.kc and args[5] is eng.pool.vc
    assert eng._state == ()
