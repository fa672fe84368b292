"""The ``evabyte`` family on the served path, at small sizes on the CPU
(hidden 64, 4 heads of 16, window 32, chunk 4, 3 layers, float32): the
eager model, ``generate()`` and window-tiled prefill + decode through a
paged cache whose ENTRIES are not positions, against the plain reference
(``benchmarks/reference/evabyte.py``: masks over the whole sequence, no
cache, no compaction) on seeded weights; a live slot's blocks handed
out, taken back at a compaction and reused; the shared decode kernel at
32 heads with lengths that are not positions; every refusal by name.

Tolerances. Everything here is float32 on both sides, so what differs is
the order of additions (blocked attention, a window pooled from the
cache or from the run): logits of magnitude ~0.4 agree to about 1e-7;
``TOL`` = 2e-5 leaves room for other BLAS builds.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import weights_evabyte as W  # noqa: E402
from benchmarks.reference import evabyte as ref  # noqa: E402
from paddle_tpu.ops import attention as attn_ops  # noqa: E402
from paddle_tpu.ops import eva as eva_ops  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.serving.paged import PagedKVPool  # noqa: E402
from paddle_tpu.serving.paged.cache_spec import CacheSpec  # noqa: E402
from paddle_tpu.text import evabyte as eb  # noqa: E402

TOL = 2e-5
WIN, CHUNK = 32, 4
HF = dict(model_type="evabyte", attention_class="eva", vocab_size=64,
          hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
          num_key_value_heads=4, intermediate_size=96, window_size=WIN,
          chunk_size=CHUNK, num_pred_heads=3, max_position_embeddings=256,
          rms_norm_eps=1e-5, rope_theta=100000, init_std=0.05,
          norm_add_unit_offset=True, fp32_skip_add=True, fp32_logits=True,
          fp32_ln=False, num_chunks=None, rope_scaling=None,
          attention_bias=False, tie_word_embeddings=False,
          hidden_act="silu")


def _model(seed=3, **over):
    hf = dict(HF, **over)
    w = W.make(seed, hf, "float32")
    cfg = eb.EvaByteConfig.from_hf(hf, dtype="float32")
    return eb.EvaByteForCausalLM(cfg, weights=w), w, hf


def _ref_logits(w, ids, hf=HF):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32), hf))


def _served_gap(w, prompt, req, hf=HF):
    """Widest gap by which a served token's reference logit (head 0)
    lies below the reference's best at its position."""
    out = np.asarray(req.output_ids)
    lg = _ref_logits(w, out[:-1], hf)[:, 0]
    p = len(prompt)
    at = lg[np.arange(p - 1, len(out) - 1), out[p:]]
    return float((lg[p - 1:].max(-1) - at).max())


def _drive(eng, prompts, new):
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    eng.run()
    return reqs


@pytest.fixture(scope="module")
def model_w():
    m, w, _ = _model()
    return m, w


# ------------------------------------------------------ the whole model
def test_eager_forward_matches_reference_mid_chunk_in_fourth_window(
        model_w):
    """109 positions = three whole windows and 13 of the fourth (three
    chunks and one position of a fourth chunk), every prediction head."""
    m, w = model_w
    ids = np.random.default_rng(0).integers(0, 64, size=(2, 109))
    got = np.asarray(m.forward_heads(ids).value)
    assert got.shape == (2, 109, 3, 64)
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(w, ids[b])).max() < TOL
    assert np.abs(np.asarray(m.forward(ids).value) - got[:, :, 0]).max() \
        == 0.0


def test_summaries_of_the_own_window_are_not_visible():
    """The reference's mask, by its own numbers: changing a key INSIDE a
    finished window moves a later window's logits only through that
    window's summary, and a position of the query's own window is seen
    exactly: with one window (T <= W) the model is plain causal softmax
    attention whatever ``mu`` and ``phi`` are."""
    _, w, hf = _model()
    ids = np.random.default_rng(1).integers(0, 64, size=(WIN,))
    base = _ref_logits(w, ids)
    w2 = dict(w, layers=dict(w["layers"],
                             mu=w["layers"]["mu"] * 3.0,
                             phi=-w["layers"]["phi"]))
    assert np.abs(_ref_logits(w2, ids) - base).max() == 0.0
    long_ = np.random.default_rng(1).integers(0, 64, size=(WIN + 5,))
    assert np.abs(_ref_logits(w2, long_)[WIN:]
                  - _ref_logits(w, long_)[WIN:]).max() > 1e-4


@pytest.mark.parametrize("s0,new", [(20, 8), (50, 60), (64, 40)],
                         ids=["one_window", "two_ends", "from_boundary"])
def test_generate_greedy_matches_reference(model_w, s0, new):
    """``generate()``: prefill by windows, then decode over a contiguous
    entry cache that is compacted inside the jitted loop."""
    m, w = model_w
    ids = np.random.default_rng(2).integers(0, 64, size=(2, s0))
    out = np.asarray(m.generate(ids, max_new_tokens=new).value)
    assert out.shape == (2, s0 + new)
    for b in range(2):
        lg = _ref_logits(w, out[b, :-1])[:, 0]
        at = lg[np.arange(s0 - 1, s0 + new - 1), out[b, s0:]]
        assert (lg[s0 - 1:].max(-1) - at).max() < TOL


def test_window_compact_is_the_pooling_formula():
    rng = np.random.default_rng(0)
    H, n, d = 4, 16, 16
    k = rng.normal(size=(H, n, d)).astype(np.float32)
    v = rng.normal(size=(H, n, d)).astype(np.float32)
    mu = rng.normal(size=(H, d)).astype(np.float32)
    phi = rng.normal(size=(H, d)).astype(np.float32)
    kb, vb = eva_ops.window_compact(jnp.asarray(k), jnp.asarray(v),
                                    jnp.asarray(mu), jnp.asarray(phi), 4)
    for h in range(H):
        for c in range(n // 4):
            kk, vv = k[h, 4 * c:4 * c + 4], v[h, 4 * c:4 * c + 4]
            for vec, rows, got in ((mu, kk, kb), (phi, vv, vb)):
                s = kk @ vec[h] / np.sqrt(d)
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ rows
                assert np.abs(np.asarray(got)[h, c] - want).max() < 1e-5


def test_rope_half_turns_half_split_pairs():
    x = np.zeros((1, 8), np.float32)
    x[0, 1] = 1.0                      # lane 1 pairs with lane 5
    y = np.asarray(eva_ops.rope_half(jnp.asarray(x), jnp.asarray([2]),
                                     100.0))
    ang = 2 * 100.0 ** (-2 / 8)
    assert np.allclose(y[0, [1, 5]], [np.cos(ang), np.sin(ang)],
                       atol=1e-6)
    assert np.abs(np.delete(y[0], [1, 5])).max() == 0.0


# ----------------------------------------- entries that are not positions
def test_cache_spec_counts_entries_not_positions(model_w):
    spec = model_w[0].cache_spec()
    assert spec.window == (WIN, CHUNK) and not spec.shareable
    assert [a.name for a in spec.arrays] == ["k", "v"]
    assert spec.bytes_per_token == 3 * 2 * 4 * 16 * 4      # an ENTRY
    S = WIN // CHUNK
    assert [spec.entries(t) for t in (0, 31, 32, 33, 64, 100)] == [
        0, 31, S, S + 1, 2 * S, 3 * S + 4]
    # the most a slot holds: the window before the last just before it
    # is compacted, or the last window's raw entries
    assert spec.capacity(20) == 20 and spec.capacity(32) == 32
    assert spec.capacity(33) == 32 and spec.capacity(64) == S + 32
    assert spec.capacity(256) == 7 * S + 32
    for T in range(1, 200):
        assert spec.capacity(T) == max(spec.entries(t) + 1
                                       for t in range(T))
    # the published sizes: 32,768 positions are 3,968 entries at most
    real = CacheSpec(8, spec.arrays[:2], window=(2048, 16))
    assert real.capacity(32768) == 3968 and real.entries(32768) == 2048
    plain = CacheSpec(2, [("k", (4,), (16,), "float32")])
    assert plain.window is None and plain.shareable
    assert plain.entries(77) == plain.capacity(77) == 77
    with pytest.raises(ValueError, match="chunk must divide"):
        CacheSpec(2, [("k", (4,), (16,), "float32")], window=(32, 5))


def test_pool_reserves_grows_and_takes_blocks_back(model_w):
    spec = model_w[0].cache_spec()
    pool = PagedKVPool(3, max_len=256, block_size=8, spec=spec)
    assert pool.blocks_per_slot == 11 and pool.num_blocks == 34
    assert pool.slot_capacity == 88            # entries, not positions
    a = pool.acquire("a", np.arange(70), 256, 0)
    assert a.new_blocks == [] and pool.live_blocks == 0
    assert pool.stats()["reserved_blocks"] == 11
    pool.grow(a.slot, 40)
    assert pool.live_blocks == 5
    assert (pool.block_tables[a.slot, :5] != 0).all()
    assert (pool.block_tables[a.slot, 5:] == 0).all()
    pool.grow(a.slot, 33)                      # already held: no change
    assert pool.live_blocks == 5
    assert pool.shrink(a.slot, 8) == 4 and pool.live_blocks == 1
    assert (pool.block_tables[a.slot, 1:] == 0).all()
    pool.check_conservation()
    with pytest.raises(ValueError, match="were reserved"):
        pool.grow(a.slot, 89)
    # a short request reserves little; reservations bound admission
    b = pool.acquire("b", np.arange(5), 12, 0)
    assert pool.stats()["reserved_blocks"] == 13
    small = PagedKVPool(3, max_len=256, block_size=8, num_blocks=14,
                        spec=spec)
    assert small.acquire("a", np.arange(5), 256, 0) is not None
    assert small.acquire("b", np.arange(5), 256, 0) is None   # waits
    assert small.acquire("b", np.arange(5), 12, 0) is not None
    small.check_conservation()
    pool.release(a.slot)
    pool.release(b.slot)
    assert pool.live_blocks == 0 and pool.stats()["reserved_blocks"] == 0
    pool.check_conservation()


def test_paged_prefill_and_decode_match_reference_across_window_ends(
        model_w):
    """Through ``ServingEngine``: three slots, five sessions whose
    prompts end mid-window, on a window's end and past several, whose
    windows end at DIFFERENT decode steps, twice in a row each; prompts
    longer than a window are prefilled window by window (a run that
    fills its window leaves it compacted). Every served token is the
    reference's best at its position, by the logit gap that ``correct``
    reads on the chip, and equals ``generate()``'s."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=3, block_size=4, max_len=256)
    assert eng.chunk_len == WIN and max(eng.scheduler.buckets) == WIN
    rng = np.random.default_rng(1)
    lens, new = (50, 70, 31, 64, 100), (80, 90, 70, 75, 72)
    prompts = [rng.integers(0, 64, size=n) for n in lens]
    reqs = _drive(eng, prompts, new)
    assert eng.pool.reuse_count >= 2
    for p, r, k in zip(prompts, reqs, new):
        # two window ends inside every session's decode
        assert (len(p) + k - 1) // WIN - len(p) // WIN >= 2
        assert len(r.generated) == k
        assert _served_gap(w, p, r) < TOL
        want = np.asarray(m.generate(p[None], max_new_tokens=k).value)[0]
        assert (np.asarray(r.output_ids) == want).all()
    eng.pool.check_conservation()
    rep = eng.metrics.entry_cache_report()
    decode_ends = sum((len(p) + k - 1) // WIN - len(p) // WIN
                      for p, k in zip(prompts, new))
    prefill_ends = sum(len(p) // WIN for p in prompts)
    assert rep["compactions"] == decode_ends + prefill_ends
    # a decode-side compaction gives back (32 - 8) / 4 = 6 blocks
    assert rep["blocks_released"] == 6 * decode_ends
    snap = eng.metrics.snapshot()
    assert snap["cache_entries"] == rep
    assert snap["span_s"]["serving/compact_dispatch"] > 0
    text = eng.metrics.prometheus_text()
    assert f"serving_kv_bytes_per_token {3 * 2 * 4 * 16 * 4}" in text
    assert f"serving_cache_compactions_total {rep['compactions']}" in text
    assert "serving_cache_entries_live" in text
    assert "serving_cache_positions_live" in text
    assert "serving_cache_blocks_released_total" in text
    # nothing compiled after the first decode step's programs
    assert sorted(k[0] for k in eng._exec) == [
        "compact", "decode", "paged_prefill", "paged_prefill",
        "paged_prefill"] or set(k[0] for k in eng._exec) == {
        "compact", "decode", "paged_prefill"}


def test_released_blocks_are_back_and_serve_a_new_session(model_w):
    """After a compaction the window's blocks are in the free list
    again (while the slot lives on), conservation holds at every step,
    and a session admitted onto those blocks is still the reference."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=4, max_len=128,
                        async_depth=0)
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, 64, size=20)
    r1 = eng.add_request(p1, max_new_tokens=60)
    held, freed = [], set()
    while eng.step():
        eng.pool.check_conservation()
        row = set(eng.pool._slot_blocks.get(0, ()))
        if held and len(row) < len(held[-1]):
            gone = held[-1] - row
            assert gone <= set(eng.pool._free_blocks)    # back in the pool
            freed |= gone
            assert eng.pool.owner_of(0) is not None      # the slot lives
        held.append(row)
        if len(freed) >= 6 and len(eng.scheduler.queue) == 0 \
                and eng.pool.free_count == 1 and len(held) > 0 \
                and not hasattr(eng, "_second"):
            p2 = rng.integers(0, 64, size=40)
            eng._second = (p2, eng.add_request(p2, max_new_tokens=30))
    assert len(freed) >= 6
    p2, r2 = eng._second
    # the second session's blocks include ones the first gave back
    assert _served_gap(w, p1, r1) < TOL and _served_gap(w, p2, r2) < TOL
    assert eng.metrics.entry_cache_report()["blocks_released"] >= 6
    assert eng.pool.live_blocks == 0
    eng.pool.check_conservation()


@pytest.mark.parametrize("depth", [0, 1, 12])
def test_any_pipeline_depth_gives_the_same_tokens(model_w, depth):
    """With ``async_depth`` steps unread the step loop still knows, from
    its own counts, which slot's window ends before which step."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=256,
                        async_depth=depth)
    rng = np.random.default_rng(5)
    lens, new = (30, 45, 64, 9), (40, 50, 35, 30)
    prompts = [rng.integers(0, 64, size=n) for n in lens]
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    deepest = 0
    while eng.step():
        deepest = max(deepest, len(eng._pending_steps))
    assert deepest == depth and not eng._pending
    for p, r, k in zip(prompts, reqs, new):
        want = np.asarray(m.generate(p[None], max_new_tokens=k).value)[0]
        assert (np.asarray(r.output_ids) == want).all()
        assert _served_gap(w, p, r) < TOL
    eng.pool.check_conservation()


def test_a_common_prefix_is_not_shared(model_w):
    """A block is rewritten in place at a compaction: nothing is
    indexed, a second request with the same prompt prefills it all."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=128)
    p = np.random.default_rng(2).integers(0, 64, size=40)
    a, b = _drive(eng, [p, p.copy()], [5, 5])
    assert eng.pool.match_prefix(p) == 0 and len(eng.pool.index) == 0
    assert a.generated == b.generated
    assert _served_gap(w, p, b) < TOL


def test_sampling_program_runs_and_repeats(model_w):
    m, _ = model_w
    outs = []
    for _ in range(2):
        eng = ServingEngine(m, num_slots=2, block_size=8, max_len=128,
                            sampling=True)
        p = np.arange(40) % 64
        r = eng.add_request(p, max_new_tokens=30, temperature=0.8,
                            top_k=8, seed=11)
        eng.run()
        outs.append(list(r.generated))
    assert outs[0] == outs[1] and len(outs[0]) == 30


def test_a_supervisor_restart_replays_across_a_window_end(model_w):
    """A restart re-queues a session mid-decode: its prompt + served
    tokens are prefilled again by windows and the continuation is the
    same bytes."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=256)
    p = np.random.default_rng(9).integers(0, 64, size=28)
    r = eng.add_request(p, max_new_tokens=50)
    for _ in range(20):
        eng.step()
    assert 0 < len(r.generated) < 50
    eng._supervisor_restart("test")
    eng.run()
    want = np.asarray(m.generate(p[None], max_new_tokens=50).value)[0]
    assert (np.asarray(r.output_ids) == want).all()
    eng.pool.check_conservation()


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("option", [
    {"speculative": True}, {"role": "prefill"}],
    ids=["speculative", "role"])
def test_engine_refuses_an_option_without_a_program(model_w, option):
    with pytest.raises(ValueError, match="no program for"):
        ServingEngine(model_w[0], num_slots=2, **option)


@pytest.mark.parametrize("what", ["hold_kv", "export_kv", "import_kv"])
def test_engine_refuses_the_kv_wire(model_w, what):
    eng = ServingEngine(model_w[0], num_slots=2, block_size=8, max_len=64)
    with pytest.raises(NotImplementedError, match=what):
        if what == "hold_kv":
            eng.add_request(np.arange(5), max_new_tokens=2, hold_kv=True)
        elif what == "export_kv":
            eng.export_kv(0)
        else:
            eng.import_kv(b"", 4)


@pytest.mark.parametrize("kwargs,match", [
    ({"prefill_chunk": 16}, "prefilled by windows"),
    ({"buckets": [16, 64]}, "cannot exceed the cache window"),
    ({"block_size": 16}, "must divide a window's 8 summaries"),
    ({"block_size": 3}, "must divide a window's 8 summaries"),
], ids=["chunk", "bucket", "block16", "block3"])
def test_engine_refuses_sizes_the_window_cannot_take(model_w, kwargs,
                                                     match):
    with pytest.raises(ValueError, match=match):
        ServingEngine(model_w[0], num_slots=2, max_len=128,
                      **{"block_size": 8, **kwargs})


@pytest.mark.parametrize("key,value,name", [
    ("attention_class", "softmax", "attention_class"),
    ("num_chunks", 8, "num_chunks"),
    ("chunk_size", 5, "window_size % chunk_size"),
    ("num_key_value_heads", 2, "num_key_value_heads"),
    ("rope_scaling", {"type": "linear", "factor": 2.0}, "rope_scaling"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act"),
    ("fp32_ln", True, "fp32_ln"),
])
def test_config_refuses_what_it_has_no_equations_for(key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        eb.EvaByteConfig.from_hf(dict(HF, **{key: value}))
    cfg = dict(HF, **{key: value})
    if key not in ("fp32_ln",):
        with pytest.raises(NotImplementedError):
            ref.logits(W.make(0, HF, "float32"),
                       jnp.zeros((8,), jnp.int32), cfg)


def test_the_published_config_is_taken_whole():
    """Every key of the catalog's row, unread keys included."""
    hf = {"attention_bias": False, "attention_class": "eva",
          "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
          "fp32_skip_add": True, "hidden_act": "silu",
          "hidden_size": 4096, "init_cutoff_factor": None,
          "init_fn": "v2", "init_std": 0.01275,
          "intermediate_size": 11008, "lazy_init": True,
          "max_position_embeddings": 32768, "max_seq_length": 32768,
          "mixedp_attn": True, "model_type": "evabyte",
          "norm_add_unit_offset": True, "num_attention_heads": 32,
          "num_chunks": None, "num_hidden_layers": 32,
          "num_key_value_heads": 32, "num_pred_heads": 8,
          "rms_norm_eps": 1e-05, "rope_scaling": None,
          "rope_theta": 100000, "tie_word_embeddings": False,
          "vocab_size": 320, "window_size": 2048}
    cfg = eb.EvaByteConfig.from_hf(hf, dtype="bfloat16")
    assert (cfg.num_heads, cfg.head_dim, cfg.summaries_per_window) \
        == (32, 128, 128)
    assert cfg.entries(32767) == 3967 and cfg.initializer_range == 0.01275
    shapes = eb.param_shapes(cfg)
    assert shapes[("head",)][0] == (4096, 8 * 320)
    assert shapes[("layers", "mu")][0] == (32, 32, 128)
    assert sum(int(np.prod(s)) for s, _, _ in shapes.values()) \
        == 32 * 202_391_552 + 320 * 4096 + 4096 + 8 * 320 * 4096


# ------------------------------------------------- the shared decode kernel
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", [True])


@pytest.mark.parametrize("dtype,BS,tol", [(jnp.float32, 32, 2e-6),
                                          (jnp.bfloat16, 64, 2e-2)],
                         ids=["f32", "bf16"])
def test_paged_kernel_at_32_heads_with_lengths_that_are_not_positions(
        interpret, dtype, BS, tol):
    """``paged_decode_attention`` as it stands, at this model's 32 heads
    of 128 in the largest blocks its chunk buffers hold (64 entries in
    bf16, one block a chunk; 128 would not fit), with ``lengths`` =
    ENTRIES: summaries + a window's part, a released slot's 0, a row of
    which only some blocks are held."""
    rng = np.random.default_rng(0)
    S, H, hd, MB, NB = 4, 32, 128, 5, 24
    assert pa.blocks_per_chunk(H, hd, BS, MB, dtype) == 1
    assert pa.blocks_per_chunk(H, hd, 2 * BS, MB, dtype) == 0
    q = jnp.asarray(rng.normal(size=(S, H, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(NB, H, BS, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(NB, H, BS, hd)), dtype)
    tables = rng.permutation(np.arange(1, NB))[:S * MB].reshape(S, MB)
    tables[1, 2:] = 0                     # two blocks held, rest trash
    tables[3, :] = 0                      # a released slot
    tables = jnp.asarray(tables, jnp.int32)
    # e.g. position 2,100 of a (2048, 16) model: 128 summaries + 53
    lengths = jnp.asarray([min(128 + 53, MB * BS - 7), 2 * BS, MB * BS,
                           0], jnp.int32)
    want = attn_ops.cached_paged_attention(q, k, v, tables, lengths)
    got = pa.paged_decode_attention(q, k, v, tables, lengths)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32))[:3].max() < tol


def test_engine_with_the_kernel_in_interpret_mode(interpret):
    """The decode program with the kernel in it (interpret mode, heads
    of 128) serves across a window's end what ``generate()`` picks."""
    m, w, hf = _model(seed=1, hidden_size=256, num_attention_heads=2,
                      num_key_value_heads=2, num_hidden_layers=2)
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=128)
    p = np.arange(27) % 64
    (r,) = _drive(eng, [p], [12])
    want = np.asarray(m.generate(p[None], max_new_tokens=12).value)[0]
    assert (np.asarray(r.output_ids) == want).all()
    assert _served_gap(w, p, r, hf) < TOL


def test_engine_kernel_places_entries_like_the_gather_path(monkeypatch):
    """The decode program whose kernel places the step's new ENTRY
    (interpret mode, heads of 128) against the ``jnp`` block write and
    the gather: two slots, five sessions, so slots are released and
    taken again; a prompt of 70 prefilled window by window (its slot
    parked in between); every session decodes across a window's end, so
    entries are written after a compaction took blocks back, and across
    block boundaries. The same tokens on both, each the reference's
    best."""
    m, w, hf = _model(seed=1, hidden_size=256, num_attention_heads=2,
                      num_key_value_heads=2, num_hidden_layers=2)
    rng = np.random.default_rng(43)
    lens, new = (27, 70, 40, 33, 12), (40, 20, 38, 36, 25)
    prompts = [rng.integers(0, 64, size=n) for n in lens]
    served = {}
    for kernel in (True, False):
        monkeypatch.setattr(pa, "_FORCE_INTERPRET", [kernel])
        eng = ServingEngine(m, num_slots=2, block_size=8, max_len=128)
        reqs = _drive(eng, prompts, new)
        assert eng.pool.reuse_count >= 2
        assert eng.metrics.entry_cache_report()["compactions"] >= 5
        served[kernel] = [np.asarray(r.output_ids) for r in reqs]
        for p, r in zip(prompts, reqs):
            assert _served_gap(w, p, r, hf) < TOL
        eng.pool.check_conservation()
    for a, b in zip(served[True], served[False]):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------- where a projection rounds
def test_projections_round_to_bf16_straight_after_the_dot():
    """``wq``, ``wk``, ``wv`` of a decode layer in bfloat16: each dot
    asks for float32 (the form XLA streams a stacked weight in), and
    what it gives is rounded to bfloat16 before the rotary or anything
    else reads it, so q, k and v hold what ``jnp.dot(h, w)`` gave."""
    from jaxpr_check import assert_same_bf16_rounding, rounded_projections
    cfg = eb.EvaByteConfig.from_hf(HF, dtype="bfloat16")
    w = W.make(5, HF, "bfloat16")
    p = {n: a[1] for n, a in w["layers"].items()}
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(6, cfg.hidden_size)), jnp.float32)
    pos = jnp.arange(40, 46, dtype=jnp.int32)

    class Access:
        def decode(self, state, layer, positions, q, k, v, kernel):
            return state, q.astype(jnp.float32)

    names = ("wq", "wk", "wv")
    got = rounded_projections(
        lambda p, x: eb.attention(cfg, p, x, pos, Access(), (), 0, 0,
                                  "decode")[0],
        (p, x), [p[n] for n in names])
    h = eb.norm(cfg, x, p["norm1"])
    assert h.dtype == jnp.bfloat16
    for y, n in zip(got, names):
        assert_same_bf16_rounding(y, h, p[n])


# ------------------------------------------------------ correct's teeth
def test_a_float8_control_fails_where_bfloat16_passes():
    """What the cell's ``correct_limits`` must tell apart, on the
    reference's own numbers at the small size: its first choices
    computed in bfloat16 lie within a tenth of the distance from its
    float32 best that its float8 (e4m3) choices lie."""
    _, w, hf = _model(seed=4)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 64, size=(96,)),
                      jnp.int32)
    gaps = {}
    for prec in ("bfloat16", "float8"):
        _, _, first = ref.score(w, ids, ids, hf, prec)
        best, at, _ = ref.score(w, ids, first, hf, "float32")
        gaps[prec] = float(np.asarray(best - at).mean())
    assert gaps["float8"] > 10 * max(gaps["bfloat16"], 1e-6)
