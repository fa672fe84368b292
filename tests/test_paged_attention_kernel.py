"""Pallas paged decode-attention kernel (ops/paged_attention.py):
interpret-mode parity vs the XLA gather oracle across block sizes /
ragged lengths around the chunk boundaries / parked slots / trash rows
/ recycled slots / dtypes, the guard that chooses it, the f32
score-accumulation precision fix, engine-level greedy parity + zero
steady-state compiles on the observed choice, and the roofline layout
binding."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability.perf import roofline as rf
from paddle_tpu.ops import attention as attn_ops
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig


@pytest.fixture
def interpret_kernel():
    pa._FORCE_INTERPRET[0] = True
    yield
    pa._FORCE_INTERPRET[0] = False


@pytest.fixture
def two_block_chunks(monkeypatch):
    """Shrink the chunk budget so that the tiny shapes here walk several
    chunks of G = 2 blocks a slot (the real budget would hold a whole
    slot of them in one)."""
    def budget(nh, hd, BS, itemsize):
        monkeypatch.setattr(pa, "_CHUNK_VMEM_BYTES",
                            2 * 4 * nh * BS * hd * itemsize)
    return budget


def _paged_case(seed, S, nh, hd, BS, MB, lengths=None, trash_fill=0.0):
    """A pool + tables fixture in the engine's layout: block 0 is the
    reserved trash block (filled with ``trash_fill`` garbage), slot s
    owns blocks ``1 + s*MB ..`` for its live prefix, padding table
    entries point at trash — exactly what a recycled slot sees."""
    rs = np.random.RandomState(seed)
    NB = S * MB + 1
    kc = rs.randn(NB, nh, BS, hd).astype(np.float32)
    vc = rs.randn(NB, nh, BS, hd).astype(np.float32)
    kc[0] = trash_fill
    vc[0] = trash_fill
    q = rs.randn(S, nh, hd).astype(np.float32)
    if lengths is None:
        lengths = rs.randint(1, MB * BS + 1, S)
    lengths = np.asarray(lengths, np.int32)
    tables = np.zeros((S, MB), np.int32)   # pad entries -> trash
    for s in range(S):
        used = (int(lengths[s]) + BS - 1) // BS
        tables[s, :used] = 1 + s * MB + np.arange(used)
    return q, kc, vc, tables, lengths


def _check_parity(q, kc, vc, tables, lens, dtype):
    import jax.numpy as jnp
    dt = jnp.dtype(dtype)
    q, kc, vc = (jnp.asarray(q, dt), jnp.asarray(kc, dt),
                 jnp.asarray(vc, dt))
    ref = attn_ops.cached_paged_attention(q, kc, vc, jnp.asarray(tables),
                                          jnp.asarray(lens))
    out = pa.paged_decode_attention(q, kc, vc, jnp.asarray(tables),
                                    jnp.asarray(lens))
    assert out.shape == q.shape and out.dtype == q.dtype
    ref32 = np.asarray(ref, np.float32)
    out32 = np.asarray(out, np.float32)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out32, ref32, rtol=tol, atol=tol)
    np.testing.assert_array_equal(out32.argmax(-1), ref32.argmax(-1))


@pytest.mark.parametrize("S,nh,hd,BS,MB", [
    (4, 4, 8, 8, 4),     # the tier-1 engine shape
    (3, 2, 16, 4, 5),    # odd slot count, small blocks
    (2, 4, 8, 16, 2),    # wide blocks
    (5, 1, 32, 8, 3),    # single head
])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_matches_gather_oracle(interpret_kernel, S, nh, hd, BS,
                                      MB, dtype):
    """Parity matrix: the kernel's output matches
    cached_paged_attention over ragged per-slot lengths (mid-block
    tails included) and trash-padded tables, in f32 and bf16 —
    numerically tight, and bit-exact on the argmax (the greedy
    contract)."""
    lengths = [1, BS, BS + 1, MB * BS, max(1, MB * BS - 3)][:S]
    assert pa.kernel_viable(nh, hd, BS, dtype)
    _check_parity(*_paged_case(7, S, nh, hd, BS, MB, lengths=lengths),
                  dtype)


# (name, length as a function of BS, G, capacity)
_CHUNK_EDGE_LENGTHS = [
    ("one", lambda BS, G, C: 1),
    ("one_block", lambda BS, G, C: BS),
    ("chunk_minus_1", lambda BS, G, C: G * BS - 1),
    ("chunk", lambda BS, G, C: G * BS),
    ("chunk_plus_1", lambda BS, G, C: G * BS + 1),
    ("capacity", lambda BS, G, C: C),
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_chunk_edges_in_one_batch(interpret_kernel,
                                         two_block_chunks, dtype):
    """Every length at which the chunk walk changes shape, side by side
    in one batch (so each slot's last chunk hands the buffers on to a
    neighbour of another length): 1, one block, one short of a chunk, a
    chunk, one over, full capacity; G = 2 blocks a chunk, 3 chunks a
    full slot."""
    S, nh, hd, BS, MB = 6, 2, 16, 8, 6
    two_block_chunks(nh, hd, BS, 4 if dtype == "float32" else 2)
    G = pa.blocks_per_chunk(nh, hd, BS, MB, dtype)
    assert G == 2
    lengths = [f(BS, G, MB * BS) for _, f in _CHUNK_EDGE_LENGTHS]
    _check_parity(*_paged_case(5, S, nh, hd, BS, MB, lengths=lengths),
                  dtype)


@pytest.mark.parametrize("name,length", _CHUNK_EDGE_LENGTHS,
                         ids=[n for n, _ in _CHUNK_EDGE_LENGTHS])
def test_kernel_whole_batch_at_one_chunk_edge(interpret_kernel,
                                              two_block_chunks, name,
                                              length):
    """The same edges with every slot at the SAME length: the hand-over
    between slots then always meets a chunk count of its own kind (all
    one chunk, all three), bf16 pool."""
    S, nh, hd, BS, MB = 3, 2, 16, 16, 6
    two_block_chunks(nh, hd, BS, 2)
    n = length(BS, pa.blocks_per_chunk(nh, hd, BS, MB, "bfloat16"),
               MB * BS)
    _check_parity(*_paged_case(13, S, nh, hd, BS, MB, lengths=[n] * S),
                  "bfloat16")


@pytest.mark.parametrize("order", ["parked_first", "full_first"])
def test_kernel_parked_slot_beside_a_full_one(interpret_kernel,
                                              two_block_chunks, order):
    """A parked slot (length 1: one live block, the rest of its chunk
    never copied) between full ones, in both orders: what the chunk
    buffer still holds of the neighbour's keys and values carries
    exactly zero weight."""
    S, nh, hd, BS, MB = 4, 2, 16, 8, 6
    two_block_chunks(nh, hd, BS, 2)
    lengths = [1, MB * BS, 1, MB * BS]
    if order == "full_first":
        lengths = lengths[::-1]
    _check_parity(*_paged_case(17, S, nh, hd, BS, MB, lengths=lengths),
                  "bfloat16")


@pytest.mark.parametrize("lengths", [
    [0, 40, 0, 0, 48, 0], [33, 0, 0, 1, 0, 17], [0, 0, 0, 0, 0, 0]],
    ids=["idle_first_and_last", "idle_runs_between", "all_idle"])
def test_kernel_passes_by_slots_with_nothing_live(interpret_kernel,
                                                  two_block_chunks,
                                                  lengths):
    """Length 0 (a released slot) is passed by: no copy, no arithmetic,
    a finite row of zeros; the chunk buffers' hand-over from slot to
    slot goes through any run of such slots, so the live slots around
    them still match the oracle."""
    import jax.numpy as jnp
    S, nh, hd, BS, MB = 6, 2, 16, 8, 6
    two_block_chunks(nh, hd, BS, 2)
    q, kc, vc, tables, _ = _paged_case(19, S, nh, hd, BS, MB,
                                       lengths=[MB * BS] * S)
    lens = np.asarray(lengths, np.int32)
    tables[lens == 0] = 0       # a released row is all trash
    dt = jnp.bfloat16
    q, kc, vc = (jnp.asarray(q, dt), jnp.asarray(kc, dt),
                 jnp.asarray(vc, dt))
    out = np.asarray(pa.paged_decode_attention(
        q, kc, vc, jnp.asarray(tables), jnp.asarray(lens)), np.float32)
    ref = np.asarray(attn_ops.cached_paged_attention(
        q, kc, vc, jnp.asarray(tables), jnp.asarray(lens)), np.float32)
    live = lens > 0
    np.testing.assert_allclose(out[live], ref[live], rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(out[~live], 0.0)


def test_kernel_ignores_trash_and_recycled_rows(interpret_kernel):
    """Adversarial occupancy: the trash block and every beyond-length
    row filled with huge garbage (a recycled slot's previous tenant).
    The length mask must keep the kernel's output identical to a pool
    where those rows are zero — garbage carries exactly-zero weight."""
    import jax.numpy as jnp
    S, nh, hd, BS, MB = 3, 2, 8, 4, 3
    q, kc, vc, tables, lens = _paged_case(
        11, S, nh, hd, BS, MB, lengths=[3, 5, BS * MB],
        trash_fill=1e4)
    # poison beyond-length rows inside each slot's own blocks too
    for s in range(S):
        for col in range(MB):
            b = tables[s, col]
            if b == 0:
                continue
            for off in range(BS):
                if col * BS + off >= lens[s]:
                    kc[b, :, off] = 1e4
                    vc[b, :, off] = 1e4
    poisoned = pa.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(tables), jnp.asarray(lens))
    kc2, vc2 = kc.copy(), vc.copy()
    kc2[0] = 0.0
    vc2[0] = 0.0
    for s in range(S):
        for col in range(MB):
            b = tables[s, col]
            if b == 0:
                continue
            for off in range(BS):
                if col * BS + off >= lens[s]:
                    kc2[b, :, off] = 0.0
                    vc2[b, :, off] = 0.0
    clean = pa.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kc2), jnp.asarray(vc2),
        jnp.asarray(tables), jnp.asarray(lens))
    np.testing.assert_array_equal(np.asarray(poisoned),
                                  np.asarray(clean))
    assert np.isfinite(np.asarray(poisoned)).all()


# ---------------------------------------------- the kernel places the entry
# (nq, nh, hd, d2, BS): one query head a KV head (the GPT's, EvaByte's),
# grouped queries (nemotron_h's), a key in two parts (mimo_v2's: blocks
# of 256, so that the transposed part has two tiles of 128 lanes)
_WRITE_KINDS = {"one_head_a_group": (4, 4, 32, 0, 32),
                "grouped": (8, 2, 32, 0, 32),
                "two_part_key": (8, 2, 32, 16, 256)}
_MB = 4     # blocks a slot; chunks of G = 2 blocks
# per case, every slot's (length, write position) from a block's, a
# chunk's and a row's positions. The position is length - 1 where None
# (the entry is live)
_WRITE_CASES = {
    # the first row of a block the slot has just been given (the second
    # of a chunk, the first of the next chunk)
    "block_first_row": lambda BS, T, C: [
        (BS + 1, None), (T + 1, None), (T + BS + 1, None), (7, None)],
    "block_last_row": lambda BS, T, C: [
        (BS, None), (T, None), (C, None), (T + BS, None)],
    "chunk_edge": lambda BS, T, C: [
        (T, None), (T + 1, None), (T - 1, None), (T + 2, None)],
    "length_one": lambda BS, T, C: [
        (1, None), (C, None), (1, None), (BS + 3, None)],
    # a parked slot (its position past the row; it attends what it
    # holds) and a released one (nothing live) beside a full one
    "parked_and_released": lambda BS, T, C: [
        (C, None), (T, C + 3), (0, C + 9), (BS + 5, None)],
}


def _write_case(kind, case, dtype, seed=0):
    import jax.numpy as jnp
    nq, nh, hd, d2, BS = _WRITE_KINDS[kind]
    slots = _WRITE_CASES[case](BS, 2 * BS, _MB * BS)
    lens = np.asarray([n for n, _ in slots], np.int32)
    wpos = np.asarray([n - 1 if w is None else w for n, w in slots],
                      np.int32)
    S = len(slots)
    q, kc, vc, tables, _ = _paged_case(seed, S, nh, hd, BS, _MB, lens)
    rs = np.random.RandomState(seed + 1)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rs.randn(S, nq, hd), dt)
    pools = [jnp.asarray(kc, dt), jnp.asarray(vc, dt)]
    new = [jnp.asarray(rs.randn(S, nh, hd), dt) for _ in range(2)]
    q2 = None
    if d2:
        q2 = jnp.asarray(rs.randn(S, nq, d2), dt)
        pools.append(jnp.asarray(rs.randn(S * _MB + 1, nh, d2, BS), dt))
        new.append(jnp.asarray(rs.randn(S, nh, d2), dt))
    return (q, q2, tuple(pools), tuple(new), jnp.asarray(tables),
            jnp.asarray(lens), jnp.asarray(wpos))


@pytest.mark.parametrize("case", list(_WRITE_CASES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kind", list(_WRITE_KINDS))
def test_kernel_places_the_new_entry(interpret_kernel, monkeypatch, kind,
                                     dtype, case):
    """The kernel with the write against the ``jnp`` block write, then
    ``cached_paged_attention``: outputs of every slot with something
    live as the oracle's; every pool EXACTLY what it was with the live
    slots' entries at their positions and nothing else changed (not a
    parked or released slot's block, not the trash block, not another
    row of the tile that was written back); every live entry bit-equal
    to the oracle's pools."""
    nq, nh, hd, d2, BS = _WRITE_KINDS[kind]
    itemsize = 4 if dtype == "float32" else 2
    monkeypatch.setattr(pa, "_CHUNK_VMEM_BYTES",
                        2 * 2 * nh * BS * (2 * hd + d2) * itemsize)
    assert pa.blocks_per_chunk(nh, hd, BS, _MB, dtype, d2) == 2
    q, q2, pools, new, tables, lens, wpos = _write_case(kind, case, dtype)
    live = pa.live_write_pos(wpos, lens)
    got, got_pools = pa.paged_write_attention(
        q, new, pools, tables, live, lens, True, q_rot=q2)
    want, want_pools = pa.paged_write_attention(
        q, new, pools, tables, live, lens, False, q_rot=q2)
    lens, live, tables = (np.asarray(a) for a in (lens, live, tables))
    np.testing.assert_array_equal(live >= 0, np.asarray(wpos) == lens - 1)
    tol = 1e-5 if dtype == "float32" else 2e-2
    some = lens > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[some],
                               np.asarray(want, np.float32)[some],
                               rtol=tol, atol=tol)
    assert not np.asarray(got, np.float32)[~some].any()   # rows of zeros
    for i, (pool, entry) in enumerate(zip(pools, new)):
        expect = np.array(pool.astype("float32"))
        for s in np.nonzero(live >= 0)[0]:
            blk, off = tables[s, live[s] // BS], live[s] % BS
            row = np.asarray(entry.astype("float32"))[s]
            if i == 2:
                expect[blk, :, :, off] = row
            else:
                expect[blk, :, off] = row
        have = np.asarray(got_pools[i].astype("float32"))
        np.testing.assert_array_equal(have, expect)
        # the oracle's pool at every live position of every slot
        oracle = np.asarray(want_pools[i].astype("float32"))
        for s in np.nonzero(some)[0]:
            for b in range(-(-lens[s] // BS)):
                n = min(BS, lens[s] - b * BS)
                rows = np.s_[..., :n] if i == 2 else np.s_[:, :n]
                np.testing.assert_array_equal(
                    have[tables[s, b]][rows], oracle[tables[s, b]][rows])


def test_read_only_call_traces_no_write(interpret_kernel):
    """Without a new entry the call is what it was: one result, no
    aliased pool, no third scalar, no write-back semaphore."""
    import jax
    q, kc, vc, tables, lens = _paged_case(0, 2, 4, 32, 8, 2)
    text = str(jax.make_jaxpr(pa.paged_decode_attention)(
        q, kc, vc, tables, lens))
    assert "input_output_aliases=()" in text
    wrote = str(jax.make_jaxpr(
        lambda *a: pa.paged_decode_attention(
            *a, new=(q, q), write_pos=lens - 1))(q, kc, vc, tables, lens))
    assert "input_output_aliases=((4, 1), (5, 2))" in wrote


def test_guard_resolution(monkeypatch):
    """kernel_viable is the only gate: the CPU without forced interpret
    refuses (tier-1 runs the XLA gather); f64 refuses even forced; on a
    backend that has Mosaic the shapes decide (whole-tile blocks, heads
    that fill the lanes, one block inside the chunk budget). No option
    and no environment name turns the kernel on or off."""
    import inspect
    import jax
    assert jax.default_backend() == "cpu"
    assert not pa.kernel_viable(4, 8, 8, np.float32)
    pa._FORCE_INTERPRET[0] = True
    try:
        assert pa.kernel_viable(4, 8, 8, np.float32)
        assert not pa.kernel_viable(4, 8, 8, np.float64)
    finally:
        pa._FORCE_INTERPRET[0] = False
    monkeypatch.setattr(pa.jax, "default_backend", lambda: "tpu")
    assert pa.kernel_viable(16, 128, 16, "bfloat16")   # the 1.3B cell
    assert pa.kernel_viable(16, 128, 8, np.float32)
    assert not pa.kernel_viable(16, 128, 8, "bfloat16")  # half a tile
    assert not pa.kernel_viable(12, 64, 16, "bfloat16")  # half the lanes
    assert not pa.kernel_viable(64, 256, 64, np.float32)  # over budget
    assert pa.blocks_per_chunk(16, 128, 16, 64, "bfloat16") == 8
    assert pa.blocks_per_chunk(16, 128, 16, 4, "bfloat16") == 4
    assert not hasattr(pa, "kernel_requested")
    assert "environ" not in inspect.getsource(pa)
    from paddle_tpu.serving.engine import ServingConfig
    assert "paged_attn" not in inspect.signature(
        ServingConfig.__init__).parameters
    with pytest.raises(TypeError):
        ServingConfig(paged_attn=True)


def test_cached_attention_scores_accumulate_f32():
    """The precision satellite: bf16 caches must score in f32 (the
    _dot_f32 discipline), so the bf16 path lands within bf16
    input-rounding distance of the f32 oracle — and the f32 path is
    unchanged bit-for-bit by the preferred_element_type annotation."""
    import jax.numpy as jnp
    rs = np.random.RandomState(3)
    S, nh, C, hd = 4, 2, 64, 32
    q = rs.randn(S, nh, hd).astype(np.float32)
    k = rs.randn(S, nh, C, hd).astype(np.float32)
    v = rs.randn(S, nh, C, hd).astype(np.float32)
    lens = np.array([1, 17, 40, 64], np.int32)
    oracle = attn_ops.cached_slot_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(lens))
    assert oracle.dtype == jnp.float32
    out_bf16 = attn_ops.cached_slot_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), jnp.asarray(lens))
    # bf16 inputs, f32 accumulation: error stays at input-rounding
    # scale (~2^-8 relative) — bf16 score accumulation over 64
    # positions would be an order of magnitude worse
    np.testing.assert_allclose(np.asarray(out_bf16, np.float32),
                               np.asarray(oracle), rtol=4e-2,
                               atol=4e-2)


def _tiny_model(seed=7):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _ref(m, prompt, n_new):
    out = m.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                     max_new_tokens=n_new, temperature=0.0)
    return np.asarray(out.numpy())[0]


@pytest.mark.parametrize("async_depth", [0, 1])
def test_engine_kernel_greedy_parity_zero_compiles(interpret_kernel,
                                                   async_depth,
                                                   monkeypatch):
    """Engine-level contract on the engine's own choice (forced
    interpret makes ``kernel_viable`` say yes on the CPU; sync and
    async schedules): every stream bit-exact with generate(), zero
    steady-state compiles (watchdog raise-mode), and the perf report
    binds the paged_pallas layout + a decode roofline fraction."""
    # the CPU has no peaks of its own; state some so the roofline
    # join (cost x measured wall x peaks) is exercised
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_BPS", "819e9")
    m = _tiny_model()
    eng = ServingEngine(m, num_slots=4, bucket_min=8,
                        block_size=8, async_depth=async_depth,
                        watchdog_mode="raise")
    assert eng.paged_attn and eng.decode_layout == "paged_pallas"
    rs = np.random.RandomState(0)
    specs = [(3, 6), (11, 9), (7, 4), (5, 8), (13, 5)]
    for wave in range(2):        # wave 1 runs under raise-mode
        reqs = []
        for plen, n_new in specs:
            prompt = rs.randint(1, 96, (plen,)).astype(np.int64)
            reqs.append((eng.add_request(prompt, max_new_tokens=n_new),
                         _ref(m, prompt, n_new)))
        eng.run()
        if wave == 0:
            eng.declare_warmup()
        for r, want in reqs:
            np.testing.assert_array_equal(np.asarray(r.output_ids),
                                          want)
    wd = eng.watchdog.report()
    assert wd["steady_state_compiles"] == 0
    rep = eng.metrics.perf_report()
    model = rep["decode_roofline"]["model"]
    assert model["layout"] == "paged_pallas"
    assert model["gather_factor"] == 1.0
    assert rep["decode_roofline"]["achieved_fraction"] is not None
    assert rep["programs"]["decode"]["roofline_fraction"] is not None
    state = eng.debug_state()
    assert state["paged_attn"] is True
    assert state["decode_layout"] == "paged_pallas"


def test_engine_keeps_gather_where_guard_refuses():
    """On CPU tier-1 the guard refuses (no Mosaic), so the engine
    is built on the XLA gather path and says so, in its state and in
    what its roofline prices."""
    m = _tiny_model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        block_size=8)
    assert not eng.paged_attn
    assert eng.decode_layout == "paged_xla"
    assert eng.debug_state()["paged_attn"] is False
    model = eng.metrics.perf_report()["decode_roofline"]["model"]
    assert model["layout"] == "paged_xla"
    assert model["gather_factor"] == rf.PAGED_GATHER_FACTOR

def test_released_slot_costs_the_kernel_nothing(interpret_kernel):
    """A released slot's position keeps counting while its table row is
    all trash. The decode program hands attention no more than the
    blocks a row holds, so the kernel is asked for nothing of it (length
    0) and not for a capacity of trash; live slots' lengths are their
    positions, as before. Its new entry is nobody's either: the write
    position it is handed is -1."""
    import jax.numpy as jnp
    from paddle_tpu.serving.paged.programs import build_paged_fns
    m = _tiny_model()
    S, BS, MB = 3, 8, 4
    NB = S * MB + 1
    seen = {}
    real = pa.paged_decode_attention

    def spy(q, kf, vf, tables, lengths, **placed):
        seen["lengths"] = lengths
        seen["write_pos"] = placed["write_pos"]
        return real(q, kf, vf, tables, lengths, **placed)

    pa.paged_decode_attention = spy
    try:
        _, decode = build_paged_fns(m.cfg, S, BS, NB, MB,
                                    attn_kernel=True)
        params = m.export_decode_params()
        L, nh = m.cfg.num_layers, m.cfg.num_heads
        hd = m.cfg.hidden_size // nh
        pool = jnp.zeros((L, NB, nh, BS, hd), jnp.float32)
        tables = np.zeros((S, MB), np.int32)
        tables[0, :2] = [1, 2]          # live, 2 blocks held
        tables[2, :4] = [3, 4, 5, 6]    # live, full row held
        pos = jnp.asarray([9, 27, 31], jnp.int32)   # slot 1 released
        import jax
        with jax.disable_jit():
            decode(params, jnp.zeros((S,), jnp.int32), pos,
                   jnp.asarray(tables), pool, pool)
    finally:
        pa.paged_decode_attention = real
    np.testing.assert_array_equal(np.asarray(seen["lengths"]),
                                  [10, 0, 32])
    # and its entry goes nowhere; the live slots' to their positions
    np.testing.assert_array_equal(np.asarray(seen["write_pos"]),
                                  [9, -1, 31])


def test_roofline_paged_pallas_layout():
    """Roofline honesty: paged_pallas prices gather factor 1.0 and no
    max-len over-read (live_kv_len caps the read), paged_xla keeps
    the 3x factor and is the default, and a layout the model does
    not price (the slot pool's "contiguous" among them) is refused."""
    base = 2 * 12 * 12 * 64 * 1024 * 2
    assert rf.kv_read_bytes_per_token(
        1024, 12, 12, 64, layout="paged_xla") == \
        rf.PAGED_GATHER_FACTOR * base
    assert rf.kv_read_bytes_per_token(
        1024, 12, 12, 64, layout="paged_pallas") == base
    assert rf.kv_read_bytes_per_token(
        1024, 12, 12, 64) == rf.PAGED_GATHER_FACTOR * base
    for unknown in ("paged_mosaic", "contiguous", None):
        with pytest.raises(ValueError, match="unknown KV layout"):
            rf.resolve_layout(unknown)
    kw = dict(batch=8, kv_len=1024, num_layers=12, num_heads=12,
              head_dim=64, n_params=124e6, peak_flops=197e12,
              hbm_bps=819e9)
    xla = rf.decode_step_model(layout="paged_xla", **kw)
    pallas = rf.decode_step_model(layout="paged_pallas",
                                  live_kv_len=256, **kw)
    whole = rf.decode_step_model(layout="paged_pallas", **kw)
    assert xla == rf.decode_step_model(**kw)
    assert xla["layout"] == "paged_xla"
    assert pallas["layout"] == "paged_pallas"
    assert pallas["gather_factor"] == 1.0
    assert pallas["kv_len_read"] == 256   # no max-len over-read
    assert xla["kv_len_read"] == 1024     # over-read is xla's price
    assert pallas["bytes_total"] < whole["bytes_total"] \
        < xla["bytes_total"]
    assert pallas["floor_s"] < xla["floor_s"]


def _reader_ctx(ops, decode_calls=100):
    """What ``benchmarks/run.py`` hands a per-layer reader, cut to what
    the two paged-attention readers take: a reduced trace, the client's
    records, the traced window's bounds, model sizes and peaks."""
    class Rec:
        def __init__(self, prompt_len, stamps):
            self.spec = {"prompt": [0] * prompt_len}
            self.stamps = stamps
    # two sequences: stamps[0] is prefill's token; the decode steps
    # inside the traced window [10, 20) read p + j positions each
    recs = [Rec(100, [9.0, 10.5, 11.5, 25.0]),     # 101 + 102
            Rec(300, [9.5, 12.0])]                 # 301
    return {
        "trace": {"ops": ops, "programs": {"jit_paged_decode(1)": {
            "seconds": 1.0, "calls": decode_calls, "durations_s": []}}},
        "programs": {"decode": "jit_paged_decode"},
        "trace_bounds": (10.0, 20.0),
        "run": {"recs": recs},
        "model": {"hidden_size": 2048, "num_hidden_layers": 24,
                  "num_attention_heads": 16, "intermediate_size": 8192,
                  "vocab_size": 50304, "max_position_embeddings": 1024},
        "kv_bytes_per_value": 2,
        "peaks": {"hbm_bytes_per_s": 819e9},
    }


def test_benchmark_readers_time_the_kernel_by_name():
    """``paged_attn_dev_ms_per_step`` is the self time of the ops whose
    instruction NAME holds ``paged_decode_attn`` per decode execution;
    ``paged_attn_roofline`` the live K/V bytes of the traced steps at
    the HBM bandwidth over that time. A program without the kernel (the
    parent's gather, the CPU) reads None from both, not zero."""
    from benchmarks.metrics import (paged_attn_dev_ms_per_step,
                                    paged_attn_roofline)
    ops = {
        "%paged_decode_attn.8 = bf16[24,16,128] custom-call(...)":
            {"seconds": 0.05, "calls": 2400},
        "%fusion.1 = bf16[8] fusion(%paged_decode_attn.8)":
            {"seconds": 9.0, "calls": 100},   # names it only as operand
    }
    ctx = _reader_ctx(ops)
    ms = paged_attn_dev_ms_per_step.read(ctx)
    assert ms == pytest.approx(0.5)
    live = (101 + 102 + 301) / 100          # positions a decode step
    nbytes = 2 * 24 * 2048 * live * 2
    assert paged_attn_roofline.read(ctx) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.5e-3)
    gather = _reader_ctx({"%copy.19 = f32[24,16,64,16,128] copy(...)":
                          {"seconds": 6.9, "calls": 100}})
    assert paged_attn_dev_ms_per_step.read(gather) is None
    assert paged_attn_roofline.read(gather) is None
    untraced = dict(ctx, trace=None)
    assert paged_attn_dev_ms_per_step.read(untraced) is None
    assert paged_attn_roofline.read(untraced) is None
