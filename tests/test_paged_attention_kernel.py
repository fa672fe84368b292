"""Pallas paged decode-attention kernel (ops/paged_attention.py):
interpret-mode parity vs the XLA gather oracle across block sizes /
ragged lengths / trash rows / recycled slots / dtypes, the
gate-and-guard resolution, the f32 score-accumulation precision fix,
engine-level greedy parity + zero steady-state compiles with the
kernel enabled, and the roofline layout binding."""
import os

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
    import jax.numpy as jnp
    lengths = [1, BS, BS + 1, MB * BS, max(1, MB * BS - 3)][:S]
    q, kc, vc, tables, lens = _paged_case(7, S, nh, hd, BS, MB,
                                          lengths=lengths)
    dt = jnp.dtype(dtype)
    q, kc, vc = (jnp.asarray(q, dt), jnp.asarray(kc, dt),
                 jnp.asarray(vc, dt))
    assert pa.kernel_viable(nh, hd, BS, dt)
    ref = attn_ops.cached_paged_attention(q, kc, vc,
                                          jnp.asarray(tables),
                                          jnp.asarray(lens))
    out = pa.paged_decode_attention(q, kc, vc, jnp.asarray(tables),
                                    jnp.asarray(lens))
    assert out.shape == (S, nh, hd) and out.dtype == q.dtype
    ref32 = np.asarray(ref, np.float32)
    out32 = np.asarray(out, np.float32)
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out32, ref32, rtol=tol, atol=tol)
    np.testing.assert_array_equal(out32.argmax(-1), ref32.argmax(-1))


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


def test_guard_and_gate_resolution(monkeypatch):
    """kernel_viable: CPU without forced interpret refuses (tier-1's
    default measured path stays the XLA fallback); f64 refuses even
    forced; the env gate defaults off and PADDLE_PAGED_ATTN=1 or the
    config knob turns it on."""
    import jax
    assert jax.default_backend() == "cpu"
    assert not pa.kernel_viable(4, 8, 8, np.float32)
    pa._FORCE_INTERPRET[0] = True
    try:
        assert pa.kernel_viable(4, 8, 8, np.float32)
        assert not pa.kernel_viable(4, 8, 8, np.float64)
    finally:
        pa._FORCE_INTERPRET[0] = False
    monkeypatch.delenv("PADDLE_PAGED_ATTN", raising=False)
    assert not pa.kernel_requested(None)
    assert pa.kernel_requested(True)
    monkeypatch.setenv("PADDLE_PAGED_ATTN", "1")
    assert pa.kernel_requested(None)
    assert not pa.kernel_requested(False)   # knob overrides env


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
    """Engine-level contract with the gate on (sync and async
    schedules): every stream bit-exact with generate(), zero
    steady-state compiles (watchdog raise-mode), and the perf report
    binds the paged_pallas layout + a decode roofline fraction."""
    # the CPU has no peaks of its own; state some so the roofline
    # join (cost x measured wall x peaks) is exercised
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_BPS", "819e9")
    m = _tiny_model()
    eng = ServingEngine(m, num_slots=4, bucket_min=8, paged=True,
                        block_size=8, paged_attn=True,
                        async_depth=async_depth,
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
    assert model["paged"] is True
    assert rep["decode_roofline"]["achieved_fraction"] is not None
    assert rep["programs"]["decode"]["roofline_fraction"] is not None
    state = eng.debug_state()
    assert state["paged_attn"] is True
    assert state["decode_layout"] == "paged_pallas"


def test_engine_gate_off_and_guard_fallback(monkeypatch):
    """Default-off on CPU tier-1: without the gate the engine stays on
    the XLA gather path; with the gate but no forced interpret the
    kernel_viable guard refuses on CPU and the engine falls back —
    layout honesty says paged_xla either way."""
    monkeypatch.delenv("PADDLE_PAGED_ATTN", raising=False)
    m = _tiny_model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8, paged=True,
                        block_size=8)
    assert not eng.paged_attn
    assert eng.decode_layout == "paged_xla"
    gated = ServingEngine(m, num_slots=2, bucket_min=8, paged=True,
                          block_size=8, paged_attn=True)
    assert not gated.paged_attn           # guard refused (CPU)
    assert gated.decode_layout == "paged_xla"
    legacy = ServingEngine(m, num_slots=2, bucket_min=8)
    assert legacy.decode_layout == "contiguous"
    model = legacy.metrics.perf_report()["decode_roofline"]["model"]
    assert model["layout"] == "contiguous"


def test_roofline_paged_pallas_layout():
    """Roofline honesty: paged_pallas prices gather factor 1.0 and no
    max-len over-read (live_kv_len caps the read), paged_xla keeps
    the 3x factor, and the bool ``paged=`` back-compat still maps to
    paged_xla."""
    base = rf.kv_read_bytes_per_token(1024, 12, 12, 64)
    assert rf.kv_read_bytes_per_token(
        1024, 12, 12, 64, layout="paged_xla") == \
        rf.PAGED_GATHER_FACTOR * base
    assert rf.kv_read_bytes_per_token(
        1024, 12, 12, 64, layout="paged_pallas") == base
    assert rf.kv_read_bytes_per_token(
        1024, 12, 12, 64, paged=True) == rf.PAGED_GATHER_FACTOR * base
    with pytest.raises(ValueError):
        rf.resolve_layout(layout="paged_mosaic")
    kw = dict(batch=8, kv_len=1024, num_layers=12, num_heads=12,
              head_dim=64, n_params=124e6, peak_flops=197e12,
              hbm_bps=819e9)
    xla = rf.decode_step_model(layout="paged_xla", **kw)
    pallas = rf.decode_step_model(layout="paged_pallas",
                                  live_kv_len=256, **kw)
    cont = rf.decode_step_model(**kw)
    assert xla["layout"] == "paged_xla" and xla["paged"] is True
    assert pallas["layout"] == "paged_pallas"
    assert pallas["paged"] is True        # still a paged POOL
    assert cont["paged"] is False
    assert pallas["gather_factor"] == 1.0
    assert pallas["kv_len_read"] == 256   # no max-len over-read
    assert xla["kv_len_read"] == 1024     # over-read is xla's price
    assert pallas["bytes_total"] < cont["bytes_total"] \
        < xla["bytes_total"]
    assert pallas["floor_s"] < xla["floor_s"]
