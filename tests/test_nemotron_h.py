"""The ``nemotron_h`` family on the served path, at small sizes on the
CPU: the eager model, prefill + decode through the paged cache AND the
per-slot state and ``generate()`` against the plain reference
(``benchmarks/reference/nemotron_h.py``) on seeded weights; the chunked
scan and the one-token step against the sequential recurrence; the
expert shares against the uncut layer; all three decode kernels in
interpret mode against their ``jnp`` formulations; every refusal by
name; and the GPT's and the latent model's cache specs, pools, donation
and decode programs shown unchanged.

Tolerances. Everything here is float32 on both sides, so what differs is
the order of additions (the chunked scan, blocked attention, the
experts' sorted runs): logits of magnitude ~1 agree to a few 1e-6;
``TOL`` = 2e-4 leaves room for other BLAS builds.
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

from benchmarks import weights_nemotron_h as W  # noqa: E402
from benchmarks.reference import nemotron_h as ref  # noqa: E402
from paddle_tpu.ops import attention as attn_ops  # noqa: E402
from paddle_tpu.ops import moe_experts as moe  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.ops import ssm  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.text import nemotron_h as nh  # noqa: E402

TOL = 2e-4
# all three kinds, a held half (experts 0-3 of 8), an expert width (48)
# that is no multiple of the lane tile, 4 query heads a KV head
HF = dict(vocab_size=96, hidden_size=128, num_hidden_layers=9,
          hybrid_override_pattern="MEMEM*EM*", num_attention_heads=8,
          num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
          mamba_head_dim=8, ssm_state_size=16, n_groups=2, conv_kernel=4,
          chunk_size=16, moe_intermediate_size=48,
          moe_shared_expert_intermediate_size=96, n_routed_experts=4,
          router_experts=8, first_held_expert=0, num_experts_per_tok=2,
          n_shared_experts=1, routed_scaling_factor=2.5,
          norm_topk_prob=True, layer_norm_epsilon=1e-5,
          max_position_embeddings=64, n_group=1, topk_group=1)


def _model(seed=3, **over):
    hf = dict(HF, **over)
    w = W.make(seed, hf, "float32")
    cfg = nh.NemotronHConfig.from_hf(hf, dtype="float32")
    return nh.NemotronHForCausalLM(cfg, weights=w), w, hf


def _ref_logits(w, ids, hf=HF):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32), hf)[0])


@pytest.fixture(scope="module")
def model_w():
    m, w, _ = _model()
    return m, w


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(0).integers(0, 96, size=(2, 37))


# ------------------------------------------------------ the whole model
def test_layer_plan_cuts_the_pattern_into_runs():
    assert nh.layer_plan("MEMEM*EMEMEM*") == [
        ("ME", 2), ("M", 1), ("*", 1), ("EM", 3), ("*", 1)]
    full = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    plan = nh.layer_plan(full)
    assert "".join(u * r for u, r in plan) == full
    assert len(plan) == 15           # 52 layers, 15 traced runs


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


# ---------------------------------------------------- ssm against ref
def _ssm_inputs(T, H=8, P=8, G=2, N=16, seed=0):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    xs = jnp.asarray(rng.normal(size=(T, H, P)), f)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        size=(T, H))), f)
    A = -jnp.asarray(rng.uniform(1, 16, size=(H,)), f)
    B = jnp.asarray(rng.normal(size=(T, G, N)), f)
    C = jnp.asarray(rng.normal(size=(T, G, N)), f)
    S0 = jnp.asarray(rng.normal(size=(H, P, N)), f)
    return xs, dt, A, B, C, S0


def _recurrence(xs, dt, A, B, C, S0):
    """The defining recurrence, one step at a time in numpy."""
    xs, dt, A, B, C, S = (np.asarray(a, np.float64)
                          for a in (xs, dt, A, B, C, S0))
    H, G = xs.shape[1], B.shape[1]
    ys = []
    for t in range(xs.shape[0]):
        Bh = np.repeat(B[t], H // G, axis=0)
        Ch = np.repeat(C[t], H // G, axis=0)
        S = np.exp(dt[t] * A)[:, None, None] * S \
            + (dt[t][:, None] * xs[t])[:, :, None] * Bh[:, None, :]
        ys.append((S * Ch[:, None, :]).sum(-1))
    return np.stack(ys), S


def _prefill(xs, dt, A, B, C, S0):
    """``ssd_prefill`` in chunks of 16, its state (packed) in and out as
    the recurrence's ``[H, P, N]``."""
    H, G = xs.shape[1], B.shape[1]
    y, S = ssm.ssd_prefill(xs, dt, A, B, C, ssm.pack_state(S0, G),
                           chunk=16)
    return y, ssm.unpack_state(S, H, G)


@pytest.mark.parametrize("T", [16, 37, 64], ids=["one", "ragged", "four"])
@pytest.mark.parametrize("with_init", [False, True], ids=["zero", "init"])
def test_chunked_scan_equals_the_recurrence(T, with_init):
    """``ssd_prefill`` in chunks of 16 against the sequential
    recurrence, with and without an initial state, at a length that is
    no multiple of the chunk."""
    xs, dt, A, B, C, S0 = _ssm_inputs(T)
    if not with_init:
        S0 = jnp.zeros_like(S0)
    y, S = _prefill(xs, dt, A, B, C, S0)
    wy, wS = _recurrence(xs, dt, A, B, C, S0)
    assert np.abs(np.asarray(y) - wy).max() < 1e-4 * np.abs(wy).max()
    assert np.abs(np.asarray(S) - wS).max() < 1e-4 * np.abs(wS).max()


def test_a_zero_step_keeps_the_state_bit_for_bit():
    xs, dt, A, B, C, S0 = _ssm_inputs(24)
    dt = dt.at[10:].set(0.0)
    _, S = _prefill(xs, dt, A, B, C, S0)
    _, want = _prefill(xs[:10], dt[:10], A, B[:10], C[:10], S0)
    assert np.abs(np.asarray(S) - np.asarray(want)).max() < 1e-6


def test_pack_state_round_trips():
    s = jnp.arange(3 * 8 * 8 * 16, dtype=jnp.float32).reshape(3, 8, 8, 16)
    for G in (1, 2, 8):
        p = ssm.pack_state(s, G)
        assert p.shape[-1] == 8 * ssm.heads_per_row(8, 8, G)
        assert (ssm.unpack_state(p, 8, G) == s).all()
    assert ssm.packed_shape(64, 64, 128, 8) == (32, 128, 128)


def _decode_continues(kernel):
    """Prefill 21 steps, then 6 one-token steps of 3 slots in layer 1 of
    2: equals the recurrence over all 27."""
    S_, H, P, G, N, Lm = 3, 8, 8, 2, 16, 2
    runs = [_ssm_inputs(27, seed=s) for s in range(S_)]
    state = jnp.zeros((Lm * S_,) + ssm.packed_shape(H, P, N, G),
                      jnp.float32) + 7.0      # other layers: untouched
    mids = []
    A = runs[0][2]
    for xs, dt, _, B, C, S0 in runs:
        _, S = _prefill(xs[:21], dt[:21], A, B[:21], C[:21], S0)
        mids.append(S)
    state = state.at[S_:].set(ssm.pack_state(jnp.stack(mids), G))
    step = ssm.ssm_state_step if kernel else ssm.ssm_state_step_jnp
    ys = []
    for t in range(21, 27):
        xs = jnp.stack([r[0][t] for r in runs])
        dt = jnp.stack([r[1][t] for r in runs])
        B = jnp.stack([r[3][t] for r in runs])
        C = jnp.stack([r[4][t] for r in runs])
        state, y = step(state, jnp.int32(1), xs, dt, A, B, C, S_)
        ys.append(np.asarray(y))
    assert (np.asarray(state[:S_]) == 7.0).all()
    for s, (xs, dt, _, B, C, S0) in enumerate(runs):
        wy, wS = _recurrence(xs, dt, A, B, C, S0)
        got = np.stack([y[s] for y in ys])
        assert np.abs(got - wy[21:]).max() < 1e-4 * np.abs(wy).max()
        gS = np.asarray(ssm.unpack_state(state[S_ + s], H, G))
        assert np.abs(gS - wS).max() < 1e-4 * np.abs(wS).max()


def test_decode_step_continues_the_chunked_scan():
    _decode_continues(kernel=False)


def test_conv_decode_continues_conv_prefill():
    rng = np.random.default_rng(0)
    ch, K, T = 24, 4, 11
    u = jnp.asarray(rng.normal(size=(T, ch)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, ch)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(ch,)), jnp.float32)
    zero = jnp.zeros(((K - 1) * ch,), jnp.float32)
    full, _ = ssm.conv_prefill(u, zero, w, b, T)
    pad = np.concatenate([np.zeros((K - 1, ch)), np.asarray(u)])
    want = sum(pad[k:k + T] * np.asarray(w)[k] for k in range(K)) \
        + np.asarray(b)
    want = want / (1 + np.exp(-want))
    assert np.abs(np.asarray(full) - want).max() < 1e-5
    # a bucket of 11 rows of which 7 are the run, then 4 single steps
    part, win = ssm.conv_prefill(u, zero, w, b, 7)
    assert np.abs(np.asarray(part[:7]) - want[:7]).max() < 1e-5
    win = win[None]
    for t in range(7, T):
        y, win = ssm.conv_decode(win, u[t][None], w, b)
        assert np.abs(np.asarray(y[0]) - want[t]).max() < 1e-5


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


def test_chunk_plan_tiles_where_a_slot_has_state():
    """The GPT's plan end-aligns its final chunk (rows 14-15 of a
    30-token prompt are computed twice, to identical keys and values); a
    recurrent state cannot pass a row twice, so its plan tiles."""
    from paddle_tpu.serving.sched.chunker import plan_chunks
    assert plan_chunks(0, 30, 16) == [0, 14]
    assert plan_chunks(0, 30, 16, tile=True) == [0, 16]
    assert plan_chunks(8, 45, 16, tile=True) == [8, 24, 40]


@pytest.mark.parametrize("chunk", [None, 16, 8],
                         ids=["whole", "chunk16", "chunk8"])
def test_paged_prefill_and_decode_match_reference(model_w, chunk):
    """Through ``ServingEngine`` over paged keys and values AND per-slot
    state: three slots, five requests of uneven lengths, so slots are
    released and taken again (a slot reused by a second request starts
    from ZERO state) and released slots keep stepping meanwhile; with
    ``prefill_chunk`` the long prompts prefill chunk by chunk, their
    state carried from chunk to chunk, and their slots are PARKED (state
    untouched) through the decode steps in between. Every served token
    is the reference's best at its position, by the logit gap that
    ``correct`` reads on the chip."""
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
    snap = eng.metrics.snapshot()
    moe_ = snap["moe"]
    steps = moe_["layer_steps"]
    assert len(steps) == 3 and min(steps) == snap["decode_steps"] > 0
    assert all(0 < h <= n * 4 for h, n in zip(moe_["experts_hit"], steps))
    text = eng.metrics.prometheus_text()
    per_token = 2 * 2 * 2 * 16 * 4      # attn layers x (k, v) x 2 x 16 f32
    per_slot = 4 * (3 * 128 + 8 * 8 * 16) * 4  # M layers x (conv + ssm)
    assert f"serving_kv_bytes_per_token {per_token}" in text
    assert f"serving_state_bytes_per_slot {per_slot}" in text
    assert 'serving_moe_expert_tokens_total{layer="6",expert="3"}' in text


@pytest.mark.parametrize("depth", [3, 12])
def test_deep_pipeline_over_slot_state(model_w, depth):
    """``async_depth`` steps of results unread (the benchmark cell keeps
    12 in flight): a slot is released, zeroed and prefilled again while
    older steps that still name it are queued on the device; tokens
    equal ``generate()``'s and every logit gap is sound."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                        buckets=[16, 32], async_depth=depth)
    rng = np.random.default_rng(5)
    lens, new = (5, 17, 9, 30, 12), (16, 9, 14, 12, 7)
    prompts = [rng.integers(0, 96, size=n) for n in lens]
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    deepest = 0
    while eng.step():
        deepest = max(deepest, len(eng._pending_steps))
    assert deepest == depth and not eng._pending
    assert eng.pool.reuse_count >= 3
    for p, r, k in zip(prompts, reqs, new):
        assert _served_gap(w, p, r) < TOL
        want = np.asarray(m.generate(p[None], max_new_tokens=k).value)[0]
        assert (np.asarray(r.output_ids) == want).all()


def test_a_common_prefix_is_not_shared(model_w):
    """Two requests with a common prefix of three whole blocks: the
    second gets NO prefix hit (its state at the boundary is not in the
    blocks) and correct logits."""
    m, w = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                        buckets=[32])
    rng = np.random.default_rng(2)
    common = rng.integers(0, 96, size=24)
    prompts = [np.concatenate([common, rng.integers(0, 96, size=n)])
               for n in (3, 5)]
    (a,) = _drive(eng, prompts[:1], [4])
    assert eng.pool.match_prefix(prompts[1]) == 0
    assert len(eng.pool.index) == 0
    (b,) = _drive(eng, prompts[1:], [4])
    for p, r in zip(prompts, (a, b)):
        assert _served_gap(w, p, r) < TOL
    cache = eng.metrics.snapshot()["prefix_cache"]
    assert cache["hits"] == 0 and cache["cached_tokens"] == 0


def _prefill_into(m, fill, start=0, patch=None):
    """One prefill of 11 rows (a bucket of 16) into slot 1 of a pool
    whose arrays all hold ``fill``: (first token, the slot's window and
    state in every state-space layer)."""
    from paddle_tpu.serving.paged import PagedKVPool
    from paddle_tpu.serving.paged.hybrid_programs import \
        build_paged_hybrid_fns
    pool = PagedKVPool(2, max_len=32, block_size=8, spec=m.cache_spec())
    prefill, _ = build_paged_hybrid_fns(m.cfg, 2, 8, pool.num_blocks,
                                        pool.blocks_per_slot)
    alloc = pool.acquire("a", np.arange(11), 32, 0)
    alloc = pool.acquire("b", np.arange(11), 32, 0)
    arrays = [jnp.full(a.shape, fill, a.dtype) for a in pool.arrays]
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :11] = np.arange(11) * 7 % 96
    i32 = np.int32
    first, _, pos, _, _, conv, state = prefill(
        m.export_decode_params(), tokens, i32(11), i32(start),
        i32(alloc.slot), i32(1), pool.table_row(alloc.slot),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), *arrays)
    assert alloc.slot == 1 and int(pos[1]) == start + 11
    assert (np.asarray(conv[:, 0]) == fill).all()   # slot 0: untouched
    return int(first[0]), np.asarray(conv[:, 1]), np.asarray(state[:, 1])


def test_a_reused_slot_starts_from_zero_state(model_w, monkeypatch):
    """A prefill that STARTS a sequence gives the same first token,
    window and state whatever the slot's last owner (and the blocks')
    left behind; one that continues (``start > 0``: the next chunk)
    reads them. With ``ssm_init`` handing back what the slot holds the
    difference shows, so this test would notice."""
    from paddle_tpu.serving.paged import hybrid_programs as hp
    m, _ = model_w
    clean = _prefill_into(m, 0.0)
    stale = _prefill_into(m, 3.0)
    assert clean[0] == stale[0]
    assert (clean[1] == stale[1]).all() and (clean[2] == stale[2]).all()
    assert np.abs(clean[2]).max() > 0
    carried = _prefill_into(m, 3.0, start=8)
    assert np.abs(carried[2] - clean[2]).max() > 1e-3
    real = hp.PagedAccess.ssm_init
    monkeypatch.setattr(
        hp.PagedAccess, "ssm_init",
        lambda self, state, mi, start, b: real(self, state, mi,
                                               jnp.int32(1), b))
    broken = _prefill_into(m, 3.0)
    assert np.abs(broken[2] - clean[2]).max() > 1e-3


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


# ----------------------------------------------------- refusals, by name
@pytest.mark.parametrize("option", [
    {"speculative": True}, {"role": "prefill"}],
    ids=["speculative", "role"])
def test_engine_refuses_an_option_without_a_program(model_w, option):
    with pytest.raises(ValueError, match="no program for"):
        ServingEngine(model_w[0], num_slots=2, **option)


@pytest.mark.parametrize("what", ["hold_kv", "export_kv", "import_kv"])
def test_engine_refuses_the_kv_wire(model_w, what):
    eng = ServingEngine(model_w[0], num_slots=2, block_size=8, max_len=64,
                        buckets=[16])
    with pytest.raises(NotImplementedError, match=what):
        if what == "hold_kv":
            eng.add_request(np.arange(5), max_new_tokens=2, hold_kv=True)
        elif what == "export_kv":
            eng.export_kv(0)
        else:
            eng.import_kv(b"", 4)


@pytest.mark.parametrize("key,value,name", [
    ("n_group", 2, "n_group"), ("topk_group", 2, "n_group"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("use_bias", True, "use_bias"), ("mlp_bias", True, "mlp_bias"),
    ("attention_bias", True, "attention_bias"),
    ("sliding_window", 128, "sliding_window"),
    ("use_conv_bias", False, "use_conv_bias"),
    ("mlp_hidden_act", "silu", "mlp_hidden_act"),
    ("n_shared_experts", 2, "n_shared_experts"),
    ("hybrid_override_pattern", "M-E*", "layer kinds")])
def test_config_refuses_what_it_has_no_equations_for(key, value, name):
    hf = dict(HF, **{key: value})
    hf.pop("num_hidden_layers")
    with pytest.raises(NotImplementedError, match=name):
        nh.NemotronHConfig.from_hf(hf)


def test_config_refuses_a_share_outside_the_router():
    with pytest.raises(ValueError, match="not a share"):
        nh.NemotronHConfig.from_hf(dict(HF, first_held_expert=6))


# -------------------------------------------------------- expert shares
@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("shares", [1, 2, 8],
                         ids=["whole", "halves", "eighths"])
def test_expert_shares_add_up_to_the_uncut_layer(shares, mode):
    """The parts that the shares of a layer's experts give (experts 0-3
    and 4-7 as two shares), plus the shared expert counted ONCE, equal
    the uncut reference's layer."""
    E = 8
    whole = dict(HF, n_routed_experts=E, router_experts=E)
    m, w, _ = _model(**whole)
    cfg = m.cfg
    ei, n_moe = 1, cfg.count("E")
    p = jax.tree.map(lambda a: a[ei], w["moe"])
    x = jax.random.normal(jax.random.PRNGKey(7), (16, 128), jnp.float32)
    xn = nh.rms_norm(x, p["norm"], cfg.rms_norm_eps)
    count = E // shares
    total = 0.0
    for i in range(shares):
        held = (i * count, count)
        rows = np.concatenate([np.arange(l * E + held[0],
                                         l * E + held[0] + count)
                               for l in range(n_moe)])
        mine = {k: v[rows] for k, v in w["experts"].items()}
        y, tokens = nh.expert_layer(cfg, p, mine, xn, ei, mode,
                                    with_shared=(i == 0), held=held)
        assert tokens.shape == (count,)
        total = total + y

    def mm(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    want, _ = ref.expert_layer(xn, p, w["experts"], ei, whole, mm, 8)
    assert np.abs(np.asarray(total - want)).max() < 1e-4


# ------------------------------------------------- kernels (interpret)
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(ssm, "_FORCE_INTERPRET", [True])
    monkeypatch.setattr(moe, "_FORCE_INTERPRET", [True])
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", [True])


def test_ssm_decode_kernel_matches_formulation(interpret):
    _decode_continues(kernel=True)
    # same numbers both: one step of each from the same state
    xs, dt, A, B, C, _ = _ssm_inputs(4, seed=5)
    state = jnp.asarray(np.random.default_rng(5).normal(
        size=(8,) + ssm.packed_shape(8, 8, 16, 2)), jnp.float32)
    dt = dt.at[2].set(0.0)                     # slot 2 is passed by
    a, ya = ssm.ssm_state_step_jnp(state, 1, xs, dt, A, B, C, 4)
    b, yb = ssm.ssm_state_step(state, 1, xs, dt, A, B, C, 4)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5
    assert np.abs(np.asarray(ya) - np.asarray(yb)).max() < 1e-5
    assert (np.asarray(b[6]) == np.asarray(state[6])).all()
    assert (np.asarray(b[:4]) == np.asarray(state[:4])).all()


@pytest.mark.parametrize("f", [48, 232], ids=["one_tile", "odd_width"])
@pytest.mark.parametrize("layer_m", [0, 1])
def test_relu2_decode_kernel_matches_the_loop(interpret, layer_m, f):
    """relu-squared experts at a width that is no multiple of 128, with
    an expert no token chose, at a row offset into the stacked matrices:
    the kernel, its ``jnp`` twin and the sorted grouped path against a
    loop over the experts."""
    rng = np.random.default_rng(0)
    T, h, E, Lm = 16, 128, 8, 2
    x = jnp.asarray(rng.normal(size=(T, h)), jnp.float32)
    up, down = (jnp.asarray(rng.normal(size=(Lm * E, f, h)) * 0.1,
                            jnp.float32) for _ in range(2))
    wr = jnp.asarray(rng.normal(size=(h, E)), jnp.float32)
    idx, w = moe.route_sigmoid(x, wr, jnp.zeros(E), 2, True, 2.5)
    idx = jnp.where(idx == 3, 4, idx)           # nobody chooses expert 3
    cw = np.asarray(moe.combine_matrix(idx, w, 0, E))
    want = np.zeros((T, h))
    for e in range(E):
        r = np.maximum(np.asarray(x, np.float64)
                       @ np.asarray(up[layer_m * E + e], np.float64).T, 0)
        want += (r * r * cw[:, e:e + 1]) @ np.asarray(
            down[layer_m * E + e], np.float64)
    base = jnp.int32(layer_m * E)
    tol = 2e-5 * np.abs(want).max()
    for got in (moe.moe_experts_relu2_jnp(x, up, down, cw, base),
                moe.moe_experts_relu2_decode(x, up, down, cw, base),
                moe.moe_experts_grouped_relu2(x, up, down, idx, w, 0, E,
                                              base, tile=8)):
        assert np.abs(np.asarray(got) - want).max() < tol


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 4, 16])
def test_grouped_query_paged_kernel_matches_the_gather(interpret, group,
                                                       dtype, tol):
    """The paged decode kernel with a KV head count against the gather
    form, at groups of 1 (the GPT), 4 and 16 query heads a KV head."""
    rng = np.random.default_rng(0)
    S, nkv, hd, BS, MB, NB = 3, 2, 128, 16, 4, 20
    q = jnp.asarray(rng.normal(size=(S, nkv * group, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(NB, nkv, BS, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(NB, nkv, BS, hd)), dtype)
    tables = jnp.asarray(rng.permutation(NB)[:S * MB].reshape(S, MB),
                         jnp.int32)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)   # part, mid, full
    want = attn_ops.cached_paged_attention(q, k, v, tables, lengths)
    got = pa.paged_decode_attention(q, k, v, tables, lengths)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol


def test_grouped_gather_equals_repeated_kv_heads():
    rng = np.random.default_rng(1)
    S, nkv, g, C, hd = 2, 2, 4, 24, 16
    q = jnp.asarray(rng.normal(size=(S, nkv * g, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, nkv, C, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, nkv, C, hd)), jnp.float32)
    lengths = jnp.asarray([7, 24], jnp.int32)
    got = attn_ops.cached_slot_attention(q, k, v, lengths)
    want = attn_ops.cached_slot_attention(
        q, jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1), lengths)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


def test_engine_with_kernels_in_interpret_mode(interpret):
    """The decode program with ALL THREE kernels in it (interpret mode)
    serves the tokens ``generate()`` picks."""
    # kernel shapes: 8 slots (a sublane tile of f32 tokens), lanes whole
    m, w, hf = _model(seed=1, head_dim=128, mamba_head_dim=64,
                      ssm_state_size=16, mamba_num_heads=4, n_groups=2)
    eng = ServingEngine(m, num_slots=8, block_size=8, max_len=32,
                        buckets=[16])
    p = np.arange(9) % 96
    (r,) = _drive(eng, [p], [5])
    want = np.asarray(m.generate(p[None], max_new_tokens=5).value)[0]
    assert (np.asarray(r.output_ids) == want).all()
    assert _served_gap(w, p, r, hf) < TOL


def test_engine_kernel_places_entries_like_the_gather_path(monkeypatch):
    """All three kernels in the decode program, the attention one
    placing the step's new entry (interpret mode), against the ``jnp``
    formulations with the block write: eight slots, eleven requests, so
    slots are released and taken again while released ones keep
    stepping; a prompt of 30 prefilled in chunks of 16 (its slot parked
    in between); outputs of up to 12 tokens over blocks of 8, so streams
    cross block boundaries. The same tokens on both."""
    m, w, hf = _model(seed=1, head_dim=128, mamba_head_dim=64,
                      ssm_state_size=16, mamba_num_heads=4, n_groups=2)
    rng = np.random.default_rng(43)
    lens = (5, 30, 9, 17, 12, 3, 7, 14, 6, 11, 4)
    new = (12, 7, 10, 9, 6, 11, 3, 5, 8, 4, 9)
    prompts = [rng.integers(0, 96, size=n) for n in lens]
    served = {}
    for kernel in (True, False):
        for mod in (ssm, moe, pa):
            monkeypatch.setattr(mod, "_FORCE_INTERPRET", [kernel])
        eng = ServingEngine(m, num_slots=8, block_size=8, max_len=64,
                            buckets=[16], prefill_chunk=16)
        reqs = _drive(eng, prompts, new)
        assert eng.pool.reuse_count >= 2
        served[kernel] = [np.asarray(r.output_ids) for r in reqs]
        for p, r in zip(prompts, reqs):
            assert _served_gap(w, p, r, hf) < TOL
    for a, b in zip(served[True], served[False]):
        np.testing.assert_array_equal(a, b)


# ------------------------------------- the other models' pools, unchanged
def test_cache_spec_counts_both_kinds(model_w):
    from paddle_tpu.serving.paged import PagedKVPool
    spec = model_w[0].cache_spec()
    assert [a.name for a in spec.arrays] == ["k", "v", "conv", "ssm"]
    assert [a.per for a in spec.arrays] == ["token"] * 2 + ["slot"] * 2
    assert [a.layers for a in spec.arrays] == [2, 2, 4, 4]
    assert not spec.shareable
    pool = PagedKVPool(3, max_len=64, block_size=8, spec=spec)
    assert [a.shape for a in pool.arrays] == [
        (2, 25, 2, 8, 16), (2, 25, 2, 8, 16), (4, 3, 384), (4, 3, 2, 16, 32)]
    assert pool.nbytes() == sum(a.nbytes for a in pool.arrays)
    with pytest.raises(ValueError, match="rebind"):
        pool.rebind(*pool.arrays[:2])
    with pytest.raises(ValueError, match="number of slots"):
        spec.shape(spec.arrays[2], 25, 8)
    assert spec.with_slots(3).shape(spec.arrays[3], 25, 8) \
        == (4, 3, 2, 16, 32)


def _digest(fn, *args):
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]


def test_gpt_spec_pool_donation_and_decode_program_unchanged():
    """The GPT through the generalised spec: a (k, v) spec with ONE
    layer count, shareable, no per-slot bytes; the pool's arrays and
    the engine's donation tuple are those of PR 35's parent commit, the
    decode program's jaxpr that of PR 43 (its cache write moved into
    ``ops.paged_attention.paged_write_attention``; the digest before
    was ``dadb0403db741ab5``)."""
    from paddle_tpu.serving.paged.cache_spec import kv_pair_spec
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig
    spec = kv_pair_spec(2, 4, 16, jnp.float32)
    assert [tuple(a) for a in spec.arrays] == [
        ("k", (4,), (16,), jnp.dtype("float32"), 2, "token"),
        ("v", (4,), (16,), jnp.dtype("float32"), 2, "token")]
    assert spec.shareable and spec.bytes_per_slot == 0
    assert spec.bytes_per_token == 2 * 2 * 4 * 16 * 4
    cfg = TransformerLMConfig(vocab_size=64, hidden_size=64, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    eng = ServingEngine(GPTForCausalLM(cfg), num_slots=3, block_size=8,
                        max_len=64, buckets=[16])
    assert [a.shape for a in eng.pool.arrays] == [(2, 25, 4, 8, 16)] * 2
    args, donate = eng._decode_dispatch_args(eng.pool)
    assert len(args) == 6 and donate == (2, 4, 5)
    assert _digest(eng._decode_fn, *args) == GPT_DECODE_DIGEST


def test_latent_spec_pool_donation_and_decode_program_unchanged():
    from paddle_tpu.text import deepseek_v3 as ds
    from tests.test_deepseek_v3 import HF as DS_HF
    m = ds.DeepseekV3ForCausalLM(ds.DeepseekV3Config.from_hf(DS_HF),
                                 seed=1)
    spec = m.cache_spec()
    assert [tuple(a) for a in spec.arrays] == [
        ("c", (), (32,), jnp.dtype("float32"), 3, "token"),
        ("k_pe", (8,), (), jnp.dtype("float32"), 3, "token")]
    assert spec.shareable and spec.bytes_per_slot == 0
    assert spec.bytes_per_token == 3 * (32 + 8) * 4
    eng = ServingEngine(m, num_slots=3, block_size=8, max_len=64,
                        buckets=[16])
    assert [a.shape for a in eng.pool.arrays] == [(3, 25, 8, 32),
                                                  (3, 25, 8, 8)]
    args, donate = eng._decode_dispatch_args(eng.pool)
    assert len(args) == 7 and donate == (2, 4, 5)
    assert _digest(eng._decode_fn, *args) == LATENT_DECODE_DIGEST


# digests of str(jax.make_jaxpr(decode program)) at the sizes above,
# taken on the parent commit (50d367b) with this same test code; the
# latent one is PR 49's (its cache write moved into
# ``ops.mla_attention.latent_write_attention`` and its length counts
# held blocks only; the digest before was ``aa31ce9df0109a05``)
GPT_DECODE_DIGEST = "bfc0be992ec17538"
LATENT_DECODE_DIGEST = "31433cb513ed06f1"
