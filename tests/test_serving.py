"""Continuous-batching inference engine (paddle_tpu.serving): exact
greedy parity with per-request generate() under staggered mixed-length
arrivals, slot-recycling correctness, zero steady-state recompiles (the
engine's own exact compile counter over AOT executables), and the
throughput contract vs sequential generate()."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (PagedKVPool, ServingConfig,
                                ServingEngine, StepScheduler,
                                default_buckets)
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig


def _model(seed=7, max_seq_len=64, num_layers=2):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=num_layers, num_heads=4,
                              max_seq_len=max_seq_len, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _ref(m, prompt, n_new):
    """Per-request greedy generate(): the parity oracle."""
    out = m.generate(paddle.to_tensor(prompt[None]),
                     max_new_tokens=n_new, temperature=0.0)
    return np.asarray(out.numpy())[0]


def _prompts(rs, lengths):
    return [rs.randint(0, 97, (n,)).astype(np.int64) for n in lengths]


def test_default_buckets_geometric():
    assert default_buckets(64, 8) == [8, 16, 32, 64]
    assert default_buckets(48, 32) == [32, 48]  # cap always included
    assert default_buckets(32, 32) == [32]


def test_default_buckets_edge_cases():
    """bucket_min at/above cache_len collapses to [cache_len];
    non-power-of-two cache_len keeps the doubling run plus the cap."""
    assert default_buckets(64, 64) == [64]
    assert default_buckets(64, 100) == [64]   # bucket_min > capacity
    assert default_buckets(48, 8) == [8, 16, 32, 48]
    assert default_buckets(100, 16) == [16, 32, 64, 100]
    with pytest.raises(ValueError):
        default_buckets(64, 0)


def test_bucket_for_boundaries():
    """Prompt exactly at a bucket boundary stays in that bucket; one
    past it moves up; past the largest bucket raises."""
    sch = StepScheduler([8, 16, 32], 32)
    assert sch.bucket_for(1) == 8
    assert sch.bucket_for(8) == 8
    assert sch.bucket_for(9) == 16
    assert sch.bucket_for(32) == 32
    with pytest.raises(ValueError):
        sch.bucket_for(33)


def test_engine_matches_generate_staggered_mixed_lengths():
    """Mixed prompt lengths spanning several buckets, arrivals
    staggered across engine steps: every request's full output must
    EXACTLY equal its own batch-1 generate()."""
    m = _model()
    eng = ServingEngine(m, num_slots=3, bucket_min=8)
    rs = np.random.RandomState(0)
    specs = [(3, 6), (11, 9), (7, 4), (20, 12), (5, 8), (13, 5),
             (9, 7), (26, 10)]
    prompts = _prompts(rs, [n for n, _ in specs])
    reqs, streamed = [], {}
    for i, (p, (_, k)) in enumerate(zip(prompts, specs)):
        def on_token(req, tok):
            streamed.setdefault(req.rid, []).append(tok)
        reqs.append(eng.add_request(p, max_new_tokens=k,
                                    on_token=on_token))
        if i % 3 == 2:      # mid-flight arrivals: some slots decoding
            eng.step()
            eng.step()
    done = eng.run()
    assert len(done) == len(specs) and all(r.done for r in reqs)
    for r, p, (_, k) in zip(reqs, prompts, specs):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, k))
        assert streamed[r.rid] == r.generated  # streaming saw each token
    snap = eng.metrics.snapshot()
    assert snap["requests_completed"] == len(specs)
    assert snap["tokens_generated"] == sum(k for _, k in specs)
    assert snap["ttft_avg_ms"] is not None


def test_slot_reuse_produces_identical_tokens():
    """More requests than slots: recycled slots (stale K/V from a
    previous occupant) must produce exactly the tokens a fresh engine
    produces — the per-slot length mask hides the old contents."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    rs = np.random.RandomState(1)
    prompts = _prompts(rs, [4, 9, 6, 12, 5])
    reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.run()
    assert eng.pool.reuse_count >= 3  # 5 requests through 2 slots
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, 6))
    # recycled == fresh, engine-to-engine
    eng2 = ServingEngine(m, num_slots=2, bucket_min=8)
    r2 = eng2.add_request(prompts[-1], max_new_tokens=6)
    eng2.run()
    np.testing.assert_array_equal(r2.output_ids, reqs[-1].output_ids)


def test_eos_stops_slot_early_and_frees_it():
    """Per-slot stop condition: declaring the first generated token as
    EOS retires that request after one token while others keep
    decoding (nobody waits for the slowest)."""
    m = _model()
    rs = np.random.RandomState(4)
    p1, p2 = _prompts(rs, [5, 8])
    eos = int(_ref(m, p1, 1)[-1])     # whatever greedy emits first
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    r1 = eng.add_request(p1, max_new_tokens=10, eos_id=eos)
    r2 = eng.add_request(p2, max_new_tokens=6)
    eng.run()
    assert r1.generated == [eos] and len(r2.generated) == 6
    np.testing.assert_array_equal(r2.output_ids, _ref(m, p2, 6))


def test_zero_steady_state_recompiles():
    """After a warmup wave covers the workload's prefill buckets,
    identical traffic adds ZERO compiles: all device work is AOT
    executables at fixed shapes (metrics.compiles counts every
    executable ever built), and the whole inventory respects the hard
    bound len(buckets) + 1."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    rs = np.random.RandomState(2)
    wave = [(3, 5), (7, 5), (10, 4), (14, 6)]
    for n, k in wave:
        eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64), k)
    eng.run()
    warm = eng.metrics.compiles
    # the tail buckets 8 and 16 + 1 decode
    assert warm == 3
    assert set(eng._exec) == {("paged_prefill", 8),
                              ("paged_prefill", 16), ("decode",)}
    assert warm <= len(eng.scheduler.buckets) + 1
    # steady state: the same traffic pattern again — zero new compiles
    for n, k in wave:
        eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64), k)
    eng.run()
    assert eng.metrics.compiles == warm, "steady-state recompiled"
    # a NEW bucket is exactly one more compile
    eng.add_request(rs.randint(0, 97, (20,)).astype(np.int64), 4)
    eng.run()
    assert eng.metrics.compiles == warm + 1


def test_compile_inventory_bound_mixed_lengths():
    """Tier-1 guard for the compile inventory: a mixed
    prompt-length workload with arbitrary admission bursts never
    builds more than len(buckets) + 1 executables."""
    m = _model()
    eng = ServingEngine(m, num_slots=4, bucket_min=8)
    rs = np.random.RandomState(11)
    specs = [(int(n), int(k)) for n, k in zip(
        rs.randint(2, 30, 20), rs.randint(2, 10, 20))]
    for p, (_, k) in zip(_prompts(rs, [n for n, _ in specs]), specs):
        eng.add_request(p, max_new_tokens=k)
    eng.run()
    assert eng.metrics.compiles <= len(eng.scheduler.buckets) + 1


def test_compile_inventory_bound_chunked_speculative():
    """The whole inventory of an engine with every program it can
    have: len(buckets) prefills (the chunk width is one more bucket
    when it is not one already) + decode + verify + the two wire
    programs — and nothing else, whatever the traffic."""
    m = _model()
    eng = ServingEngine(m, num_slots=3, bucket_min=8, prefill_chunk=12,
                        speculative=True, spec_k=3)
    eng.warmup_kv_handoff()
    rs = np.random.RandomState(15)
    specs = [(int(n), int(k)) for n, k in zip(
        rs.randint(2, 40, 12), rs.randint(2, 8, 12))]
    prompts = _prompts(rs, [n for n, _ in specs])
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, (_, k) in zip(prompts, specs)]
    eng.run()
    for r, p, (_, k) in zip(reqs, prompts, specs):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, k))
    assert eng.metrics.scheduler_report()["chunked_requests"] > 0
    kinds = {k[0] for k in eng._exec}
    assert kinds == {"paged_prefill", "decode", "paged_spec_verify",
                     "kv_export", "kv_import"}
    widths = {k[1] for k in eng._exec if k[0] == "paged_prefill"}
    assert widths <= set(eng.scheduler.buckets) | {12}
    assert eng.metrics.compiles <= len(eng.scheduler.buckets) + 1 + 4


def test_slot_pool_is_gone_and_says_so():
    """paged=False no longer selects anything: the config refuses it,
    for any model, and names the removal."""
    with pytest.raises(ValueError, match="slot-contiguous KV pool was "
                                         "removed"):
        ServingConfig(paged=False)
    with pytest.raises(ValueError, match="removed"):
        ServingEngine(_model(), num_slots=2, paged=False)


@pytest.mark.parametrize("kwargs", [{}, {"paged": None},
                                    {"paged": True}],
                         ids=["default", "none", "true"])
def test_engine_without_options_is_paged(kwargs, monkeypatch):
    """ServingEngine(model) builds the paged pool with its radix
    index; paged=None and paged=True build the same thing, and the
    environment name the slot pool's gate used to read is read by
    nothing."""
    monkeypatch.setenv("PADDLE_" + "PAGED_KV", "0")   # the old gate
    m = _model()
    eng = ServingEngine(m, **kwargs)
    assert type(eng.pool) is PagedKVPool
    assert eng.pool.index is not None
    assert eng.decode_layout == "paged_xla"   # the CPU's path
    assert not hasattr(eng.config, "paged")
    p = np.arange(1, 20, dtype=np.int64)
    r = eng.add_request(p, max_new_tokens=4)
    eng.run()
    np.testing.assert_array_equal(r.output_ids, _ref(m, p, 4))


def test_run_returns_submission_order():
    """run()'s contract: completed requests come back sorted by rid
    (submission order) even when they FINISH out of order; the
    scheduler's own completed list keeps finish order."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    rs = np.random.RandomState(7)
    prompts = _prompts(rs, [5, 6, 4])
    r0 = eng.add_request(prompts[0], max_new_tokens=12)
    r1 = eng.add_request(prompts[1], max_new_tokens=2)
    r2 = eng.add_request(prompts[2], max_new_tokens=2)
    done = eng.run()
    assert all(r.done for r in (r0, r1, r2))
    assert [r.rid for r in done] == [r0.rid, r1.rid, r2.rid]
    # the long request finished last, so finish order differs
    assert eng.scheduler.completed[-1] is r0
    assert eng.scheduler.completed != done


def test_deep_queue_parity():
    """Queue much deeper than the slot pool, prompts in mixed
    buckets: every admission is accounted once and every request
    still matches its own batch-1 generate() exactly."""
    m = _model()
    eng = ServingEngine(m, num_slots=4, bucket_min=8)
    rs = np.random.RandomState(8)
    specs = [(5, 4), (7, 5), (3, 6), (6, 4), (11, 5), (13, 4),
             (9, 6), (14, 5), (4, 4), (8, 5), (12, 4), (10, 6)]
    prompts = _prompts(rs, [n for n, _ in specs])
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, (_, k) in zip(prompts, specs)]
    eng.run()
    hist = eng.metrics.prefill_group_hist
    assert eng.metrics.prefill_requests == len(specs)
    assert sum(g * c for g, c in hist.items()) == len(specs)
    assert eng.pool.reuse_count >= len(specs) - 4
    for r, p, (_, k) in zip(reqs, prompts, specs):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, k))


def test_sync_mode_matches_pipelined_engine():
    """async_depth=0 (the PR-1 synchronous schedule) and the
    pipelined default produce identical tokens — the pipeline
    changes the schedule, never the math."""
    m = _model()
    rs = np.random.RandomState(10)
    specs = [(3, 6), (11, 4), (7, 9), (20, 5), (5, 7), (13, 3)]
    prompts = _prompts(rs, [n for n, _ in specs])
    eng_a = ServingEngine(m, num_slots=3, bucket_min=8)
    eng_b = ServingEngine(m, num_slots=3, bucket_min=8, async_depth=0)
    ra = [eng_a.add_request(p, max_new_tokens=k)
          for p, (_, k) in zip(prompts, specs)]
    rb = [eng_b.add_request(p, max_new_tokens=k)
          for p, (_, k) in zip(prompts, specs)]
    eng_a.run()
    eng_b.run()
    for a, b in zip(ra, rb):
        np.testing.assert_array_equal(a.output_ids, b.output_ids)
    # sync mode never leaves tokens in flight, so it never masks
    assert eng_b.metrics.speculative_masked == 0


@pytest.mark.parametrize("chunk", [None, 8], ids=["whole", "chunked"])
@pytest.mark.parametrize("depth", [2, 4, 12])
def test_deep_pipeline_matches_generate(depth, chunk):
    """``async_depth=k``: up to k steps' results stay unread while the
    device works through them (a host that is away for less than k
    steps stalls nothing). More requests than slots, uneven lengths, so
    slots are prereleased, reused and prefilled while older steps that
    still name them are in flight: every request's tokens equal
    generate()'s, nothing is left unread, and the pipeline did reach
    its depth."""
    m = _model()
    rs = np.random.RandomState(21)
    specs = [(3, 14), (11, 4), (7, 19), (20, 5), (5, 17), (13, 3),
             (30, 9), (4, 1)]
    prompts = _prompts(rs, [n for n, _ in specs])
    eng = ServingEngine(m, num_slots=3, bucket_min=8, block_size=4,
                        async_depth=depth, prefill_chunk=chunk)
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, (_, k) in zip(prompts, specs)]
    deepest = 0
    while eng.step():
        deepest = max(deepest, len(eng._pending_steps))
        assert len(eng._pending_steps) <= depth
        assert sum(eng._pending_steps) == len(eng._pending)
    assert deepest == depth
    assert not eng._pending and not eng._pending_steps
    for r, p, (_, k) in zip(reqs, prompts, specs):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, k))
    assert eng.pool.reuse_count >= len(specs) - 3
    eng.pool.check_conservation()


@pytest.mark.parametrize("depth", [1, 3, 8])
def test_deep_pipeline_masks_every_step_past_an_eos(depth):
    """An EOS is known only when its token is read, ``depth`` steps
    after its dispatch: the steps dispatched meanwhile computed a token
    for the stopped request each, and every one of them is masked. The
    slot's next request starts clean."""
    m = _model()
    rs = np.random.RandomState(4)
    p1, p2, p3 = _prompts(rs, [5, 8, 6])
    want = _ref(m, p1, 3)
    eos = int(want[-1])                # greedy's third token
    assert eos not in want[len(p1):-1]
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        async_depth=depth)
    r1 = eng.add_request(p1, max_new_tokens=40, eos_id=eos)
    r2 = eng.add_request(p2, max_new_tokens=30)
    r3 = eng.add_request(p3, max_new_tokens=5)
    eng.run()
    np.testing.assert_array_equal(r1.output_ids, want)
    np.testing.assert_array_equal(r2.output_ids, _ref(m, p2, 30))
    np.testing.assert_array_equal(r3.output_ids, _ref(m, p3, 5))
    assert eng.metrics.speculative_masked == depth


def test_async_depth_refusals():
    with pytest.raises(ValueError, match="async_depth"):
        ServingConfig(async_depth=-1)
    with pytest.raises(ValueError, match="HARVESTED"):
        ServingConfig(async_depth=2, speculative=True)
    assert ServingConfig(async_depth=1, speculative=True).speculative


def test_forced_donation_parity_on_cpu():
    """donate_buffers=True: JAX enforces donation semantics (the input
    buffers are invalidated after the call) even on backends that
    don't alias them — the engine's rebind discipline must survive
    with identical tokens, and snapshot() must surface the status."""
    import jax

    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        donate_buffers=True)
    rs = np.random.RandomState(12)
    prompts = _prompts(rs, [4, 9, 6, 12])
    reqs = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, 5))
    snap = eng.metrics.snapshot()
    assert snap["kv_donation"]["enabled"] is True
    on_cpu = jax.devices()[0].platform == "cpu"
    assert snap["kv_donation"]["effective"] == (not on_cpu)
    # auto mode: donation only where it aliases
    eng2 = ServingEngine(m, num_slots=2, bucket_min=8)
    assert eng2.metrics.kv_donation["enabled"] == (not on_cpu)


def test_snapshot_surfaces_pipeline_metrics():
    """snapshot() carries the hot-path observability: prefill group histogram, KV donation status, and the
    dispatch-vs-sync wall split."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    rs = np.random.RandomState(13)
    for p in _prompts(rs, [5, 9, 7]):
        eng.add_request(p, max_new_tokens=4)
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["prefill_requests"] == 3
    assert sum(int(g) * c for g, c in snap["prefill_groups"].items()) == 3
    kvd = snap["kv_donation"]
    mem_keys = {"decode_alias_bytes", "decode_temp_bytes", "pool_bytes"}
    # the decode executable's memory picture rides along wherever the
    # backend's executable gives one (all three keys or none)
    assert set(kvd) in ({"enabled", "effective"},
                        {"enabled", "effective"} | mem_keys)
    if "pool_bytes" in kvd:
        assert kvd["pool_bytes"] == eng.pool.kc.nbytes + eng.pool.vc.nbytes
        assert min(kvd["decode_temp_bytes"], kvd["decode_alias_bytes"]) >= 0
    assert snap["dispatch_s"] > 0 and snap["sync_s"] >= 0
    assert snap["speculative_masked"] >= 0


def test_admission_validation():
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8, max_len=32)
    with pytest.raises(ValueError):          # prompt beyond any bucket
        eng.add_request(np.zeros(40, np.int64), max_new_tokens=1)
    with pytest.raises(ValueError):          # overflows slot capacity
        eng.add_request(np.zeros(30, np.int64), max_new_tokens=10)
    with pytest.raises(ValueError):
        eng.add_request(np.zeros(4, np.int64), max_new_tokens=0)
    with pytest.raises(ValueError):          # cache > position table
        ServingEngine(m, num_slots=1, max_len=128)


def test_cached_slot_attention_masks_stale_rows():
    """ops/attention.cached_slot_attention: per-slot cache-length
    masking gives each slot exactly the attention it would get over
    its live prefix alone — stale rows (huge garbage included) carry
    zero weight."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import cached_slot_attention

    rs = np.random.RandomState(3)
    S, nh, C, hd = 3, 2, 16, 8
    q = jnp.asarray(rs.randn(S, nh, hd).astype(np.float32))
    kc = jnp.asarray((rs.randn(S, nh, C, hd) * 50).astype(np.float32))
    vc = jnp.asarray((rs.randn(S, nh, C, hd) * 50).astype(np.float32))
    lengths = jnp.asarray(np.array([1, 7, 16], np.int32))
    out = np.asarray(cached_slot_attention(q, kc, vc, lengths))
    for s, L in enumerate([1, 7, 16]):
        ks, vs = kc[s, :, :L], vc[s, :, :L]
        sc = np.einsum("hd,hkd->hk", np.asarray(q[s]), np.asarray(ks))
        sc = sc / np.sqrt(np.float32(hd))
        w = np.asarray(jax.nn.softmax(jnp.asarray(sc), axis=-1))
        ref = np.einsum("hk,hkd->hd", w, np.asarray(vs))
        np.testing.assert_allclose(out[s], ref, rtol=1e-4, atol=1e-3)


def test_throughput_vs_sequential_generate():
    """Acceptance contract: >= 1.3x tokens/sec over sequential
    per-request generate() on a staggered mixed-length CPU workload,
    both sides cold (compiles included — shape-variety cost is exactly
    what bucketed prefill + the fixed-shape decode amortize; generate()
    compiles one executable per distinct signature)."""
    specs = [(3, 6), (11, 9), (7, 4), (20, 12), (5, 8), (13, 5),
             (9, 7), (17, 10), (25, 6), (6, 11)]
    rs = np.random.RandomState(5)
    prompts = _prompts(rs, [n for n, _ in specs])

    m_eng = _model()
    eng = ServingEngine(m_eng, num_slots=4, bucket_min=8)
    t0 = time.perf_counter()
    for i, (p, (_, k)) in enumerate(zip(prompts, specs)):
        eng.add_request(p, max_new_tokens=k)
        if i == 4:          # staggered: second wave arrives mid-flight
            eng.step()
            eng.step()
    eng.run()
    t_engine = time.perf_counter() - t0
    n_tokens = eng.metrics.tokens_generated
    assert n_tokens == sum(k for _, k in specs)

    m_seq = _model()        # fresh decode LRU: sequential cold serving
    t0 = time.perf_counter()
    for p, (_, k) in zip(prompts, specs):
        m_seq.generate(paddle.to_tensor(p[None]), max_new_tokens=k,
                       temperature=0.0).numpy()
    t_seq = time.perf_counter() - t0

    tps_engine = n_tokens / t_engine
    tps_seq = n_tokens / t_seq
    assert tps_engine >= 1.3 * tps_seq, (
        f"engine {tps_engine:.1f} tok/s vs sequential {tps_seq:.1f} "
        f"tok/s (ratio {tps_engine / tps_seq:.2f}, need >= 1.3)")


@pytest.mark.slow
def test_serving_soak_slot_churn():
    """Soak (slow tier): 24 mixed requests through 4 slots in three
    arrival waves — full parity, heavy recycling, and the compile
    inventory bound len(buckets) + 1 holding across the whole soak.
    A fourth wave repeating the first three's arrival pattern must
    add zero compiles."""
    m = _model(max_seq_len=64, num_layers=3)
    eng = ServingEngine(m, num_slots=4, bucket_min=8)
    rs = np.random.RandomState(6)
    specs = [(int(n), int(k)) for n, k in zip(
        rs.randint(2, 30, 24), rs.randint(2, 14, 24))]
    prompts = _prompts(rs, [n for n, _ in specs])
    reqs = []
    for wave in range(3):
        for p, (_, k) in list(zip(prompts, specs))[wave * 8:
                                                   (wave + 1) * 8]:
            reqs.append(eng.add_request(p, max_new_tokens=k))
        eng.run()
    assert eng.metrics.compiles <= len(eng.scheduler.buckets) + 1
    assert eng.pool.reuse_count >= 20
    for r, p, (_, k) in zip(reqs, prompts, specs):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, k))
    # repeat the identical three-wave pattern: fully warm, zero new
    warm = eng.metrics.compiles
    reqs2 = []
    for wave in range(3):
        for p, (_, k) in list(zip(prompts, specs))[wave * 8:
                                                   (wave + 1) * 8]:
            reqs2.append(eng.add_request(p, max_new_tokens=k))
        eng.run()
    assert eng.metrics.compiles == warm
    for r, r2 in zip(reqs, reqs2):
        np.testing.assert_array_equal(r.output_ids, r2.output_ids)
