"""SLO-feedback scheduling subsystem (paddle_tpu.serving.sched):
chunked prefill parity + compile-inventory guard at two block sizes,
decode/prefill co-scheduling, per-slot sampling semantics, and the
load-shedding admission policy (ISSUE 7 acceptance contracts)."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (FIFOPolicy, ServingEngine,
                                SLOFeedbackPolicy, plan_chunks)
from paddle_tpu.serving.sched import build_sampling_head, resolve_policy
from paddle_tpu.serving.scheduler import Request
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
    out = m.generate(paddle.to_tensor(prompt[None]),
                     max_new_tokens=n_new, temperature=0.0)
    return np.asarray(out.numpy())[0]


def _prompts(rs, lengths):
    return [rs.randint(0, 97, (n,)).astype(np.int64) for n in lengths]


def _warm_inventory(eng, chunk, rs):
    """Deterministically cover the engine's whole compile inventory:
    every bucket a whole (unchunked) tail can take (tails <= chunk),
    the chunk-width prefill, and the decode step."""
    for b in eng.scheduler.buckets:
        if b <= chunk:
            eng.add_request(
                rs.randint(0, 97, (b,)).astype(np.int64), 2)
    eng.add_request(
        rs.randint(0, 97, (chunk + 3,)).astype(np.int64), 2)
    eng.run()


# ------------------------------------------------------- chunk planning

def test_plan_chunks_coverage_and_end_alignment():
    """Chunk plans tile full-width from the start and END-ALIGN the
    final chunk: every prompt position in [start0, n) is covered, no
    chunk writes a K/V position >= n, starts strictly increase, and
    the final chunk's last row is the prompt's last token."""
    for start0, n, c in [(0, 50, 16), (0, 17, 16), (0, 129, 32),
                         (24, 44, 8), (8, 63, 8), (16, 33, 16)]:
        starts = plan_chunks(start0, n, c)
        assert starts[0] == start0
        assert starts[-1] == n - c          # end-aligned final chunk
        assert all(b > a for a, b in zip(starts, starts[1:]))
        covered = set()
        for s in starts:
            assert s + c <= n               # never writes past n
            covered.update(range(s, s + c))
        assert covered == set(range(start0, n))


def test_plan_chunks_rejects_short_tails():
    with pytest.raises(ValueError):
        plan_chunks(0, 8, 8)        # tail == chunk: not chunkable
    with pytest.raises(ValueError):
        plan_chunks(16, 20, 8)      # tail < chunk


# -------------------------------------------- chunked prefill parity

@pytest.mark.parametrize("block_size", [4, 16])
def test_chunked_prefill_exact_greedy_parity(block_size):
    """ISSUE 7 acceptance: chunked and unchunked prefill produce
    EXACTLY the same greedy tokens as batch-1 generate(), across a
    mixed short/long staggered workload — with a chunk that spans two
    blocks (4) and with two chunks to a block (16)."""
    m = _model()
    eng = ServingEngine(m, num_slots=3, bucket_min=8,
                        block_size=block_size, prefill_chunk=8)
    rs = np.random.RandomState(0)
    specs = [(5, 6), (40, 5), (11, 4), (56, 7), (23, 5), (7, 6),
             (33, 4), (3, 8)]
    prompts = _prompts(rs, [n for n, _ in specs])
    reqs = []
    for i, (p, (_, k)) in enumerate(zip(prompts, specs)):
        reqs.append(eng.add_request(p, max_new_tokens=k))
        if i % 3 == 2:          # staggered arrivals mid-flight
            eng.step()
            eng.step()
    eng.run()
    for r, p, (_, k) in zip(reqs, prompts, specs):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, k))
    sched = eng.metrics.snapshot()["scheduler"]
    assert sched["chunked_requests"] == sum(
        1 for n, _ in specs if n > 8)
    assert sched["prefill_chunks"] > sched["chunked_requests"]
    eng.pool.check_conservation()


def test_chunked_prefill_paged_shared_prefix_tail_only():
    """Chunked prefill composes with the radix prefix cache: a second
    request sharing a long stem chunk-prefills ONLY its uncached tail
    (prefix_hit + chunk starts begin at the cached span) with exact
    parity."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        block_size=4, prefill_chunk=8)
    rs = np.random.RandomState(3)
    stem = rs.randint(0, 97, (24,)).astype(np.int64)
    p1 = np.concatenate([stem, rs.randint(0, 97, (20,)).astype(np.int64)])
    p2 = np.concatenate([stem, rs.randint(0, 97, (17,)).astype(np.int64)])
    r1 = eng.add_request(p1, max_new_tokens=5)
    eng.run()
    r2 = eng.add_request(p2, max_new_tokens=5)
    eng.run()
    np.testing.assert_array_equal(r1.output_ids, _ref(m, p1, 5))
    np.testing.assert_array_equal(r2.output_ids, _ref(m, p2, 5))
    t2 = eng.request_trace(r2.rid)
    hits = [e for e in t2.events if e["event"] == "prefix_hit"]
    assert len(hits) == 1 and hits[0]["cached_tokens"] == 24
    chunks = [e for e in t2.events if e["event"] == "prefill_chunk"]
    assert chunks and chunks[0]["start"] == 24   # tail-only chunking
    assert chunks[-1]["final"] is True
    assert chunks[-1]["start"] == len(p2) - 8    # end-aligned
    eng.pool.check_conservation()


def test_chunked_prefill_interleaves_with_decode():
    """The whole point of chunking: while a long prompt prefills chunk
    by chunk, OTHER slots keep decoding — a short request admitted
    alongside retires before the long one's prefill even finishes
    (under whole-prompt prefill it would have waited behind one
    monolithic dispatch)."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8, prefill_chunk=8)
    rs = np.random.RandomState(5)
    long_p = rs.randint(0, 97, (56,)).astype(np.int64)   # 7 chunks
    short_p = rs.randint(0, 97, (4,)).astype(np.int64)
    rl = eng.add_request(long_p, max_new_tokens=4)
    rsh = eng.add_request(short_p, max_new_tokens=3)
    eng.run()
    np.testing.assert_array_equal(rl.output_ids, _ref(m, long_p, 4))
    np.testing.assert_array_equal(rsh.output_ids, _ref(m, short_p, 3))
    tl = eng.request_trace(rl.rid)
    tsh = eng.request_trace(rsh.rid)
    chunks = [e for e in tl.events if e["event"] == "prefill_chunk"]
    assert len(chunks) == 7
    assert [c["chunk"] for c in chunks] == list(range(7))
    assert all(c["chunk_len"] == 8 for c in chunks)
    # the short request RETIRED between the long one's first and last
    # chunk — decode progressed while the prefill was still running
    t_retired = tsh.t_of("retired")
    assert chunks[0]["t"] < t_retired < chunks[-1]["t"]


@pytest.mark.parametrize("block_size", [4, 16])
def test_chunked_compile_inventory_guard(block_size):
    """ISSUE 7 satellite: under chunked prefill the compile inventory
    stays within the buckets and ANY prompt-length mix after
    warmup triggers ZERO steady-state compiles — enforced by the
    watchdog's raise mode, so a silent recompile is a hard test
    failure, not a counter drift."""
    m = _model()
    eng = ServingEngine(m, num_slots=4, bucket_min=8,
                        block_size=block_size, prefill_chunk=8,
                        watchdog_mode="raise")
    rs = np.random.RandomState(11)
    _warm_inventory(eng, 8, rs)
    warm = eng.metrics.compiles
    # whole tails are <= chunk, so the inventory collapses to the
    # buckets <= chunk (the chunk width is one of them) + decode
    assert set(eng._exec) == {("paged_prefill", 8), ("decode",)}
    assert warm == 2 <= len(eng.scheduler.buckets) + 1
    eng.declare_warmup()
    for n in rs.randint(1, 60, 50):
        eng.add_request(rs.randint(0, 97, (int(n),)).astype(np.int64),
                        2)
        if n % 4 == 0:
            eng.step()
    eng.run()                       # raise mode: any compile throws
    assert eng.metrics.compiles == warm
    assert eng.watchdog.report()["steady_state_compiles"] == 0


def test_chunked_token_budget_paces_dispatches():
    """prefill_token_budget caps chunk tokens per step: with budget ==
    chunk a 5-chunk prompt takes 5 steps of chunk dispatches; with
    budget 2x chunk it takes 3 (ceil(5/2)) — observable through the
    per-step chunk counter."""
    m = _model()
    rs = np.random.RandomState(9)
    long_p = rs.randint(0, 97, (40,)).astype(np.int64)   # 5 chunks of 8

    def steps_until_prefilled(budget):
        eng = ServingEngine(m, num_slots=2, bucket_min=8,
                            prefill_chunk=8,
                            prefill_token_budget=budget)
        eng.add_request(long_p, max_new_tokens=2)
        steps = 0
        while eng._chunk_q or not eng.scheduler.active:
            eng.step()
            steps += 1
            assert steps < 50
        return steps, eng

    s1, eng1 = steps_until_prefilled(8)
    s2, eng2 = steps_until_prefilled(16)
    assert s1 == 5 and s2 == 3
    eng1.run()
    eng2.run()
    a = eng1.scheduler.completed[-1].output_ids
    np.testing.assert_array_equal(a, _ref(m, long_p, 2))
    np.testing.assert_array_equal(
        a, eng2.scheduler.completed[-1].output_ids)


@pytest.mark.parametrize("block_size", [4, 16])
def test_chunked_sync_mode_matches_pipelined(block_size):
    """async_depth=0 + chunking: the synchronous schedule harvests
    each final chunk immediately — tokens identical to the pipelined
    default and to generate()."""
    m = _model()
    rs = np.random.RandomState(17)
    prompts = _prompts(rs, [5, 30, 44])
    outs = []
    for depth in (1, 0):
        eng = ServingEngine(m, num_slots=2, bucket_min=8,
                            prefill_chunk=8, async_depth=depth,
                            block_size=block_size)
        reqs = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        eng.run()
        outs.append([r.output_ids.copy() for r in reqs])
    for a, b, p in zip(outs[0], outs[1], prompts):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, _ref(m, p, 5))


@pytest.mark.parametrize("block_size", [4, 16])
def test_failed_chunk_dispatch_leaks_nothing(block_size):
    """The PR-6 rollback discipline extends to chunked prefill: a
    dispatch failure MID-CHUNK-CHAIN (earlier chunks already wrote
    K/V) releases the slot and its blocks, clears the chunk queue,
    requeues the request uncounted, and a retry serves it with exact
    parity — recomputed from scratch, stale chunk rows masked."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8, prefill_chunk=8,
                        block_size=block_size)
    rs = np.random.RandomState(19)
    prompt = rs.randint(0, 97, (44,)).astype(np.int64)   # 6 chunks
    orig = eng._compiled
    calls = {"n": 0}

    def failing(key, fn, args, donate=()):
        if key[0] == "paged_prefill":
            calls["n"] += 1
            if calls["n"] == 3:        # third chunk dispatch fails
                raise RuntimeError("injected chunk failure")
        return orig(key, fn, args, donate=donate)

    eng._compiled = failing
    r = eng.add_request(prompt, max_new_tokens=4)
    with pytest.raises(RuntimeError, match="injected"):
        eng.run()
    assert eng.pool.free_count == 2 and not eng.scheduler.active
    assert not eng._chunk_q and not eng._prefilling
    assert r.slot is None and r.inflight == 0
    eng.pool.check_conservation()
    assert eng.pool.live_blocks == 0
    assert eng.metrics.requests_admitted == 0
    eng._compiled = orig
    eng.run()
    assert r.done
    np.testing.assert_array_equal(r.output_ids, _ref(m, prompt, 4))
    assert eng.metrics.requests_admitted == 1


# --------------------------------------------------- per-slot sampling

def test_sampling_head_support_and_greedy_blend():
    """Unit contract for the in-program sampling head: temp<=0 and
    top_k==1 rows are EXACT argmax; sampled rows only ever draw from
    the top-k set / the top-p nucleus; draws are deterministic per
    (seed, key index)."""
    import jax.numpy as jnp

    head = build_sampling_head(32)
    rs = np.random.RandomState(0)
    logits_row = rs.randn(32).astype(np.float32) * 2.0
    order = np.argsort(logits_row)[::-1]

    def draws(temp, topk, topp, n=64, seed=5):
        toks = []
        for i in range(n):
            out = head(jnp.asarray(logits_row[None]),
                       jnp.asarray([seed], jnp.int32),
                       jnp.asarray([i], jnp.int32),
                       jnp.asarray([temp], jnp.float32),
                       jnp.asarray([topk], jnp.int32),
                       jnp.asarray([topp], jnp.float32))
            toks.append(int(out[0]))
        return toks

    # greedy rows: exact argmax however the other knobs are set
    assert set(draws(0.0, 0, 1.0)) == {int(order[0])}
    assert set(draws(0.7, 1, 1.0)) == {int(order[0])}
    # top-k support: every draw within the k most likely
    top5 = set(int(t) for t in order[:5])
    got = set(draws(1.2, 5, 1.0))
    assert got <= top5 and len(got) > 1
    # top-p support: every draw inside the smallest nucleus >= p
    probs = np.exp(logits_row - logits_row.max())
    probs /= probs.sum()
    cum = np.cumsum(probs[order])
    nucleus = set(int(t) for t in order[:int(np.searchsorted(
        cum, 0.8) + 1)])
    assert set(draws(1.0, 0, 0.8)) <= nucleus
    # determinism: same (seed, index) stream twice
    assert draws(0.9, 8, 0.9) == draws(0.9, 8, 0.9)
    # different seeds decorrelate
    assert draws(1.2, 0, 1.0, seed=1) != draws(1.2, 0, 1.0, seed=2)


@pytest.mark.parametrize("block_size", [4, 16])
def test_sampled_and_greedy_slots_share_one_dispatch(block_size):
    """Per-slot sampling: greedy requests stay BIT-EXACT with
    generate() while neighboring slots sample, sampled streams are
    reproducible per seed, and the whole mix adds no compiles beyond
    the one decode executable."""
    m = _model()
    rs = np.random.RandomState(2)
    prompts = _prompts(rs, [5, 9, 12, 7])

    def run_wave():
        eng = ServingEngine(m, num_slots=4, bucket_min=8,
                            sampling=True, block_size=block_size)
        reqs = [
            eng.add_request(prompts[0], 6),
            eng.add_request(prompts[1], 6, temperature=0.8, top_k=12,
                            seed=11),
            eng.add_request(prompts[2], 6, temperature=1.1, top_p=0.9,
                            seed=12),
            eng.add_request(prompts[3], 6),
        ]
        eng.run()
        return eng, reqs

    eng, reqs = run_wave()
    _, reqs2 = run_wave()
    np.testing.assert_array_equal(reqs[0].output_ids,
                                  _ref(m, prompts[0], 6))
    np.testing.assert_array_equal(reqs[3].output_ids,
                                  _ref(m, prompts[3], 6))
    for a, b in zip(reqs, reqs2):       # same seeds -> same streams
        np.testing.assert_array_equal(a.output_ids, b.output_ids)
    # sampled streams actually sampled (argmax would match greedy ref)
    assert not np.array_equal(reqs[1].output_ids,
                              _ref(m, prompts[1], 6))
    # tokens all in-vocab
    for r in reqs:
        assert all(0 <= t < 97 for t in r.generated)


def test_sampling_survives_chunked_prefill_unchanged():
    """Chunking must not perturb a sampled request's stream: keys
    derive from (seed, token position), so chunked and unchunked
    prefill of the same prompt yield the IDENTICAL sampled output."""
    m = _model()
    rs = np.random.RandomState(21)
    long_p = rs.randint(0, 97, (44,)).astype(np.int64)
    outs = []
    for chunk in (None, 8):
        eng = ServingEngine(m, num_slots=2, bucket_min=8,
                            sampling=True, prefill_chunk=chunk)
        r = eng.add_request(long_p, 8, temperature=0.7, top_k=10,
                            seed=42)
        eng.run()
        outs.append(r.output_ids.copy())
    np.testing.assert_array_equal(outs[0], outs[1])


def test_greedy_engine_rejects_sampled_requests():
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    with pytest.raises(ValueError, match="sampling=True"):
        eng.add_request(np.zeros(4, np.int64), 4, temperature=0.5)
    # greedy-equivalent requests are fine on a greedy engine
    eng.add_request(np.zeros(4, np.int64), 2, temperature=0.9, top_k=1)
    eng.add_request(np.zeros(4, np.int64), 2, temperature=0.0)
    eng.run()


def test_request_sampling_validation():
    with pytest.raises(ValueError):
        Request(np.zeros(4, np.int64), 2, temperature=-0.1)
    with pytest.raises(ValueError):
        Request(np.zeros(4, np.int64), 2, top_k=-1)
    with pytest.raises(ValueError):
        Request(np.zeros(4, np.int64), 2, top_p=0.0)
    with pytest.raises(ValueError):
        Request(np.zeros(4, np.int64), 2, top_p=1.5)
    r = Request(np.zeros(4, np.int64), 2, temperature=0.5, seed=None)
    assert r.seed == r.rid and r.sampled


# ------------------------------------------------- scheduling policies

def _fake_req(age_s, now):
    r = Request(np.zeros(4, np.int64), 4)
    r.t_arrival = now - age_s
    return r


def test_slo_feedback_policy_sheds_only_lost_causes():
    now = time.perf_counter()
    pol = SLOFeedbackPolicy(slo_ttft_ms=100.0)
    fresh = _fake_req(0.01, now)
    stale = _fake_req(0.5, now)
    d = pol.triage([fresh, stale], now)
    assert [r for r, _ in d.shed] == [stale]
    assert d.shed[0][1] < 0 and not d.deprioritized
    # live service feedback tightens the estimate: a request with 40ms
    # left is viable at est 0 but lost once delivery takes ~80ms
    borderline = _fake_req(0.06, now)
    assert not pol.triage([borderline], now).shed
    for _ in range(20):
        pol.observe_service(80.0)
    assert pol.triage([borderline], now).shed
    # untargeted policy is inert
    assert resolve_policy("slo_feedback", None).triage(
        [stale], now).empty


def test_slo_feedback_defer_mode_defers_once():
    now = time.perf_counter()
    pol = SLOFeedbackPolicy(slo_ttft_ms=50.0, mode="defer")
    stale = _fake_req(0.4, now)
    d = pol.triage([stale], now)
    assert [r for r, _ in d.deprioritized] == [stale] and not d.shed
    stale.deprioritized = True          # what the scheduler stamps
    assert pol.triage([stale], now).empty
    with pytest.raises(ValueError):
        SLOFeedbackPolicy(slo_ttft_ms=1.0, mode="nope")


def test_resolve_policy_knob():
    assert isinstance(resolve_policy(None), FIFOPolicy)
    assert isinstance(resolve_policy("fifo"), FIFOPolicy)
    p = resolve_policy("slo_feedback", 123.0)
    assert isinstance(p, SLOFeedbackPolicy) and p.slo_ttft_ms == 123.0
    assert resolve_policy(p) is p
    with pytest.raises(ValueError):
        resolve_policy("round_robin")


def test_engine_sheds_under_overload_and_accounts_it():
    """Engine-level shedding: a one-slot engine flooded with requests
    under a tight TTFT target sheds the stale backlog — shed requests
    retire DONE with zero tokens, the counters / SLO verdicts /
    snapshot section / flight events all agree, and the engine drains
    cleanly."""
    m = _model()
    eng = ServingEngine(m, num_slots=1, bucket_min=8,
                        slo_ttft_ms=40.0, policy="slo_feedback")
    rs = np.random.RandomState(4)
    reqs = [eng.add_request(p, max_new_tokens=8)
            for p in _prompts(rs, [6] * 10)]
    done = eng.run()
    assert len(done) == len(reqs) and all(r.done for r in reqs)
    shed = [r for r in reqs if r.shed_reason]
    served = [r for r in reqs if not r.shed_reason]
    assert shed and served                  # some of each
    for r in shed:
        assert r.generated == [] and r.shed_reason == "slo_lost"
        names = [e["event"] for e in eng.request_trace(r.rid).events]
        assert names == ["enqueued", "shed", "retired"]
        assert eng.request_trace(r.rid).reason == "shed"
    for r in served:
        np.testing.assert_array_equal(r.output_ids,
                                      _ref(m, r.prompt, 8))
    snap = eng.metrics.snapshot()
    sched = snap["scheduler"]
    assert sched["policy"] == "slo_feedback"
    assert sched["shed_total"] == len(shed)
    assert sched["shed"] == {"slo_lost": len(shed)}
    # every request got an SLO verdict; shed ones violate, never attain
    slo = snap["slo"]
    assert slo["requests"] == len(reqs)
    assert slo["violations"].get("slo_lost") == len(shed)
    assert slo["attained"] <= len(served)
    # the policy label rides on the metrics family
    assert 'scheduler_policy="slo_feedback"' in \
        eng.metrics.prometheus_text()


def test_engine_feeds_service_latency_back_to_policy():
    """The engine closes the SLO-feedback loop: after a served
    request's first token, the policy's service EWMA reflects the
    delivered admission->first-token latency (it is NOT a config guess
    that stays 0.0 forever). Compile-tainted samples are excluded —
    only requests admitted after the last build feed the estimate —
    and declare_warmup() resets the estimate for steady state."""
    m = _model()
    pol = SLOFeedbackPolicy(slo_ttft_ms=60_000.0)   # never sheds
    eng = ServingEngine(m, num_slots=2, bucket_min=8, policy=pol)
    assert pol.service_est_ms == 0.0
    rs = np.random.RandomState(11)
    prompts = _prompts(rs, [6, 9])
    # first pass compiles the inventory: every first token here paid
    # an XLA build, so none of them may seed the EWMA
    for p in prompts:
        eng.add_request(p, max_new_tokens=3)
    eng.run()
    assert pol.service_est_ms == 0.0
    # steady-state pass over the compiled paths: the estimate moves
    reqs = [eng.add_request(p, max_new_tokens=3) for p in prompts]
    eng.run()
    assert all(r.generated for r in reqs)
    assert pol.service_est_ms > 0.0
    # the estimate is a plausible admission->first-token figure for
    # the served requests, not garbage
    ttfts = [(r.t_first_token - r.t_admitted) * 1000.0 for r in reqs]
    assert pol.service_est_ms <= max(ttfts) + 1e-6
    eng.declare_warmup()
    assert pol.service_est_ms == 0.0


def test_prefill_token_budget_validation():
    from paddle_tpu.serving import ServingConfig
    # budget without chunking would silently never apply
    with pytest.raises(ValueError):
        ServingConfig(prefill_token_budget=16)
    # coerced to int, then range-checked against the chunk width
    with pytest.raises(ValueError):
        ServingConfig(prefill_chunk=8, prefill_token_budget=7.9)
    with pytest.raises(ValueError):
        ServingConfig(prefill_chunk=8, prefill_token_budget=-8)
    cfg = ServingConfig(prefill_chunk=8, prefill_token_budget=16.0)
    assert cfg.prefill_token_budget == 16
    assert isinstance(cfg.prefill_token_budget, int)
    # default: one chunk per step
    assert ServingConfig(prefill_chunk=8).prefill_token_budget == 8
    assert ServingConfig().prefill_token_budget is None


def test_fifo_default_never_sheds():
    m = _model()
    eng = ServingEngine(m, num_slots=1, bucket_min=8, slo_ttft_ms=1.0)
    rs = np.random.RandomState(6)
    reqs = [eng.add_request(p, max_new_tokens=4)
            for p in _prompts(rs, [5] * 6)]
    eng.run()
    assert all(r.generated for r in reqs)   # everyone served, late
    sched = eng.metrics.snapshot()["scheduler"]
    assert sched["policy"] == "fifo" and sched["shed_total"] == 0


def test_engine_defer_mode_serves_everyone_late():
    """defer mode: lost-cause requests move behind viable ones (once,
    flight-evented) but still get served — zero sheds, every output
    exact."""
    m = _model()
    pol = SLOFeedbackPolicy(slo_ttft_ms=40.0, mode="defer")
    eng = ServingEngine(m, num_slots=1, bucket_min=8, policy=pol)
    rs = np.random.RandomState(8)
    prompts = _prompts(rs, [6] * 8)
    reqs = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        assert not r.shed_reason
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, 6))
    sched = eng.metrics.snapshot()["scheduler"]
    assert sched["shed_total"] == 0 and sched["deprioritized"] > 0
    deferred = [r for r in reqs if r.deprioritized]
    assert deferred
    names = [e["event"] for e in
             eng.request_trace(deferred[0].rid).events]
    assert "deprioritized" in names


def test_debug_state_carries_scheduler_section():
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8, prefill_chunk=8,
                        policy="slo_feedback", slo_ttft_ms=5000.0)
    rs = np.random.RandomState(14)
    eng.add_request(rs.randint(0, 97, (20,)).astype(np.int64), 2)
    eng.step()
    state = eng.debug_state()
    sched = state["scheduler"]
    assert sched["policy"] == "slo_feedback"
    assert sched["prefill_chunk"] == 8
    assert "chunked_inflight" in sched
    eng.run()


def test_prefill_arm_counters_follow_the_dispatches():
    """The paged prefill program's two arms, counted a dispatch: an
    unshared prompt attends its own keys only; a radix hit also reads
    its cached prefix; a chunked prompt's first chunk is the first arm,
    every later one the second, reading what lies below its start (the
    end-aligned final chunk included)."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8, block_size=4,
                        prefill_chunk=16)
    rs = np.random.RandomState(38)
    stem = rs.randint(0, 97, (12,)).astype(np.int64)
    unshared = np.concatenate([stem, rs.randint(0, 97, (3,))])   # 15
    hit = np.concatenate([stem, rs.randint(0, 97, (5,))])        # 12 + 5
    chunked = rs.randint(0, 97, (41,)).astype(np.int64)  # 0, 16, 25
    reqs = []
    for p in (unshared, hit, chunked):
        reqs.append(eng.add_request(p, max_new_tokens=3))
        eng.run()
    for r, p in zip(reqs, (unshared, hit, chunked)):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, 3))
    snap = eng.metrics.snapshot()
    assert snap["prefills"] == 2
    assert snap["scheduler"]["prefill_chunks"] == 3
    assert snap["prefills_without_prefix"] == 2      # unshared, chunk 0
    assert snap["prefills_with_prefix"] == 3         # hit, chunks 1 and 2
    assert snap["prefill_prefix_tokens_read"] == 12 + 16 + 25
    text = eng.metrics.prometheus_text()
    assert 'serving_prefill_arm_dispatches_total{arm="prefix"} 3' in text
    assert 'serving_prefill_prefix_tokens_read_total 53' in text
