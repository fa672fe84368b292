"""Serving engine over its paged pool: exact greedy
parity with per-request generate() under shared-prefix traffic, tail-
only prefill for cache hits (flight-recorder + counter evidence — the
ISSUE 6 acceptance contract), zero steady-state recompiles with paging
enabled (watchdog-verified), eviction under block pressure, and the
leak-free dispatch-failure rollback."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ServingEngine, StepScheduler
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

QUEUED = "queued"


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


def test_paged_matches_generate_shared_and_disjoint_prompts():
    """Mixed traffic — shared-stem prompts, disjoint prompts, staggered
    arrivals, more requests than slots (slot AND block recycling) —
    every output exactly equals batch-1 generate()."""
    m = _model()
    eng = ServingEngine(m, num_slots=3, bucket_min=8,
                        block_size=4)
    rs = np.random.RandomState(0)
    stem = rs.randint(0, 97, (16,)).astype(np.int64)
    prompts = [np.concatenate([stem, rs.randint(0, 97, (k,))
                               .astype(np.int64)]) for k in (3, 6, 2, 9)]
    prompts += [rs.randint(0, 97, (n,)).astype(np.int64)
                for n in (5, 11, 7)]
    specs = [6, 4, 8, 5, 7, 3, 6]
    reqs = []
    for i, (p, k) in enumerate(zip(prompts, specs)):
        reqs.append(eng.add_request(p, max_new_tokens=k))
        if i % 3 == 2:
            eng.step()
            eng.step()
    eng.run()
    for r, p, k in zip(reqs, prompts, specs):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, k))
    assert eng.metrics.snapshot()["prefix_cache"]["hits"] >= 3
    eng.pool.check_conservation()


def test_second_request_prefills_only_the_tail():
    """ISSUE 6 acceptance: two requests sharing an N-token prefix —
    the second's prefill dispatches ONLY the uncached tail, asserted
    via flight-recorder events AND the prefix_cache hit counters, with
    exact greedy parity against non-paged generate()."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        block_size=4)
    rs = np.random.RandomState(3)
    N = 24                                     # shared, block-aligned
    shared = rs.randint(0, 97, (N,)).astype(np.int64)
    p1 = np.concatenate([shared, rs.randint(0, 97, (5,)).astype(np.int64)])
    p2 = np.concatenate([shared, rs.randint(0, 97, (3,)).astype(np.int64)])
    r1 = eng.add_request(p1, max_new_tokens=6)
    eng.run()
    r2 = eng.add_request(p2, max_new_tokens=6)
    eng.run()
    # parity with the non-paged oracle
    np.testing.assert_array_equal(r1.output_ids, _ref(m, p1, 6))
    np.testing.assert_array_equal(r2.output_ids, _ref(m, p2, 6))
    # counters: one miss (r1), one hit serving the full shared span
    pc = eng.metrics.snapshot()["prefix_cache"]
    assert pc["hits"] == 1 and pc["misses"] == 1
    assert pc["cached_tokens"] == N
    assert pc["computed_tokens"] == len(p1) + (len(p2) - N)
    # flight recorder: r2 carries the prefix_hit with the saved span,
    # r1 has none; both keep the full lifecycle chain
    t2 = eng.request_trace(r2.rid)
    hits = [e for e in t2.events if e["event"] == "prefix_hit"]
    assert len(hits) == 1
    assert hits[0]["cached_tokens"] == N
    assert hits[0]["tail_tokens"] == len(p2) - N
    names = [e["event"] for e in t2.events]
    assert names.index("admitted") < names.index("prefix_hit") \
        < names.index("prefill_dispatched")
    t1 = eng.request_trace(r1.rid)
    assert not any(e["event"] == "prefix_hit" for e in t1.events)
    # the cost model does not credit cached spans as prefill compute
    acct = eng.cost_model()["prefill_accounting"]
    assert acct["prefix_cached_tokens"] == N
    assert acct["tokens_computed"] == pc["computed_tokens"]


def test_paged_zero_steady_state_recompiles():
    """The zero-recompile invariant survives paging: after a warmup
    wave covers the tail buckets, identical traffic adds zero compiles
    (watchdog-verified) and the whole inventory is bounded by
    len(buckets) + 1 — prefix-length variety is traced, not compiled."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        block_size=4, watchdog_mode="raise")
    rs = np.random.RandomState(2)
    stem = rs.randint(0, 97, (12,)).astype(np.int64)
    wave = [np.concatenate([stem, rs.randint(0, 97, (k,))
                            .astype(np.int64)]) for k in (2, 5, 3, 7)]
    for p in wave:
        eng.add_request(p, max_new_tokens=4)
    eng.run()
    warm = eng.metrics.compiles
    assert warm <= len(eng.scheduler.buckets) + 1
    eng.declare_warmup()
    for p in wave:                 # same traffic: all hits, no builds
        eng.add_request(p, max_new_tokens=4)
    eng.run()                      # watchdog_mode="raise" would throw
    assert eng.metrics.compiles == warm
    assert eng.watchdog.report()["steady_state_compiles"] == 0
    pc = eng.metrics.snapshot()["prefix_cache"]
    assert pc["hits"] >= len(wave)


def test_paged_parity_under_block_pressure_with_eviction():
    """An undersized physical pool: admissions wait for blocks, LRU
    cached blocks are evicted and reused — outputs stay exactly equal
    to generate() throughout."""
    m = _model()
    # 2 slots, 16 blocks of 4 = tight for 64-token slot capacity
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        block_size=4, num_blocks=17, max_len=32)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 97, (n,)).astype(np.int64)
               for n in (9, 14, 6, 12, 8, 11)]
    reqs = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    eng.run()
    for r, p in zip(reqs, prompts):
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, 5))
    assert eng.pool.evictions > 0, "pressure never evicted"
    eng.pool.check_conservation()


def test_paged_sync_mode_matches_pipelined():
    m = _model()
    rs = np.random.RandomState(10)
    stem = rs.randint(0, 97, (8,)).astype(np.int64)
    prompts = [np.concatenate([stem, rs.randint(0, 97, (k,))
                               .astype(np.int64)]) for k in (3, 6, 2)]
    outs = []
    for depth in (1, 0):
        eng = ServingEngine(m, num_slots=2, bucket_min=8,
                            block_size=4, async_depth=depth)
        rr = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        eng.run()
        outs.append([r.output_ids for r in rr])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_engine_kernel_places_entries_like_the_gather_path():
    """The decode program whose attention kernel places the step's new
    entry (interpret mode) against the one that writes blocks in
    ``jnp`` and gathers: three slots, six requests, so slots are
    released and taken again while released ones keep stepping; a
    prompt of 21 prefilled in chunks of 8, its slot PARKED through the
    decode steps in between; outputs of up to 14 tokens over blocks of
    4, so every stream crosses block boundaries and the kernel's chunk.
    Every stream is ``generate()``'s on both."""
    from paddle_tpu.ops import paged_attention as pa
    m = _model()
    rs = np.random.RandomState(43)
    lens, new = (5, 21, 9, 3, 12, 7), (14, 6, 11, 9, 5, 13)
    prompts = [rs.randint(0, 97, (n,)).astype(np.int64) for n in lens]
    want = [_ref(m, p, k) for p, k in zip(prompts, new)]
    for kernel in (True, False):
        pa._FORCE_INTERPRET[0] = kernel
        try:
            eng = ServingEngine(m, num_slots=3, bucket_min=8, block_size=4,
                                prefill_chunk=8)
            assert eng.paged_attn == kernel
            reqs = [eng.add_request(p, max_new_tokens=k)
                    for p, k in zip(prompts, new)]
            eng.run()
        finally:
            pa._FORCE_INTERPRET[0] = False
        assert eng.pool.reuse_count >= 2
        assert eng.metrics.snapshot()["scheduler"]["prefill_chunks"] >= 3
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(r.output_ids, w)
        eng.pool.check_conservation()


def test_plan_prefix_respects_tail_and_capacity():
    """plan_prefix: always leaves >= 1 tail token, stays block-aligned,
    and shrinks the used prefix until the bucket-padded tail fits the
    slot's addressable capacity."""
    sch = StepScheduler([8, 16, 32, 48], 48)
    # full prompt cached: back off one block so a tail remains
    start, bucket = sch.plan_prefix(16, 16, 4, 48)
    assert start == 12 and bucket == 8
    # plain hit: aligned prefix, tail bucketed up
    start, bucket = sch.plan_prefix(23, 16, 4, 48)
    assert start == 16 and bucket == 8
    # capacity squeeze: 44 + bucket_for(2)=8 > 48 -> shrink to 40
    start, bucket = sch.plan_prefix(46, 44, 4, 48)
    assert start == 40 and bucket == 8 and start + bucket <= 48
    # no cache: start 0, whole prompt bucketed
    start, bucket = sch.plan_prefix(30, 0, 4, 48)
    assert start == 0 and bucket == 32


@pytest.mark.parametrize("block_size", [4, 16])
def test_failed_prefill_dispatch_leaks_no_slot(block_size):
    """Satellite regression: a prefill dispatch failure between
    acquire and admission completion must release the slot and every
    pinned/allocated block, requeue the request, and leave the engine
    able to serve it once the fault clears. At blocks of 4 the prompts
    hold full (committable) blocks, at 16 none."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        block_size=block_size)
    rs = np.random.RandomState(6)
    prompts = [rs.randint(0, 97, (n,)).astype(np.int64) for n in (5, 9)]
    orig = eng._compiled

    def failing(key, fn, args, donate=()):
        if key[0] == "paged_prefill":
            raise RuntimeError("injected dispatch failure")
        return orig(key, fn, args, donate=donate)

    eng._compiled = failing
    reqs = [eng.add_request(p, max_new_tokens=4) for p in prompts]
    with pytest.raises(RuntimeError, match="injected"):
        eng.run()
    # nothing leaked: all slots free, no active entries, requests back
    # in the queue in order, no phantom in-flight tokens
    assert eng.pool.free_count == 2
    assert not eng.scheduler.active
    assert [r.rid for r in eng.scheduler.queue] == [r.rid for r in reqs]
    for r in reqs:
        assert r.state == QUEUED and r.slot is None and r.inflight == 0
    eng.pool.check_conservation()
    assert eng.pool.live_blocks == 0
    # the rolled-back attempt never reached the admission counters
    assert eng.metrics.requests_admitted == 0
    # fault clears: the same engine drains the queue with full parity
    eng._compiled = orig
    eng.run()
    for r, p in zip(reqs, prompts):
        assert r.done
        np.testing.assert_array_equal(r.output_ids, _ref(m, p, 4))
    # admission accounting is once-per-request despite the retry, and
    # the flight trace voids the first attempt explicitly
    assert eng.metrics.requests_admitted == len(reqs)
    pcts = eng.metrics.snapshot()["latency_percentiles"]
    assert pcts["queue_wait"]["count"] == len(reqs)
    names = [e["event"] for e in eng.request_trace(reqs[0].rid).events]
    assert names.count("admitted") == 2        # voided attempt + retry
    assert names.count("admission_rolled_back") == 1
    i_rb = names.index("admission_rolled_back")
    assert names.index("admitted") < i_rb and "admitted" in names[i_rb:]


def test_cached_paged_attention_matches_slot_attention():
    """ops.attention.cached_paged_attention == cached_slot_attention
    when the block table lays the same K/V out contiguously; trash-
    padded table entries are invisible under the length mask."""
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import (cached_paged_attention,
                                          cached_slot_attention)

    rs = np.random.RandomState(4)
    S, nh, hd, BS, MB = 3, 2, 8, 4, 4
    C = MB * BS
    NB = S * MB + 1
    kc = jnp.asarray(rs.randn(NB, nh, BS, hd).astype(np.float32) * 10)
    vc = jnp.asarray(rs.randn(NB, nh, BS, hd).astype(np.float32) * 10)
    q = jnp.asarray(rs.randn(S, nh, hd).astype(np.float32))
    lengths = jnp.asarray(np.array([3, 9, 16], np.int32))
    # slot s owns blocks [1 + s*MB, ...); pad unused entries with trash
    tables = np.zeros((S, MB), np.int32)
    for s, L in enumerate([3, 9, 16]):
        used = -(-L // BS)
        tables[s, :used] = 1 + s * MB + np.arange(used)
    tables = jnp.asarray(tables)
    out = cached_paged_attention(q, kc, vc, tables, lengths)
    # reference: materialize each slot's contiguous view by hand
    kv_slot = np.zeros((S, nh, C, hd), np.float32)
    vv_slot = np.zeros((S, nh, C, hd), np.float32)
    tb = np.asarray(tables)
    for s in range(S):
        for b in range(MB):
            kv_slot[s, :, b * BS:(b + 1) * BS] = np.asarray(
                kc[tb[s, b]]).transpose(0, 1, 2)[:, :, :]
            vv_slot[s, :, b * BS:(b + 1) * BS] = np.asarray(vc[tb[s, b]])
    ref = cached_slot_attention(q, jnp.asarray(kv_slot),
                                jnp.asarray(vv_slot), lengths)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def _parent_decode_step(params, toks, pos, tables, kc, vc, nh, BS):
    """The decode step as the parent of ISSUE 26 formulated it, layer
    by layer in plain jnp: each layer sliced out of the pool, a ROW
    scatter into the slice, the pool put together again. Attended
    lengths as the program hands them on since ISSUE 29: a slot's
    positions so far, capped by the blocks its table row holds (a
    released row, all trash, attends nothing)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.attention import cached_paged_attention
    from paddle_tpu.text.models import _decode_forward_builder
    (S,), (L, _, _, _, hd) = toks.shape, kc.shape
    ln, _ = _decode_forward_builder(nh, hd, nh * hd)
    x = params["wemb"][toks] + params["pemb"][pos]
    wpos = jnp.minimum(pos, tables.shape[1] * BS - 1)
    bidx, off = tables[jnp.arange(S), wpos // BS], wpos % BS
    from paddle_tpu.serving.paged.pool import TRASH_BLOCK
    lengths = jnp.minimum(pos + 1,
                          (tables != TRASH_BLOCK).sum(axis=1) * BS)
    for l in range(L):
        p = {k: v[l] for k, v in params["stacked"].items()}
        q, k, v = (ln(x, p["ln1_w"], p["ln1_b"]) @ p["qkv_w"]
                   + p["qkv_b"]).reshape(S, 3, nh, hd).transpose(1, 0, 2, 3)
        kc = kc.at[l, bidx, :, off].set(k)
        vc = vc.at[l, bidx, :, off].set(v)
        o = cached_paged_attention(q, kc[l], vc[l], tables, lengths)
        x = x + (o.reshape(S, nh * hd) @ p["out_w"] + p["out_b"])
        m = jax.nn.gelu(ln(x, p["ln2_w"], p["ln2_b"]) @ p["fc1_w"]
                        + p["fc1_b"], approximate=True)
        x = x + (m @ p["fc2_w"] + p["fc2_b"])
    logits = ln(x, params["lnf_w"], params["lnf_b"]) @ params["head"]
    return jnp.argmax(logits, -1).astype(jnp.int32), pos + 1, kc, vc


@pytest.mark.parametrize("trash_writers", [1, 3], ids=["one", "meeting"])
def test_paged_decode_writes_only_its_rows_in_every_layer(trash_writers):
    """One paged_decode step on a pool of distinct values changes, in
    EVERY layer l, exactly the rows (l, bidx[s], :, off[s]) and nothing
    else (no layer bleeds into its neighbour's flat block range), and
    tokens and pool are those of the parent's formulation. Slots: two
    live ones at different positions behind a SHARED prefix block, a
    fresh one, one parked past its row (pos >= MB*BS), and released
    rows (all trash). With one trash writer every row is determined;
    with several ("meeting": two released rows and a chunk-parked slot
    whose last block is trash) the whole-block write lets any ONE of
    them win the trash block, which stays garbage behind the length
    mask: everything outside the trash block still matches."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.paged.pool import TRASH_BLOCK as TR
    from paddle_tpu.serving.paged.programs import build_paged_fns

    m = _model(num_layers=3)
    cfg, params = m.cfg, m.export_decode_params()
    L, nh, BS, MB = cfg.num_layers, cfg.num_heads, 4, 4
    hd, C = cfg.hidden_size // nh, MB * BS
    tables = [[1, 2, TR, TR],      # live, pos 5: block 2, offset 1
              [1, 3, 4, TR],       # shares prefix block 1; pos 10
              [5, 6, 7, 8],        # parked past its row: (8, BS-1)
              [TR, TR, TR, TR],    # released: trash, offset 3
              [9, TR, TR, TR]]     # fresh: block 9, offset 0
    pos = [5, 10, C + 3, 7, 0]
    if trash_writers == 3:
        tables += [[TR] * MB, [10, 11, TR, TR]]   # released; chunk-parked
        pos += [2, C - 1]
    S, NB = len(pos), 12
    # slots whose token is read through a trash block that several wrote
    live = np.setdiff1d(np.arange(S), [3, 5, 6] if trash_writers > 1 else [])
    rs = np.random.RandomState(26)
    kc0 = rs.randn(L, NB, nh, BS, hd).astype(np.float32)
    vc0 = rs.randn(L, NB, nh, BS, hd).astype(np.float32)
    toks = jnp.asarray(rs.randint(1, cfg.vocab_size, S).astype(np.int32))
    pos, tables = jnp.asarray(pos, jnp.int32), jnp.asarray(tables, jnp.int32)

    _, decode = build_paged_fns(cfg, S, BS, NB, MB)
    nxt, pos1, kc1, vc1 = jax.jit(decode)(
        params, toks, pos, tables, jnp.asarray(kc0), jnp.asarray(vc0))
    r_nxt, r_pos1, r_kc, r_vc = jax.jit(
        _parent_decode_step, static_argnums=(6, 7))(
        params, toks, pos, tables, jnp.asarray(kc0), jnp.asarray(vc0),
        nh, BS)

    wpos = np.minimum(np.asarray(pos), C - 1)
    bidx = np.asarray(tables)[np.arange(S), wpos // BS]
    expect = np.zeros((L, NB, nh, BS), bool)
    expect[:, bidx, :, wpos % BS] = True
    keep = np.arange(NB) != TR if trash_writers > 1 else np.ones(NB, bool)
    for new, old, ref in ((kc1, kc0, r_kc), (vc1, vc0, r_vc)):
        new = np.asarray(new)
        changed = (new != old).any(-1)
        np.testing.assert_array_equal(changed[:, keep], expect[:, keep])
        # untouched rows are the input's bits; written rows the parent's
        np.testing.assert_array_equal(new[:, keep],
                                      np.asarray(ref)[:, keep])
    if trash_writers > 1:
        # the trash block took whole blocks only: per layer at most
        # one of its rows differs from the input
        assert (changed[:, TR].any(1).sum(-1) <= 1).all()
    np.testing.assert_array_equal(np.asarray(nxt)[live],
                                  np.asarray(r_nxt)[live])
    np.testing.assert_array_equal(np.asarray(pos1), np.asarray(r_pos1))


# ------------------------------------------------ the prefill program
# paged_prefill against forward_t over a contiguous cache (ISSUE 38):
# 36 blocks of 16 a slot; buckets 32 (the jnp form), 128 and 256 (the
# flash kernel, interpreted); the cached prefix a radix hit of 3 blocks,
# or the first of two chunks: the second END-ALIGNED where the prompt
# is no whole number of chunks (its start falls inside a block and its
# first 11 rows recompute the first chunk's last), else tiled and short
_PF_BS, _PF_MB, _PF_NB = 16, 36, 48
_PF_ROW = [int(b) for b in
           np.random.RandomState(38).permutation(np.arange(1, 48))[:36]]


def _prefill_model(dtype):
    paddle.seed(38)
    cfg = TransformerLMConfig(vocab_size=211, hidden_size=128,
                              num_layers=3, num_heads=2,
                              max_seq_len=_PF_MB * _PF_BS, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    import jax
    import jax.numpy as jnp
    params = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, dtype), m.export_decode_params())
    return cfg, params


def _to_view(pool, row):
    """[L, NB, nh, BS, hd] through a table row -> [L, nh, C, hd]."""
    g = np.asarray(pool)[:, row]                 # [L, MB, nh, BS, hd]
    L, MB, nh, BS, hd = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(L, nh, MB * BS, hd)


@pytest.fixture
def flash_interpreted():
    from paddle_tpu.ops import attention
    attention._FORCE_INTERPRET[0] = True
    yield
    attention._FORCE_INTERPRET[0] = False


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("full", [True, False], ids=["full", "padded"])
@pytest.mark.parametrize("prefix", ["none", "radix", "chunked"])
@pytest.mark.parametrize("bucket", [32, 128, 256])
def test_paged_prefill_matches_forward_t(bucket, prefix, full, dtype,
                                         flash_interpreted):
    """Every cache position the run owns, in every layer, is what
    ``forward_t`` writes into a contiguous cache, and the token it
    emits is one whose reference logit is the reference's best (to the
    dtype's rounding); every other element of the pool is bit for bit
    what it was: the shared prefix blocks, the slot's blocks past the
    run, the bucket's padding rows, other slots' blocks and the trash
    block that the row's padding entries name."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.paged.pool import TRASH_BLOCK as TR
    from paddle_tpu.serving.paged.programs import build_paged_fns
    from paddle_tpu.text.models import _decode_forward_builder

    cfg, params = _prefill_model(dtype)
    L, nh, BS, MB, NB = cfg.num_layers, cfg.num_heads, _PF_BS, _PF_MB, _PF_NB
    hd, C = cfg.hidden_size // nh, MB * BS
    _, forward_t = _decode_forward_builder(nh, hd, cfg.hidden_size)
    prefill, _ = build_paged_fns(cfg, 4, BS, NB, MB)
    prefill = jax.jit(prefill)
    forward_t = jax.jit(forward_t)
    from paddle_tpu.ops import attention
    assert attention._use_pallas(
        jnp.zeros((1, nh, bucket, hd), dtype)) == (bucket >= 128)
    tail = bucket if full else bucket - 11
    # (start, length, final) of each dispatch; the LAST one is judged
    runs = {"none": [(0, tail, 1)],
            "radix": [(3 * BS, tail, 1)],
            "chunked": [(0, bucket, 0), (bucket - 11, bucket, 1) if full
                        else (bucket, tail, 1)]}[prefix]
    n = runs[-1][0] + runs[-1][1]
    assert n <= C
    rs = np.random.RandomState(bucket + len(prefix) + full)
    ids = rs.randint(1, cfg.vocab_size, n).astype(np.int32)
    # the slot's row: blocks for the prompt and 5 tokens more, then trash
    held = -(-(n + 5) // BS)
    row = np.asarray(_PF_ROW[:held] + [TR] * (MB - held), np.int32)
    kc = rs.randn(L, NB, nh, BS, hd).astype(np.float32)
    vc = rs.randn(L, NB, nh, BS, hd).astype(np.float32)
    kc, vc = jnp.asarray(kc, dtype), jnp.asarray(vc, dtype)
    # the reference's contiguous cache starts as the slot's view of the
    # pool, so that rows nobody writes compare equal as well
    rk = jnp.asarray(_to_view(kc.astype(jnp.float32), row), dtype)[:, None]
    rv = jnp.asarray(_to_view(vc.astype(jnp.float32), row), dtype)[:, None]
    if prefix == "radix":
        # the shared prefix: computed by the reference, put into the
        # pool's blocks as another request's prefill left them
        _, rk, rv = forward_t(params, jnp.asarray(ids[None, :3 * BS]),
                              jnp.int32(0), rk, rv)

        def shared(cache, ref):
            cache = np.array(cache.astype(jnp.float32))
            cache[:, row[:3]] = np.asarray(ref.astype(jnp.float32))[
                :, 0, :, :3 * BS].reshape(L, nh, 3, BS, hd).transpose(
                0, 2, 1, 3, 4)
            return jnp.asarray(cache, dtype)
        kc, vc = shared(kc, rk), shared(vc, rv)
    toks, pos = jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32)
    for start, length, final in runs:
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :length] = ids[start:start + length]
        before = (np.asarray(kc.astype(jnp.float32)),
                  np.asarray(vc.astype(jnp.float32)))
        first, toks, pos, kc, vc = prefill(
            params, jnp.asarray(tokens), jnp.int32(length),
            jnp.int32(start), jnp.int32(2), jnp.int32(final),
            jnp.asarray(row), toks, pos, kc, vc)
        logits, rk, rv = forward_t(params, jnp.asarray(tokens),
                                   jnp.int32(start), rk, rv)
    tol = 2e-5 if dtype == "float32" else 4e-2
    owned = np.zeros(C, bool)
    owned[start:start + length] = True
    for new, old, ref in ((kc, before[0], rk), (vc, before[1], rv)):
        new = np.asarray(new.astype(jnp.float32))
        ref = np.asarray(ref.astype(jnp.float32))[:, 0]
        np.testing.assert_allclose(_to_view(new, row)[:, :, owned],
                                   ref[:, :, owned], rtol=tol, atol=tol)
        # nothing else moved: compare the pool with the run's own
        # positions put back as they were
        back = _to_view(new, row)
        back[:, :, owned] = _to_view(old, row)[:, :, owned]
        restored = new.copy()
        restored[:, row[:held]] = back.reshape(
            L, nh, MB, BS, hd).transpose(0, 2, 1, 3, 4)[:, :held]
        np.testing.assert_array_equal(restored, old)
    last = np.asarray(logits.astype(jnp.float32))[0, length - 1]
    assert last.max() - last[int(first[0])] <= tol
    assert int(toks[2]) == int(first[0])
    assert int(pos[2]) == n
