"""Cache observatory (paddle_tpu.observability.cache) in isolation:
the SHARDS-style reuse-distance sampler validated against the exact
LRU oracle (rate=1.0 is pinned EQUAL; sampled rates within tolerance
on fixed seeds), fleet merge rules for MRC curves and heat digests,
radix thrash (evict-then-reinsert) accounting, block-lifetime and
savings attribution through the PagedKVPool observer hooks, the
pinned report schema, and tools/cache_report.py self-runs — a healthy
shared-prefix drain exits 0, a planted thrash workload exits 1 naming
the verdict, unrecognizable input exits 2."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.observability import (CACHE_KEYS, CacheObservatory,
                                      MetricsRegistry,
                                      ReuseDistanceSampler,
                                      disabled_cache_report, exact_mrc,
                                      merge_heat_digests,
                                      merge_mrc_points,
                                      top_prefix_digest)
from paddle_tpu.serving.paged import PagedKVPool, RadixPrefixIndex
from paddle_tpu.serving.paged.radix import path_fingerprint

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOL = os.path.join(_ROOT, "tools", "cache_report.py")

CAPS = (1, 2, 4, 8, 16, 32, 64)


def _zipf_trace(rs, n_access, n_obj, a=1.3):
    """Skewed integer-id access stream — the shape real prefix
    traffic has (few hot stems, long cold tail)."""
    ranks = np.minimum(rs.zipf(a, size=n_access), n_obj) - 1
    # spread ids so the spatial hash sees arbitrary values, not 0..n
    # (NOT by the sampler's own Knuth constant — that would correlate
    # with its threshold test and bias which objects get sampled)
    return [int(r) * 7919 + 13 for r in ranks]


# ------------------------------------------------- sampler vs oracle

def test_sampler_rate_one_equals_exact_oracle():
    """rate=1.0 samples everything and scales distances by 1 — the
    estimator must agree with the exact LRU simulation EXACTLY, at
    every capacity, on any trace."""
    rs = np.random.RandomState(7)
    for seed in range(3):
        trace = _zipf_trace(np.random.RandomState(seed), 3000, 400)
        s = ReuseDistanceSampler(rate=1.0, max_tracked=1 << 16)
        for obj in trace:
            s.record(obj)
        oracle = exact_mrc(trace, CAPS)
        for pt in s.mrc(CAPS):
            # equal up to the report's 6-decimal rounding
            assert pt["est_hit_rate"] == \
                pytest.approx(oracle[pt["blocks"]], abs=1e-6), pt
        # and the scalar accessor agrees with the curve
        assert s.est_hit_rate(8) == pytest.approx(oracle[8])
    del rs


def test_sampled_rate_tracks_oracle_within_tolerance():
    """At rate<1 the estimate is statistical; on fixed-seed tiered
    traffic (hot stems / warm / cold tail — the shape the prefix
    cache sees, with enough distinct paths that the spatial sample is
    representative) it stays within a few points of the oracle at
    every evaluated capacity."""
    def tiered(rs, n):
        out = []
        for _ in range(n):
            u = rs.rand()
            if u < 0.6:
                r = rs.randint(0, 40)              # hot stems
            elif u < 0.9:
                r = 40 + rs.randint(0, 200)        # warm
            else:
                r = 240 + rs.randint(0, 2000)      # cold tail
            out.append(int(r) * 7919 + 13)
        return out

    caps = (16, 32, 64, 128, 256)
    for seed in (11, 12, 13):
        trace = tiered(np.random.RandomState(seed), 30000)
        s = ReuseDistanceSampler(rate=0.25, max_tracked=1 << 16)
        for obj in trace:
            s.record(obj)
        oracle = exact_mrc(trace, caps)
        # spatial sampling keeps a fraction ~rate of distinct objects
        assert 0.15 < s.tracked / 2240 < 0.35
        for pt in s.mrc(caps):
            est, exact = pt["est_hit_rate"], oracle[pt["blocks"]]
            assert est is not None
            assert abs(est - exact) <= 0.05, (pt["blocks"], est, exact)


def test_sampler_memory_is_bounded():
    """max_tracked caps the recency stack: a distinct-id flood keeps
    tracked <= cap, ages out the oldest (dropped grows), and re-access
    of an aged-out id counts cold — a conservative bias toward
    predicting misses, never phantom hits."""
    s = ReuseDistanceSampler(rate=1.0, max_tracked=64)
    for obj in range(5000):
        s.record(obj)
    assert s.tracked <= 64
    assert s.dropped == 5000 - 64
    assert s.cold == 5000
    s.record(0)                      # long since aged out
    assert s.cold == 5001 and s.reuses == 0
    # histogram stays bounded too: at most one bucket per tracked slot
    s2 = ReuseDistanceSampler(rate=1.0, max_tracked=32,
                              max_distance=16)
    for rep in range(50):
        for obj in range(32):
            s2.record(obj)
    assert s2.overflow > 0           # d=31 scaled past max_distance
    assert all(d < 16 for d in s2._hist)


def test_sampler_rejects_bad_rate():
    with pytest.raises(ValueError):
        ReuseDistanceSampler(rate=0.0)
    with pytest.raises(ValueError):
        ReuseDistanceSampler(rate=1.5)


def test_empty_sampler_reports_none_not_zero():
    s = ReuseDistanceSampler(rate=1.0)
    assert s.est_hit_rate(8) is None
    assert all(p["est_hit_rate"] is None for p in s.mrc((2, 4)))
    assert exact_mrc([], (2, 4)) == {2: None, 4: None}


# ----------------------------------------------------- fleet merges

def test_merge_mrc_points_is_access_weighted_and_exact():
    """Two replicas' curves merge to the access-weighted mean per
    capacity — algebraically the pooled-histogram estimate, never an
    unweighted average of averages. Capacities survive only if every
    replica evaluated them."""
    a = [{"blocks": 8, "est_hit_rate": 0.5},
         {"blocks": 16, "est_hit_rate": 0.75}]
    b = [{"blocks": 8, "est_hit_rate": 0.9},
         {"blocks": 16, "est_hit_rate": 1.0},
         {"blocks": 32, "est_hit_rate": 1.0}]
    merged = merge_mrc_points([a, b], weights=[100, 300])
    assert [p["blocks"] for p in merged] == [8, 16]   # intersection
    assert merged[0]["est_hit_rate"] == pytest.approx(
        (0.5 * 100 + 0.9 * 300) / 400)
    assert merged[1]["est_hit_rate"] == pytest.approx(
        (0.75 * 100 + 1.0 * 300) / 400)
    # a replica with no sampled traffic contributes zero weight
    c = [{"blocks": 8, "est_hit_rate": None}]
    merged = merge_mrc_points([a, c], weights=[100, 0])
    assert merged == [{"blocks": 8, "est_hit_rate": 0.5}]
    assert merge_mrc_points([a, []], weights=[1, 1]) == []


def test_merge_heat_digests_sums_by_fingerprint():
    d1 = [{"fp": "0000aaaa", "depth": 2, "hits": 5, "last_tick": 10,
           "tokens_saved": 80},
          {"fp": "0000bbbb", "depth": 1, "hits": 2, "last_tick": 4,
           "tokens_saved": 32}]
    d2 = [{"fp": "0000aaaa", "depth": 2, "hits": 3, "last_tick": 25,
           "tokens_saved": 48}]
    merged = merge_heat_digests([d1, d2])
    assert merged[0] == {"fp": "0000aaaa", "depth": 2, "hits": 8,
                         "last_tick": 25, "tokens_saved": 128}
    assert merged[1]["fp"] == "0000bbbb"
    # re-truncation to k after the merge
    assert len(merge_heat_digests([d1, d2], k=1)) == 1


def test_top_prefix_digest_ranks_and_filters():
    entries = [{"fp": f"{i:08x}", "depth": 1, "hits": h,
                "last_tick": i, "tokens_saved": h * 16}
               for i, h in enumerate((0, 3, 9, 1))]
    top = top_prefix_digest(entries, k=2)
    assert [e["hits"] for e in top] == [9, 3]   # zero-hit filtered


# ------------------------------------------- fingerprints and thrash

def test_path_fingerprints_stable_across_instances():
    """The same token path fingerprints identically in any process /
    index instance (the fleet merge key), and access_fingerprints
    matches what insert stamps on the nodes."""
    toks = [3, 1, 4, 1, 5, 9, 2, 6]
    a, b = RadixPrefixIndex(4), RadixPrefixIndex(4)
    a.insert(toks, [1, 2])
    b.insert(toks, [7, 8])
    fps_a = [a._by_block[1].fp, a._by_block[2].fp]
    fps_b = [b._by_block[7].fp, b._by_block[8].fp]
    assert fps_a == fps_b == a.access_fingerprints(toks)
    # chained: child fp depends on the parent path
    assert fps_a[0] == path_fingerprint(0, (3, 1, 4, 1))
    assert fps_a[1] == path_fingerprint(fps_a[0], (5, 9, 2, 6))
    # divergent tails diverge; partial final block contributes nothing
    assert a.access_fingerprints([3, 1, 4, 1, 0, 0, 0, 0])[0] == fps_a[0]
    assert a.access_fingerprints([3, 1, 4, 1, 0, 0, 0, 0])[1] != fps_a[1]
    assert a.access_fingerprints([3, 1, 4, 1, 5]) == [fps_a[0]]


def test_radix_thrash_counts_evict_then_reinsert_once():
    idx = RadixPrefixIndex(2)
    idx.insert([1, 2, 3, 4], [1, 2])
    assert idx.evict_lru({2}.__contains__) == 2    # leaf [3,4] out
    assert idx.thrash_count == 0
    idx.insert([1, 2, 3, 4], [1, 5])        # same path back
    assert idx.thrash_count == 1
    # the eviction memory credits each evicted path once
    assert idx.evict_lru({5}.__contains__) == 5
    idx.insert([1, 2, 3, 4], [1, 6])
    assert idx.thrash_count == 2
    # a NEW path is not thrash
    idx.insert([1, 2, 9, 9], [7])
    assert idx.thrash_count == 2


def test_radix_evicted_fp_memory_is_bounded():
    idx = RadixPrefixIndex(1)
    cap = idx._evicted_fp_cap
    for i in range(cap + 50):
        idx.insert([i], [i + 1])
        idx.evict_lru({i + 1}.__contains__)
    assert len(idx._evicted_fps) <= cap
    assert idx.thrash_count == 0


# --------------------------------------- observatory over a real pool

def _pool(num_slots=4, max_len=32, block_size=4, num_blocks=None):
    return PagedKVPool(num_slots, num_layers=1, num_heads=1,
                       max_len=max_len, head_dim=2,
                       block_size=block_size, num_blocks=num_blocks)


def _admit(pool, rid, prompt, total=None):
    """acquire+commit the way the engine does; returns the alloc."""
    prompt = np.asarray(prompt)
    cached = pool.match_prefix(prompt)
    start = min(cached, len(prompt) - 1) // pool.block_size \
        * pool.block_size
    alloc = pool.acquire(rid, prompt, total or (len(prompt) + 2), start)
    assert alloc is not None
    pool.commit_prefix(alloc.slot, prompt)
    return alloc


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _StubPerf:
    """PR-10 join stand-in: a fixed prefill-family wall."""

    def __init__(self, seconds):
        self._s = seconds

    def prefill_seconds(self):
        return self._s


def test_observatory_accounts_hits_heat_and_lifetimes():
    clock = _FakeClock()
    obs = CacheObservatory(MetricsRegistry(), sample_rate=1.0,
                           clock=clock)
    pool = _pool(num_slots=3, max_len=16, block_size=4)
    obs.attach_pool(pool)
    assert pool.observer is obs

    shared = [1, 2, 3, 4, 5, 6, 7, 8]
    a = _admit(pool, 0, shared + [9])          # cold: 2 probed, 0 hit
    clock.t = 1.0
    b = _admit(pool, 1, shared + [10])         # warm: 2 probed, 2 hit
    assert obs.accesses == 4 and obs.hits == 2
    assert obs.measured_hit_rate() == 0.5

    rep = obs.report()
    assert tuple(rep) == CACHE_KEYS
    assert rep["enabled"] and rep["hit_rate"] == 0.5
    assert rep["capacity_blocks"] == pool.num_blocks - 1
    assert [p["factor"] for p in rep["mrc"]] == [0.5, 1.0, 2.0, 4.0]
    # both shared full blocks were pinned once -> heat 1 each, and
    # tokens_saved = hits * block_size
    top = rep["heat"]["top"]
    assert len(top) == 2
    assert all(e["hits"] == 1 and e["tokens_saved"] == 4 for e in top)
    assert rep["heat"]["total_hits"] == 2
    assert rep["churn"]["thrash_reinserts"] == 0

    # lifetimes: blocks born at t=0 free at t=2 -> 2000ms percentiles
    clock.t = 2.0
    pool.release(a.slot)
    pool.release(b.slot)
    # a's private tail block + b's private tail block freed; shared
    # blocks parked evictable (still alive, still serving hits)
    life = obs.report()["churn"]["block_lifetime_ms"]
    assert life["count"] == 2
    assert life["p50_ms"] == pytest.approx(1500.0, abs=501)

    # the sampler saw every probed fingerprint at rate 1.0: the MRC
    # at current capacity must predict the measured rate on this
    # fully-resident workload
    pt = next(p for p in obs.report()["mrc"] if p["factor"] == 1.0)
    assert pt["est_hit_rate"] == pytest.approx(0.5)


def test_observatory_savings_join_and_estimate_no_accrual():
    obs = CacheObservatory(MetricsRegistry(), sample_rate=1.0)
    pool = _pool()
    obs.attach_pool(pool)
    assert obs.note_reuse(8) is None          # no perf join yet
    assert obs.per_token_prefill_ms() is None
    computed = {"n": 0}
    obs.bind_cost_source(_StubPerf(2.0), lambda: computed["n"])
    assert obs.per_token_prefill_ms() is None  # no computed tokens yet
    computed["n"] = 1000                       # 2s / 1000 tok = 2ms/tok
    assert obs.per_token_prefill_ms() == pytest.approx(2.0)
    # estimate does NOT accrue; note_reuse does, once
    assert obs.estimate_saved_ms(100) == pytest.approx(200.0)
    sav = obs.report()["savings"]
    assert sav["saved_tokens"] == 8 and sav["saved_ttft_ms"] == 0.0
    assert obs.note_reuse(100) == pytest.approx(200.0)
    sav = obs.report()["savings"]
    assert sav["saved_tokens"] == 108
    assert sav["saved_ttft_ms"] == pytest.approx(200.0)
    assert sav["per_token_prefill_ms"] == pytest.approx(2.0)
    assert obs.estimate_saved_ms(0) is None and obs.note_reuse(0) is None


def test_observatory_disabled_shape_and_schema_parity():
    obs = CacheObservatory(MetricsRegistry(), enabled=False)
    obs.attach_pool(_pool())                  # no-op, registers nothing
    assert obs.report() == disabled_cache_report()
    assert tuple(disabled_cache_report()) == CACHE_KEYS
    assert obs.note_reuse(5) is None
    assert obs.estimate_saved_ms(5) is None


def test_observatory_survives_pool_swap():
    """The supervisor-restart contract: attach_pool on a fresh pool
    re-points pull sources; sampler/savings/counter history stays."""
    obs = CacheObservatory(MetricsRegistry(), sample_rate=1.0)
    pool1 = _pool(num_slots=2, max_len=16)
    obs.attach_pool(pool1)
    _admit(pool1, 0, [1, 2, 3, 4, 5])
    _admit(pool1, 1, [1, 2, 3, 4, 6])
    assert obs.accesses == 2 and obs.hits == 1
    before = obs.sampler.sampled_accesses
    pool2 = _pool(num_slots=2, max_len=16)
    obs.attach_pool(pool2)
    assert pool2.observer is obs and obs._pool is pool2
    assert obs.accesses == 2 and obs.sampler.sampled_accesses == before
    _admit(pool2, 2, [1, 2, 3, 4, 7])         # fresh pool: cold again
    assert obs.accesses == 3 and obs.hits == 1
    assert obs.report()["capacity_blocks"] == pool2.num_blocks - 1


# ------------------------------------------------- CLI self-runs

def _healthy_report():
    """A shared-prefix drain on an amply-sized pool: hits, zero
    evictions."""
    obs = CacheObservatory(MetricsRegistry(), sample_rate=1.0)
    pool = _pool(num_slots=4, max_len=32)
    obs.attach_pool(pool)
    shared = list(range(16))
    allocs = []
    for rid in range(8):
        if len(allocs) == pool.num_slots:
            pool.release(allocs.pop(0).slot)
        allocs.append(_admit(pool, rid, shared + [100 + rid]))
    assert pool.evictions == 0
    rep = obs.report()
    assert rep["hit_rate"] > 0.5
    return rep


def _thrash_report():
    """Two disjoint prefix families ping-ponging through a pool that
    can only hold one of them: every acquire evicts the other family,
    every commit re-inserts previously evicted paths."""
    obs = CacheObservatory(MetricsRegistry(), sample_rate=1.0)
    pool = _pool(num_slots=1, max_len=16, num_blocks=5)  # 4 usable
    obs.attach_pool(pool)
    fam_a = list(range(10, 18))
    fam_b = list(range(50, 58))
    for cycle in range(10):
        for rid, fam in ((2 * cycle, fam_a), (2 * cycle + 1, fam_b)):
            alloc = _admit(pool, rid, fam, total=12)
            pool.release(alloc.slot)
    rep = obs.report()
    churn = rep["churn"]
    assert churn["evictions"] >= 8
    assert churn["thrash_reinserts"] / churn["evictions"] >= 0.5
    return rep


def _run_tool(*argv):
    return subprocess.run([sys.executable, _TOOL, *argv],
                          capture_output=True, text=True, timeout=60)


def test_cache_report_cli_healthy_exits_zero(tmp_path):
    path = tmp_path / "cache_ok.json"
    path.write_text(json.dumps(_healthy_report()))
    res = _run_tool(str(path))
    assert res.returncode == 0, res.stderr
    assert "healthy:" in res.stdout
    assert "miss-ratio curve" in res.stdout
    assert "hot prefixes" in res.stdout
    assert "THRASHING" not in res.stdout


def test_cache_report_cli_thrash_exits_one(tmp_path):
    # wrapped in a snapshot-like doc: the CLI auto-locates ["cache"]
    path = tmp_path / "snap_thrash.json"
    path.write_text(json.dumps({"cache": _thrash_report()}))
    res = _run_tool(str(path))
    assert res.returncode == 1, res.stdout + res.stderr
    assert "THRASHING" in res.stdout
    assert "below the live prefix working set" in res.stdout


def test_cache_report_cli_disabled_and_bad_input(tmp_path):
    off = tmp_path / "off.json"
    off.write_text(json.dumps(disabled_cache_report()))
    res = _run_tool(str(off))
    assert res.returncode == 0 and "disabled" in res.stdout
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"foo": 1}))
    assert _run_tool(str(bad)).returncode == 2
    assert _run_tool(str(tmp_path / "missing.json")).returncode == 2


def test_cache_report_cli_has_no_heavy_imports():
    src = open(_TOOL).read()
    assert "import jax" not in src and "paddle_tpu" not in \
        src.split('"""', 2)[2]


# ------------------------------------- windowed prefix-cache gauges

def test_metrics_windowed_prefix_gauges():
    """Satellite (a): snapshot()["prefix_cache"]["windowed"] carries
    a recent-window hit rate and cached-token rate alongside the
    lifetime counters."""
    from paddle_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics(perf=False)
    m.record_prefix_reuse(0, 16)
    m.record_prefix_reuse(12, 4)
    m.record_prefix_reuse(12, 4)
    w = m.prefix_cache_report()["windowed"]
    assert w["window_s"] == ServingMetrics.PREFIX_WINDOW_S
    assert w["admissions"] == 3
    assert w["hit_rate"] == pytest.approx(2 / 3, abs=1e-4)
    assert w["cached_tokens_per_s"] == pytest.approx(
        24 / ServingMetrics.PREFIX_WINDOW_S, abs=1e-3)
    snap = m.registry.snapshot()
    assert "serving_prefix_cache_windowed_hit_rate" in snap
    assert "serving_prefix_cached_tokens_per_sec" in snap
