"""Paged KV pool (paddle_tpu.serving.paged) host-side logic in
isolation: radix-trie insert/lookup/longest-prefix/LRU-leaf eviction,
block refcount lifecycle through acquire/commit/release, and
property-style fuzz — every lookup is a TRUE longest cached prefix
(checked against a mirror trie) and block refcounts are conserved
across interleaved admit/retire/evict traffic."""
import numpy as np
import pytest

from paddle_tpu.serving.paged import PagedKVPool, RadixPrefixIndex
from paddle_tpu.serving.paged.pool import TRASH_BLOCK


def _pool(num_slots=4, max_len=32, block_size=4, num_blocks=None):
    return PagedKVPool(num_slots, num_layers=1, num_heads=1,
                       max_len=max_len, head_dim=2,
                       block_size=block_size, num_blocks=num_blocks)


# --------------------------------------------------------------- radix

def test_radix_insert_lookup_longest_prefix():
    idx = RadixPrefixIndex(4)
    idx.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
    assert idx.match([1, 2, 3, 4, 5, 6, 7, 8]) == [10, 11]
    # partial block never matches; divergence cuts the walk
    assert idx.match([1, 2, 3, 4, 5, 6, 7]) == [10]
    assert idx.match([1, 2, 3, 4, 9, 9, 9, 9]) == [10]
    assert idx.match([9, 2, 3, 4]) == []
    assert idx.match([1, 2, 3]) == []
    assert len(idx) == 2 and 10 in idx and 12 not in idx


def test_radix_insert_existing_node_keeps_first_block():
    """The first writer's block is the shared copy: re-inserting the
    same token path under a different block id is a no-op for that
    span (the caller's private block simply stays unindexed)."""
    idx = RadixPrefixIndex(2)
    assert idx.insert([5, 6, 7, 8], [1, 2]) == [1, 2]
    assert idx.insert([5, 6, 9, 9], [3, 4]) == [4]   # [5,6] node exists
    assert idx.match([5, 6, 7, 8]) == [1, 2]
    assert idx.match([5, 6, 9, 9]) == [1, 4]
    with pytest.raises(ValueError):   # one block, two paths: forbidden
        idx.insert([0, 0], [1])


def test_radix_lru_leaf_eviction_order():
    """Eviction takes refcount-zero LEAVES only, least-recent tick
    first — interior nodes survive while descendants exist, so cached
    paths stay contiguous from the root."""
    idx = RadixPrefixIndex(2)
    idx.insert([1, 1, 2, 2], [1, 2])     # path: (1,1) -> (2,2)
    idx.insert([1, 1, 3, 3], [1, 3])     # (1,1) exists; adds (3,3)
    assert idx.match([1, 1, 3, 3]) == [1, 3]
    # interior node 1 is not a leaf: only 2 and 3 are candidates; 2 is
    # older (3's insert ticked later)
    assert idx.evict_lru(lambda b: True) == 2
    assert idx.match([1, 1, 2, 2]) == [1]
    # a match refreshes the path: touch 3, then nothing else; 3 is the
    # only leaf left, evictable predicate can still veto it
    assert idx.evict_lru(lambda b: b != 3) is None
    assert idx.evict_lru(lambda b: True) == 3
    assert idx.evict_lru(lambda b: True) == 1    # now a leaf
    assert len(idx) == 0


# ---------------------------------------------------------------- pool

def test_pool_acquire_pins_prefix_and_allocates_tail():
    pool = _pool()
    p1 = np.arange(10)           # 2 full blocks + partial
    a1 = pool.acquire(0, p1, total_tokens=14, prefix_tokens=0)
    assert a1.slot == 0 and a1.prefix_blocks == [] \
        and len(a1.new_blocks) == 4          # ceil(14/4)
    pool.commit_prefix(a1.slot, p1)          # indexes blocks 0..8
    assert pool.match_prefix(p1) == 8
    # second request shares the full cached prefix
    p2 = np.concatenate([p1[:8], [77, 78, 79, 80]])
    a2 = pool.acquire(1, p2, total_tokens=16, prefix_tokens=8)
    assert a2.prefix_blocks == a1.new_blocks[:2]
    # pinned blocks are refcounted by both holders
    for b in a2.prefix_blocks:
        assert pool._ref[b] == 2
    row = pool.block_tables[a2.slot]
    assert list(row[:2]) == a2.prefix_blocks
    assert all(b == TRASH_BLOCK for b in row[4:])
    pool.check_conservation()
    # release both: indexed blocks park evictable, private ones free
    pool.release(a1.slot)
    pool.release(a2.slot)
    assert pool.live_blocks == 0
    assert pool.evictable_blocks == len(pool.index)
    pool.check_conservation()


def test_pool_slots_are_lowest_free_first():
    """Slot determinism: whatever the release order, acquisition
    always hands out the lowest free slot; a full pool refuses."""
    pool = _pool()
    p = np.arange(4)
    slots = [pool.acquire(i, p + 10 * i, 8, 0).slot for i in range(4)]
    assert slots == [0, 1, 2, 3]
    assert pool.acquire(99, p, 8, 0) is None
    for s in (3, 1, 2):
        pool.release(s)
    assert [pool.acquire(10 + i, p, 8, 0).slot for i in range(3)] \
        == [1, 2, 3]
    assert pool.reuse_count == 3
    pool.check_conservation()


def test_pool_slot_acquire_release_fuzz():
    """Admit-when-full churn fuzz: across random acquire/release
    traffic the free set and the owned set always partition the
    slots, acquisition is always the minimum free slot, acquire on a
    full pool is None, double-release raises, and a quarantined slot
    is in neither set until it is handed back."""
    pool = _pool()
    rs = np.random.RandomState(9)
    live = set()
    for i in range(300):
        if live and (pool.free_count == 0 or rs.rand() < 0.45):
            slot = int(rs.choice(sorted(live)))
            pool.release(slot)
            live.discard(slot)
            with pytest.raises(ValueError):
                pool.release(slot)
        else:
            free_before = set(pool._free_slots)
            alloc = pool.acquire(i, rs.randint(0, 50, 6), 10, 0)
            assert alloc.slot == min(free_before)
            assert pool.owner_of(alloc.slot) == i
            live.add(alloc.slot)
        free = set(pool._free_slots)
        assert free | live == {0, 1, 2, 3} and not free & live
        assert pool.free_count + len(live) == 4
        assert pool.occupancy == len(live) / 4
        if pool.free_count == 0:
            assert pool.acquire(-1, np.arange(4), 8, 0) is None
    assert pool.reuse_count >= 50
    pool.check_conservation()
    for slot in sorted(live):
        pool.release(slot)
    with pytest.raises(ValueError):       # live slots cannot be set aside
        pool.quarantine(pool.acquire(0, np.arange(4), 8, 0).slot)
    pool.quarantine(3)
    assert pool.quarantined == [3] and 3 not in pool._free_slots
    pool.unquarantine_all()
    assert pool.quarantined == [] and pool.free_count == 3


def test_pool_capacity_refusal_and_trash_reset():
    pool = _pool(num_slots=2, max_len=16, block_size=4, num_blocks=5)
    # 4 usable blocks (block 0 is trash): one 16-token request fills
    a = pool.acquire(0, np.arange(8), total_tokens=16, prefix_tokens=0)
    assert a is not None and pool.free_blocks == 0
    # a second request needs fresh blocks nothing can provide
    assert pool.acquire(1, np.arange(4) + 50, total_tokens=4,
                        prefix_tokens=0) is None
    pool.release(a.slot)
    assert all(b == TRASH_BLOCK for b in pool.block_tables[a.slot])
    # uncommitted (never indexed) blocks free immediately
    assert pool.free_blocks == 4 and pool.evictable_blocks == 0
    pool.check_conservation()


def test_pool_eviction_reclaims_lru_cached_blocks():
    """When the free list runs dry, refcount-zero cached blocks are
    reclaimed LRU-leaf-first; pinned (live) prefixes are untouchable."""
    pool = _pool(num_slots=4, max_len=16, block_size=4, num_blocks=7)
    pa = np.arange(8)                      # fills 2 blocks, both full
    a = pool.acquire(0, pa, 8, 0)
    pool.commit_prefix(a.slot, pa)
    pool.release(a.slot)                   # 2 evictable cached blocks
    assert pool.evictable_blocks == 2 and pool.free_blocks == 4
    pb = np.arange(8) + 100
    b = pool.acquire(1, pb, 8, 0)
    pool.commit_prefix(b.slot, pb)         # b stays LIVE (pinned)
    # 2 free left; next request needs 4 -> evicts a's 2 LRU blocks
    pc = np.arange(16) + 200
    c = pool.acquire(2, pc, 16, 0)
    assert c is not None and pool.evictions == 2
    assert pool.match_prefix(pa) == 0      # a's cache is gone
    assert pool.match_prefix(pb) == 8      # live b untouched
    pool.check_conservation()


def test_pool_acquire_refuses_when_prefix_pins_consume_evictable():
    """free=0 and the only evictable blocks ARE the matched prefix the
    admission is about to pin: acquire must refuse up front (the pins
    make them non-reclaimable) instead of pinning, failing the fresh
    allocation mid-way, and leaking the pinned refs."""
    pool = _pool(num_slots=2, max_len=12, block_size=4, num_blocks=4)
    pa = np.arange(8)
    a = pool.acquire(0, pa, 8, 0)
    pool.commit_prefix(a.slot, pa)
    pool.release(a.slot)               # blocks 1,2 evictable; 3 free
    b = pool.acquire(1, np.array([90, 91, 92, 93]), 4, 0)
    assert b is not None and pool.free_blocks == 0
    assert pool.evictable_blocks == 2
    ref_before = dict(pool._ref)
    # needs 1 fresh block; the 2 "evictable" blocks are its own prefix
    assert pool.acquire(2, pa, 12, prefix_tokens=8) is None
    assert pool._ref == ref_before     # nothing pinned, nothing leaked
    assert pool.evictable_blocks == 2
    pool.check_conservation()
    # retirement restores real capacity and the same request admits
    pool.release(b.slot)
    c = pool.acquire(2, pa, 12, prefix_tokens=8)
    assert c is not None and c.prefix_blocks == a.new_blocks
    pool.check_conservation()


def test_pool_acquire_rolls_back_when_eviction_cannot_reach_leaves():
    """A ref-0 INTERIOR radix block under a live private tail counts
    evictable but leaf-only eviction cannot reclaim it: acquire must
    roll its pins back and return None (wait for retirement) instead
    of raising mid-allocation."""
    pool = _pool(num_slots=3, max_len=12, block_size=4, num_blocks=6)
    pa = np.arange(8)
    a = pool.acquire(0, pa, 8, 0)
    pool.commit_prefix(a.slot, pa)
    pool.release(a.slot)               # blocks 1,2 cached at ref 0
    # trimmed-prefix admission: 8 tokens are cached but only 4 are
    # used, so the private recompute of span [4,8) plus a divergent
    # third block commits a LIVE leaf under cached ref-0 interior 2
    pc = np.concatenate([pa, [70, 71, 72, 73]])
    c = pool.acquire(1, pc, 12, prefix_tokens=4)
    pool.commit_prefix(c.slot, pc)
    d = pool.acquire(2, np.array([90, 91, 92, 93]), 4, 0)
    assert pool.free_blocks == 0 and pool.evictable_blocks == 1
    ref_before = dict(pool._ref)
    pe = np.concatenate([pa[:4], [60, 61, 62, 63]])
    assert pool.acquire(3, pe, 8, prefix_tokens=4) is None
    assert pool._ref == ref_before     # pinned prefix rolled back
    assert pool.evictable_blocks == 1
    pool.check_conservation()
    pool.release(d.slot)               # a real block frees
    e = pool.acquire(3, pe, 8, prefix_tokens=4)
    assert e is not None
    pool.check_conservation()


def test_pool_acquire_rejects_unaligned_or_oversized():
    pool = _pool(max_len=16, block_size=4)
    with pytest.raises(ValueError):
        pool.acquire(0, np.arange(8), 8, prefix_tokens=3)
    with pytest.raises(ValueError):
        pool.acquire(0, np.arange(8), 17, prefix_tokens=0)  # > capacity
    with pytest.raises(ValueError):        # prefix not actually cached
        pool.acquire(0, np.arange(8), 8, prefix_tokens=4)


def test_device_tables_are_snapshots_immune_to_host_mutation():
    """device_tables()/table_row() hand jax a SNAPSHOT: the pool
    mutates block_tables in place (acquire/release), and a device
    array that aliased or lazily read the live buffer would let an
    in-flight async dispatch observe future row edits (observed as
    rare shared-prefix corruption under the pipelined engine)."""
    pool = _pool()
    a = pool.acquire(0, np.arange(8), 8, 0)
    dev = pool.device_tables()
    row = pool.table_row(a.slot)
    before_dev = np.asarray(dev).copy()
    before_row = np.asarray(row).copy()
    pool.release(a.slot)               # zeroes the row to TRASH in place
    b = pool.acquire(1, np.arange(8) + 50, 16, 0)
    assert b is not None               # rewrites rows again
    np.testing.assert_array_equal(np.asarray(dev), before_dev)
    np.testing.assert_array_equal(np.asarray(row), before_row)


# ---------------------------------------------------------------- fuzz

class _MirrorTrie:
    """Pure-python oracle for longest-cached-prefix lookups."""

    def __init__(self, bs):
        self.bs = bs
        self.root = {}
        self.owner = {}   # node-dict id path is implicit; block -> path

    def _keys(self, toks):
        n = (len(toks) // self.bs) * self.bs
        return [tuple(int(t) for t in toks[i:i + self.bs])
                for i in range(0, n, self.bs)]

    def insert(self, toks, blocks):
        node = self.root
        for key, b in zip(self._keys(toks), blocks):
            child = node.setdefault(key, {"block": int(b), "kids": {}})
            node = child["kids"]

    def match(self, toks):
        out, node = [], self.root
        for key in self._keys(toks):
            child = node.get(key)
            if child is None:
                break
            out.append(child["block"])
            node = child["kids"]
        return out

    def remove(self, block):
        def walk(node):
            for key, child in list(node.items()):
                if child["block"] == block:
                    assert not child["kids"], "oracle: evicted interior"
                    del node[key]
                    return True
                if walk(child["kids"]):
                    return True
            return False
        assert walk(self.root)


def test_fuzz_lookup_is_true_longest_prefix_and_refs_conserved():
    """Random shared-prefix prompt traffic through acquire / commit /
    release with a deliberately undersized pool (evictions fire):
    after every operation the pool's refcounts equal the recount from
    live slot rows, every match equals the mirror-trie oracle's
    longest cached prefix, and the free/live/evictable partition
    holds. PR 13 rides the same oracle: a live CacheObservatory is
    attached, and per-node heat counts, LRU-tick monotonicity and the
    evict-then-reinsert (thrash) counter are cross-checked against
    mirror bookkeeping after every op."""
    from paddle_tpu.observability import (CacheObservatory,
                                          MetricsRegistry)

    rs = np.random.RandomState(42)
    BS = 4
    pool = _pool(num_slots=3, max_len=24, block_size=BS, num_blocks=13)
    obs = CacheObservatory(MetricsRegistry(), sample_rate=1.0)
    obs.attach_pool(pool)
    mirror = _MirrorTrie(BS)
    bases = [rs.randint(0, 9, (8,)) for _ in range(3)]   # shared stems
    live = {}    # slot -> prompt
    rid = 0
    # PR 13 mirrors: per-block admission heat, each indexed block's
    # root path (as a key tuple), the evicted-path set, thrash count
    mirror_hits = {}
    path_of = {}
    mirror_evicted = set()
    mirror_thrash = 0

    def audit():
        pool.check_conservation()
        # refcount == number of live rows holding the block
        counts = {}
        for slot in live:
            for b in pool._slot_blocks[slot]:
                counts[b] = counts.get(b, 0) + 1
        for b, r in pool._ref.items():
            assert counts.get(b, 0) == r, (b, r, counts)
        # heat / tick / thrash accounting matches the mirrors
        assert pool.index.thrash_count == mirror_thrash
        root = pool.index._root
        for b, node in pool.index._by_block.items():
            assert node.hits == mirror_hits.get(b, 0), (b, node.hits)
            if node.parent is not root:
                # a child is never fresher than its parent: every
                # match/insert touch walks root-down
                assert node.tick <= node.parent.tick

    for step in range(400):
        if live and (rs.rand() < 0.4 or pool.free_count == 0):
            slot = int(rs.choice(sorted(live)))
            del live[slot]
            pool.release(slot)
        else:
            base = bases[rs.randint(len(bases))]
            extra = rs.randint(0, 9, (int(rs.randint(1, 9)),))
            prompt = np.concatenate([base[:rs.randint(0, 9)], extra])
            if len(prompt) == 0:
                continue
            cached = pool.match_prefix(prompt)
            assert cached == len(mirror.match(prompt)) * BS
            start = min(cached, len(prompt) - 1) // BS * BS
            total = len(prompt) + int(rs.randint(1, 5))
            if total > pool.slot_capacity:
                continue
            evicted_before = pool.evictions
            alloc = pool.acquire(rid, prompt, total, start)
            if alloc is None:
                audit()
                continue
            # acquire heats exactly the pinned prefix blocks, once
            for b in alloc.prefix_blocks:
                mirror_hits[b] = mirror_hits.get(b, 0) + 1
            # mirror any evictions acquire performed (the pool evicts
            # leaves first, so peel stale blocks leaf-inward)
            if pool.evictions > evicted_before:
                stale = set(mirror_all_blocks(mirror.root)) \
                    - set(pool.index._by_block)
                while stale:
                    n_before = len(stale)
                    for b in list(stale):
                        if mirror_is_leaf(mirror.root, b):
                            mirror.remove(b)
                            mirror_evicted.add(path_of.pop(b))
                            mirror_hits.pop(b, None)
                            stale.discard(b)
                    assert len(stale) < n_before, "stale interior block"
            created = pool.commit_prefix(alloc.slot, prompt)
            # a created block whose root path was evicted earlier is a
            # thrash re-insert; the pool credits each eviction once
            keys = mirror._keys(prompt)
            row = pool._slot_blocks[alloc.slot]
            for b in created:
                path = tuple(keys[:row.index(b) + 1])
                if path in mirror_evicted:
                    mirror_evicted.discard(path)
                    mirror_thrash += 1
                path_of[b] = path
                mirror_hits.setdefault(b, 0)
            mirror.insert(prompt, row[:len(prompt) // BS])
            live[alloc.slot] = prompt
            rid += 1
        audit()
        # oracle agreement on every stem after every op
        for base in bases:
            probe = np.concatenate([base, [99]])
            assert pool.match_prefix(probe) == \
                len(mirror.match(probe)) * BS
    assert pool.evictions > 0, "fuzz never exercised eviction"
    assert rid > 50
    # drain everything: all refs return to zero
    for slot in list(live):
        pool.release(slot)
    assert pool.live_blocks == 0
    pool.check_conservation()


def mirror_all_blocks(node):
    for child in node.values():
        yield child["block"]
        yield from mirror_all_blocks(child["kids"])


def mirror_is_leaf(node, block):
    for child in node.values():
        if child["block"] == block:
            return not child["kids"]
        found = mirror_is_leaf(child["kids"], block)
        if found is not None:
            return found
    return None
