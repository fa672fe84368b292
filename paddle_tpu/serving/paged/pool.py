"""Paged KV pool: block-granular refcounted cache + fixed-shape block
tables + the radix prefix index that makes blocks shareable.

Physical layout: the arrays the model's CACHE SPEC names
(``cache_spec.CacheSpec``), each ``[layers, num_blocks, *lead,
block_size, *trail]`` (a GPT: the pair ``kc/vc [layers, num_blocks,
heads, block_size, head_dim]``; latent attention: a latent and a rotary
key a token, no head axis), after them the spec's PER-SLOT arrays
``[layers, num_slots, *shape]`` (recurrent state: no block, no table;
such a pool shares no prefix), and an int32 block table ``[num_slots,
blocks_per_slot]`` mapping each slot's logical block i to a physical
block. Both shapes are fixed at
construction, so every AOT serving executable keeps one signature for
the engine's lifetime — paging changes WHERE a slot's K/V lives, never
the compiled program's shape.

Block 0 is the reserved TRASH block: free table rows and row padding
point at it, so a released slot's stale in-flight decode write (the
one-step-deep pipeline keeps a token in flight past retirement) lands
in garbage no reader sees instead of a block that may already belong
to someone else.

Refcounting: ``ref[b]`` counts live slots whose table references block
b. Blocks indexed in the radix tree at ref 0 are EVICTABLE (kept,
reusable as cache hits, reclaimed LRU-leaf-first when the free list
runs dry); unindexed blocks free immediately at ref 0. An admission
pins its matched prefix (ref++) BEFORE allocating anything, so it can
never evict blocks it is about to reuse.

Entries that are not positions (``CacheSpec.window``): the table row
addresses a slot's ENTRIES and ``blocks_per_slot`` covers the most a
slot of ``max_len`` positions ever holds. ``acquire`` then RESERVES the
blocks the request can come to need (a count: admission waits while the
reservations of the live slots would exceed the pool) and allocates
none; the engine asks for blocks as the slot's entries grow (``grow``)
and gives back those behind a compacted window (``shrink``) while the
slot lives. A grow inside a reservation cannot find the pool dry.

Host/device discipline: the engine routes every
executable's returned kc/vc through ``rebind`` (single owner of the
live buffers under donation), while the block table is host-authored
(numpy) and uploaded via ``device_tables()`` only when admission or
release dirtied it.
"""
import heapq

import numpy as np

from .radix import RadixPrefixIndex

TRASH_BLOCK = 0


class PagedAllocation:
    """What ``acquire`` hands the engine: the claimed slot plus the
    prefix-reuse facts the dispatch and the observability need."""

    __slots__ = ("slot", "prefix_tokens", "prefix_blocks", "new_blocks")

    def __init__(self, slot, prefix_tokens, prefix_blocks, new_blocks):
        self.slot = slot
        self.prefix_tokens = int(prefix_tokens)
        self.prefix_blocks = list(prefix_blocks)
        self.new_blocks = list(new_blocks)


class PagedKVPool:
    """Block allocator + slot table over the paged cache arrays."""

    def __init__(self, num_slots, num_layers=None, num_heads=None,
                 max_len=None, head_dim=None, block_size=16,
                 num_blocks=None, dtype=None, spec=None):
        import jax.numpy as jnp
        if spec is None:
            from .cache_spec import kv_pair_spec
            spec = kv_pair_spec(num_layers, num_heads, head_dim,
                                jnp.float32 if dtype is None else dtype)
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_slots = int(num_slots)
        self.spec = spec = spec.with_slots(self.num_slots)
        self.block_size = int(block_size)
        self.max_len = int(max_len)
        # a table row addresses ENTRIES: positions, unless the spec's
        # windows are compacted
        self.blocks_per_slot = -(-spec.capacity(self.max_len)
                                 // self.block_size)
        # default: every slot fully backed, plus the trash block —
        # sharing then stretches the same bytes further. Smaller
        # num_blocks oversubscribes: admission waits when blocks run
        # dry (acquire returns None), never corrupts.
        if num_blocks is None:
            num_blocks = self.num_slots * self.blocks_per_slot + 1
        self.num_blocks = int(num_blocks)
        if self.num_blocks < self.blocks_per_slot + 1:
            raise ValueError(
                f"num_blocks {self.num_blocks} cannot back even one "
                f"slot ({self.blocks_per_slot} blocks) plus the trash "
                "block")
        self.arrays = tuple(
            jnp.zeros(spec.shape(a, self.num_blocks, self.block_size),
                      a.dtype) for a in spec.arrays)
        self.index = RadixPrefixIndex(self.block_size)
        # block state: free heap (block 0 reserved as trash), refcounts
        # for allocated blocks, the evictable count (indexed & ref 0)
        self._free_blocks = list(range(1, self.num_blocks))
        self._ref = {}
        self._evictable = 0
        self._live = 0   # blocks at ref > 0, maintained incrementally
        # (the health tick reads live_blocks EVERY step — an O(blocks)
        # scan there would be per-step overhead; check_conservation
        # validates this counter against the full scan)
        self.evictions = 0
        # optional cache-observatory hook (observability.cache.
        # CacheObservatory.attach_pool sets itself here): notified on
        # block alloc/free and once per successful admission. None
        # keeps every hot-path branch a single attribute test.
        self.observer = None
        # slot state (deterministic allocator: lowest free index first)
        self._free_slots = list(range(self.num_slots))
        self._owner = {}
        self._quarantined = set()
        self._slot_blocks = {}
        # blocks each live slot may come to hold (CacheSpec.window)
        self._reserved = {}
        self.reuse_count = 0
        self._ever_used = set()
        self.block_tables = np.full(
            (self.num_slots, self.blocks_per_slot), TRASH_BLOCK,
            np.int32)
        self._tables_dev = None
        self._dirty = True

    # ------------------------------------------------------- slot facade
    @property
    def free_count(self):
        return len(self._free_slots)

    @property
    def occupancy(self):
        """Fraction of slots owned by live requests (quarantined
        slots are neither free nor occupied)."""
        return len(self._owner) / self.num_slots

    @property
    def quarantined(self):
        """Slots excluded from admission (sorted)."""
        return sorted(self._quarantined)

    def quarantine(self, slot):
        """Exclude a FREE slot from future admission (the engine's
        repeated-same-slot-failure response; raises when the slot is
        live — quarantine happens after rollback released it). The
        slot's table row already points at trash, so no blocks are
        pinned by a quarantined slot."""
        if slot in self._owner:
            raise ValueError(f"slot {slot} is live; release it first")
        if slot in self._quarantined:
            return
        self._free_slots.remove(slot)
        heapq.heapify(self._free_slots)
        self._quarantined.add(slot)

    def unquarantine_all(self):
        for slot in sorted(self._quarantined):
            heapq.heappush(self._free_slots, slot)
        self._quarantined.clear()

    @property
    def slot_capacity(self):
        """Tokens one slot's table row can address."""
        return self.blocks_per_slot * self.block_size

    def owner_of(self, slot):
        return self._owner.get(slot)

    # ------------------------------------------------------ block alloc
    @property
    def free_blocks(self):
        return len(self._free_blocks)

    @property
    def evictable_blocks(self):
        return self._evictable

    @property
    def live_blocks(self):
        return self._live

    def _alloc_block(self):
        """One fresh block at ref 1, from the free heap or by evicting
        the LRU ref-0 radix LEAF. Returns None when neither source has
        a block: the evictable count includes ref-0 INTERIOR nodes that
        leaf-only eviction cannot reach while live descendants pin the
        path, so running dry here is a legitimate wait-for-retirement
        condition, not a bug — acquire() rolls back and returns None."""
        obs = self.observer
        if self._free_blocks:
            b = heapq.heappop(self._free_blocks)
        else:
            b = self.index.evict_lru(
                lambda blk: self._ref.get(blk, 0) == 0)
            if b is None:
                return None
            self.evictions += 1
            self._evictable -= 1
            if obs is not None:
                # the evicted block's cached life ends here, before
                # its rebirth below as a fresh private block
                obs.on_block_free(b, evicted=True)
        self._ref[b] = 1
        self._live += 1
        if obs is not None:
            obs.on_block_alloc(b)
        return b

    def _deref(self, b):
        """Drop one reference: at ref 0 an indexed block parks
        evictable, an unindexed one frees immediately."""
        r = self._ref[b] = self._ref[b] - 1
        if r < 0:
            raise AssertionError(f"block {b} refcount underflow")
        if r == 0:
            self._live -= 1
            if b in self.index:
                self._evictable += 1
            else:
                del self._ref[b]
                heapq.heappush(self._free_blocks, b)
                if self.observer is not None:
                    self.observer.on_block_free(b, evicted=False)

    def match_prefix(self, prompt):
        """Longest cached prefix of ``prompt`` in TOKENS (always a
        block multiple). Touches the matched path's LRU ticks. Nothing
        where the spec's blocks are not shareable (per-slot state)."""
        if not self.spec.shareable:
            return 0
        return len(self.index.match(prompt)) * self.block_size

    def acquire(self, owner, prompt, total_tokens, prefix_tokens):
        """Claim the lowest free slot for ``owner``, pin the first
        ``prefix_tokens`` (block-aligned, from the radix index) into
        its table row, and allocate fresh blocks for the rest of
        ``total_tokens`` (prompt + max_new). Returns a PagedAllocation,
        or None when no slot is free or the fresh blocks cannot all be
        sourced from the free list + reachable evictable leaves — the
        refusal is transactional (any pins/allocations made are rolled
        back) so the caller keeps the request queued with the pool
        untouched; retirement frees blocks, never a deadlock while one
        request fits the pool."""
        if not self._free_slots:
            return None
        bs = self.block_size
        if prefix_tokens % bs:
            raise ValueError(
                f"prefix_tokens {prefix_tokens} is not block-aligned "
                f"(block_size {bs})")
        n_total = -(-self.spec.capacity(int(total_tokens)) // bs)
        if n_total > self.blocks_per_slot:
            raise ValueError(
                f"{total_tokens} tokens need {n_total} blocks; a slot "
                f"row holds {self.blocks_per_slot}")
        if self.spec.window is not None:
            return self._acquire_reserved(owner, n_total)
        n_prefix = prefix_tokens // bs
        n_new = n_total - n_prefix
        # the row's LAST block must be freshly allocated (private):
        # the decode/parked-chunk programs clamp overflowing write
        # positions into it, so a shared prefix block there would
        # corrupt every sharer. total_tokens includes max_new >= 1
        # beyond the prompt while the pinned prefix is block-aligned
        # within it, so n_new >= 1 always holds — assert it rather
        # than assume, so a future sharing change fails loudly here.
        if n_new < 1:
            raise ValueError(
                f"total_tokens {total_tokens} must exceed the pinned "
                f"prefix ({prefix_tokens} tokens): the row's last "
                f"block must be private, never a shared prefix block")
        matched = self.index.match(prompt)
        prefix_blocks = matched[:n_prefix]
        if len(prefix_blocks) < n_prefix:
            raise ValueError(
                f"prefix_tokens {prefix_tokens} exceeds the cached "
                f"prefix ({len(prefix_blocks) * bs} tokens)")
        # capacity pre-check: ref-0 prefix blocks are about to be
        # pinned, so they are NOT reclaimable supply for the fresh
        # allocations — count them out. (Still optimistic about ref-0
        # INTERIOR nodes leaf-only eviction can't reach; the allocation
        # loop below handles that by rolling back, never raising.)
        pinned_ref0 = sum(
            1 for b in prefix_blocks if self._ref.get(b, 0) == 0)
        if n_new > (len(self._free_blocks) + self._evictable
                    - pinned_ref0):
            return None
        # pin the prefix FIRST: ref>0 blocks are invisible to eviction,
        # so the fresh allocations below cannot steal our own prefix
        for b in prefix_blocks:
            r = self._ref.get(b, 0)
            self._ref[b] = r + 1
            if r == 0:
                self._evictable -= 1
                self._live += 1
        new_blocks = []
        for _ in range(n_new):
            b = self._alloc_block()
            if b is None:
                # eviction ran out of reachable leaves: undo the pins
                # and partial allocations so acquire either fully
                # succeeds or leaves the pool untouched, and wait
                for nb in new_blocks:
                    self._deref(nb)
                for pb in prefix_blocks:
                    self._deref(pb)
                return None
            new_blocks.append(b)
        slot = heapq.heappop(self._free_slots)
        self._owner[slot] = owner
        if slot in self._ever_used:
            self.reuse_count += 1
        self._ever_used.add(slot)
        row = prefix_blocks + new_blocks
        self._slot_blocks[slot] = row
        self.block_tables[slot, :] = TRASH_BLOCK
        self.block_tables[slot, :len(row)] = row
        self._dirty = True
        obs = self.observer
        if obs is not None:
            # one admission = one cache reference per full prompt
            # block (counted on SUCCESS only: the scheduler re-probes
            # refused requests, and double-counting retries would
            # skew the reuse-distance trace). Heat lands on the
            # blocks actually pinned; the hit count vs the full match
            # judges cache CONTENT, independent of pin truncation.
            obs.on_admission(self.index.access_fingerprints(prompt),
                             len(matched))
            self.index.note_hits(prefix_blocks)
        return PagedAllocation(slot, prefix_tokens, prefix_blocks,
                               new_blocks)

    def _acquire_reserved(self, owner, n_blocks):
        """``acquire`` where entries are not positions: the slot and a
        RESERVATION of the blocks it can come to hold; its row starts
        empty and ``grow`` fills it. None while the live reservations
        leave too little (retirement frees them)."""
        if sum(self._reserved.values()) + n_blocks > self.num_blocks - 1:
            return None
        slot = heapq.heappop(self._free_slots)
        self._owner[slot] = owner
        if slot in self._ever_used:
            self.reuse_count += 1
        self._ever_used.add(slot)
        self._reserved[slot] = n_blocks
        self._slot_blocks[slot] = []
        self.block_tables[slot, :] = TRASH_BLOCK
        self._dirty = True
        return PagedAllocation(slot, 0, [], [])

    def grow(self, slot, entries):
        """Back the first ``entries`` entries of a live slot's row with
        blocks (those it lacks, from the free list) before a dispatch
        writes them. Inside the slot's reservation, so never dry."""
        row = self._slot_blocks[slot]
        need = -(-int(entries) // self.block_size)
        if need <= len(row):
            return
        if need > self._reserved[slot]:
            raise ValueError(
                f"slot {slot}: {entries} entries need {need} blocks, "
                f"{self._reserved[slot]} were reserved at admission")
        while len(row) < need:
            b = self._alloc_block()
            assert b is not None, "reserved blocks ran dry"
            self.block_tables[slot, len(row)] = b
            row.append(b)
        self._dirty = True

    def shrink(self, slot, entries):
        """Give back the blocks behind a live slot's first ``entries``
        entries (a window was compacted: what lay there is summarised
        in the entries that stay). Their table entries point at trash
        from the next upload on. Returns how many went back."""
        row = self._slot_blocks[slot]
        keep = -(-int(entries) // self.block_size)
        gone = row[keep:]
        if gone:
            del row[keep:]
            for b in gone:
                self._deref(b)
            self.block_tables[slot, keep:] = TRASH_BLOCK
            self._dirty = True
        return len(gone)

    def commit_prefix(self, slot, prompt):
        """Index the slot's FULL prompt blocks in the radix tree so
        later admissions can hit them. Only blocks every row of which
        is a prompt token are shareable — the partial last block (and
        every decode block after it) takes decode writes and stays
        private. Call after the prefill dispatch succeeded; an
        admission rolled back before commit leaves the index untouched.
        Commits nothing where the spec's blocks are not shareable."""
        if slot not in self._owner:
            raise ValueError(f"slot {slot} is not live")
        if not self.spec.shareable:
            return []
        n_full = len(prompt) // self.block_size
        blocks = self._slot_blocks[slot][:n_full]
        return self.index.insert(prompt, blocks)

    def release(self, slot):
        """Return a slot: deref every block in its row (indexed blocks
        at ref 0 park evictable, unindexed ones free immediately) and
        point the row at trash so the in-flight pipeline's stale write
        for this slot cannot touch a reusable block."""
        if slot not in self._owner:
            raise ValueError(f"slot {slot} is not live")
        del self._owner[slot]
        for b in self._slot_blocks.pop(slot):
            self._deref(b)
        self._reserved.pop(slot, None)
        heapq.heappush(self._free_slots, slot)
        self.block_tables[slot, :] = TRASH_BLOCK
        self._dirty = True

    # ---------------------------------------------------- device arrays
    def device_tables(self):
        """The block table as a device array, re-uploaded only when an
        admission/release dirtied it ([num_slots, blocks_per_slot]
        int32 — a few KB, dwarfed by one decode dispatch)."""
        import jax.numpy as jnp
        if self._tables_dev is None or self._dirty:
            # snapshot before upload: device_put may defer reading the
            # host buffer past this call, and acquire/release mutate
            # block_tables in place — handing jax the live buffer lets
            # an in-flight transfer observe FUTURE row edits (rare
            # shared-prefix corruption under the async pipeline)
            self._tables_dev = jnp.asarray(self.block_tables.copy())
            self._dirty = False
        return self._tables_dev

    def table_row(self, slot):
        import jax.numpy as jnp
        # same snapshot discipline as device_tables: never hand jax a
        # view of the live, in-place-mutated table
        return jnp.asarray(self.block_tables[slot].copy())

    # the GPT's names for its pair (kv_wire, the speculative verify
    # program and the tests read them)
    @property
    def kc(self):
        return self.arrays[0]

    @property
    def vc(self):
        return self.arrays[1]

    def rebind(self, *arrays):
        """Single-owner discipline: the compiled call's returned
        arrays become the live buffers (with donation the previous
        ones are already invalid); any shape/dtype drift is caught
        here, before a donating backend's next AOT call consumes a
        mismatched buffer."""
        if len(arrays) != len(self.arrays):
            raise ValueError(
                f"rebind: got {len(arrays)} arrays, the cache spec "
                f"names {len(self.arrays)}")
        for spec, new, old in zip(self.spec.arrays, arrays, self.arrays):
            if new.shape != old.shape:
                raise ValueError(
                    f"rebind shape drift: got {new.shape}, pool owns "
                    f"{old.shape} ({spec.name})")
            if new.dtype != old.dtype:
                raise ValueError(
                    f"rebind dtype drift: got {new.dtype}, pool owns "
                    f"{old.dtype} ({spec.name})")
        self.arrays = tuple(arrays)

    def nbytes(self):
        return int(sum(a.nbytes for a in self.arrays))

    # ------------------------------------------------------------ stats
    def stats(self):
        """The ``snapshot()["prefix_cache"]["pool"]`` section: block
        economy + radix shape, all ints (JSON-safe)."""
        return {
            "block_size": self.block_size,
            "blocks_per_slot": self.blocks_per_slot,
            "num_blocks": self.num_blocks,
            "free_blocks": len(self._free_blocks),
            "live_blocks": self.live_blocks,
            "evictable_blocks": self._evictable,
            "reserved_blocks": sum(self._reserved.values()),
            "indexed_blocks": len(self.index),
            "radix_depth": self.index.stats()["depth"],
            "evictions": self.evictions,
            "thrash_reinserts": self.index.thrash_count,
        }

    def audit(self):
        """``check_conservation`` as a report instead of an assert —
        the health observatory's periodic leak probe
        (``ServingConfig(health_audit_every=)``): a violated invariant
        feeds the ``kv_block_leak`` detector as evidence, it must not
        crash the serve loop that is about to capture the incident."""
        try:
            self.check_conservation()
        except AssertionError as e:
            return {"ok": False, "error": str(e) or repr(e)}
        return {"ok": True, "error": None}

    def check_conservation(self):
        """Invariant audit for tests: trash + free + tracked refcounted
        blocks partition the pool, and the evictable count equals the
        indexed-ref-0 population."""
        tracked = set(self._ref)
        free = set(self._free_blocks)
        assert not (tracked & free), (tracked, free)
        assert tracked | free | {TRASH_BLOCK} == set(
            range(self.num_blocks))
        assert self._evictable == sum(
            1 for b, r in self._ref.items() if r == 0 and b in self.index)
        assert self._live == sum(
            1 for r in self._ref.values() if r > 0), \
            (self._live, dict(self._ref))
        for b, r in self._ref.items():
            assert r >= 0, (b, r)
            if r == 0:
                assert b in self.index  # unindexed ref-0 blocks free
        # reservations (CacheSpec.window): never beyond the pool, each
        # slot inside its own, its table row its blocks and then trash
        assert sum(self._reserved.values()) <= self.num_blocks - 1
        for slot, n in self._reserved.items():
            row = self._slot_blocks[slot]
            assert len(row) <= n, (slot, len(row), n)
            assert list(self.block_tables[slot, :len(row)]) == row
            assert (self.block_tables[slot, len(row):]
                    == TRASH_BLOCK).all(), slot
        return True
