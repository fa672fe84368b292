"""Paged KV pool with refcounted blocks + radix-tree shared-prefix
reuse (the vLLM paging / SGLang radix-cache pattern, TPU-native).

A pool that gave every slot one contiguous ``max_len`` cache region
would make two requests sharing a 500-token system prompt each prefill
all 500 tokens. This package, the serving engine's only cache, makes
the KV cache BLOCK-granular and CONTENT-addressed so the shared span is
computed once and reused:

  * **paged cache** (pool.PagedKVPool) — ONE pair of arrays shaped
    ``[layers, num_blocks, heads, block_size, head_dim]``; a slot's
    logical cache is a row of a fixed-shape int32 block table
    ``[num_slots, max_blocks_per_slot]`` mapping logical block i to a
    physical block. Block 0 is a reserved TRASH block: released rows
    and table padding point there, so stale in-flight writes land in
    garbage nobody reads. The arrays and the table never change shape,
    so the AOT decode/prefill executables keep ONE signature forever —
    the zero-recompile invariant survives paging (watchdog-verified);
  * **refcounted blocks** — a block's refcount counts the live slots
    referencing it. Fully-frozen prompt blocks (every row a prompt
    token; decode never writes them again) are additionally indexed in
    the radix tree; at refcount zero an indexed block is not freed but
    parked EVICTABLE, reclaimed lowest-LRU-leaf-first only when the
    free list runs dry. Unindexed blocks free immediately at ref zero;
  * **radix prefix index** (radix.RadixPrefixIndex) — a trie keyed on
    prompt token IDs, one block-sized token group per edge. Admission
    does longest-cached-prefix lookup: a request whose prompt shares a
    cached prefix pins those blocks (ref++) into its block table and
    prefills ONLY the uncached tail (bucketed into the engine's
    existing prefill bucket set), turning shared-prompt prefill into a
    cache hit — tokens-saved, hit/miss counters and a ``prefix_hit``
    flight-recorder event carry the evidence.

Safety invariants (tests/test_paged_kv.py pins them):

  * decode writes land at positions >= prompt_len, and only FULL
    prompt blocks (positions < floor(prompt_len/BS)*BS) are ever
    indexed/shared — so a shared block is immutable by construction;
  * prefix blocks are pinned (ref++) BEFORE any allocation/eviction in
    the same admission, so an admission can never evict its own prefix;
  * eviction takes refcount-zero radix LEAVES only (lowest LRU tick
    first), so every cached prefix path stays contiguous from the root.
"""
from .pool import PagedAllocation, PagedKVPool  # noqa: F401
from .radix import RadixPrefixIndex  # noqa: F401
