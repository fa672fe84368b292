"""Chunked-prefill planning (the Sarathi-Serve co-scheduling trick).

A long prompt must never monopolize the engine step loop: one 4k-token
prefill dispatch stalls every decoding slot for its whole duration
(the deep_queue artifact's p99~755ms vs p50~7ms TTFT spread). Instead
the prompt splits into fixed-width chunks that interleave with decode
steps under a per-step token budget — decode latency stays bounded by
the CHUNK cost, not the prompt length.

Zero-recompile invariant: every chunk dispatch is the SAME compiled
program — a fixed ``[1, chunk]`` token window whose ``start`` /
``chunk_len`` are traced scalars (the PR-6 tail-only-prefill trick) —
so prompt-length variety costs zero compiles and the whole chunked
inventory is ONE program (the tail prefill at the chunk-width bucket).

The plan keeps every dispatch full-width, which is what makes the
no-pad-row guarantee possible: interior chunks tile from the start,
and the FINAL chunk is END-ALIGNED at ``[n - chunk, n)`` — it may
re-cover a suffix of the previous chunk (recomputing < chunk tokens;
K/V rows recompute to identical values because each row is a function
of the rows below it only), but no dispatch ever writes a K/V row at
a position >= n, so no clamp-shift or pad-row hazard exists at any
prompt length.

A model with RECURRENT state a slot (``CacheSpec.slot_arrays``) cannot
recompute rows its state has passed: its plan TILES, the final chunk
starting where the one before it ended and running short
(``tail_len < chunk``; its program passes the bucket's other rows by).
"""


class ChunkPlan:
    """One request's remaining chunked-prefill schedule.

    Plans over ``req.prefill_ids`` — the prompt plus any tokens a
    supervisor-restart replay already emitted — snapshotted at plan
    time so the chunk windows stay stable while the plan drains."""

    __slots__ = ("req", "slot", "ids", "starts", "next", "chunk",
                 "start0")

    def __init__(self, req, slot, start0, chunk, tile=False):
        self.req = req
        self.slot = slot
        self.ids = req.prefill_ids
        self.chunk = int(chunk)
        self.start0 = int(start0)       # cached-prefix end
        self.starts = plan_chunks(self.start0, len(self.ids),
                                  self.chunk, tile)
        self.next = 0                   # index of the next chunk

    @property
    def done(self):
        return self.next >= len(self.starts)

    @property
    def final_is_next(self):
        return self.next == len(self.starts) - 1

    def peek(self):
        """(start, length, final) of the next chunk to dispatch."""
        start = self.starts[self.next]
        n = len(self.ids)
        return start, min(self.chunk, n - start), self.final_is_next

    def advance(self):
        self.next += 1


def plan_chunks(start0, prompt_len, chunk, tile=False):
    """Chunk start offsets covering ``[start0, prompt_len)`` with
    full-width ``chunk`` dispatches: interior chunks tile from
    ``start0``; the final chunk is end-aligned at ``prompt_len -
    chunk`` so its last row is the prompt's last token (the one whose
    logits produce the first generated token) and NO dispatch writes a
    K/V position >= prompt_len. With ``tile`` the final chunk starts
    where the one before it ended instead (no row is computed twice:
    recurrent state). Requires ``prompt_len - start0 > chunk`` (shorter
    tails take the ordinary unchunked prefill)."""
    tail = prompt_len - start0
    if tail <= chunk:
        raise ValueError(
            f"tail {tail} does not need chunking at chunk={chunk}")
    m = -(-tail // chunk)               # ceil
    starts = [start0 + i * chunk for i in range(m - 1)]
    starts.append(start0 + (m - 1) * chunk if tile
                  else prompt_len - chunk)
    return starts
