"""SLO-feedback scheduling subsystem: chunked prefill co-scheduled
with decode, load-shedding admission, and per-slot sampling.

Three pieces close the observability->control loop the PR-3/4 layers
left open:

  * **chunked prefill** (chunker) — long prompts split into
    fixed-width chunks dispatched under a per-step token budget and
    interleaved with decode steps (Sarathi-Serve co-scheduling), so a
    4k-token prompt never stalls the decoding slots; a chunk is a
    tail prefill at the chunk-width bucket whose ``start`` /
    ``tail_len`` are traced scalars, so ANY prompt-length mix reuses
    the one compiled program — the zero-recompile invariant survives,
    watchdog-verified;
  * **scheduling policy** (policy) — pluggable admission control:
    ``FIFOPolicy`` (the default, PR-1..6 behavior) or
    ``SLOFeedbackPolicy``, which reads each queued request's live TTFT
    headroom (target minus elapsed minus an EWMA of delivered
    admission->first-token latency) and sheds or defers requests whose
    SLO is already lost — decode capacity goes to requests that can
    still attain, which is what keeps goodput up under 2-10x overload;
  * **per-slot sampling** (sampling) — temperature / top-k / top-p per
    slot inside the ONE compiled decode (and prefill) executable,
    PRNG keys derived from (request seed, token position) so no key
    state threads through the pipeline; greedy slots remain bit-exact
    with ``generate()``.

``ServingConfig(prefill_chunk=..., prefill_token_budget=...,
policy="slo_feedback", sampling=True)`` turns the pieces on
individually — all default OFF, preserving prior behavior exactly.
"""
from .chunker import ChunkPlan, plan_chunks  # noqa: F401
from .policy import (  # noqa: F401
    FIFOPolicy, SchedulingPolicy, SLOFeedbackPolicy, TriageDecision,
    resolve_policy,
)
from .sampling import (  # noqa: F401
    SlotSampler, build_sampling_head, request_sampling_params,
)
