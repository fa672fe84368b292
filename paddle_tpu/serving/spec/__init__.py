"""Self-drafting speculative decoding on the slot pool (ROADMAP open
item #2): an n-gram/prompt-lookup drafter proposes up to k tokens per
active slot from the slot's own context (no second model), and ONE
extra AOT program verifies all k+1 positions in a
single fixed-shape dispatch — amortizing the HBM-bound parameter + KV
read that plain decode pays per token. Greedy streams stay bit-exact
with generate() by construction (longest-accepted-prefix harvest over
per-query causally-masked logits); acceptance collapse falls back to
plain decode per slot via an EWMA gate.

Engine knobs: ``ServingConfig(speculative=True, spec_k=4,
spec_min_accept=...)`` / env ``PADDLE_SPEC_DECODE=1``. Greedy-only in
this iteration (speculation x sampling is rejected at config time).
"""
from .decoder import SpecDecoder  # noqa: F401
from .drafter import NGramDrafter  # noqa: F401
from .programs import build_paged_spec_verify_fn  # noqa: F401
