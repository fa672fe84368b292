"""95th percentile over ALL gaps between consecutive output tokens of
the requests due in the window: the stutter a prefill causes when it
stalls the decode batch. Not end-to-end (PERF.md, section 2): it sits on
the edge between gaps with and without a prefill and flips between
them from run to run; ``gap_mean_ms`` is what is bounded."""
from benchmarks import harness


def read(ctx):
    gaps = ctx["client"].get("gap_ms")
    return harness.percentile(gaps, 95) if gaps else None
