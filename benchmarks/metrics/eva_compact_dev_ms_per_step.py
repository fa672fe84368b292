"""Device time of the window compaction (the program ``paged_compact``,
one execution a finished window a slot) per decode execution in the
trace: what ending windows costs a step on average."""
from benchmarks.metrics import _eva


def read(ctx):
    got = _eva.compactions(ctx)
    return None if got is None else got[0]
