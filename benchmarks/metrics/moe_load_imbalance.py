"""How unevenly the window's decode tokens were routed: the tokens of
the busiest (layer, expert) over the mean of all (the program's
counter). 1 is even; the higher, the fewer distinct experts a step
hits."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    d = _arch_decode.moe_delta(ctx)
    if d is None:
        return None
    flat = [t for row in d[0] for t in row]
    mean = sum(flat) / len(flat)
    return max(flat) / mean if mean > 0 else None
