"""What the window layers' rings save: the bytes the decoding slots'
positions would hold if EVERY layer kept each of them over the bytes
they do hold, their positions in the full layers plus their rings (the
program's gauges ``serving_cache_full_equiv_bytes`` /
``serving_cache_live_bytes``, from the step loop's own counts), at the
window's two ends together. A model whose every layer keeps every
position reads 1."""
from benchmarks.metrics import _mixed


def read(ctx):
    got = _mixed.ring_counters(ctx)
    if got is None:
        return None
    a, b = got
    held = a["cache_live_bytes"] + b["cache_live_bytes"]
    return (a["cache_full_equiv_bytes"] + b["cache_full_equiv_bytes"]) \
        / held if held else None
