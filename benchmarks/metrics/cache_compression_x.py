"""How many positions one cache entry stands for: the positions the
decoding slots have reached over the entries they hold (the program's
gauges ``serving_cache_positions_live`` / ``serving_cache_entries_live``,
from the step loop's own counts), at the window's two ends together. A
cache of one entry a position reads 1."""
from benchmarks.metrics import _eva


def read(ctx):
    got = _eva.counters(ctx)
    if got is None:
        return None
    a, b = got
    held = a["entries_live"] + b["entries_live"]
    return (a["positions_live"] + b["positions_live"]) / held \
        if held else None
