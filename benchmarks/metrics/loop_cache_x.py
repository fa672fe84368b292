"""Cache layers a weight layer has (the program's gauge
``serving_cache_passes``): a looped model keeps keys and values of its
own in every pass, so a cached position costs that many times a plain
model's of the same depth."""
from benchmarks.metrics import _loop


def read(ctx):
    got = _loop.loop_counters(ctx)
    return None if got is None else float(got[1]["cache_passes"])
