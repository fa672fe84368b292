"""Passes of the layer stack a decoded token ran, over the window: the
program's counters ``serving_loop_passes_total`` over the tokens of
``serving_loop_exit_pass`` (kept on the device by the decode program).
``total_ut_steps`` at the published exit threshold of 1."""
from benchmarks.metrics import _loop


def read(ctx):
    got = _loop.loop_counters(ctx)
    if got is None:
        return None
    a, b = got
    tokens = sum(b["exit_pass"]) - sum(a["exit_pass"])
    return (b["passes_run"] - a["passes_run"]) / tokens if tokens > 0 \
        else None
