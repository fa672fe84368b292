"""Device time of what a looped model adds BETWEEN its passes (the
program's scopes ``loop/norm``: the final norm every pass ends with;
``loop/gate``: the exit gate, the exit rule and the loop's counters) per
decode execution in the trace."""
from benchmarks.metrics import _loop


def read(ctx):
    return _loop.glue_ms_per_step(ctx)
