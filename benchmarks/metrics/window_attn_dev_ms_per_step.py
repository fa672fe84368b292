"""Device time of the window layers' ring write and ring attention (the
program's scope ``window``: ``jnp``, no kernel) per decode execution in
the trace, all window layers."""
from benchmarks.metrics import _mixed


def read(ctx):
    return _mixed.window_ms_per_step(ctx)
