"""Device time of the flash-attention forward kernel (``name=
"flash_fwd"``: the forward pass's calls and the backward pass's
recomputing ones) per train step in the trace."""
from benchmarks.metrics import _train_trace


def read(ctx):
    return _train_trace.kernel_ms_per_step(ctx, "flash_fwd")
