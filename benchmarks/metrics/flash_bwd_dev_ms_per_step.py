"""Device time of the two flash-attention backward kernels
(``flash_bwd_dq``, ``flash_bwd_dkv``) per train step in the trace."""
from benchmarks.metrics import _train_trace


def read(ctx):
    return _train_trace.kernel_ms_per_step(ctx, "flash_bwd_")
