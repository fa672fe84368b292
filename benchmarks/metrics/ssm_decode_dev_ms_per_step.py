"""Device time of the state-space layers' one-token state update (the
program's ``ssm_decode_step`` kernel) per decode execution in the trace,
all state-space layers."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    return _arch_decode.kernel_ms_per_step(ctx, "ssm_decode")
