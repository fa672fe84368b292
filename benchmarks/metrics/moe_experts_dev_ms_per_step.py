"""Device time of the routed experts' decode kernel (the program's
``moe_experts_swiglu_decode``) per decode execution in the trace, all
expert layers."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    return _arch_decode.kernel_ms_per_step(ctx, "moe_experts")
