"""The routed experts' decode kernel against its roofline: the matrices
of the experts hit (the program's counter, window mean), the pairs'
operations, summed over the expert layers, over the kernel's own device
time a step."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    ms = _arch_decode.kernel_ms_per_step(ctx, "moe_experts")
    d = _arch_decode.moe_delta(ctx)
    if ms is None or d is None:
        return None
    ops = nbytes = 0
    for hits, steps in zip(d[1], d[2]):
        o, b = ctx["flops"].moe_experts_cost(
            ctx["model"], ctx["num_slots"], hits / steps,
            ctx["weight_bytes"])
        ops, nbytes = ops + o, nbytes + b
    return _arch_decode.roofline_pct(ctx, ops, nbytes, ms)
