"""The state-space decode kernel against its roofline: every slot's
recurrent state read once and written once, its convolution window and
the step's inputs (``flops_<arch>.ssm_decode_cost``), in every
state-space layer, over the kernel's own device time a step. The window
is moved by XLA beside the kernel, not inside it: its bytes are in the
numerator and its time is not in the denominator's kernel, so the share
reads a little HIGH (the window is 0.9 % of a slot's bytes)."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    ms = _arch_decode.kernel_ms_per_step(ctx, "ssm_decode")
    cost = getattr(ctx.get("flops"), "ssm_decode_cost", None)
    if ms is None or cost is None:
        return None
    ops, nbytes = cost(ctx["model"], ctx["num_slots"], ctx["weight_bytes"])
    layers = ctx["flops"].layer_counts(ctx["model"])[0]
    return _arch_decode.roofline_pct(ctx, layers * ops, layers * nbytes,
                                     ms)
