"""The window layers' decode attention against its roofline: every
slot's ring of ``sliding_window`` entries read once, one entry written,
the queries in and the outputs out (``flops_<arch>.window_attn_cost``),
in every WINDOW layer, over the device time of the program's scope
``window`` a step. A program that moves a whole ring to write one entry,
or a layout's padding, reads as a lower share."""
from benchmarks.metrics import _arch_decode, _mixed


def read(ctx):
    ms = _mixed.window_ms_per_step(ctx)
    cost = getattr(ctx.get("flops"), "window_attn_cost", None)
    if not ms or cost is None:
        return None
    ops, nbytes = cost(ctx["model"], ctx["num_slots"],
                       ctx["kv_bytes_per_value"])
    layers = ctx["flops"].layer_counts(ctx["model"])[1]
    return _arch_decode.roofline_pct(ctx, layers * ops, layers * nbytes,
                                     ms)
