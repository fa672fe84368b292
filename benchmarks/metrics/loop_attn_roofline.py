"""A looped model's paged decode-attention kernel against its roofline:
the live positions a traced step reads (from the client's stamps), each
position's key and value once, the new position's key and value written,
the queries in and the outputs out (``flops_<arch>.loop_attn_cost``), in
EVERY pass of every layer (``cache_layers`` calls a step), over the
kernel's own device time a step. The tiles a device layout makes of the
written rows are not counted."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    ms = _arch_decode.kernel_ms_per_step(ctx, "loop_attn")
    cost = getattr(ctx.get("flops"), "loop_attn_cost", None)
    live = _arch_decode.live_positions_per_step(ctx, traced=True)
    if ms is None or cost is None or live is None:
        return None
    ops, nbytes = cost(ctx["model"], live, ctx["num_slots"],
                       ctx["kv_bytes_per_value"])
    calls = ctx["flops"].cache_layers(ctx["model"])
    return _arch_decode.roofline_pct(ctx, calls * ops, calls * nbytes, ms)
