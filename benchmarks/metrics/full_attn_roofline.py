"""The full layers' paged decode-attention kernel against its roofline:
the live positions a traced step reads (from the client's stamps), each
position's key (the whole ``head_dim``, however the pool splits it) and
value once for the whole group of query heads, the queries in and the
outputs out (``flops_<arch>.full_attn_cost``), in every FULL layer, over
the kernel's own device time a step."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    ms = _arch_decode.kernel_ms_per_step(ctx, "full_attn")
    cost = getattr(ctx.get("flops"), "full_attn_cost", None)
    live = _arch_decode.live_positions_per_step(ctx, traced=True)
    if ms is None or cost is None or live is None:
        return None
    ops, nbytes = cost(ctx["model"], live, ctx["num_slots"],
                       ctx["kv_bytes_per_value"])
    layers = ctx["flops"].layer_counts(ctx["model"])[0]
    return _arch_decode.roofline_pct(ctx, layers * ops, layers * nbytes,
                                     ms)
