"""The paged decode-attention kernel with GROUPED QUERIES against its
roofline: the live positions a traced step reads (from the client's
stamps), each position's key and value once for the whole group of
query heads, scores and the weighted sum for every query head
(``flops_<arch>.gqa_decode_attn_cost``), in every attention layer, over
the kernel's own device time a step. The new entry's write, the queries
in and the outputs out are not counted (a few KB a slot a layer), so the
share reads a little low."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    ms = _arch_decode.kernel_ms_per_step(ctx, "gqa_attn")
    cost = getattr(ctx.get("flops"), "gqa_decode_attn_cost", None)
    live = _arch_decode.live_positions_per_step(ctx, traced=True)
    if ms is None or cost is None or live is None:
        return None
    ops, nbytes = cost(ctx["model"], live, ctx["kv_bytes_per_value"])
    layers = ctx["flops"].layer_counts(ctx["model"])[2]
    return _arch_decode.roofline_pct(ctx, layers * ops, layers * nbytes,
                                     ms)
