"""A full + window attention + expert decode step against its memory
bound, the WHOLE step: the weights every step reads (attention
projections, the dense layer, routers, head), the matrices of the
experts the step's tokens HIT (the program's counter), the live keys
and values of the FULL layers and every slot's ring in the WINDOW layers
(``flops_<arch>.decode_step_bytes``), at the chip's HBM bandwidth, over
the median device time of a decode execution."""
from benchmarks import trace_reduce
from benchmarks.metrics import _arch_decode


def read(ctx):
    flops = ctx.get("flops")
    if ctx["trace"] is None or not hasattr(flops, "window_attn_cost"):
        return None
    med = trace_reduce.median_execution_s(ctx["trace"],
                                          ctx["programs"]["decode"])
    hit = _arch_decode.experts_hit_per_step(ctx)
    live = _arch_decode.live_positions_per_step(ctx, traced=False)
    if med is None or hit is None or live is None:
        return None
    nbytes = flops.decode_step_bytes(ctx["model"], live, hit,
                                     ctx["weight_bytes"], ctx["num_slots"])
    return _arch_decode.roofline_pct(ctx, 0, nbytes, 1e3 * med)
