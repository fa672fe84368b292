"""A latent-attention, expert-layer decode step against its memory
bound: the weights every step reads (attention, dense MLP, shared
experts, routers, head), the matrices of the experts the step's tokens
HIT (the program's counter) and the live latent cache
(``flops_<arch>.py``), at the chip's HBM bandwidth, over the median
device time of a decode execution."""
from benchmarks import trace_reduce
from benchmarks.metrics import _arch_decode


def read(ctx):
    if ctx["trace"] is None or "flops" not in ctx:
        return None
    med = trace_reduce.median_execution_s(ctx["trace"],
                                          ctx["programs"]["decode"])
    hit = _arch_decode.experts_hit_per_step(ctx)
    live = _arch_decode.live_positions_per_step(ctx, traced=False)
    if med is None or hit is None or live is None:
        return None
    nbytes = ctx["flops"].decode_step_bytes(ctx["model"], live, hit,
                                            ctx["weight_bytes"])
    return _arch_decode.roofline_pct(ctx, 0, nbytes, 1e3 * med)
