"""A parallel-hybrid decode step against its memory bound, the WHOLE
step: every layer's matrices (both mixers and the feed-forward) and the
head, every slot's recurrent state and convolution window read AND
written in every layer, and the live keys and values of a traced step
(``flops_<arch>.decode_step_bytes``), at the chip's HBM bandwidth, over
the median device time of a decode execution. The share that bounds any
later claim on the cell."""
from benchmarks import trace_reduce
from benchmarks.metrics import _arch_decode


def read(ctx):
    flops = ctx.get("flops")
    # the flops file of a model whose EVERY layer keeps slot state
    if ctx["trace"] is None or not hasattr(flops, "state_bytes_per_step"):
        return None
    med = trace_reduce.median_execution_s(ctx["trace"],
                                          ctx["programs"]["decode"])
    live = _arch_decode.live_positions_per_step(ctx, traced=True)
    if med is None or live is None:
        return None
    nbytes = flops.decode_step_bytes(ctx["model"], live,
                                     ctx["weight_bytes"], ctx["num_slots"])
    return _arch_decode.roofline_pct(ctx, 0, nbytes, 1e3 * med)
