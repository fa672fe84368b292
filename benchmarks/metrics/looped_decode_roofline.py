"""A looped model's decode step against its memory bound, the WHOLE
step: the layers' weights ONCE A PASS (no program can read them less
often: the stack does not stay on the chip between passes), the head,
and every call of the attention kernel's bytes (the live positions' keys
and values in every pass of every layer, the new entries, q and o:
``flops_<arch>.decode_step_bytes``), at the chip's HBM bandwidth, over
the median device time of a decode execution. The share that bounds any
later claim on the cell."""
from benchmarks import trace_reduce
from benchmarks.metrics import _arch_decode


def read(ctx):
    flops = ctx.get("flops")
    if ctx["trace"] is None or not hasattr(flops, "loop_attn_cost"):
        return None
    med = trace_reduce.median_execution_s(ctx["trace"],
                                          ctx["programs"]["decode"])
    live = _arch_decode.live_positions_per_step(ctx, traced=True)
    if med is None or live is None:
        return None
    nbytes = flops.decode_step_bytes(ctx["model"], live,
                                     ctx["weight_bytes"], ctx["num_slots"])
    return _arch_decode.roofline_pct(ctx, 0, nbytes, 1e3 * med)
