"""A decode step of a model whose finished windows are compacted against
its memory bound, the WHOLE step: every layer's weights, the keys and
values of every live ENTRY in every layer, and the step's share of the
compactions' reads and writes (``flops_<arch>.decode_step_bytes``), at
the chip's HBM bandwidth, over the median device time of a decode
execution plus the compaction program's time a step. It counts every
byte the step must move and all the device time that moves them, so it
cannot read over 100 %: the share that bounds any later claim here."""
from benchmarks import trace_reduce
from benchmarks.metrics import _arch_decode, _eva


def read(ctx):
    if ctx["trace"] is None:
        return None
    med = trace_reduce.median_execution_s(ctx["trace"],
                                          ctx["programs"]["decode"])
    live = _eva.live_entries_per_step(ctx, traced=True)
    comp = _eva.compactions(ctx)
    if med is None or live is None or comp is None:
        return None
    nbytes = ctx["flops"].decode_step_bytes(
        ctx["model"], live, ctx["weight_bytes"], comp[1])
    return _arch_decode.roofline_pct(ctx, 0, nbytes, 1e3 * med + comp[0])
