"""The paged decode-attention kernel against its memory bound: the keys
and values of the LIVE positions a decode step reads, in every layer,
at the chip's HBM bandwidth, over the kernel's own device time a step.
Memory-bound: one query row a head, so its operations would need far
less time than its bytes. Live positions are counted as
``decode_roofline`` counts them (for every token the client stamped,
its sequence's length as that step saw it), over the traced part of the
window, per decode execution in the trace. What the kernel reads
besides (a released slot's one trash block, the rows of a live block
beyond the length) is not counted, so the share cannot pass 100."""
from benchmarks import flops
from benchmarks.metrics import _arch_decode, paged_attn_dev_ms_per_step


def read(ctx):
    ms = paged_attn_dev_ms_per_step.read(ctx)
    if ms is None or not ctx.get("trace_bounds"):
        return None
    steps = _arch_decode.traced_decode_steps(ctx)
    lo, hi = ctx["trace_bounds"]
    live = 0
    for r in ctx["run"]["recs"]:
        p = len(r.spec["prompt"])
        live += sum(p + j for j, s in enumerate(r.stamps)
                    if j >= 1 and lo <= s < hi)
    if not live:
        return None
    nbytes = flops.kv_bytes(ctx["model"], live / steps,
                            ctx["kv_bytes_per_value"])
    t_min = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * 1e3 * t_min / ms
