"""Decode against its memory bound: a step has to read every weight
once and the live keys and values of the sequences in the batch; at the
chip's HBM bandwidth that takes t_min, and the share is t_min over the
median device time of a decode execution. Memory-bound: at 48 slots the
step's operations would need far less time than its bytes. Live KV is
the window's mean over decode steps, from the client's token stamps."""
from benchmarks import flops, trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    med = trace_reduce.median_execution_s(ctx["trace"],
                                          ctx["programs"]["decode"])
    a, b = ctx["run"]["before"], ctx["run"]["after"]
    steps = b["decode_steps"] - a["decode_steps"]
    if med is None or steps <= 0:
        return None
    lo, hi = ctx["run"]["t_open"], ctx["run"]["t_close"]
    live = 0
    for r in ctx["run"]["recs"]:
        p = len(r.spec["prompt"])
        live += sum(p + j for j, s in enumerate(r.stamps)
                    if j >= 1 and lo <= s < hi)
    nbytes = flops.weight_bytes_per_decode_step(
        ctx["model"], ctx["weight_bytes"]) + flops.kv_bytes(
        ctx["model"], live / steps, ctx["kv_bytes_per_value"])
    t_min = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * t_min / med
