"""Shared by the readers of a served architecture's decode step
(``planes/serve_arch.py``): the program's expert counters over the
window, the live cache positions a step reads, a kernel's device time a
step. Every helper returns None where there is nothing to read (no
trace, or a program that keeps no such counter or kernel)."""
from benchmarks import trace_reduce


def moe_delta(ctx):
    """(tokens [layers][experts], hits [layers], steps [layers]) the
    program's expert counters gained inside the window."""
    a = ctx["run"]["before"].get("moe")
    b = ctx["run"]["after"].get("moe")
    if not a or not b:
        return None
    tokens = [[y - x for x, y in zip(ra, rb)] for ra, rb in
              zip(a["expert_tokens"], b["expert_tokens"])]
    hits = [y - x for x, y in zip(a["experts_hit"], b["experts_hit"])]
    steps = [y - x for x, y in zip(a["layer_steps"], b["layer_steps"])]
    if not steps or min(steps) <= 0:
        return None
    return tokens, hits, steps


def experts_hit_per_step(ctx):
    """Distinct experts hit a decode step, summed over expert layers."""
    d = moe_delta(ctx)
    if d is None:
        return None
    return sum(h / s for h, s in zip(d[1], d[2]))


def live_positions_per_step(ctx, traced):
    """Mean, over the decode steps of the window (or of its traced
    part), of the cache positions a step reads: for every token stamped
    there, its sequence's length as that step saw it."""
    lo, hi = ctx["run"]["t_open"], ctx["run"]["t_close"]
    if traced:
        if not ctx.get("trace_bounds"):
            return None
        lo, hi = ctx["trace_bounds"]
    live = tokens = 0
    for r in ctx["run"]["recs"]:
        p = len(r.spec["prompt"])
        for j, s in enumerate(r.stamps):
            if j >= 1 and lo <= s < hi:
                live += p + j
                tokens += 1
    if not tokens:
        return None
    return live * ctx["num_slots"] / tokens


def traced_decode_steps(ctx):
    if ctx["trace"] is None:
        return None
    _, calls, _ = trace_reduce.program_seconds(
        ctx["trace"], ctx["programs"]["decode"])
    return calls or None


def kernel_ms_per_step(ctx, key):
    """Self time of the ops whose instruction name holds the program's
    name for kernel ``key``, per decode execution in the trace."""
    steps = traced_decode_steps(ctx)
    needle = (ctx.get("kernels") or {}).get(key)
    if not steps or not needle:
        return None
    sec = sum(v["seconds"] for k, v in ctx["trace"]["ops"].items()
              if needle in k.split(" = ", 1)[0])
    return 1e3 * sec / steps if sec else None


def roofline_pct(ctx, ops, nbytes, ms):
    """The larger of operations over the peak and bytes over the
    bandwidth, as a share of ``ms``."""
    t_min = max(ops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * 1e3 * t_min / ms
