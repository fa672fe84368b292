"""Shared by the readers of a cell whose cache ENTRIES are not positions
(``evabyte``): the entries a decode step reads, from the client's
stamps; the compaction program's device time and calls a decode step;
the program's entry-cache counters at the window's ends. Every helper
returns None where there is nothing to read (no trace, or a program that
has no such program or counter, as the parent of the PR that brought
this has not)."""
from benchmarks import trace_reduce
from benchmarks.metrics import _arch_decode


def live_entries_per_step(ctx, traced):
    """Mean, over the decode steps of the window (or of its traced
    part), of the cache entries a step reads: for every token stamped
    there, the entries its sequence held at the step that made it (the
    step wrote position ``prompt + j - 1`` and attended up to it)."""
    entries = getattr(ctx.get("flops"), "entries", None)
    if entries is None:
        return None
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
                live += entries(ctx["model"], p + j - 1) + 1
                tokens += 1
    if not tokens:
        return None
    return live * ctx["num_slots"] / tokens


def compactions(ctx):
    """(device ms a decode step, calls a decode step) of the compaction
    program in the trace; zeros where the program is there and no window
    ended in the traced part."""
    name = (ctx.get("programs") or {}).get("compact")
    steps = _arch_decode.traced_decode_steps(ctx)
    if not name or not steps:
        return None
    sec, calls, _ = trace_reduce.program_seconds(ctx["trace"], name)
    return 1e3 * sec / steps, calls / steps


def counters(ctx):
    """The entry cache's counters (before, after) the window."""
    a = ctx["run"]["before"].get("moe")
    b = ctx["run"]["after"].get("moe")
    if not a or not b or "entries_live" not in a:
        return None
    return a, b
