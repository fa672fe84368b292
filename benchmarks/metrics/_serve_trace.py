"""Shared by the serving readers that need the traced window: which
requests the traced prefill executions belong to."""
from benchmarks import trace_reduce

# the device runs at most about a decode step behind the host
_DEVICE_LAG_S = 0.1


def traced_prefills(ctx):
    """(device seconds, prompt lengths) of the prefill executions inside
    the traced window. Every admission is one prefill dispatch, in
    order; the n executions the trace holds are matched by count to the
    first n requests admitted from just before the trace began (host
    and device clocks meet only at the edges, where at most one prompt
    is swapped for its neighbour)."""
    if ctx["trace"] is None or not ctx.get("trace_bounds"):
        return 0.0, []
    sec, calls, _ = trace_reduce.program_seconds(
        ctx["trace"], ctx["programs"]["prefill"])
    lo = ctx["trace_bounds"][0] - _DEVICE_LAG_S
    admitted = sorted((r.req.t_admitted, len(r.spec["prompt"]))
                      for r in ctx["run"]["recs"]
                      if r.req is not None and r.req.t_admitted is not None
                      and r.req.t_admitted >= lo)
    if calls == 0 or len(admitted) < calls:
        return 0.0, []
    return sec, [n for _, n in admitted[:calls]]
