"""Shared by the serving readers that take the program's own spans from
``ServingMetrics.span_s`` at both ends of the window."""


def window_delta(ctx, name):
    """Seconds span ``name`` accrued inside the window, or None where
    the program has no such span."""
    a, b = ctx["run"]["before"]["span_s"], ctx["run"]["after"]["span_s"]
    if name not in b:
        return None
    return b[name] - a.get(name, 0.0)


def decode_steps(ctx):
    a, b = ctx["run"]["before"], ctx["run"]["after"]
    return b["decode_steps"] - a["decode_steps"]
