"""Shared by the training readers that take the program's own host
spans: the ring ``paddle_tpu.observability.default_recorder()`` keeps
every ``profiler.host_scope``/``record_scope`` with its start on
``time.perf_counter``, the clock of the harness's ``trace_bounds``."""


def traced_span_seconds(ctx, name):
    """(seconds, count) of the program's spans called ``name`` that
    start inside the traced part of the window, or None: no traced
    window, a program without that span, or a ring that has since
    dropped spans of the window."""
    if not ctx.get("trace_bounds") or not ctx.get("traced_steps"):
        return None
    from paddle_tpu.observability import default_recorder
    lo, hi = ctx["trace_bounds"]
    ring = default_recorder()
    spans = ring.spans()
    if not spans or (ring.dropped and spans[0].t0 > lo):
        return None
    mine = [s.dur for s in spans if s.name == name and lo <= s.t0 < hi]
    return (sum(mine), len(mine)) if mine else None


def per_traced_step_ms(ctx, name):
    got = traced_span_seconds(ctx, name)
    return None if got is None else 1e3 * got[0] / ctx["traced_steps"]
