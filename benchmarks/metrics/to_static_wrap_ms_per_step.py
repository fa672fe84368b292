"""What ``to_static`` does around the jitted call, per step of the
traced part of the window: ``jit/call`` minus ``jit/enqueue`` (gather
the captured arrays going in; write the mutated state and gradients
back and rebuild the outputs coming out)."""
from benchmarks.metrics import _ring


def read(ctx):
    call = _ring.traced_span_seconds(ctx, "jit/call")
    enqueue = _ring.traced_span_seconds(ctx, "jit/enqueue")
    if call is None or enqueue is None:
        return None
    return 1e3 * (call[0] - enqueue[0]) / ctx["traced_steps"]
