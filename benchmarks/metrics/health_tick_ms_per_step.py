"""The observers' share of a step: the program's span
``serving/health_tick`` (one step-ledger row and the anomaly detectors
over it, after every step and outside ``serving/step``), over the
window, per decode step. Serves ``.gap`` and ``.tput``."""
from benchmarks.metrics import _serve_spans


def read(ctx):
    steps = _serve_spans.decode_steps(ctx)
    tick = _serve_spans.window_delta(ctx, "serving/health_tick")
    if steps <= 0 or tick is None:
        return None
    return 1e3 * tick / steps
