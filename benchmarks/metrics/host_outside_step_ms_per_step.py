"""Host work of the gateway's drive loop around one engine step: the
program's span ``serving/drive`` (an iteration of the driver that
stepped, from before it asks for the lock until ``step()`` returned)
minus ``serving/step``, over the window, per decode step. It holds the
driver's wait for its lock, the health tick and the loop itself;
``engine_host_ms_per_step`` holds none of them. Serves ``.gap`` and
``.tput``."""
from benchmarks.metrics import _serve_spans


def read(ctx):
    steps = _serve_spans.decode_steps(ctx)
    drive = _serve_spans.window_delta(ctx, "serving/drive")
    step = _serve_spans.window_delta(ctx, "serving/step")
    if steps <= 0 or drive is None or step is None:
        return None
    return 1e3 * (drive - step) / steps
