"""Device time of the prefill programs' executions in the trace, per
thousand prompt tokens they computed."""
from benchmarks.metrics import _serve_trace


def read(ctx):
    sec, prompts = _serve_trace.traced_prefills(ctx)
    if not prompts:
        return None
    return 1e3 * sec / (sum(prompts) / 1e3)
