"""Share of a layer's routed experts that a decode step's tokens hit
(the program's counter: distinct experts hit a layer-step over the
routed experts): what fraction of the experts' bytes a step reads."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    d = _arch_decode.moe_delta(ctx)
    if d is None:
        return None
    return 100.0 * sum(d[1]) / (sum(d[2])
                                * ctx["model"]["n_routed_experts"])
