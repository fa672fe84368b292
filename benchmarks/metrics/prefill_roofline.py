"""Prefill against its compute bound: the operations the traced
prefills' prompts need (causal attention over each prompt's own length,
the head on its last position only) at the chip's bf16 peak, over those
executions' device time. Compute-bound."""
from benchmarks import flops
from benchmarks.metrics import _serve_trace


def read(ctx):
    sec, prompts = _serve_trace.traced_prefills(ctx)
    if not prompts:
        return None
    ops = sum(flops.prefill_ops(ctx["model"], n) for n in prompts)
    return 100.0 * ops / ctx["peaks"]["bf16_flops_per_s"] / sec
