"""The flash-attention kernels (forward and both backward kernels)
against their compute bound: the operations causal attention needs for
the steps in the trace, at the chip's bf16 peak, over the kernels'
device time in the trace. Compute-bound at sequences of 1024."""
from benchmarks import flops, trace_reduce


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    sec = sum(v["seconds"] for k, v in red["ops"].items()
              if any(name in k for name in ctx["flash_kernels"]))
    _, steps, _ = trace_reduce.program_seconds(
        red, ctx["programs"]["train_step"])
    if not sec or not steps:
        return None
    m = ctx["model"]
    heads = m["num_attention_heads"]
    per_layer = sum(flops.flash_attention_ops(
        ctx["batch"], heads, ctx["seq_len"], m["hidden_size"] // heads, bwd)
        for bwd in (False, True))
    ops = steps * m["num_hidden_layers"] * per_layer
    return 100.0 * ops / ctx["peaks"]["bf16_flops_per_s"] / sec
