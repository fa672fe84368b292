"""Model FLOP/s utilisation: forward + backward operations a token
needs (flops.py: no recomputation, no optimizer) x tokens per second,
over the chip's bf16 peak. The rate is that of the traced part of the
window: the steps completed (blocked on) when the profiler was told to
stop, over the seconds from its start to that moment, so that the
profiler's own start and stop, which fall inside a traced run's window,
are not in it."""
from benchmarks import flops


def read(ctx):
    if not ctx.get("peaks") or not ctx.get("traced_steps"):
        return None
    rate = ctx["traced_steps"] * ctx["tokens_per_step"] \
        / ctx["traced_seconds"]
    ops = flops.train_ops_per_token(ctx["model"], ctx["seq_len"])
    return 100.0 * ops * rate / ctx["peaks"]["bf16_flops_per_s"]
