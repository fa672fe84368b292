"""Device time of the paged decode-attention kernel (the program's
``paged_decode_attn``, which places the step's new entry itself) per
decode execution in the trace of a LOOPED model: all ``passes x layers``
calls of a step."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    return _arch_decode.kernel_ms_per_step(ctx, "loop_attn")
