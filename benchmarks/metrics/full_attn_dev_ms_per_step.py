"""Device time of the paged decode-attention kernel (the program's
``paged_decode_attn``, key in two parts) per decode execution in the
trace of a cell whose model keeps every position in its FULL attention
layers only: all full layers."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    return _arch_decode.kernel_ms_per_step(ctx, "full_attn")
