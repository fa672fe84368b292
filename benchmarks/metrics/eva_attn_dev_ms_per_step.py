"""Device time of the paged decode-attention kernel (the program's
``paged_decode_attn``) per decode execution in the trace of a cell whose
cache entries are not positions, all layers."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    return _arch_decode.kernel_ms_per_step(ctx, "eva_attn")
