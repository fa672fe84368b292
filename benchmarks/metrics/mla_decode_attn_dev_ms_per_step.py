"""Device time of the absorbed latent-attention kernel (the program's
``mla_paged_decode_attn``) per decode execution in the trace, all
layers."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    return _arch_decode.kernel_ms_per_step(ctx, "mla_decode_attn")
