"""Device time of the STATE-SPACE side of a parallel block (everything
the program stages under the scope ``branch/ssm``: the shared pre-norm,
the in-projection and its multipliers, the convolution, the
``ssm_decode_step`` kernel, the gated norm, the out-projection) per
decode execution in the trace, all layers; read beside
``attn_branch_dev_ms_per_step``."""
from benchmarks.metrics import _parallel


def read(ctx):
    return _parallel.branch_ms_per_step(ctx, "ssm")
