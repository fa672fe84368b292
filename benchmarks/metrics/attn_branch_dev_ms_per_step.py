"""Device time of the ATTENTION side of a parallel block (everything
the program stages under the scope ``branch/attn``: the q/k/v projection
and its multipliers, the rotation, the ``paged_decode_attn`` kernel with
its cache write, the out-projection) per decode execution in the trace,
all layers; read beside ``ssm_branch_dev_ms_per_step``."""
from benchmarks.metrics import _parallel


def read(ctx):
    return _parallel.branch_ms_per_step(ctx, "attn")
