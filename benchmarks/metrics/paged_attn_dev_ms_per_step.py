"""Device time of the GPT's paged decode-attention kernel (the
program's ``paged_decode_attn``) per decode execution in the trace, all
layers: self time of the ops whose instruction name holds the kernel's
``name=``. None where the trace has no such op (a decode program that
gathers instead: the parent of PR 29, the CPU)."""
from benchmarks.metrics import _arch_decode

KERNEL = "paged_decode_attn"


def read(ctx):
    steps = _arch_decode.traced_decode_steps(ctx)
    if not steps:
        return None
    sec = sum(v["seconds"] for k, v in ctx["trace"]["ops"].items()
              if KERNEL in k.split(" = ", 1)[0])
    return 1e3 * sec / steps if sec else None
