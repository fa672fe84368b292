"""Shared by the training readers that take a kernel's device time from
the trace: on the TPU an op's event text is its whole HLO instruction,
and a Pallas kernel's ``name=`` becomes the instruction's own name
(``%jvp_flash_fwd_.12 = ...``, ``%flash_bwd_dq.7 = ...``)."""
from benchmarks import trace_reduce


def kernel_ms_per_step(ctx, needle):
    """Self time of the ops whose instruction name holds ``needle``,
    per execution of the train step in the trace; None where the trace
    has no such op (a program whose kernels carry no name)."""
    red = ctx["trace"]
    if red is None:
        return None
    sec = sum(v["seconds"] for k, v in red["ops"].items()
              if needle in k.split(" = ", 1)[0])
    _, steps, _ = trace_reduce.program_seconds(
        red, ctx["programs"]["train_step"])
    if not sec or not steps:
        return None
    return 1e3 * sec / steps
