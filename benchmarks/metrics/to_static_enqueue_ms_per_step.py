"""``to_static``'s own span ``jit/enqueue`` (the call of the jitted
step alone: jax's dispatch of a compiled program), per step of the
traced part of the window."""
from benchmarks.metrics import _ring


def read(ctx):
    return _ring.per_traced_step_ms(ctx, "jit/enqueue")
