"""The DataLoader's own span ``io/next`` (the production of one batch
for as long as the consumer waits for it), per step of the traced part
of the window: ``data_wait_ms_per_step`` seen from inside."""
from benchmarks.metrics import _ring


def read(ctx):
    return _ring.per_traced_step_ms(ctx, "io/next")
