"""Share of the traced train steps' op self time that is in no group of
``_scopes.TRAIN_GROUPS`` and in no flash kernel: the coverage of the
training ``*_dev_ms_per_step`` readers."""
from benchmarks.metrics import _scopes


def read(ctx):
    return _scopes.train_unscoped_pct(ctx)
