"""Device time of the blocks' feed-forward halves (scope ``block/mlp``),
forward and ``bwd/`` alike, per traced train step: op self times joined
to the program's table of scopes (``_scopes.py``)."""
from benchmarks.metrics import _scopes


def read(ctx):
    return _scopes.train_group_ms(ctx, "block_mlp")
