"""Device time of the feed-forward parts (scopes ``mlp``;
``moe/router``, ``moe/shared``, ``moe/experts``: the combine outside the
experts' kernel), per decode execution in the trace: op self times
joined to the program's table of scopes (``_scopes.py``). One file for
``.gap`` and ``.tput``."""
from benchmarks.metrics import _scopes


def read(ctx):
    return _scopes.decode_group_ms(ctx, "ffn")
