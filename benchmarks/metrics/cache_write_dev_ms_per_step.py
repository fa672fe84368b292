"""Device time of the cache's writes and gathers (scopes ``kv_write``,
``state_write``, ``kv_gather``), per decode execution in the trace: op
self times joined to the program's table of scopes (``_scopes.py``). One
file for ``.gap`` and ``.tput``."""
from benchmarks.metrics import _scopes


def read(ctx):
    return _scopes.decode_group_ms(ctx, "cache_write")
