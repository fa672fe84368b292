"""Device time of the model's two ends (scopes ``lm_head``, ``sample``,
``embed``; the GPT's share one matrix), per decode execution in the
trace: op self times joined to the program's table of scopes
(``_scopes.py``). One file for ``.gap`` and ``.tput``."""
from benchmarks.metrics import _scopes


def read(ctx):
    return _scopes.decode_group_ms(ctx, "lm_head")
