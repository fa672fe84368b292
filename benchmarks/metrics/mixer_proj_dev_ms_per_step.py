"""Device time of the mixers' own projections and glue, the named
kernels excluded (scopes ``attn`` less ``kv_write``; ``mla/q_absorb``,
``mla/attn``, ``mla/out``; ``ssm/in_proj``, ``ssm/conv``, ``ssm/scan``,
``ssm/out``, ``attn/qkv``, ``attn/paged``, ``attn/out``; ``eva/qkv``,
``eva/attn``, ``eva/out``), per decode execution in the trace: op self
times joined to the program's table of scopes (``_scopes.py``). One file
for ``.gap`` and ``.tput``."""
from benchmarks.metrics import _scopes


def read(ctx):
    return _scopes.decode_group_ms(ctx, "mixer_proj")
