"""Share of the decode executions' op self time that is in no group of
``_scopes.SERVE_GROUPS``, in no named kernel, or ``ambiguous``: the
coverage of the ``*_dev_ms_per_step`` readers. One file for ``.gap`` and
``.tput``."""
from benchmarks.metrics import _scopes


def read(ctx):
    return _scopes.decode_unscoped_pct(ctx)
