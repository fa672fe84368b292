"""Shared by the readers of a cell whose model mixes FULL attention
layers (every position in blocks) with WINDOW layers (a ring a slot):
the device time of the window layers' ring write and ring attention a
decode step, from the program's scope ``window`` (``_scopes.py``: op
self times joined to the program's table of scopes); the program's ring
cache gauges at the window's ends. Every helper returns None where there
is nothing to read (no trace, a program that keeps no such scope or
gauge, as the parent of the PR that brought this has not)."""
from benchmarks import trace_reduce
from benchmarks.metrics import _scopes

_GROUP = {"window": ("window",)}


def window_ms_per_step(ctx):
    """Device ms a decode execution spends under the scope ``window``
    (every window layer: the ring's write and the attention over it)."""
    memo = ctx.setdefault("_scopes", {})
    if "mixed_window" in memo:
        return memo["mixed_window"]
    memo["mixed_window"] = None
    red, wd = ctx.get("trace"), _scopes._watchdog()
    if red is None or wd is None:
        return None
    module = ctx["programs"]["decode"]
    _, steps, _ = trace_reduce.program_seconds(red, module)
    if not steps:
        return None
    sp = _scopes.split(red["ops"], wd.program_scopes(), module, _GROUP,
                       (), executed=set(red["programs"]), watchdog=wd)
    if sp is None or not sp["has"]["window"]:
        return None
    memo["mixed_window"] = 1e3 * sp["groups"]["window"] / steps
    return memo["mixed_window"]


def ring_counters(ctx):
    """The ring cache's gauges (before, after) the window."""
    a = ctx["run"]["before"].get("moe")
    b = ctx["run"]["after"].get("moe")
    if not a or not b or "cache_live_bytes" not in a:
        return None
    return a, b
