"""Shared by the readers of a cell whose model is LOOPED (one stack of
layers run several times over the same weights, a cache entry for every
pass of every layer): the program's loop counters over the window
(through the plane's ``moe_counts`` hook), and the device time of what
the loop adds between passes, from the program's scopes ``loop/norm``
and ``loop/gate`` (``_scopes.py``: op self times joined to the program's
table of scopes). Every helper returns None where there is nothing to
read (no trace, a program that keeps no such counter or scope, as the
parent of the PR that brought this has not)."""
from benchmarks import trace_reduce
from benchmarks.metrics import _scopes

_GROUP = {"loop": ("loop/norm", "loop/gate")}


def loop_counters(ctx):
    """The loop's counters (before, after) the window."""
    a = ctx["run"]["before"].get("moe")
    b = ctx["run"]["after"].get("moe")
    if not a or not b or "passes_run" not in a:
        return None
    return a, b


def glue_ms_per_step(ctx):
    """Device ms a decode execution spends under ``loop/norm`` and
    ``loop/gate``: the final norm between passes, the exit gate, the
    exit rule and the counters."""
    memo = ctx.setdefault("_scopes", {})
    if "loop_glue" in memo:
        return memo["loop_glue"]
    memo["loop_glue"] = None
    red, wd = ctx.get("trace"), _scopes._watchdog()
    if red is None or wd is None:
        return None
    module = ctx["programs"]["decode"]
    _, steps, _ = trace_reduce.program_seconds(red, module)
    if not steps:
        return None
    sp = _scopes.split(red["ops"], wd.program_scopes(), module, _GROUP,
                       (), executed=set(red["programs"]), watchdog=wd)
    if sp is None or not sp["has"]["loop"]:
        return None
    memo["loop_glue"] = 1e3 * sp["groups"]["loop"] / steps
    return memo["loop_glue"]
