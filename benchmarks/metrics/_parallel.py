"""Shared by the readers of a cell whose model runs TWO MIXERS SIDE BY
SIDE in every layer (a state-space mixer and grouped-query attention on
the same normed input, summed into the residual stream): the device time
of everything the program stages under each branch's enclosing scope
(``branch/ssm``, ``branch/attn``: the branch's projections, its glue AND
its kernel, whose ops carry the scope they were staged under), from op
self times joined to the program's table of scopes (``_scopes.py``), and
the program's cache gauges (through the plane's ``moe_counts`` hook).
Every helper returns None where there is nothing to read (no trace, a
program that opens no such scope or keeps no such gauge, as the parent
of the PR that brought this does not)."""
from benchmarks import trace_reduce
from benchmarks.metrics import _scopes

_GROUPS = {"ssm": ("branch/ssm",), "attn": ("branch/attn",)}


def branch_ms_per_step(ctx, branch):
    """Device ms a decode execution spends under ``branch/<branch>``,
    kernels included."""
    memo = ctx.setdefault("_scopes", {})
    if "branches" not in memo:
        memo["branches"] = None
        red, wd = ctx.get("trace"), _scopes._watchdog()
        if red is None or wd is None:
            return None
        module = ctx["programs"]["decode"]
        _, steps, _ = trace_reduce.program_seconds(red, module)
        if not steps:
            return None
        # no kernel is a class of its own here: each is its branch's
        sp = _scopes.split(red["ops"], wd.program_scopes(), module,
                           _GROUPS, (), executed=set(red["programs"]),
                           watchdog=wd)
        if sp is not None:
            memo["branches"] = {
                g: 1e3 * sp["groups"][g] / steps for g in _GROUPS
                if sp["has"][g]}
    return (memo["branches"] or {}).get(branch)


def cache_gauges(ctx):
    """``{"state_bytes_per_slot", "kv_bytes_per_token"}`` of a program
    whose slots carry state beside their blocks."""
    got = ctx["run"]["after"].get("moe")
    if not got or "state_bytes_per_slot" not in got:
        return None
    return got
