"""Device time by the program's own layer names: a trace's op self
times (``ctx["trace"]["ops"]``, keyed by the event's name = the whole
HLO instruction) joined to the program's table of what each compiled
program's instructions belong to
(``paddle_tpu.observability.watchdog.program_scopes``: instruction ->
``op_name``, the ``profiler.device_scope`` names open where the op was
staged), restricted to ONE program, summed by GROUP of scopes.

A group is a list of scope names. A name matches an ``op_name`` as
consecutive whole path components (``attn`` never matches
``paged_attn``; ``bwd/block/mlp`` counts for ``block/mlp``); where
names of two groups match, the innermost decides (``attn/kv_write`` is
the cache write's). The ops a named kernel's reader already counts
(instruction name holds a kernel's ``name=``) are a class of their
own, and an instruction that two programs executed in the trace hold
under different scopes is ``ambiguous`` and counted as unscoped. So

    kernels + every group + unscoped = the program's op time.

``ctx["trace"]["ops"]`` sums an event name over ALL programs, so an
instruction that another executed program holds too (under the same
scope: a prefill bucket's copy of a decode fusion) brings that
program's seconds along. Such ops stay where their scope puts them and
are listed: every traced run logs one ``# scopes <program>`` line with
their sum (``shared_ms``), the largest of them, and ``residual_pct``,
the ops' sum over the program's own device seconds less one, which is
what they and nothing else should explain.

Every reader returns None without a trace, with a program that keeps no
table (the parent of PR 39) and where the cell's model has no scope of
the group.
"""
from benchmarks import harness, trace_reduce

# serving: the decode program, per execution
SERVE_GROUPS = {
    # the mixers' own projections and glue, kernels excluded
    "mixer_proj": ("attn", "mla/q_absorb", "mla/attn", "mla/out",
                   "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/out",
                   "attn/qkv", "attn/paged", "attn/out",
                   "eva/qkv", "eva/attn", "eva/out"),
    "ffn": ("mlp", "moe/router", "moe/shared", "moe/experts"),
    # the model's two ends (the GPT's share one matrix)
    "lm_head": ("lm_head", "sample", "embed"),
    "cache_write": ("kv_write", "state_write", "kv_gather"),
}
# the GPT plane names no kernels; its decode program has this one
GPT_KERNELS = ("paged_decode_attn",)

# training: the to_static step, forward and bwd/ alike
TRAIN_GROUPS = {
    "lm_head_loss": ("lm_head", "loss"),
    "optimizer": ("optimizer/step",),
    "block_mlp": ("block/mlp",),
    "block_attn_proj": ("block/attn",),
}
TRAIN_KERNELS = ("flash_fwd", "flash_bwd_")


def _watchdog():
    """The program's module that keeps the table, or None where the
    program has none (a parent commit)."""
    try:
        from paddle_tpu.observability import watchdog
    except ImportError:
        return None
    return watchdog if hasattr(watchdog, "program_scopes") else None


def group_of(path, groups):
    """The group whose scope name matches ``path`` (a tuple of
    components) innermost: the match that ends last, the longer one on
    a tie. None where no name matches."""
    best, best_key = None, (0, 0)
    for group, names in groups.items():
        for name in names:
            want = tuple(name.split("/"))
            n = len(want)
            for i in range(len(path) - n, -1, -1):
                if path[i:i + n] == want:
                    if (i + n, n) > best_key:
                        best, best_key = group, (i + n, n)
                    break
    return best


def split(ops, table, module, groups, kernels, executed=None,
          watchdog=None):
    """Seconds of the ops of the programs whose HLO module name holds
    ``module``: ``{"groups": {group: s}, "kernels": s, "unscoped": s,
    "total": s, "has": {group: bool}, "unscoped_ops": {event: s},
    "shared_ops": {event: s}}`` (``shared_ops``: counted above where
    their scope puts them, and held by another executed program too).
    ``ops`` is a reduction's ``ops``; ``table`` the program's
    ``program_scopes()``; ``executed`` the module names that ran in the
    trace (ambiguity is judged among those; all of ``table`` where
    None). None where no program of ``table`` has that name."""
    wd = watchdog or _watchdog()
    ran = {k: rec for k, rec in table.items()
           if executed is None or rec["module"] in executed}
    mine, others = {}, set()
    for rec in table.values():
        if rec["module"] and module in rec["module"]:
            mine.update(rec["instructions"])
    if not mine:
        return None
    for rec in ran.values():
        if not (rec["module"] and module in rec["module"]):
            others.update(rec["instructions"])
    ambiguous = wd.ambiguous_instructions(ran)
    path_of = {k: wd.scope_path(v) for k, v in mine.items()}
    out = {"groups": {g: 0.0 for g in groups}, "kernels": 0.0,
           "unscoped": 0.0, "total": 0.0, "unscoped_ops": {},
           "shared_ops": {},
           "has": {g: any(group_of(p, {g: groups[g]}) for p in
                          path_of.values()) for g in groups}}
    for event, rec in ops.items():
        key = wd.instruction_key(event)
        if key not in mine:
            continue
        sec = rec["seconds"]
        out["total"] += sec
        if key in others:
            out["shared_ops"][event] = sec
        if any(n in event.split(" = ", 1)[0] for n in kernels):
            out["kernels"] += sec
            continue
        group = None if key in ambiguous \
            else group_of(path_of[key], groups)
        if group is None:
            out["unscoped"] += sec
            out["unscoped_ops"][event] = sec
        else:
            out["groups"][group] += sec
    return out


def _split_of(ctx, which, groups, kernels):
    """(split, executions of the program in the trace), memoised on the
    run's context; None where there is nothing to read."""
    memo = ctx.setdefault("_scopes", {})
    if which in memo:
        return memo[which]
    memo[which] = None
    red, wd = ctx.get("trace"), _watchdog()
    if red is None or wd is None:
        return None
    module = ctx["programs"][which]
    seconds, steps, _ = trace_reduce.program_seconds(red, module)
    if not steps:
        return None
    sp = split(red["ops"], wd.program_scopes(), module, groups, kernels,
               executed=set(red["programs"]), watchdog=wd)
    if sp is None or not sp["total"]:
        return None
    per = 1e3 / steps

    def top(ops, n):
        return [[trace_reduce.op_label(k, 90), per * v] for k, v in
                sorted(ops.items(), key=lambda kv: -kv[1])[:n]]
    harness.log(
        f"scopes {module}", executions=steps,
        program_ms=per * seconds, ops_ms=per * sp["total"],
        residual_pct=100.0 * (sp["total"] / seconds - 1.0),
        kernels_ms=per * sp["kernels"],
        unscoped_ms=per * sp["unscoped"],
        groups_ms={g: per * v for g, v in sp["groups"].items()},
        unscoped_top_ms=top(sp["unscoped_ops"], 8),
        shared_ms=per * sum(sp["shared_ops"].values()),
        shared_top_ms=top(sp["shared_ops"], 4))
    memo[which] = (sp, steps)
    return memo[which]


def _decode(ctx):
    kernels = tuple((ctx.get("kernels") or {}).values()) or GPT_KERNELS
    return _split_of(ctx, "decode", SERVE_GROUPS, kernels)


def _train(ctx):
    return _split_of(ctx, "train_step", TRAIN_GROUPS, TRAIN_KERNELS)


def _group_ms(found, group):
    if found is None or not found[0]["has"][group]:
        return None
    return 1e3 * found[0]["groups"][group] / found[1]


def _unscoped_pct(found):
    if found is None:
        return None
    return 100.0 * found[0]["unscoped"] / found[0]["total"]


def decode_group_ms(ctx, group):
    """Device ms of ``group``'s ops per decode execution in the trace."""
    return _group_ms(_decode(ctx), group)


def decode_unscoped_pct(ctx):
    return _unscoped_pct(_decode(ctx))


def train_group_ms(ctx, group):
    """Device ms of ``group``'s ops per traced train step."""
    return _group_ms(_train(ctx), group)


def train_unscoped_pct(ctx):
    return _unscoped_pct(_train(ctx))
