"""Device self time of a kept trace by program and by the program's own
layer names (``profiler.device_scope``).

    python3 benchmarks/tools/scope_report.py <file.xplane.pb> [--top 12]
    python3 benchmarks/tools/scope_report.py --workload <cell> \
        [--seed n] [--seconds s] [--keep <file.xplane.pb>]

A trace's op events carry no scope (their name is the HLO instruction,
their stats hold no ``op_name``: looked at on the chip, PR 25 and PR
39), so the join needs the program's table,
``paddle_tpu.observability.watchdog.program_scopes()``. The second form
runs the cell traced in this process (``benchmarks/run.py --trace 1
--keep-trace``; needs the chip), keeps the trace and writes the table
beside it (``<file>.programs.json``: it has to come from the process
that built the programs); the first reads the two, no chip needed
(``--programs`` where the table lies elsewhere). Unlike the
benchmark's readers (which see only op times summed over all programs)
this tool gives every op event to the program whose execution it lies
in (the ``XLA Modules`` line), so an instruction name that two programs
share is no problem here.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402


def _watchdog():
    from paddle_tpu.observability import watchdog
    return watchdog


def ops_by_program(path):
    """{program name: {event name: [self seconds, calls]}} and
    {program name: [executions, seconds]}: every op event of the device
    planes under the execution of ``XLA Modules`` it starts in (``""``
    for one that starts in none)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, runs = {}, {}
    for plane in data.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        ops_line = tr._line(plane, tr.OPS_LINES)
        mod_line = tr._line(plane, tr.MODULE_LINES)
        if ops_line is None:
            continue
        mods = tr._events(mod_line) if mod_line is not None else []
        for s, e, name in mods:
            rec = runs.setdefault(tr.program_name(name), [0, 0.0])
            rec[0] += 1
            rec[1] += (e - s) / 1e9
        events, j = tr._events(ops_line), 0
        per = {}
        for ev in events:
            while j < len(mods) and mods[j][1] <= ev[0]:
                j += 1
            inside = j < len(mods) and mods[j][0] <= ev[0]
            prog = tr.program_name(mods[j][2]) if inside else ""
            per.setdefault(prog, []).append(ev)
        for prog, evs in per.items():
            dst = ops.setdefault(prog, {})
            for name, (sec, calls) in tr.self_times(evs).items():
                rec = dst.setdefault(name, [0.0, 0])
                rec[0] += sec
                rec[1] += calls
    return ops, runs


def report(ops, runs, table, top=12):
    """Rows of the report: per program its executions, seconds, and op
    self seconds by scope (``"/"``-joined ``scope_path``; ``(none)``
    for an instruction the table holds without a scope, ``(not in the
    table)`` for an event no program of that name holds)."""
    wd = _watchdog()
    by_module = {}
    for rec in table.values():
        if rec.get("module"):
            by_module.setdefault(rec["module"], {}).update(
                rec["instructions"])
    rows = []
    for prog in sorted(ops, key=lambda p: -sum(
            v[0] for v in ops[p].values())):
        known = by_module.get(prog, {})
        scopes, loose = {}, {}
        for event, (sec, _calls) in ops[prog].items():
            key = wd.instruction_key(event)
            if key in known:
                name = "/".join(wd.scope_path(known[key])) or "(none)"
            else:
                name = "(not in the table)"
            scopes[name] = scopes.get(name, 0.0) + sec
            if name.startswith("("):
                loose[tr.op_label(event, 100)] = sec
        total = sum(scopes.values())
        calls, seconds = runs.get(prog, (0, 0.0))
        rows.append({
            "program": prog or "(outside any execution)",
            "executions": calls, "seconds": seconds, "op_seconds": total,
            "scopes": sorted(scopes.items(), key=lambda kv: -kv[1]),
            "unscoped_ops": sorted(loose.items(),
                                   key=lambda kv: -kv[1])[:top]})
    return rows


def show(rows, out=sys.stdout):
    for r in rows:
        per = 1e3 / r["executions"] if r["executions"] else 0.0
        out.write(f"{r['program']}: {r['executions']} executions, "
                  f"{r['seconds']:.4f} s, ops {r['op_seconds']:.4f} s\n")
        for name, sec in r["scopes"]:
            share = 100.0 * sec / r["op_seconds"] if r["op_seconds"] else 0
            out.write(f"  {sec:10.4f} s {share:5.1f} % "
                      f"{sec * per:9.4f} ms/execution  {name}\n")
        for label, sec in r["unscoped_ops"]:
            out.write(f"      unscoped {sec:9.4f} s  {label}\n")


def run_cell(args):
    """Run the cell traced here, keep the trace, write the table."""
    from benchmarks import run as bench_run
    keep = args.keep or os.path.join(
        ROOT, ".bench_trace", args.workload + ".kept.xplane.pb")
    argv = ["--workload", args.workload, "--seed", str(args.seed),
            "--trace", "1", "--keep-trace", keep]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    if args.rehearse:
        argv.append("--rehearse")
    rc = bench_run.main(argv)
    table = _watchdog().program_scopes()
    with open(keep + ".programs.json", "w") as f:
        json.dump(table, f)
    ops, runs = ops_by_program(keep) if os.path.isfile(keep) \
        else (None, None)
    return rc, table, ops, runs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?")
    ap.add_argument("--programs", help="default: <trace>.programs.json")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--keep", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    if args.workload:
        rc, table, ops, runs = run_cell(args)
        if rc or ops is None:
            return rc
    elif args.trace:
        with open(args.programs or args.trace + ".programs.json") as f:
            table = json.load(f)
        ops, runs = ops_by_program(args.trace)
    else:
        ap.error("give a kept trace or --workload")
    show(report(ops, runs, table, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
