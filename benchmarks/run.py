"""Run one cell of the benchmark once, in this process.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1> [--rehearse] [--keep-trace]

Finds the chip (or exits non-zero with no result line), sets the cell up
from ``--seed``, warms every shape the window uses, measures for
``--seconds``, checks the timed path against the plain reference and
prints ONE JSON object as the last line of its output. Everything a cell
is made of is found by name through ``BENCHMARK.json``: see README.md.

``--rehearse`` runs the same code at the tiny sizes of each file's
``rehearse`` section on whatever device jax has (the CPU here); it
prints no device metric and no result line.
"""
import time
_T0 = time.perf_counter()   # "process start" for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402


def cell_metrics(bench, group, cell_name):
    """The metrics of ``group`` that this cell reports: those that list
    it under ``workloads``, and those without the key."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def resolve(bench, cell_name, rehearse):
    cell = next((c for c in bench["workloads"] if c["name"] == cell_name),
                None)
    if cell is None:
        sys.exit(f"benchmarks/run.py: no workload {cell_name!r} in "
                 f"BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = harness.load_json(ROOT, conf["file"])
    traffic = harness.load_json(
        harness.find_by_name("traffic", cell["traffic"], ".json"))
    if rehearse:
        config, traffic = harness.rehearsed(config), harness.rehearsed(traffic)
    return cell, config, traffic


def read_per_layer(bench, cell, out, red, tracer):
    """Each per-layer metric of the cell through its own reader; a
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    mctx = dict(out["readings"])
    mctx.update(trace=red, programs=out["programs"],
                memory_peak_bytes=out["memory_peak_bytes"],
                trace_bounds=(tracer.t_start, tracer.t_stop)
                if tracer else None)
    values = {}
    for m in cell_metrics(bench, "per_layer", cell["name"]):
        mod = harness.load_module(
            harness.find_by_name("metrics", m["name"]),
            "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(mctx)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return values


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb to this path")
    args = ap.parse_args(argv)

    harness.REHEARSAL = args.rehearse
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    cell, config, traffic = resolve(bench, args.workload, args.rehearse)
    devs = harness.find_chip(cell["chips"], args.rehearse)
    dev = devs[0]
    peaks = None if args.rehearse else harness.peaks_for(dev.device_kind)
    harness.log("cell", name=cell["name"], seed=args.seed, seconds=seconds,
                trace=args.trace, platform=dev.platform,
                kind=dev.device_kind, devices=len(devs))

    plane = harness.load_module(
        harness.find_by_name("planes", config["plane"]), "bench_plane")
    out = plane.run({
        "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
        "rehearse": args.rehearse, "t0": _T0, "devices": devs[:cell["chips"]],
        "limits": config["correct_limits"], "peaks": peaks})

    correct = True
    for name, value, limit in out["checks"]:
        ok = value <= limit
        correct = correct and ok
        harness.log(f"check {name}: {value!r} against limit {limit!r}: "
                    f"{'ok' if ok else 'NOT CORRECT'}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": device}
    tracer = out["tracer"]
    if args.trace:
        from benchmarks import trace_reduce
        try:
            red = trace_reduce.reduce(tracer.path, out["host_spans"])
        except ValueError:
            if not args.rehearse:   # a CPU trace has no device plane
                raise
            red = None
        tracer.cleanup(keep_to=args.keep_trace)
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = trace_reduce.breakdown(red)
        out["readings"]["peaks"] = peaks
        result["metrics"] = read_per_layer(bench, cell, out, red, tracer)
        if red is not None:
            harness.log("programs", **{
                k: {"calls": v["calls"], "seconds": v["seconds"]}
                for k, v in red["programs"].items()})
    else:
        for m in cell_metrics(bench, "end_to_end", cell["name"]):
            if m["name"] not in out["values"]:
                sys.exit(f"benchmarks/run.py: the run gave no "
                         f"{m['name']} (no request finished?)")
            result["metrics"][m["name"]] = {
                "value": float(out["values"][m["name"]]), "unit": m["unit"]}
    if args.rehearse:
        harness.log("rehearsal only: no result line", correct=correct,
                    attempted=result["attempted"], failed=result["failed"],
                    metrics=sorted(result["metrics"]))
        return 0 if correct else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
