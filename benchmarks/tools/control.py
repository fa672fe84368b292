"""Read, over several seeds in one process, the numbers `correct`
compares: from sound runs of the program, and from the control (the
plain reference computed in float8, the nearest precision below the
bfloat16 the configurations state, put in the program's place).

    python3 benchmarks/tools/control.py --workload <cell> \
        --seeds 1,2,3 [--seconds 12] [--no-control]

A limit goes above the sound runs' largest and below the control's
smallest (PERF.md gives the readings each limit was set from). Needs the
chip (run it with the chip tool); ``--rehearse`` checks its control flow
on the CPU at tiny sizes, as benchmarks/tests does.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402

CONTROL = "float8"


def serve_seed(config, traffic, seed, seconds, control=True):
    from benchmarks.planes import serve
    module = harness.load_module(
        harness.find_by_name("generators", traffic["generator"]),
        "bench_generator")
    prog = serve.ServeProgram(config, seed)
    run_ = prog.drive(module, traffic, seed, seconds)
    cm = serve.client_metrics(run_, seconds)
    prog.close()
    args = (cm["ok"], seed, config["model"], config["precision"],
            config["sizing"]["max_len"])
    gap, n_tok = serve.served_gap(*args)
    out = {"seed": seed, "served_tokens": n_tok, "failed": cm["failed"],
           "sound": {"served_logit_gap": gap}}
    if control:
        out["control"] = {"served_logit_gap":
                          serve.served_gap(*args, control=CONTROL)[0]}
    return out


def train_seed(config, traffic, seed, control=True):
    from benchmarks.planes import train
    prog = train.TrainProgram(config, traffic, seed, harness.Spans())
    first, gen = prog.first, prog.gen
    prog.close()
    want = train.reference_first_steps(config, traffic, seed, gen)
    out = {"seed": seed, "sound": train.compare(first, want)[0]}
    if control:
        ctrl = train.reference_first_steps(config, traffic, seed, gen,
                                           precision=CONTROL)
        out["control"] = train.compare(ctrl, want)[0]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--ramp", type=float, default=None,
                    help="a shorter ramp than the traffic file's: only "
                         "the served tokens are read here, no latency")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    harness.REHEARSAL = args.rehearse
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = bench_run.resolve(bench, args.workload,
                                              args.rehearse)
    devs = harness.find_chip(cell["chips"], args.rehearse)
    if args.ramp is not None:
        traffic = dict(traffic, ramp_s=args.ramp)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        if config["plane"] == "serve":
            row = serve_seed(config, traffic, seed, args.seconds,
                             not args.no_control)
        else:
            row = train_seed(config, traffic, seed, not args.no_control)
        rows.append(row)
        print("# control " + json.dumps(row), flush=True)
    summary = {"workload": cell["name"], "limits": config["correct_limits"],
               "device": {"platform": devs[0].platform,
                          "kind": devs[0].device_kind}}
    for name in rows[0]["sound"]:
        summary[name] = {"sound_largest": max(r["sound"][name]
                                              for r in rows)}
        if "control" in rows[0]:
            summary[name]["control_smallest"] = min(r["control"][name]
                                                    for r in rows)
    print(json.dumps(summary))
    return rows


if __name__ == "__main__":
    main()
