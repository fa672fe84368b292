"""Find the knee of a steady serving cell: one process, one set-up, the
cell's own traffic at each of a few fixed rates.

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 3,4,5 \
        [--seconds 20] [--seed 1]

The knee is the highest rate at which at least 99% of the requests due
in the window finish and the queue at the end of the window is no deeper
than at its middle. The cell then runs at about four fifths of it; the
number is written into the traffic file by hand, and the table into
PERF.md. Run it with the chip tool: it measures, so it needs the chip.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402


def queue_depth(recs, t):
    """Requests due by ``t`` that had no slot yet at ``t``."""
    n = 0
    for r in recs:
        if r.due <= t:
            adm = r.req.t_admitted if r.req is not None else None
            if adm is None or adm > t:
                n += 1
    return n


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        print("# rehearsal: the sweep's control flow only; no rate or "
              "time printed here is a measurement")
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = bench_run.resolve(bench, args.workload,
                                              args.rehearse)
    devs = harness.find_chip(cell["chips"], args.rehearse)
    from benchmarks.planes import serve
    module = harness.load_module(
        harness.find_by_name("generators", traffic["generator"]),
        "bench_generator")
    prog = serve.ServeProgram(config, args.seed)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        params = dict(traffic, rate_per_s=rate)
        run_ = prog.drive(module, params, args.seed + i, args.seconds)
        cm = serve.client_metrics(run_, args.seconds)
        recs = [r for r in run_["recs"]]
        mid = queue_depth(recs, run_["t_open"] + args.seconds / 2)
        end = queue_depth(recs, run_["t_close"])
        row = {"rate_per_s": rate, "attempted": cm["attempted"],
               "finished_share": 1 - cm["failed"] / max(1, cm["attempted"]),
               "queue_mid": mid, "queue_end": end,
               "sustained": cm["failed"] <= 0.01 * cm["attempted"]
               and end <= mid,
               "ttft_p50_ms": harness.percentile(cm["ttft_ms"], 50),
               "ttft_p95_ms": cm["values"]["ttft_p95_ms"],
               "gap_p50_ms": harness.percentile(cm["gap_ms"], 50),
               "gap_p95_ms": cm["values"]["gap_p95_ms"],
               "tokens_per_s": cm["values"]["serve_tokens_per_s"],
               "decode_steps_per_s": (run_["after"]["decode_steps"]
                                      - run_["before"]["decode_steps"])
               / args.seconds}
        rows.append(row)
        print("# sweep " + json.dumps(row), flush=True)
        time.sleep(1.0)
    prog.close()
    print(json.dumps({"device": {"platform": devs[0].platform,
                                 "kind": devs[0].device_kind},
                      "workload": cell["name"], "sweep": rows}))


if __name__ == "__main__":
    main()
