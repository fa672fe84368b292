"""One window of a serving cell, read from both sides: the waits as the
program timed them (``Request.t_received`` / ``t_arrival`` /
``t_first_token``, its ``serving/*`` spans, its TTFT histogram) beside
the client's own stamps, and the step period added up from the spans.

    python3 benchmarks/tools/wait_report.py --workload <cell> --seed <n> \
        [--seconds 40]

Prints ``# wait ...`` lines of JSON. On a program without the fields or
spans (an older commit) the entries that need them read null. Needs the
chip (run it with the chip tool); ``--rehearse`` checks its control flow
on the CPU at tiny sizes and prints no time.
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


def _pcts(values):
    if not values:
        return None
    return {"n": len(values), "p50": harness.percentile(values, 50),
            "p95": harness.percentile(values, 95), "max": max(values)}


def request_waits(run):
    """Per request of the window, in ms: first-token time and the wait
    in submit() as the program and as the client timed them, and the
    difference request by request."""
    rows = {"program_ttft_from_received": [], "client_ttft_from_sent": [],
            "ttft_program_minus_client": [],
            "program_ttft_from_arrival": [],
            "program_submit_wait": [], "client_submit_wait": [],
            "submit_wait_program_minus_client": []}
    for r in run["recs"]:
        req = r.req
        if r.phase != "window" or req is None or not r.stamps \
                or req.t_first_token is None:
            continue
        client = (r.stamps[0] - r.sent) * 1e3
        rows["client_ttft_from_sent"].append(client)
        rows["client_submit_wait"].append((r.accepted - r.sent) * 1e3)
        rows["program_ttft_from_arrival"].append(
            (req.t_first_token - req.t_arrival) * 1e3)
        received = getattr(req, "t_received", None)
        if received is None:
            continue
        mine = (req.t_first_token - received) * 1e3
        rows["program_ttft_from_received"].append(mine)
        rows["ttft_program_minus_client"].append(mine - client)
        wait = (req.t_arrival - received) * 1e3
        rows["program_submit_wait"].append(wait)
        rows["submit_wait_program_minus_client"].append(
            wait - rows["client_submit_wait"][-1])
    return {k: _pcts(v) for k, v in rows.items()}


def step_period(run, seconds):
    """The window's seconds per decode step beside the spans that
    should add up to them, all in ms per decode step."""
    a, b = run["before"], run["after"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None

    def per_step(name):
        if name not in b["span_s"]:
            return None
        return 1e3 * (b["span_s"][name] - a["span_s"].get(name, 0.0)) \
            / steps
    out = {"decode_steps": steps, "period": 1e3 * seconds / steps,
           "requests_admitted": b["requests_admitted"]
           - a["requests_admitted"]}
    for name in ("drive", "drive_lock_wait", "step", "sync",
                 "health_tick", "on_token", "harvest", "submit_wait",
                 "retirement", "triage", "admit", "prefill_dispatch",
                 "decode_dispatch"):
        out[name] = per_step("serving/" + name)
    if out["drive"] is not None:
        out["outside_step"] = out["drive"] - out["step"]
        out["unnamed"] = out["period"] - out["drive"]
    out["host_in_step"] = out["step"] - out["sync"]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    harness.REHEARSAL = args.rehearse
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    cell, config, traffic = bench_run.resolve(bench, args.workload,
                                              args.rehearse)
    harness.find_chip(cell["chips"], args.rehearse)
    from benchmarks.planes import serve
    from paddle_tpu.observability import default_recorder
    module = harness.load_module(
        harness.find_by_name("generators", traffic["generator"]),
        "bench_generator")
    prog = serve.ServeProgram(config, args.seed)
    ring = default_recorder()
    marks = {}
    run_ = prog.drive(
        module, traffic, args.seed, seconds,
        on_open=lambda: marks.update(open=len(ring), dropped=ring.dropped),
        on_close=lambda: marks.update(close=len(ring),
                                      dropped_in=ring.dropped
                                      - marks["dropped"]))
    percentiles = prog.engine.metrics.latency_percentiles()
    cm = serve.client_metrics(run_, seconds)
    prog.close()
    out = {"waits_ms": request_waits(run_),
           "program_histograms_ms": percentiles,
           "per_decode_step_ms": step_period(run_, seconds),
           "ring": {"spans_written_in_window":
                    marks["close"] - marks["open"] + marks["dropped_in"],
                    "dropped_in_window": marks["dropped_in"],
                    "capacity": ring.capacity},
           "failed": cm["failed"]}
    if args.rehearse:
        harness.log("wait report (rehearsal): times not measured",
                    keys=sorted(out))
        return out
    for key, value in out.items():
        print("# wait " + json.dumps({key: value}), flush=True)
    return out


if __name__ == "__main__":
    main()
