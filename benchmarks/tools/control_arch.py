"""Read, over several seeds in one process, what ``correct`` compares in
a cell of ``planes/serve_arch.py``: ``served_logit_gap`` from sound runs
of the program, and from the controls (the plain reference computed in a
lower precision, put in the program's place): ``float8``, the nearest
precision below the bfloat16 the configuration states, and ``bfloat16``
itself (how far a sound bf16 computation may lie from the float32
reference). Besides the widest gap it prints how the gaps are spread
(quantiles, share over 0.1), for the first token of a session (prefill)
and for the rest (decode) apart.

    python3 benchmarks/tools/control_arch.py --workload <cell> \
        --seeds 1,2,3 [--seconds 8] [--sessions 4] [--controls float8]

Needs the chip (run it with the chip tool); ``--rehearse`` checks its
control flow on the CPU at tiny sizes.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks import harness  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.planes import serve, serve_arch  # noqa: E402


def gaps_of(sample, w, model, arch, pad_to, control=None):
    """(first-token gaps, decode-token gaps) of the sample's served
    tokens (or of the control's own first choices) under the float32
    reference."""
    gaps = list(serve_arch.token_gaps(sample, w, model, arch, pad_to,
                                      control))
    return (np.concatenate([g[:1] for g in gaps]),
            np.concatenate([g[1:] for g in gaps]))


def spread(gaps):
    if not len(gaps):
        return {}
    q = np.quantile(gaps, [0.5, 0.9, 0.99, 0.999])
    return {"n": int(len(gaps)), "max": float(gaps.max()),
            "p50": float(q[0]), "p90": float(q[1]), "p99": float(q[2]),
            "p999": float(q[3]), "mean": float(gaps.mean()),
            "share_over_0.1": float((gaps > 0.1).mean()),
            "share_over_1": float((gaps > 1.0).mean())}


def one_seed(config, traffic, seed, seconds, sessions, controls):
    arch = serve_arch.arch_files(config["arch"])
    module = harness.load_module(
        harness.find_by_name("generators", traffic["generator"]),
        "bench_generator")
    prog = serve_arch.ServeArchProgram(config, seed, arch)
    run_ = prog.drive(module, traffic, seed, seconds)
    cm = serve.client_metrics(run_, seconds)
    prog.close()
    sample = serve_arch.checked_sample(
        cm["ok"], dict(traffic, check_sessions=sessions), seed)
    model = serve_arch.model_of(config)
    pad = int(traffic.get("reference_pad", 2048))
    w = arch.weights.make(seed, model, config["precision"])
    out = {"seed": seed, "failed": cm["failed"], "sessions": len(sample),
           "tokens_per_s": cm["values"]["serve_tokens_per_s"]}
    t0 = time.perf_counter()
    first, rest = gaps_of(sample, w, model, arch, pad)
    out["sound"] = {"served_logit_gap": float(max(first.max(),
                                                  rest.max())),
                    "prefill": spread(first), "decode": spread(rest)}
    out["reference_s"] = time.perf_counter() - t0
    for c in controls:
        first, rest = gaps_of(sample, w, model, arch, pad, control=c)
        out[c] = {"served_logit_gap": float(max(first.max(), rest.max())),
                  "prefill": spread(first), "decode": spread(rest)}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--sessions", type=int, default=4)
    ap.add_argument("--controls", default="float8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    harness.REHEARSAL = args.rehearse
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = bench_run.resolve(bench, args.workload,
                                              args.rehearse)
    harness.find_chip(cell["chips"], args.rehearse)
    controls = [c for c in args.controls.split(",") if c]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(one_seed(config, traffic, seed, args.seconds,
                                  args.sessions, controls)), flush=True)


if __name__ == "__main__":
    main()
