"""What one span of the program costs on this host, in microseconds:
``ServingMetrics.span()`` (the serving step loop's span) and
``profiler.record_scope`` entered and left empty, with no profiler
session and inside one.

    python3 benchmarks/tools/span_cost.py [--n 20000]

A host number: run it where the benchmark runs (the chip tool's
machine), on the commits to compare. Prints one line of JSON.
"""
import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _us_per_span(make, n):
    for _ in range(200):
        with make():
            pass
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    return 1e6 * best


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    args = ap.parse_args(argv)
    import jax
    from paddle_tpu import profiler
    from paddle_tpu.serving.metrics import ServingMetrics
    metrics = ServingMetrics()
    kinds = {"metrics_span": lambda: metrics.span("serving/cost_probe"),
             "record_scope": lambda: profiler.record_scope(
                 "serving/cost_probe")}
    out = {"n": args.n, "platform": jax.devices()[0].platform}
    for kind, make in kinds.items():
        out[kind + "_us"] = _us_per_span(make, args.n)
    trace_dir = os.path.join(ROOT, ".bench_trace", "span_cost")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for kind, make in kinds.items():
            out[kind + "_traced_us"] = _us_per_span(make, args.n // 4)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
