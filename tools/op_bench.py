"""Per-op micro-benchmark harness.

Reference parity: paddle/fluid/operators/benchmark/op_tester.cc (runs a
single op from a config and times it) + tools/check_op_benchmark_result.py
(CI regression compare). Usage:

  python tools/op_bench.py                    # built-in op set
  python tools/op_bench.py matmul softmax     # subset
  python tools/op_bench.py --compare old.json # regression check (>10% slow)

Prints one JSON line per op: {"op": ..., "shape": ..., "us": ...}.
Times the jit-compiled executable (the eager dispatch path) after warmup.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_CONFIGS = {
    "matmul": lambda paddle: (paddle.matmul,
                              [np.random.randn(1024, 1024).astype("float32"),
                               np.random.randn(1024, 1024).astype("float32")]),
    "bmm": lambda paddle: (paddle.bmm,
                           [np.random.randn(32, 256, 256).astype("float32"),
                            np.random.randn(32, 256, 256).astype("float32")]),
    "softmax": lambda paddle: (paddle.nn.functional.softmax,
                               [np.random.randn(64, 4096).astype("float32")]),
    "layer_norm": lambda paddle: (
        lambda x: paddle.nn.functional.layer_norm(
            x, x.shape[-1:],
            paddle.to_tensor(np.ones(1024, "float32")),
            paddle.to_tensor(np.zeros(1024, "float32"))),
        [np.random.randn(64, 1024).astype("float32")]),
    "relu": lambda paddle: (paddle.nn.functional.relu,
                            [np.random.randn(1024, 1024).astype("float32")]),
    "add": lambda paddle: (paddle.add,
                           [np.random.randn(1024, 1024).astype("float32"),
                            np.random.randn(1024, 1024).astype("float32")]),
    "conv2d": lambda paddle: (
        lambda x, w: paddle.nn.functional.conv2d(x, w, None, 1, 1),
        [np.random.randn(16, 64, 56, 56).astype("float32"),
         np.random.randn(64, 64, 3, 3).astype("float32")]),
    "reduce_sum": lambda paddle: (paddle.sum,
                                  [np.random.randn(2048, 2048)
                                   .astype("float32")]),
    "transpose": lambda paddle: (
        lambda x: paddle.transpose(x, [1, 0]),
        [np.random.randn(2048, 2048).astype("float32")]),
    "embedding": lambda paddle: (
        lambda ids, w: paddle.nn.functional.embedding(ids, w),
        [np.random.randint(0, 30000, (64, 512)).astype("int64"),
         np.random.randn(30000, 256).astype("float32")]),
}


def bench_one(paddle, name, warmup=5, iters=50):
    fn, arrays = _CONFIGS[name](paddle)
    tensors = [paddle.to_tensor(a) for a in arrays]
    out = None
    for _ in range(warmup):
        out = fn(*tensors)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*tensors)
    _sync(out)
    us = (time.perf_counter() - t0) / iters * 1e6
    return {"op": name, "shape": [list(a.shape) for a in arrays],
            "us": round(us, 2)}


def _sync(out):
    if isinstance(out, (tuple, list)):
        out = out[0]
    out.numpy()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("ops", nargs="*", default=None)
    ap.add_argument("--compare", help="baseline json-lines file")
    ap.add_argument("--threshold", type=float, default=1.10,
                    help="fail if new/old exceeds this ratio")
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU backend")
    args = ap.parse_args()

    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as paddle

    names = args.ops or sorted(_CONFIGS)
    results = []
    for n in names:
        r = bench_one(paddle, n)
        results.append(r)
        print(json.dumps(r))

    if args.compare:
        old = {}
        with open(args.compare) as f:
            for line in f:
                d = json.loads(line)
                old[d["op"]] = d["us"]
        regressed = [(r["op"], old[r["op"]], r["us"]) for r in results
                     if r["op"] in old and r["us"] > old[r["op"]]
                     * args.threshold]
        for op, was, now in regressed:
            print(f"REGRESSION {op}: {was}us -> {now}us", file=sys.stderr)
        if regressed:
            sys.exit(1)


if __name__ == "__main__":
    main()
