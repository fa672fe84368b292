"""MFU ceiling analysis from a perfetto trace + sweep artifact.

Digests the XPlane/perfetto capture that `GPT_PROFILE_DIR` (see
tools/baseline_bench.py, emitted by the O2_nf_profiled config of
tools/gpt_mfu_sweep.py) writes, into the per-step device-time breakdown
the round-5 deliverable asks for ("profile-backed ceiling analysis"):
which fraction of the step is MXU matmul work vs Pallas kernels vs
data movement vs host gaps — i.e. where the non-MFU time actually goes.

Usage: python tools/mfu_analysis.py [profile_dir] [n_steps]
  profile_dir defaults to chiprun_out/gpt_profile, n_steps 5.
"""
import glob
import gzip
import json
import os
import re
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BUCKETS = [
    ("matmul (MXU)", re.compile(r"dot|conv|einsum|gemm|matmul", re.I)),
    ("pallas/mosaic kernels", re.compile(
        r"custom.?call|mosaic|flash|fused_ce|pallas", re.I)),
    ("collectives", re.compile(
        r"all.?reduce|all.?gather|reduce.?scatter|collective|permute",
        re.I)),
    ("data movement", re.compile(
        r"copy|transpose|reshape|broadcast|concat|slice|gather|scatter|"
        r"pad|convert|bitcast", re.I)),
    ("elementwise/fusion", re.compile(r"fusion|loop|add|mul|select", re.I)),
]


def load_events(profile_dir):
    files = sorted(glob.glob(os.path.join(
        profile_dir, "**", "perfetto_trace.json.gz"), recursive=True))
    if not files:
        raise SystemExit(f"no perfetto_trace.json.gz under {profile_dir}")
    with gzip.open(files[-1]) as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


def main():
    profile_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        _ROOT, "chiprun_out", "gpt_profile")
    n_steps = int(sys.argv[2]) if len(sys.argv) > 2 else 5
    evs = load_events(profile_dir)

    # thread/process name tables
    names = {}
    for e in evs:
        if e.get("ph") == "M" and e.get("name") in ("thread_name",
                                                    "process_name"):
            key = (e.get("pid"), e.get("tid"), e["name"])
            names[key] = e.get("args", {}).get("name", "")

    # aggregate complete events per thread
    per_thread = {}
    for e in evs:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        k = (e.get("pid"), e.get("tid"))
        agg = per_thread.setdefault(k, {"total": 0.0, "ops": {}})
        agg["total"] += e["dur"]
        agg["ops"][e["name"]] = agg["ops"].get(e["name"], 0.0) + e["dur"]

    if not per_thread:
        raise SystemExit("no complete events in trace")

    # device lanes: prefer threads whose process/thread name mentions
    # TPU/device; fall back to the busiest thread
    def lane_name(k):
        return (names.get((k[0], k[1], "thread_name"), "") + " / "
                + names.get((k[0], None, "process_name"),
                            names.get((k[0], 0, "process_name"), "")))

    device = {k: v for k, v in per_thread.items()
              if re.search(r"tpu|device|xla", lane_name(k), re.I)}
    if not device:
        busiest = max(per_thread, key=lambda k: per_thread[k]["total"])
        device = {busiest: per_thread[busiest]}

    ops = {}
    for v in device.values():
        for name, dur in v["ops"].items():
            ops[name] = ops.get(name, 0.0) + dur
    total_us = sum(ops.values())

    buckets = {label: 0.0 for label, _ in _BUCKETS}
    buckets["other"] = 0.0
    for name, dur in ops.items():
        for label, pat in _BUCKETS:
            if pat.search(name):
                buckets[label] += dur
                break
        else:
            buckets["other"] += dur

    print(json.dumps({
        "profile_dir": os.path.relpath(profile_dir, _ROOT),
        "device_lanes": [lane_name(k) for k in device],
        "device_time_ms_per_step": round(total_us / 1e3 / n_steps, 3),
        "breakdown_ms_per_step": {
            k: round(v / 1e3 / n_steps, 3)
            for k, v in sorted(buckets.items(), key=lambda x: -x[1])},
        "top_ops_ms_per_step": {
            k: round(v / 1e3 / n_steps, 3)
            for k, v in sorted(ops.items(), key=lambda x: -x[1])[:15]},
    }, indent=1))


if __name__ == "__main__":
    main()
