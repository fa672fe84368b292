#!/usr/bin/env python
"""Cross-run perf regression gate: compare the latest bench run
against the perf ledger's baseline and exit nonzero on regression.

A bench run appends one normalized row per (scenario, metric) to a
perf ledger (``observability.perf.append_rows``; nothing in the repo
writes one since the CPU serving bench was removed, ROADMAP D6); this
CLI reads the whole ledger, judges the LAST row of every (scenario, metric,
config_digest) group against the MEDIAN of its history with robust
thresholds (relative delta gated by a MAD noise estimate — see
paddle_tpu/observability/perf/ledger.py, loaded directly by file so
the gate starts in milliseconds without importing jax), prints the
trajectory table, and exits:

  * 0 — no regressions (clean, improvements, or first-run baselines);
  * 1 — at least one regression, each named as scenario/metric with
        its baseline, current value and threshold;
  * 2 — an explicitly given ledger path does not exist / has no rows.

A missing DEFAULT ledger exits 0 with a note: the gate must not fail
the build before the first bench run ever lands. Wired into tier-1
via tests/test_perf.py, which self-runs it against synthetic ledgers
(clean two-run → 0, planted 2x decode slowdown → 1) — the same
self-run discipline as tools/incident_report.py and
tools/chaos_sweep.py --fast.

``--prune-run RUN_ID`` / ``--prune-series SCENARIO/METRIC``
(repeatable) rewrite the ledger first, dropping a poisoned run's rows
or retiring a stale metric series (ledger.prune — the recorded triage
operation; compare() judges each series' LAST row, so a bad trailing
run keeps the gate red until triaged or outrun), then judge what's
left.

Usage: python tools/perf_diff.py [LEDGER] [--threshold F] [--mad-k K]
                                 [--scenario S] [--history N]
                                 [--prune-run R]... [--prune-series S/M]...
"""
import argparse
import importlib.util
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DEFAULT_LEDGER = os.path.join(_REPO, "chiprun_out",
                               "perf_ledger.jsonl")


def _load_ledger_module():
    path = os.path.join(_REPO, "paddle_tpu", "observability", "perf",
                        "ledger.py")
    spec = importlib.util.spec_from_file_location("_perf_ledger", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def render_table(results, history_n=5, out=sys.stdout):
    """Fixed-width trajectory table: recent history -> current, with
    the verdict per (scenario, metric)."""
    headers = ["scenario", "metric", "runs", "trajectory", "baseline",
               "current", "worse_by", "verdict"]
    rows = []
    for r in results:
        traj = " ".join(_fmt(v) for v in r["history"][-history_n:])
        worse = "-" if r["worse_by"] is None \
            else f"{r['worse_by'] * 100.0:+.1f}%"
        rows.append([r["scenario"], r["metric"], str(r["runs"]),
                     traj or "-", _fmt(r["baseline"]),
                     _fmt(r["current"]), worse, r["verdict"]])
    widths = [max(len(h), *(len(row[i]) for row in rows)) if rows
              else len(h) for i, h in enumerate(headers)]
    print("  ".join(h.ljust(w) for h, w in zip(headers, widths)),
          file=out)
    print("  ".join("-" * w for w in widths), file=out)
    for row in rows:
        print("  ".join(c.ljust(w) for c, w in zip(row, widths)),
              file=out)


def main(argv=None):
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    parser.add_argument("ledger", nargs="?", default=None,
                        help="perf ledger JSONL (default: "
                             "chiprun_out/perf_ledger.jsonl)")
    parser.add_argument("--threshold", type=float, default=0.35,
                        help="default relative-worsening threshold "
                             "(rows may carry their own)")
    parser.add_argument("--mad-k", type=float, default=3.0,
                        help="MAD multiplier of the noise gate")
    parser.add_argument("--scenario", default=None,
                        help="only judge this scenario")
    parser.add_argument("--history", type=int, default=5,
                        help="trajectory points shown per metric")
    parser.add_argument("--prune-run", action="append", default=[],
                        metavar="RUN_ID",
                        help="drop every ledger row from this run_id "
                             "before judging (triage a poisoned run, "
                             "e.g. a host-overloaded smoke run); "
                             "repeatable")
    parser.add_argument("--prune-series", action="append", default=[],
                        metavar="SCENARIO/METRIC",
                        help="drop this whole (scenario, metric) "
                             "series before judging (retire a stale "
                             "metric name); repeatable")
    args = parser.parse_args(argv)

    explicit = args.ledger is not None
    path = args.ledger or _DEFAULT_LEDGER
    if not os.path.exists(path):
        if explicit:
            print(f"perf_diff: no such ledger: {path}",
                  file=sys.stderr)
            return 2
        print(f"perf_diff: no ledger yet at {path} — nothing to "
              f"judge")
        return 0

    ledger = _load_ledger_module()
    if args.prune_run or args.prune_series:
        kept, dropped = ledger.prune(path, run_ids=args.prune_run,
                                     series=args.prune_series)
        print(f"perf_diff: pruned {dropped} row(s) from {path} "
              f"({kept} kept)")
    rows, skipped = ledger.read_rows(path)
    if args.scenario:
        rows = [r for r in rows if r["scenario"] == args.scenario]
    if not rows:
        if explicit:
            print(f"perf_diff: no ledger rows in {path}",
                  file=sys.stderr)
            return 2
        print(f"perf_diff: no rows in {path} — nothing to judge")
        return 0

    results = ledger.compare(rows,
                             default_rel_threshold=args.threshold,
                             mad_k=args.mad_k)
    print(f"perf ledger: {path}  rows={len(rows)}"
          + (f"  skipped={skipped}" if skipped else ""))
    render_table(results, history_n=args.history)

    baselines = [r for r in results if r["verdict"] == "baseline"]
    if baselines and len(baselines) == len(results):
        print(f"\nbaseline established for {len(baselines)} "
              f"(scenario, metric) series — nothing to compare yet")
    regressions = [r for r in results if r["verdict"] == "regression"]
    if regressions:
        print(f"\nREGRESSION in {len(regressions)} metric(s):")
        for r in regressions:
            worse = "-" if r["worse_by"] is None \
                else f"{r['worse_by'] * 100.0:.1f}%"
            print(f"  {r['scenario']}/{r['metric']}: "
                  f"{_fmt(r['current'])} vs baseline "
                  f"{_fmt(r['baseline'])} ({worse} worse, threshold "
                  f"{r['threshold'] * 100.0:.0f}%) "
                  f"run={r['current_run']}")
        return 1
    improved = sum(1 for r in results if r["verdict"] == "improvement")
    print(f"\nno regressions across {len(results)} series"
          + (f" ({improved} improved)" if improved else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
