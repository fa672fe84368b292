#!/usr/bin/env python
"""Cache observatory report: render a ``/debug/cache`` body (or a full
``snapshot()`` containing one) as the operator-facing
cache story — measured hit rate, the miss-ratio curve ("what would
0.5x/2x/4x capacity do"), the hot-prefix digest, savings attribution,
eviction churn — and judge THRASH:

  * 0 — healthy (no thrash signature, or cache telemetry disabled);
  * 1 — THRASHING: evictions >= --min-evictions AND thrash reinserts /
        evictions >= --thrash-ratio — the pool keeps evicting paths it
        immediately recomputes, i.e. capacity is below the live
        working set (the MRC table above names what more would buy);
  * 2 — input missing or not recognizable as a cache report.

Input shapes accepted (auto-detected): the ``/debug/cache`` body
itself, or any dict with a ``"cache"`` section (``/debug/state``,
``snapshot()``). Reads a file path or stdin (``-``).

Zero heavy imports (no jax, no paddle_tpu) — starts in milliseconds,
usable against a live engine:
``curl :8000/debug/cache | python tools/cache_report.py -``.
Self-run by tier-1 (tests/test_cache.py) on a healthy shared-prefix
drain (exit 0) and a planted thrash workload (exit 1), the same
discipline as tools/incident_report.py and tools/perf_diff.py.

Usage: python tools/cache_report.py [REPORT.json|-]
           [--thrash-ratio F] [--min-evictions N] [--top K]
"""
import argparse
import json
import sys


def find_cache_report(doc):
    """Locate the cache-report dict inside ``doc`` (see module doc for
    accepted shapes); None when nothing recognizable is present."""
    if not isinstance(doc, dict):
        return None
    if "enabled" in doc and "churn" in doc and "mrc" in doc:
        return doc
    cache = doc.get("cache")
    if isinstance(cache, dict) and "enabled" in cache:
        return cache
    return None


def _fmt(v, spec="{}"):
    return "-" if v is None else spec.format(v)


def _table(headers, rows, out):
    widths = [max(len(h), *(len(r[i]) for r in rows)) if rows
              else len(h) for i, h in enumerate(headers)]
    print("  ".join(h.ljust(w) for h, w in zip(headers, widths)),
          file=out)
    print("  ".join("-" * w for w in widths), file=out)
    for r in rows:
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)),
              file=out)


def render(report, top=8, out=sys.stdout):
    """Print the human-readable cache story."""
    hr = report.get("hit_rate")
    print(f"cache: accesses={report.get('accesses')} "
          f"hits={report.get('hits')} "
          f"hit_rate={_fmt(hr, '{:.2%}')} "
          f"capacity={report.get('capacity_blocks')} blocks",
          file=out)

    sampled = report.get("sampled") or {}
    if sampled:
        print(f"sampler: rate={sampled.get('rate')} "
              f"sampled_accesses={sampled.get('accesses')} "
              f"tracked={sampled.get('tracked')} "
              f"dropped={sampled.get('dropped')}", file=out)

    mrc = report.get("mrc")
    if mrc:
        print("\nmiss-ratio curve (estimated LRU hit rate by "
              "capacity):", file=out)
        rows = [[_fmt(p.get("factor"), "{}x"), str(p["blocks"]),
                 _fmt(p.get("est_hit_rate"), "{:.2%}")] for p in mrc]
        _table(["factor", "blocks", "est_hit_rate"], rows, out)

    heat = report.get("heat") or {}
    entries = (heat.get("top") or [])[:top]
    if entries:
        print(f"\nhot prefixes (top {len(entries)} of "
              f"{heat.get('indexed_blocks')} indexed blocks, "
              f"{heat.get('total_hits')} total hits):", file=out)
        rows = [[e["fp"], str(e["depth"]), str(e["hits"]),
                 str(e["tokens_saved"]), str(e["last_tick"])]
                for e in entries]
        _table(["fingerprint", "depth", "hits", "tokens_saved",
                "last_tick"], rows, out)

    savings = report.get("savings") or {}
    if savings:
        print(f"\nsavings: saved_tokens={savings.get('saved_tokens')} "
              f"est_ttft_saved_ms="
              f"{_fmt(savings.get('saved_ttft_ms'), '{:.1f}')} "
              f"per_token_prefill_ms="
              f"{_fmt(savings.get('per_token_prefill_ms'), '{:.4f}')}",
              file=out)

    churn = report.get("churn") or {}
    if churn:
        life = churn.get("block_lifetime_ms") or {}
        print(f"churn: evictions={churn.get('evictions')} "
              f"thrash_reinserts={churn.get('thrash_reinserts')} "
              f"block_lifetime_ms p50={_fmt(life.get('p50_ms'))} "
              f"p90={_fmt(life.get('p90_ms'))} "
              f"p99={_fmt(life.get('p99_ms'))}", file=out)


def thrash_verdict(report, ratio=0.5, min_evictions=8):
    """(is_thrashing, reason). Conservative: needs BOTH real eviction
    volume and a high reinsert fraction — a busy cache evicting cold
    tails is healthy."""
    churn = report.get("churn") or {}
    evictions = churn.get("evictions") or 0
    thrash = churn.get("thrash_reinserts") or 0
    if evictions >= min_evictions and thrash / evictions >= ratio:
        return True, (
            f"THRASHING: {thrash} of {evictions} evictions were "
            f"reinserted ({thrash / evictions:.0%} >= {ratio:.0%}) — "
            f"KV pool capacity is below the live prefix working set")
    return False, (
        f"healthy: {thrash} reinsert(s) over {evictions} eviction(s)")


def main(argv=None):
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    parser.add_argument("report", nargs="?", default="-",
                        help="cache report JSON path, or - for stdin")
    parser.add_argument("--thrash-ratio", type=float, default=0.5,
                        help="reinserts/evictions fraction that "
                             "means thrash (default 0.5)")
    parser.add_argument("--min-evictions", type=int, default=8,
                        help="eviction floor below which no thrash "
                             "verdict fires (default 8)")
    parser.add_argument("--top", type=int, default=8,
                        help="hot prefixes shown (default 8)")
    args = parser.parse_args(argv)

    try:
        if args.report == "-":
            doc = json.load(sys.stdin)
        else:
            with open(args.report) as f:
                doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cache_report: cannot read {args.report}: {e}",
              file=sys.stderr)
        return 2

    report = find_cache_report(doc)
    if report is None:
        print("cache_report: no cache section found in input",
              file=sys.stderr)
        return 2
    if not report.get("enabled"):
        print("cache observatory disabled on this engine — "
              "nothing to judge")
        return 0

    render(report, top=args.top)
    thrashing, reason = thrash_verdict(
        report, ratio=args.thrash_ratio,
        min_evictions=args.min_evictions)
    print(f"\n{reason}")
    return 1 if thrashing else 0


if __name__ == "__main__":
    sys.exit(main())
