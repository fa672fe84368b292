"""Performance observatory: per-program device-time attribution,
roofline-anchored efficiency, and the cross-run perf ledger.

PRs 3/4/8 made a single run richly observable; this package makes
performance COMPARABLE — within a step ("which compiled program did
the wall go to, and how close to the hardware floor does it run") and
across runs ("is that faster or slower than last time"):

  * **attribution.ProgramPerf** — every AOT executable dispatch
    (prefill buckets, chunks among them, and the pooled decode)
    records measured dispatch/sync wall seconds against its AOT-table
    key into registry histograms; ``snapshot()["perf"]`` and
    ``/debug/perf`` decompose a step into named programs;
  * **roofline** — the analytic decode-step HBM/FLOPs model (KV-read
    bytes per token by batch/seq/heads/layout, paged gather factor)
    plus device peak/HBM tables; joined with ``executable_cost`` it
    yields the ``serving_roofline_fraction{program}`` gauge — the
    go/no-go yardstick for ROADMAP direction #2's Pallas kernel;
  * **ledger** — the schema-versioned cross-run JSONL perf ledger
    and the robust
    median+MAD comparison ``tools/perf_diff.py`` gates CI with.

roofline.py and ledger.py are deliberately stdlib-only so the CLI
tools load them via importlib without importing paddle_tpu (no jax at
tool startup).
"""
from .attribution import (  # noqa: F401
    PERF_KEYS, PERF_PROGRAM_KEYS, PERF_SPEC_KEYS, ProgramPerf,
    build_decode_model, disabled_perf_report, disabled_spec_report,
    format_program_key,
)
from .ledger import (  # noqa: F401
    LEDGER_ROW_KEYS, MEASUREMENTS, PERF_LEDGER_SCHEMA, append_rows,
    compact, compare, config_digest, make_row, prune, read_rows,
)
from .roofline import (  # noqa: F401
    PAGED_GATHER_FACTOR,
    decode_step_model, hbm_bps_for, kv_read_bytes_per_token,
    roofline_floor,
)
