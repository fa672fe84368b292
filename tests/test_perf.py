"""Performance observatory (paddle_tpu.observability.perf): per-
program device-time attribution, the decode-step roofline model, the
cross-run perf ledger, and the tools/perf_diff.py regression gate.

Acceptance criteria pinned here: a two-bucket + chunked + decode
drain attributes its measured time to distinct program keys whose sum
is tolerance-pinned against the serving/step span total; a synthetic
ledger with a planted 2x decode slowdown makes perf_diff exit 1 naming the (scenario, metric); a clean two-run
ledger exits 0 (the tier-1 CI self-run, mirroring incident_report /
chaos_sweep); a single-row ledger is a baseline, exit 0.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import perf as perf_mod
from paddle_tpu.observability.perf import (
    PERF_LEDGER_SCHEMA, append_rows, compare, config_digest,
    decode_step_model, disabled_perf_report, format_program_key,
    hbm_bps_for, kv_read_bytes_per_token, make_row, read_rows,
    roofline_floor,
)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PERF_DIFF = os.path.join(_ROOT, "tools", "perf_diff.py")


def _model(seed=7):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# ------------------------------------------------------ roofline model

def test_roofline_floor_bound_switch():
    # 1e6 flops at 1e6 flop/s = 1s; 10 bytes at 1e6 B/s = trivial
    t, bound = roofline_floor(1e6, 10, 1e6, 1e6)
    assert t == pytest.approx(1.0) and bound == "flops"
    t, bound = roofline_floor(10, 1e6, 1e6, 1e6)
    assert t == pytest.approx(1.0) and bound == "hbm"
    # missing terms drop out; nothing known -> (None, None)
    t, bound = roofline_floor(1e6, None, 1e6, 1e6)
    assert t == pytest.approx(1.0) and bound == "flops"
    assert roofline_floor(None, None, 1e6, 1e6) == (None, None)
    assert roofline_floor(1e6, 1e6, None, None) == (None, None)


def test_kv_read_bytes_scales_and_paged_gather_tax():
    inplace = dict(kv_bytes=2, layout="paged_pallas")
    base = kv_read_bytes_per_token(128, 12, 12, 64, **inplace)
    assert base == 2 * 12 * 12 * 64 * 128 * 2
    # linear in kv_len and heads
    assert kv_read_bytes_per_token(256, 12, 12, 64, **inplace) \
        == 2 * base
    assert kv_read_bytes_per_token(128, 12, 24, 64, **inplace) \
        == 2 * base
    # the XLA-composed gather (the default layout: the one every
    # backend can run) pays the gather materialization
    paged = kv_read_bytes_per_token(128, 12, 12, 64, kv_bytes=2)
    assert paged == perf_mod.PAGED_GATHER_FACTOR * base


def test_decode_step_model_accounting():
    m = decode_step_model(batch=8, kv_len=1024, num_layers=12,
                          num_heads=12, head_dim=64, n_params=124e6,
                          param_bytes=2, kv_bytes=2,
                          layout="paged_pallas",
                          peak_flops=197e12, hbm_bps=819e9)
    assert m["bytes_total"] == pytest.approx(
        m["kv_read_bytes"] + m["kv_write_bytes"]
        + m["param_read_bytes"])
    assert m["kv_read_bytes"] == 8 * m["kv_read_bytes_per_token"]
    # decode is memory-bound: intensity far below the ~240 flops/byte
    # ridge of a v5e, so the floor is the HBM term
    assert m["arithmetic_intensity"] < 10
    assert m["bound"] == "hbm"
    assert m["floor_s"] == pytest.approx(m["bytes_total"] / 819e9)
    paged = decode_step_model(batch=8, kv_len=1024, num_layers=12,
                              num_heads=12, head_dim=64,
                              n_params=124e6, param_bytes=2,
                              kv_bytes=2, layout="paged_xla",
                              peak_flops=197e12, hbm_bps=819e9)
    assert paged["bytes_total"] > m["bytes_total"]
    assert paged["floor_s"] > m["floor_s"]
    # no device facts -> floor unknown, traffic model still reported
    blind = decode_step_model(batch=8, kv_len=1024, num_layers=12,
                              num_heads=12, head_dim=64,
                              n_params=124e6)
    assert blind["floor_s"] is None and blind["bound"] is None
    assert blind["bytes_total"] > 0


def test_hbm_table_and_env_override(monkeypatch):
    assert hbm_bps_for("TPU v5e chip") == 819e9
    assert hbm_bps_for("TPU v5 lite") == 819e9
    assert hbm_bps_for("TPU v4") == 1228e9
    assert hbm_bps_for("cpu") is None
    monkeypatch.setenv("PADDLE_TPU_HBM_BPS", "123e9")
    assert hbm_bps_for("cpu") == 123e9


@pytest.mark.parametrize("lookup", ["hbm", "flops"])
def test_unknown_tpu_kind_raises_never_priced_as_v5e(monkeypatch, lookup):
    """A TPU the peak tables do not know is an error — not a v5e."""
    from paddle_tpu.serving.engine import _peak_flops_for
    fn, env = {"hbm": (hbm_bps_for, "PADDLE_TPU_HBM_BPS"),
               "flops": (_peak_flops_for, "PADDLE_TPU_PEAK_FLOPS")}[lookup]
    monkeypatch.delenv(env, raising=False)
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        fn("TPU v9 hyper")
    monkeypatch.setenv(env, "1e12")      # the stated override covers it
    assert fn("TPU v9 hyper") == 1e12


def test_cpu_engine_reports_no_roofline_fraction(monkeypatch):
    """No peaks are known for the CPU: every device-referenced fraction
    is None (never computed against a reference chip), the measured
    times still report."""
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_HBM_BPS", raising=False)
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    eng.add_request(np.arange(1, 6, dtype=np.int64), max_new_tokens=4)
    eng.run()
    rep = eng.metrics.perf_report()
    assert rep["device"]["device_peak"] is False
    assert rep["device"]["device_hbm"] is False
    assert rep["device"]["peak_flops"] is None
    dec = rep["programs"]["decode"]
    assert dec["avg_ms"] > 0
    assert dec["roofline_fraction"] is None
    assert dec["roofline_floor_ms"] is None
    assert rep["decode_roofline"]["achieved_fraction"] is None
    assert rep["decode_roofline"]["model"]["floor_s"] is None
    eng.close()


def test_gpt_roofline_cli_decode_mode():
    """tools/gpt_roofline.py --decode: the ROADMAP direction-#2
    decode-step HBM model, the XLA gather vs the in-place kernel,
    with the gather tax as a number — and the train-step default
    output unchanged."""
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools",
                                      "gpt_roofline.py"),
         "--decode", "8", "1024"],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout.strip())
    assert set(out) == {"config", "paged_xla", "paged_pallas",
                        "pallas_vs_paged_xla_x"}
    assert out["paged_xla"]["bound"] == "hbm"
    # the Pallas paged-kernel column: gather tax gone, one direct
    # read of the K/V; the modelled win is the whole tax
    assert out["paged_xla"]["kv_read_bytes_per_token"] \
        == 3.0 * out["paged_pallas"]["kv_read_bytes_per_token"]
    assert out["paged_pallas"]["kv_read_bytes_per_token"] \
        == 2 * 12 * 12 * 64 * 1024 * 2
    assert out["paged_pallas"]["gather_factor"] == 1.0
    assert out["pallas_vs_paged_xla_x"] > 1.5
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools",
                                      "gpt_roofline.py"), "4", "512"],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr
    lines = [json.loads(ln) for ln in res.stdout.splitlines()]
    assert len(lines) == 2
    assert all("step_floor_ms_unfused_head" in ln for ln in lines)


# ------------------------------------------- per-program attribution

def test_format_program_key():
    assert format_program_key(("decode",)) == "decode"
    assert format_program_key(("paged_prefill", 32)) \
        == "paged_prefill/b32"
    assert format_program_key(("paged_spec_verify",)) \
        == "paged_spec_verify"
    assert format_program_key("decode") == "decode"


def _drive(eng, rs, specs):
    for n, k in specs:
        eng.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                        max_new_tokens=k)
    eng.run()


def test_program_attribution_sums_to_step_total(monkeypatch):
    # the CPU has no peaks of its own; state some so the roofline
    # join (cost x measured wall x peaks) is exercised
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_BPS", "819e9")
    """Satellite acceptance: a two-bucket prefill + chunked + decode
    drain yields DISTINCT program keys whose summed measured time is
    tolerance-pinned against the serving/step span total. Measured
    over a WARM drain (deltas between reports), so compile time never pollutes the comparison."""
    m = _model()
    eng = ServingEngine(m, num_slots=2, bucket_min=8,
                        prefill_chunk=12)
    rs = np.random.RandomState(0)
    # buckets 8 (len 5/6) and 16 (len 9), plus a chunked prompt (20
    # > prefill_chunk) and enough decode to dominate
    wave = [(5, 6), (9, 5), (20, 4), (6, 5)]
    _drive(eng, rs, wave)                  # warmup: compiles
    eng.declare_warmup()
    r0 = eng.metrics.perf_report()
    spans0 = dict(eng.metrics.span_s)
    _drive(eng, rs, wave)                  # warm, zero-compile drain
    r1 = eng.metrics.perf_report()
    spans1 = dict(eng.metrics.span_s)

    progs = r1["programs"]
    expect = {"decode", "paged_prefill/b8", "paged_prefill/b12",
              "paged_prefill/b16"}
    assert expect <= set(progs), progs.keys()
    for entry in progs.values():
        assert entry["dispatches"] > 0 and entry["total_s"] > 0

    def delta(key):
        return spans1.get(key, 0.0) - spans0.get(key, 0.0)

    attributed = r1["attributed_s"] - r0["attributed_s"]
    step_total = delta("serving/step")
    span_sum = (sum(delta(k) for k in spans1
                    if k.endswith("_dispatch"))
                + delta("serving/sync"))
    assert attributed > 0
    # containment: every attributed second was measured inside the
    # step span (dispatch/sync legs are strict sub-regions)
    assert attributed <= step_total
    # correspondence with the span counters that time the same code
    # regions (the spans additionally cover flight-recorder calls, so
    # they upper-bound the tighter per-program measurement)
    assert attributed <= span_sum * 1.05 + 1e-4
    assert attributed >= span_sum * 0.5
    # the tolerance pin on "the step decomposes into programs": on a
    # warm drain the dispatch+sync legs carry the device work, the
    # rest of the step is host bookkeeping
    assert attributed >= 0.2 * step_total
    # the roofline join is live for decode
    dec = progs["decode"]
    assert dec["roofline_fraction"] is not None
    assert dec["bound"] in ("hbm", "flops")
    assert r1["decode_roofline"]["model"]["layout"] == "paged_xla"
    eng.close()


def test_disabled_perf_report_shape():
    rep = disabled_perf_report()
    assert rep["enabled"] is False and rep["programs"] == {}
    assert set(rep) == set(perf_mod.PERF_KEYS)


# ------------------------------------------------------- perf ledger

def _row(scenario, metric, value, ts, direction="higher_better",
         thr=None, digest="cfg0"):
    return make_row(timestamp=ts, run_id=f"run_{ts}", source="test",
                    scenario=scenario, metric=metric, value=value,
                    unit="x", direction=direction,
                    config_digest=digest, rel_threshold=thr,
                    device="cpu")


def test_make_row_validates():
    r = _row("s", "m", 1.5, "t0")
    assert r["schema"] == PERF_LEDGER_SCHEMA and r["value"] == 1.5
    with pytest.raises(ValueError):
        _row("s", "m", float("nan"), "t0")
    with pytest.raises(ValueError):
        _row("s", "m", 1.0, "t0", direction="sideways_better")
    with pytest.raises(ValueError):
        _row("", "m", 1.0, "t0")


def test_make_row_measurement_marker():
    """Optional writer-declared provenance: deterministic counter
    metrics are marked so zero cross-run variance reads as by-design,
    not as a computed constant that slipped into the gated ledger."""
    assert "measurement" not in _row("s", "m", 1.0, "t0")
    r = make_row(timestamp="t0", run_id="r", source="test",
                 scenario="s", metric="m", value=1.0, unit="x",
                 direction="higher_better", config_digest="c",
                 device="cpu", measurement="deterministic")
    assert r["measurement"] == "deterministic"
    with pytest.raises(ValueError):
        make_row(timestamp="t0", run_id="r", source="test",
                 scenario="s", metric="m", value=1.0, unit="x",
                 direction="higher_better", config_digest="c",
                 device="cpu", measurement="vibes")


def test_ledger_roundtrip_tolerates_junk(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    append_rows(path, [_row("s", "m", 1.0, "t0")])
    append_rows(path, [_row("s", "m", 1.1, "t1")])
    with open(path, "a") as fh:
        fh.write("not json at all\n")
        fh.write('{"schema": "foreign/v9", "value": 3}\n')
    rows, skipped = read_rows(path)
    assert [r["value"] for r in rows] == [1.0, 1.1]
    assert skipped == 2
    # a row missing required keys is rejected BEFORE anything lands
    with pytest.raises(ValueError):
        append_rows(path, [{"schema": PERF_LEDGER_SCHEMA,
                            "value": 2.0}])
    assert read_rows(path)[0] == rows


def test_config_digest_isolates_configs():
    a = config_digest({"requests": 72, "specs": [(3, 6)]})
    b = config_digest({"requests": 96, "specs": [(3, 6)]})
    assert a != b and a == config_digest(
        {"specs": [(3, 6)], "requests": 72})
    # rows under different digests never compare: both stay baselines
    rows = [_row("s", "m", 1.0, "t0", digest=a),
            _row("s", "m", 99.0, "t1", digest=b)]
    results = compare(rows)
    assert [r["verdict"] for r in results] == ["baseline", "baseline"]


def test_compare_verdicts_direction_and_noise():
    # stable history, current within threshold -> ok
    rows = [_row("s", "tps", v, f"t{i}")
            for i, v in enumerate([100.0, 102.0, 98.0, 101.0])]
    (res,) = compare(rows)
    assert res["verdict"] == "ok" and res["baseline"] == 100.0
    # higher_better collapse -> regression
    (res,) = compare(rows[:-1] + [_row("s", "tps", 40.0, "t9")])
    assert res["verdict"] == "regression"
    assert res["worse_by"] == pytest.approx(0.6)
    # lower_better: the same numeric move flips verdict
    lrows = [_row("s", "ms", v, f"t{i}", direction="lower_better")
             for i, v in enumerate([100.0, 102.0, 98.0, 40.0])]
    (res,) = compare(lrows)
    assert res["verdict"] == "improvement"
    (res,) = compare(lrows[:-1] + [_row("s", "ms", 250.0, "t9",
                                        direction="lower_better")])
    assert res["verdict"] == "regression"
    # the MAD noise gate: a wildly-noisy history widens its own gate,
    # so a move that clears the relative threshold but sits inside
    # the historical spread does NOT flag
    noisy = [_row("s", "tps", v, f"t{i}", thr=0.2)
             for i, v in enumerate([100.0, 40.0, 160.0, 45.0, 155.0])]
    noisy.append(_row("s", "tps", 70.0, "t9", thr=0.2))
    (res,) = compare(noisy)
    assert res["verdict"] == "ok"       # 30% worse, but inside noise


def test_compact_bounds_series_and_preserves_verdicts(tmp_path):
    """ISSUE 11 satellite: --ledger-keep compaction keeps the newest
    N rows per (scenario, metric, config_digest) series, drops junk,
    rewrites atomically — and compare() verdicts are unchanged."""
    from paddle_tpu.observability.perf import compact

    path = str(tmp_path / "ledger.jsonl")
    # a stable series with a regressed head, an ok series, and a
    # second config digest that must stay isolated
    stable = [_row("s", "tps", v, f"t{i}")
              for i, v in enumerate([100.0, 101.0, 99.0, 100.0,
                                     102.0, 98.0, 100.0])]
    regressed = stable + [_row("s", "tps", 40.0, "t9")]
    other = [_row("o", "ms", v, f"t{i}", direction="lower_better")
             for i, v in enumerate([10.0, 11.0, 10.5, 10.2])]
    foreign = [_row("s", "tps", 77.0, "t5", digest="cfgX")]
    append_rows(path, regressed + other + foreign)
    with open(path, "a") as fh:
        fh.write("junk line\n")
    before = {(r["scenario"], r["metric"], r["config_digest"]):
              r["verdict"] for r in compare(read_rows(path)[0])}
    kept, dropped = compact(path, keep_last=4)
    rows, skipped = read_rows(path)
    assert skipped == 0                       # junk gone for good
    assert kept == len(rows) == 4 + 4 + 1     # capped per series
    assert dropped == (len(regressed) - 4) + 1  # overflow + junk
    # every series keeps its NEWEST rows in append order
    s_rows = [r["value"] for r in rows
              if r["scenario"] == "s" and r["config_digest"] == "cfg0"]
    assert s_rows == [102.0, 98.0, 100.0, 40.0]
    after = {(r["scenario"], r["metric"], r["config_digest"]):
             r["verdict"] for r in compare(rows)}
    assert after == before                     # verdicts unchanged
    assert after[("s", "tps", "cfg0")] == "regression"
    assert after[("o", "ms", "cfg0")] == "ok"
    assert after[("s", "tps", "cfgX")] == "baseline"
    # a second compaction at the same keep is a no-op
    assert compact(path, keep_last=4) == (9, 0)
    with pytest.raises(ValueError):
        compact(path, keep_last=0)


def test_ledger_prune_runs_and_series(tmp_path):
    """Triage knob: prune retires a poisoned run's rows (compare()
    judges each series' LAST row, so a bad trailing run keeps the
    gate red) and whole stale series, atomically, junk dropped."""
    from paddle_tpu.observability.perf import prune

    path = str(tmp_path / "ledger.jsonl")
    healthy = [_row("s", "tps", v, f"t{i}")
               for i, v in enumerate([100.0, 101.0, 99.0])]
    poisoned = [_row("s", "tps", 40.0, "t9"),      # run_t9: red head
                _row("o", "ms", 9.0, "t9", direction="lower_better")]
    stale = [_row("old", "gone_x", v, f"t{i}")
             for i, v in enumerate([1.0, 2.0])]
    append_rows(path, healthy + stale + poisoned)
    with open(path, "a") as fh:
        fh.write("junk line\n")
    (res,) = [r for r in compare(read_rows(path)[0])
              if r["metric"] == "tps"]
    assert res["verdict"] == "regression"
    kept, dropped = prune(path, run_ids=["run_t9"],
                          series=["old/gone_x"])
    rows, skipped = read_rows(path)
    assert skipped == 0                        # junk gone for good
    assert kept == len(rows) == len(healthy)
    assert dropped == len(poisoned) + len(stale) + 1
    assert all(r["run_id"] != "run_t9" for r in rows)
    assert all(r["scenario"] != "old" for r in rows)
    # the survivor series is healthy again: its last row is clean
    (res,) = [r for r in compare(rows) if r["metric"] == "tps"]
    assert res["verdict"] == "ok"
    # no-match prune is a no-op; malformed series specs are rejected
    assert prune(path, run_ids=["run_nope"]) == (len(healthy), 0)
    with pytest.raises(ValueError):
        prune(path, series=["no-slash"])


# ------------------------------------------------- perf_diff CLI gate

def _run_diff(path, *extra):
    return subprocess.run(
        [sys.executable, _PERF_DIFF, path, *extra],
        capture_output=True, text=True, timeout=60)


def test_perf_diff_clean_two_run_ledger_exits_zero(tmp_path):
    """The tier-1 CI self-run (mirrors incident_report/chaos_sweep):
    two consecutive runs within noise must NOT false-positive."""
    path = str(tmp_path / "ledger.jsonl")
    for ts, jitter in (("t0", 1.0), ("t1", 1.04)):
        append_rows(path, [
            _row("headline", "tokens_per_sec", 1200.0 * jitter, ts),
            _row("overload", "goodput_improvement", 4.2 / jitter, ts),
            _row("perf", "decode_avg_ms", 0.31 * jitter, ts,
                 direction="lower_better", thr=0.5),
        ])
    res = _run_diff(path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "no regressions" in res.stdout
    assert "headline" in res.stdout and "tokens_per_sec" in res.stdout


def test_perf_diff_planted_decode_slowdown_exits_one(tmp_path):
    """A planted 2x decode slowdown must exit 1 and NAME the
    offending (scenario, metric) — while the healthy neighbors stay
    quiet."""
    path = str(tmp_path / "ledger.jsonl")
    for i, ts in enumerate(["t0", "t1", "t2"]):
        append_rows(path, [
            _row("headline", "tokens_per_sec", 1200.0 + i, ts),
            _row("perf", "decode_avg_ms", 0.30 + 0.01 * i, ts,
                 direction="lower_better", thr=0.5),
        ])
    append_rows(path, [
        _row("headline", "tokens_per_sec", 1201.0, "t3"),
        _row("perf", "decode_avg_ms", 0.62, "t3",           # 2x slower
             direction="lower_better", thr=0.5),
    ])
    res = _run_diff(path)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "REGRESSION" in res.stdout
    assert "perf/decode_avg_ms" in res.stdout
    assert "headline/tokens_per_sec" not in res.stdout.split(
        "REGRESSION")[1]


def test_perf_diff_single_row_is_baseline_exit_zero(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    append_rows(path, [_row("headline", "tokens_per_sec", 1200.0,
                            "t0")])
    res = _run_diff(path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "baseline" in res.stdout
    # an explicitly named missing ledger is an error (exit 2); the
    # default path missing is not (pre-first-bench builds must pass)
    res = _run_diff(str(tmp_path / "nope.jsonl"))
    assert res.returncode == 2


def test_perf_diff_prune_run_clears_planted_regression(tmp_path):
    """--prune-run retires a poisoned trailing run (e.g. a host-
    overloaded smoke run) and judges what's left — the recorded
    triage operation, not a hand edit of the ledger."""
    path = str(tmp_path / "ledger.jsonl")
    for i, ts in enumerate(["t0", "t1", "t2"]):
        append_rows(path, [
            _row("headline", "tokens_per_sec", 1200.0 + i, ts),
            _row("perf", "decode_avg_ms", 0.30 + 0.01 * i, ts,
                 direction="lower_better", thr=0.5)])
    append_rows(path, [                        # the overloaded run
        _row("headline", "tokens_per_sec", 300.0, "t9"),
        _row("perf", "decode_avg_ms", 1.4, "t9",
             direction="lower_better", thr=0.5)])
    assert _run_diff(path).returncode == 1
    res = _run_diff(path, "--prune-run", "run_t9")
    assert res.returncode == 0, res.stdout + res.stderr
    assert "pruned 2 row(s)" in res.stdout
    assert "no regressions" in res.stdout
    # the prune is durable: a re-judge without flags stays green
    assert _run_diff(path).returncode == 0
    # --prune-series retires a stale (scenario, metric) series
    res = _run_diff(path, "--prune-series", "perf/decode_avg_ms")
    assert res.returncode == 0
    assert "decode_avg_ms" not in res.stdout.split("pruned")[1]
