"""The ``ouro`` cell at its tiny ``rehearse`` sizes on the CPU: the plain
reference against the program, the sound rehearsal of the whole cell
(untraced and traced), and ``correct``'s teeth: the float8 control, and
a timed path broken underneath in each of the ways a LOOPED model can be
wrong (every pass reading the first pass's cache entries, a token read
from the first pass, the positions not rotated); the byte counts at the
configuration's own sizes; each new metric reader on a hand-made
context, None where there is nothing to read.

The limits used here are read off these sizes (float32 on both sides),
as the cell's own are read off the chip (PERF.md).
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks import run as bench_run
from benchmarks.planes import serve, serve_arch

CELL = "ouro_2p6b.reasoning_pair_decode_resident"
SEEDS = (1, 2, 3)
NEW_METRICS = ("loop_attn_dev_ms_per_step", "loop_attn_roofline",
               "looped_decode_roofline", "loop_glue_dev_ms_per_step",
               "loop_passes_per_token", "loop_cache_x")


def _cell(rehearse=True):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.resolve(bench, CELL, rehearse=rehearse)
    return config, traffic, serve_arch.arch_files(config["arch"])


def test_reference_agrees_with_the_program_at_rehearse_sizes():
    """Eager logits of the program's model class around the benchmark's
    weights against the reference's: four passes over three layers."""
    config, _, arch = _cell()
    model = serve_arch.model_of(config)
    assert model["total_ut_steps"] == 4 and model["num_hidden_layers"] == 3
    for seed in SEEDS:
        w = arch.weights.make(seed, model, "float32")
        net = arch.program.build_model(model, "float32", w)
        ids = np.random.default_rng(seed).integers(
            0, model["vocab_size"], size=(1, 77))
        got = np.asarray(net.forward(ids).value)[0]
        want, p, at = arch.reference.logits(
            w, jnp.asarray(ids[0], jnp.int32), model)
        assert np.abs(got - np.asarray(want)).max() < 2e-4
        assert (np.asarray(at) == 3).all()       # threshold 1: the last
        assert np.abs(np.asarray(p).sum(0) - 1).max() < 1e-6


def test_float8_control_fails_where_sound_values_pass():
    """The reference's own first choices in float8 lie well below its
    float32 best (mean gap over positions), while the float32 program's
    served tokens have gap 0: the rehearsal's limit separates them."""
    config, _, arch = _cell()
    model = serve_arch.model_of(config)
    limit = config["correct_limits"]["served_logit_gap_mean"]
    for seed in SEEDS:
        w = arch.weights.make(seed, model, "float32")
        ids = jnp.asarray(np.random.default_rng(seed).integers(
            0, model["vocab_size"], size=(256,)), jnp.int32)
        _, _, first = arch.reference.score(w, ids, ids, model, "float8")
        best, at, _ = arch.reference.score(w, ids, first, model, "float32")
        assert float((best - at).mean()) > 10 * limit


@pytest.mark.parametrize("trace", ["0", "1"], ids=["untraced", "traced"])
def test_the_sound_rehearsal_is_correct(trace, capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "3000000005",
                         "--seconds", "2", "--trace", trace, "--rehearse"])
    out = capsys.readouterr().out
    assert rc == 0, out
    if trace == "1":    # the counters' readers need no device trace
        assert "loop_cache_x" in out and "loop_passes_per_token" in out


def _every_pass_reads_the_first_passes_entries(monkeypatch):
    """Entry ``r * L + l`` becomes ``l``: one (k, v) pair a layer."""
    from paddle_tpu.serving.paged import hybrid_programs as hp
    dec, pre = hp.PagedAccess.attn_decode, hp.PagedAccess.attn_prefill
    monkeypatch.setattr(
        hp.PagedAccess, "attn_decode",
        lambda self, state, li, *a: dec(self, state,
                                        li % self.cfg.num_layers, *a))
    monkeypatch.setattr(
        hp.PagedAccess, "attn_prefill",
        lambda self, state, li, *a: pre(self, state,
                                        li % self.cfg.num_layers, *a))


def _read_from_the_first_pass(monkeypatch):
    from paddle_tpu.text import ouro
    monkeypatch.setattr(ouro, "exit_pass",
                        lambda p, threshold: jnp.zeros(p.shape[1:],
                                                       jnp.int32))


def _positions_not_rotated(monkeypatch):
    from paddle_tpu.text import ouro
    monkeypatch.setattr(ouro, "rope_half", lambda x, pos, theta: x)


@pytest.mark.parametrize("breaker", [
    _every_pass_reads_the_first_passes_entries, _read_from_the_first_pass,
    _positions_not_rotated],
    ids=["one_entry_a_layer", "first_pass_read", "no_rotary"])
def test_a_broken_timed_path_is_not_correct(breaker, monkeypatch, capsys):
    breaker(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "4", "--seconds",
                         "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct": false' in out
    assert "check served_logit_gap" in out and "NOT CORRECT" in out


def test_byte_counts_agree_with_the_program_at_the_real_sizes():
    """Arithmetic only, at the configuration's own sizes: the weights
    file, ``flops_ouro.py`` and the program's cache spec count the same
    parameters and the same bytes a cached position; a decode step's
    bytes are what PERF.md reckons; nothing is cut."""
    config, _, arch = _cell(rehearse=False)
    model, flops = serve_arch.model_of(config), arch.flops
    assert config["reduced"] == []
    assert arch.weights.count_params(model) == 2_667_974_657 \
        == flops.total_params(model)
    assert flops.layer_params(model) == 51_388_416
    assert (flops.passes(model), flops.cache_layers(model)) == (4, 192)
    sz = config["sizing"]
    assert (sz["num_slots"], sz["max_len"], sz["block_size"]) \
        == (2, 2560, 64)
    spec = arch.program.serving_programs(model, "bfloat16", 2, 64, 81,
                                         40)[0]
    assert spec.bytes_per_token == 1_572_864 \
        == flops.cache_bytes_per_token(model, 2)
    assert spec.bytes_per_slot == 0 and spec.shareable
    assert [spec.shape(a, 81, 64) for a in spec.arrays] \
        == [(192, 81, 16, 64, 128)] * 2
    pool = 81 * 64 * spec.bytes_per_token
    assert pool == 8_153_726_976                      # 8.154 GB
    assert 13.48e9 < 2 * flops.total_params(model) + pool < 13.50e9
    # the stack once a PASS, the head once
    assert flops.weight_bytes_per_step(model, 2) == 2 * (
        4 * (48 * 51_388_416 + 2 * 2048) + 2048 * 49152) \
        == 19_934_511_104
    assert 24.3 < 1e3 * flops.weight_bytes_per_step(model, 2) / 819e9 < 24.4
    ops, nbytes = flops.loop_attn_cost(model, 3000, 2, 2)
    assert nbytes == (3000 + 2) * 8192 + 2 * 8192
    assert ops == 3000 * 16 * 4 * 128
    assert ops / 197e12 < nbytes / 819e9    # the bytes bound the kernel
    step = flops.decode_step_bytes(model, 3000, 2, 2)
    assert step == flops.weight_bytes_per_step(model, 2) + 192 * nbytes
    assert 24.6e9 < step < 24.7e9


def test_the_configuration_keeps_the_catalog_rows_keys():
    """Every key of the catalog row's ``config`` is a top-level key of
    the configuration's file with the row's value; nothing is reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ouro-2.6B")
    config, _, _ = _cell(rehearse=False)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    assert serve_arch.model_of(config) == row["config"]


# ------------------------------------------------------------ the readers
def _reader(name):
    return harness.load_module(harness.find_by_name("metrics", name),
                               "m_" + name.replace(".", "_"))


def _loop(tokens):
    return {"passes": 4, "cache_passes": 4,
            "exit_pass": [0, 0, 0, tokens], "passes_run": 4 * tokens,
            "gate_mass": [0.4 * tokens, 0.3 * tokens, 0.2 * tokens,
                          0.1 * tokens]}


J = "jit(paged_decode)/jit(main)/while/body/closed_call/"


def _ctx(trace, monkeypatch=None):
    """100 decode steps of 2 slots at 1,000 live positions each; in the
    trace the attention kernel took 0.5 s, the final norm and the gate
    0.002 s and a decode execution 35 ms."""
    config, _, arch = _cell(rehearse=False)
    rec = serve.Rec({"prompt": np.zeros(999, np.int64), "max_new": 9}, 0.0)
    rec.stamps = [0.5, 1.5]            # the second token saw 1,000
    norm = "%fusion.7 = bf16[2,2048]{1,0:T(2,128)(2,1)} fusion(...)"
    gate = "%fusion.8 = f32[2]{0:T(128)} fusion(...)"
    other = "%fusion.9 = bf16[2,6144]{1,0:T(2,128)(2,1)} fusion(...)"
    ops = {"%paged_decode_attn.11 = (bf16[2,16,128]) custom-call(...)":
           {"seconds": 0.5, "calls": 19200},
           norm: {"seconds": 0.0015, "calls": 400},
           gate: {"seconds": 0.0005, "calls": 400},
           other: {"seconds": 2.0, "calls": 19200}}
    programs = {"jit_paged_decode": {"calls": 100, "seconds": 3.5,
                                     "durations_s": [0.035] * 100}}
    if trace and monkeypatch is not None:
        from paddle_tpu.observability import watchdog as wd
        table = {"('decode',)": {
            "module": "jit_paged_decode",
            "instructions": {
                wd.instruction_key(norm): J + "loop/norm/mul",
                wd.instruction_key(gate): J + "loop/gate/logistic",
                wd.instruction_key(other): J + "attn/qkv/dot_general"}}}
        monkeypatch.setattr(wd, "program_scopes", lambda: table)
    return {"trace": {"ops": ops, "programs": programs} if trace else None,
            "trace_bounds": (1.0, 2.0) if trace else None,
            "programs": arch.program.PROGRAMS,
            "kernels": arch.program.KERNELS, "flops": arch.flops,
            "model": serve_arch.model_of(config), "num_slots": 2,
            "weight_bytes": 2, "kv_bytes_per_value": 2,
            "peaks": harness.peaks_for("TPU v5 lite"),
            "run": {"t_open": 1.0, "t_close": 2.0, "recs": [rec],
                    "before": {"moe": _loop(10), "decode_steps": 0},
                    "after": {"moe": _loop(210), "decode_steps": 100}}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_a_trace_or_counters(name):
    read = _reader(name).read
    ctx = _ctx(trace=False)
    if name in ("loop_passes_per_token", "loop_cache_x"):
        assert read(ctx) is not None       # counters need no trace
        # a program that keeps no loop counters (another model's)
        ctx["run"]["after"]["moe"] = ctx["run"]["before"]["moe"] = {
            "expert_tokens": [[1]], "experts_hit": [1], "layer_steps": [1]}
        assert read(ctx) is None
        ctx["run"]["after"]["moe"] = ctx["run"]["before"]["moe"] = None
    assert read(ctx) is None


def test_readers_against_a_hand_calculation(monkeypatch):
    ctx = _ctx(trace=True, monkeypatch=monkeypatch)
    val = {n: _reader(n).read(ctx) for n in NEW_METRICS}
    assert val["loop_attn_dev_ms_per_step"] == pytest.approx(5.0)
    assert val["loop_glue_dev_ms_per_step"] == pytest.approx(0.02)
    assert val["loop_passes_per_token"] == pytest.approx(4.0)
    assert val["loop_cache_x"] == 4.0
    live = 2 * 1000
    attn_ms = 1e3 * 192 * ((live + 2) * 8192 + 2 * 8192) / 819e9
    assert val["loop_attn_roofline"] == pytest.approx(100 * attn_ms / 5.0)
    step_ms = 1e3 * 19_934_511_104 / 819e9 + attn_ms
    assert val["looped_decode_roofline"] == pytest.approx(
        100 * step_ms / 35.0)
    assert all(v < 100 for k, v in val.items() if "roofline" in k)


def test_benchmark_json_lists_the_cell_and_its_readers():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert bench["workloads"][-1] is cell and len(bench["workloads"]) == 8
    conf = bench["configs"][-1]
    assert conf["name"] == "ouro_2p6b" and conf["reduced"] == []
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_METRICS]
    assert [m["name"] for m in bench["per_layer"][-6:]] \
        == list(NEW_METRICS)
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        harness.find_by_name("metrics", m["name"])
    reported = {m["name"] for m in
                bench_run.cell_metrics(bench, "per_layer", CELL)}
    assert len(reported) == 19 and reported >= {
        "decode_dev_ms.tput", "hbm_peak_pct.tput", "kv_blocks_peak_pct",
        "decode_unscoped_pct.tput", "mixer_proj_dev_ms_per_step.tput"}
    assert {m["name"] for m in
            bench_run.cell_metrics(bench, "end_to_end", CELL)} \
        == {"serve_tokens_per_s", "setup_s"}
