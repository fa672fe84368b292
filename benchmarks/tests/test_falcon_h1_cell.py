"""The ``falcon_h1`` cell at its tiny ``rehearse`` sizes on the CPU: the
plain reference against the program, the sound rehearsal of the whole
cell (untraced and traced), and ``correct``'s teeth: the float8 control,
and a timed path broken underneath in each of the ways a PARALLEL hybrid
can be wrong (a slot's state not cleared on reuse, every layer reading
the first layer's keys and values, the positions not rotated, the muP
vector dropped, the attention branch left out of the sum); the byte
counts at the configuration's own sizes; each new metric reader on a
hand-made context, None where there is nothing to read.

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

CELL = "falcon_h1_34b_pp12.reasoning_decode_resident"
SEEDS = (1, 2, 3)
NEW_METRICS = ("parallel_decode_roofline", "gqa_attn_roofline",
               "ssm_branch_dev_ms_per_step", "attn_branch_dev_ms_per_step",
               "slot_state_share_pct")
ACCEPTED = ("slot_occupancy_pct", "kv_blocks_peak_pct",
            "engine_host_ms_per_step.tput", "device_idle_pct.tput",
            "host_outside_step_ms_per_step.tput",
            "health_tick_ms_per_step.tput", "hbm_peak_pct.tput",
            "decode_dev_ms.tput", "mixer_proj_dev_ms_per_step.tput",
            "ffn_dev_ms_per_step.tput", "lm_head_dev_ms_per_step.tput",
            "cache_write_dev_ms_per_step.tput", "decode_unscoped_pct.tput",
            "ssm_decode_dev_ms_per_step", "ssm_decode_roofline",
            "gqa_attn_dev_ms_per_step")


def _cell(rehearse=True):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.resolve(bench, CELL, rehearse=rehearse)
    return config, traffic, serve_arch.arch_files(config["arch"])


def test_reference_agrees_with_the_program_at_rehearse_sizes():
    """Eager logits of the program's model class around the benchmark's
    weights against the reference's: two groups, a query group of 5,
    every multiplier at its published value."""
    config, _, arch = _cell()
    model = serve_arch.model_of(config)
    assert model["mamba_n_groups"] == 2
    assert model["num_attention_heads"] \
        == 5 * model["num_key_value_heads"]
    assert model["key_multiplier"] == 0.011048543456039804
    for seed in SEEDS:
        w = arch.weights.make(seed, model, "float32")
        net = arch.program.build_model(model, "float32", w)
        ids = np.random.default_rng(seed).integers(
            0, model["vocab_size"], size=(1, 77))
        got = np.asarray(net.forward(ids).value)[0]
        want, terms = arch.reference.logits(
            w, jnp.asarray(ids[0], jnp.int32), model)
        assert np.abs(got - np.asarray(want)).max() < 2e-4
        assert np.abs(np.asarray(want)).max() > 0.3
        assert np.asarray(terms).shape == (3, 4)


def test_each_product_of_a_matrix_and_its_multipliers_is_n_0_02():
    """The weights file's rule at the configuration's own multipliers:
    every column of every matrix times what it meets has spread 0.02,
    times ``sqrt(hidden / fan_in)`` for the three matrices that write a
    branch into the residual stream."""
    config, _, arch = _cell()
    model = serve_arch.model_of(config)
    w = arch.weights.make(5, model, "float32")
    mult, gain = arch.weights.multipliers(model), \
        arch.weights.fan_in_gain(model)
    assert set(mult) == {"wemb", "head", "in_proj", "out_proj", "wqkv",
                         "wo", "wg", "wd"}
    assert set(gain) == {"out_proj", "wo", "wd"}
    real = arch.weights.fan_in_gain(serve_arch.model_of(
        _cell(rehearse=False)[0]))
    assert [round(real[k], 4) for k in ("out_proj", "wo", "wd")] \
        == [1.118, 1.4142, 0.488]
    for leaf, segments in mult.items():
        a = np.asarray(w[leaf] if leaf in w else w["layers"][leaf])
        assert sum(cols for cols, _ in segments) == a.shape[-1]
        at, want = 0, 0.02 * gain.get(leaf, 1.0)
        for cols, m in segments:
            got = (a[..., at:at + cols] * m).std()
            assert abs(got - want) < 0.15 * want, (leaf, at, got)
            at += cols
    assert abs(np.asarray(w["layers"]["wu"]).std() - 0.02) < 0.001


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
    assert "# branch balance" in out
    if trace == "1":    # the gauges' reader needs no device trace
        assert "slot_state_share_pct" in out


def _a_state_not_cleared_on_reuse(monkeypatch):
    from paddle_tpu.serving.paged import hybrid_programs as hp
    real = hp.PagedAccess.ssm_init
    monkeypatch.setattr(
        hp.PagedAccess, "ssm_init",
        lambda self, state, mi, start, b: real(self, state, mi,
                                               jnp.int32(1), b))


def _every_layer_reads_the_first_layers_cache(monkeypatch):
    """The two cache indices of a layer do not advance together: the
    state's does, the keys' and values' stays at 0."""
    from paddle_tpu.serving.paged import hybrid_programs as hp
    dec, pre = hp.PagedAccess.attn_decode, hp.PagedAccess.attn_prefill
    monkeypatch.setattr(
        hp.PagedAccess, "attn_decode",
        lambda self, state, li, *a: dec(self, state, li * 0, *a))
    monkeypatch.setattr(
        hp.PagedAccess, "attn_prefill",
        lambda self, state, li, *a: pre(self, state, li * 0, *a))


def _positions_not_rotated(monkeypatch):
    from paddle_tpu.text import falcon_h1
    monkeypatch.setattr(falcon_h1, "rope_half", lambda x, pos, theta: x)


def _the_mup_vector_dropped(monkeypatch):
    from paddle_tpu.text import falcon_h1
    real = falcon_h1.FalconH1Config.mup_vector
    monkeypatch.setattr(
        falcon_h1.FalconH1Config, "mup_vector",
        lambda self: np.full_like(real(self), self.ssm_in_multiplier))


def _the_attention_branch_left_out(monkeypatch):
    from paddle_tpu.text import falcon_h1
    monkeypatch.setattr(
        falcon_h1, "attn_branch",
        lambda cfg, p, u, positions, access, state, *a:
        (jnp.zeros(u.shape, jnp.float32), state))


@pytest.mark.parametrize("breaker", [
    _a_state_not_cleared_on_reuse,
    _every_layer_reads_the_first_layers_cache, _positions_not_rotated,
    _the_mup_vector_dropped, _the_attention_branch_left_out],
    ids=["stale_state", "one_cache_layer", "no_rotary", "no_mup",
         "no_attention"])
def test_a_broken_timed_path_is_not_correct(breaker, monkeypatch, capsys):
    breaker(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "4", "--seconds",
                         "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct": false' in out
    assert "check served_logit_gap" in out and "NOT CORRECT" in out


def test_byte_counts_agree_with_the_program_at_the_real_sizes():
    """Arithmetic only, at the configuration's own sizes: the weights
    file, ``flops_falcon_h1.py`` and the program's cache spec count the
    same parameters, the same bytes a cached token and the same bytes of
    state a slot; a decode step's bytes are what PERF.md reckons; only
    the depth is cut."""
    config, _, arch = _cell(rehearse=False)
    model, flops = serve_arch.model_of(config), arch.flops
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 72}
    assert arch.weights.count_params(model) == 5_254_594_112
    matmul = flops.total_params(model)
    assert matmul == 6 * 430_080_000 + 2 * 261_120 * 5_120
    assert 0 < arch.weights.count_params(model) - matmul < 1e-4 * matmul
    assert (flops.mamba_params(model), flops.attn_params(model),
            flops.mlp_params(model)) == (68_321_280, 31_457_280,
                                         330_301_440)
    assert flops.layer_counts(model) == (6, 0, 6)
    sz = config["sizing"]
    assert (sz["num_slots"], sz["max_len"], sz["block_size"],
            sz["async_depth"]) == (40, 6144, 256, 12)
    spec = arch.program.serving_programs(model, "bfloat16", 40, 256, 961,
                                         24)[0]
    assert spec.bytes_per_token == 12_288 \
        == 6 * flops.cache_bytes_per_token_layer(model, 2)
    assert spec.bytes_per_slot == 25_350_144 \
        == 6 * flops.state_bytes_per_slot_layer(model, 2)
    assert flops.state_bytes_per_slot_layer(model, 2) \
        == 4_194_304 + 30_720
    assert not spec.shareable and spec.state == ()
    assert [spec.shape(a, 961, 256) for a in spec.arrays] == [
        (6, 961, 4, 256, 128), (6, 961, 4, 256, 128), (6, 40, 15360),
        (6, 40, 32, 256, 128)]
    assert [a.dtype.name for a in spec.arrays] == [
        "bfloat16", "bfloat16", "bfloat16", "float32"]
    pool = 961 * 256 * spec.bytes_per_token + 40 * spec.bytes_per_slot
    assert pool == 4_037_050_368                          # 4.037 GB
    # steady state: 84-85 % of a chip of 16 GiB
    held = 2 * arch.weights.count_params(model) + pool
    assert 0.84 < held / (16 << 30) < 0.85
    # a step at 3,600 live positions a slot
    assert flops.weight_bytes_per_step(model, 2) == 7_834_828_800
    assert flops.state_bytes_per_step(model, 40, 2) == 2_028_011_520
    assert flops.cache_bytes_per_step(model, 40 * 3600, 2) \
        == 1_769_472_000
    step = flops.decode_step_bytes(model, 40 * 3600, 2, 40)
    assert step == 11_632_312_320 and 14.1 < 1e3 * step / 819e9 < 14.3
    ops, nbytes = flops.ssm_decode_cost(model, 40, 2)
    assert nbytes == 40 * (2 * 4_194_304 + 2 * 30_720 + 5120 * 2
                           + 4096 * 4)
    assert ops == 40 * (6 * 32 * 128 * 256 + 2 * 4 * 5120)
    assert ops / 197e12 < nbytes / 819e9    # the bytes bound the kernel
    ops, nbytes = flops.gqa_decode_attn_cost(model, 40 * 3600, 2)
    assert (ops, nbytes) == (40 * 3600 * 20 * 4 * 128, 40 * 3600 * 2048)
    assert ops / 197e12 < nbytes / 819e9


def test_the_configuration_keeps_the_catalog_rows_keys():
    """Every key of the catalog row's ``config`` is a top-level key of
    the configuration's file with the row's value, except the depth."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
    config, _, _ = _cell(rehearse=False)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (config[key], value) == (6, 72)
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert set(serve_arch.model_of(config)) == set(row["config"])
    for key in ("deployment", "assumed", "sizing", "correct_limits",
                "rehearse"):
        assert config[key]


# ------------------------------------------------------------ the readers
def _reader(name):
    return harness.load_module(harness.find_by_name("metrics", name),
                               "m_" + name.replace(".", "_"))


J = "jit(paged_decode)/jit(main)/while/body/closed_call/"
GAUGES = {"state_bytes_per_slot": 25_350_144, "kv_bytes_per_token": 12_288}


def _ctx(trace, monkeypatch=None):
    """100 decode steps of 40 slots at 1,000 live positions each; in the
    trace the state kernel took 0.3 s, the attention kernel 0.2 s, the
    two branches' projections 0.1 and 0.05 s, and a decode execution
    16 ms."""
    config, _, arch = _cell(rehearse=False)
    rec = serve.Rec({"prompt": np.zeros(999, np.int64), "max_new": 9}, 0.0)
    rec.stamps = [0.5, 1.5]            # the second token saw 1,000
    events = {
        "%ssm_decode_step.11 = (f32[40,32,128]) custom-call(...)":
            (0.3, "branch/ssm/ssm/scan/pallas_call"),
        "%paged_decode_attn.12 = (bf16[40,4,16,128]) custom-call(...)":
            (0.2, "branch/attn/attn/paged/pallas_call"),
        "%fusion.7 = f32[40,9248]{1,0:T(8,128)} fusion(...)":
            (0.1, "branch/ssm/ssm/in_proj/dot_general"),
        "%fusion.8 = f32[40,3584]{1,0:T(8,128)} fusion(...)":
            (0.05, "branch/attn/attn/qkv/dot_general"),
        "%fusion.9 = bf16[40,5120]{1,0:T(8,128)(2,1)} fusion(...)":
            (0.001, "branch/mix/add"),
        "%fusion.10 = f32[40,21504]{1,0:T(8,128)} fusion(...)":
            (0.5, "mlp/dot_general")}
    ops = {e: {"seconds": s, "calls": 600} for e, (s, _) in events.items()}
    programs = {"jit_paged_decode": {"calls": 100, "seconds": 1.6,
                                     "durations_s": [0.016] * 100}}
    if trace and monkeypatch is not None:
        from paddle_tpu.observability import watchdog as wd
        table = {"('decode',)": {
            "module": "jit_paged_decode",
            "instructions": {wd.instruction_key(e): J + scope
                             for e, (_, scope) in events.items()}}}
        monkeypatch.setattr(wd, "program_scopes", lambda: table)
    return {"trace": {"ops": ops, "programs": programs} if trace else None,
            "trace_bounds": (1.0, 2.0) if trace else None,
            "programs": arch.program.PROGRAMS,
            "kernels": arch.program.KERNELS, "flops": arch.flops,
            "model": serve_arch.model_of(config), "num_slots": 40,
            "weight_bytes": 2, "kv_bytes_per_value": 2,
            "peaks": harness.peaks_for("TPU v5 lite"),
            "run": {"t_open": 1.0, "t_close": 2.0, "recs": [rec],
                    "before": {"moe": dict(GAUGES), "decode_steps": 0},
                    "after": {"moe": dict(GAUGES), "decode_steps": 100}}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_a_trace_or_counters(name):
    read = _reader(name).read
    ctx = _ctx(trace=False)
    if name == "slot_state_share_pct":
        assert read(ctx) is not None       # gauges need no trace
        # a program that keeps other counters there (another model's)
        ctx["run"]["after"]["moe"] = ctx["run"]["before"]["moe"] = {
            "expert_tokens": [[1]], "experts_hit": [1], "layer_steps": [1]}
        assert read(ctx) is None
        ctx["run"]["after"]["moe"] = ctx["run"]["before"]["moe"] = None
    assert read(ctx) is None


def test_readers_against_a_hand_calculation(monkeypatch):
    ctx = _ctx(trace=True, monkeypatch=monkeypatch)
    val = {n: _reader(n).read(ctx) for n in NEW_METRICS + ACCEPTED[-3:]}
    assert val["ssm_branch_dev_ms_per_step"] == pytest.approx(4.0)
    assert val["attn_branch_dev_ms_per_step"] == pytest.approx(2.5)
    assert val["ssm_decode_dev_ms_per_step"] == pytest.approx(3.0)
    assert val["gqa_attn_dev_ms_per_step"] == pytest.approx(2.0)
    live = 40 * 1000
    state = 2 * 40 * 25_350_144
    assert val["slot_state_share_pct"] == pytest.approx(
        100 * state / (state + live * 12_288))
    attn_ms = 1e3 * 6 * live * 2048 / 819e9
    assert val["gqa_attn_roofline"] == pytest.approx(100 * attn_ms / 2.0)
    ssm_ms = 1e3 * 6 * 40 * (2 * 4_194_304 + 2 * 30_720 + 5120 * 2
                             + 4096 * 4) / 819e9
    assert val["ssm_decode_roofline"] == pytest.approx(100 * ssm_ms / 3.0)
    step_ms = 1e3 * (7_834_828_800 + state + 6 * live * 2048) / 819e9
    assert val["parallel_decode_roofline"] == pytest.approx(
        100 * step_ms / 16.0)
    assert all(0 < v < 100 for k, v in val.items() if "roofline" in k)


def test_benchmark_json_lists_the_cell_and_its_readers():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert cell["traffic"] == "reasoning_decode_resident"
    conf = next(c for c in bench["configs"]
                if c["name"] == "falcon_h1_34b_pp12")
    assert conf["reduced"] == ["num_hidden_layers"]
    assert len(conf["why"]) <= 200
    assert [c["name"] for c in bench["workloads"]
            if c["config"] == conf["name"]] == [CELL]
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
            harness.find_by_name("metrics", m["name"])
    reported = {m["name"] for m in
                bench_run.cell_metrics(bench, "per_layer", CELL)}
    assert reported == set(NEW_METRICS) | set(ACCEPTED)
    assert {m["name"] for m in
            bench_run.cell_metrics(bench, "end_to_end", CELL)} \
        == {"serve_tokens_per_s", "setup_s"}
