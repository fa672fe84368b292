"""The ``mimo_v2`` cell at its tiny ``rehearse`` sizes on the CPU: the
plain reference against the program, the sound rehearsal of the whole
cell, and ``correct``'s teeth: the float8 control, and a timed path
broken underneath in each of the four ways the window layers can be
wrong (the sink dropped, the value scale dropped, the window's lower
edge dropped, a ring entry of another sequence seen).

The limits used here are read off these sizes (float32 on both sides),
as the cell's own are read off the chip (PERF.md).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks import run as bench_run
from benchmarks.planes import serve_arch

CELL = "mimo_v2_flash_pp8ep16.mixedlen_decode_resident"
SEEDS = (1, 2, 3)


def _cell():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.resolve(bench, CELL, rehearse=True)
    return config, traffic, serve_arch.arch_files(config["arch"])


def test_reference_agrees_with_the_program_at_rehearse_sizes():
    """Eager logits of the program's model class around the benchmark's
    weights against the reference's: both kinds of attention, two KV
    head counts, a key wider than its value, the held half of the
    experts."""
    config, _, arch = _cell()
    model = serve_arch.model_of(config)
    assert set(model["hybrid_layer_pattern"]) == {0, 1}
    assert model["num_key_value_heads"] != model["swa_num_key_value_heads"]
    assert model["head_dim"] > model["v_head_dim"]
    assert model["n_routed_experts"] < model["router_experts"]
    for seed in SEEDS:
        w = arch.weights.make(seed, model, "float32")
        net = arch.program.build_model(model, "float32", w)
        ids = np.random.default_rng(seed).integers(
            0, model["vocab_size"], size=(1, 77))
        got = np.asarray(net.forward(ids).value)[0]
        want, chosen = arch.reference.logits(
            w, jnp.asarray(ids[0], jnp.int32), model)
        assert np.abs(got - np.asarray(want)).max() < 2e-4
        # both halves of the router's experts are chosen: the held share
        # leaves part of the sum out, in both alike
        assert int(chosen.max()) >= model["n_routed_experts"]


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


def test_the_sound_rehearsal_is_correct(capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "3000000005",
                         "--seconds", "2", "--trace", "0", "--rehearse"])
    assert rc == 0, capsys.readouterr().out


def _no_sink(monkeypatch):
    from paddle_tpu.serving.paged import mixed_programs as mp
    dec, pre = mp.PagedAccess.win_decode, mp.PagedAccess.win_prefill
    monkeypatch.setattr(
        mp.PagedAccess, "win_decode",
        lambda self, state, wi, pos, q, k, v, sink:
        dec(self, state, wi, pos, q, k, v, None))
    monkeypatch.setattr(
        mp.PagedAccess, "win_prefill",
        lambda self, state, wi, start, q, k, v, positions, length, sink:
        pre(self, state, wi, start, q, k, v, positions, length, None))


def _no_value_scale(monkeypatch):
    from paddle_tpu.text import mimo_v2
    real = mimo_v2.MimoV2Config.from_hf.__func__

    def from_hf(cls, config, **over):
        cfg = real(cls, config, **over)
        cfg.value_scale = 1.0
        return cfg
    monkeypatch.setattr(mimo_v2.MimoV2Config, "from_hf",
                        classmethod(from_hf))


def _no_lower_edge(monkeypatch):
    """A prefill band's 2 W keys all count, down to 2 W - 1 back."""
    from paddle_tpu.ops import attention as attn_ops
    real = attn_ops.grouped_causal_attention
    monkeypatch.setattr(
        attn_ops, "grouped_causal_attention",
        lambda *a, window=None, **k: real(*a, window=None, **k))


def _foreign_ring_entries(monkeypatch):
    """Every ring entry counts as the sequence's own, reached or not."""
    from paddle_tpu.text import mimo_v2
    real = mimo_v2.ring_positions
    monkeypatch.setattr(mimo_v2, "ring_positions",
                        lambda last, W: jnp.abs(real(last, W)))


@pytest.mark.parametrize("breaker", [
    _no_sink, _no_value_scale, _no_lower_edge, _foreign_ring_entries],
    ids=["sink_dropped", "value_scale_dropped", "lower_edge_dropped",
         "foreign_ring_entry"])
def test_a_broken_timed_path_is_not_correct(breaker, monkeypatch, capsys):
    breaker(monkeypatch)
    rc = bench_run.main(["--workload", CELL, "--seed", "4", "--seconds",
                         "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct": false' in out
    assert "check served_logit_gap" in out and "NOT CORRECT" in out


def test_byte_counts_agree_with_the_program_at_the_real_sizes():
    """Arithmetic only, at the configuration's own sizes: the weights
    file, ``flops_mimo_v2.py`` and the program's cache spec count the
    same parameters, the same bytes a cached position and the same bytes
    of ring a slot; a decode step's bytes are what PERF.md reckons."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, _ = bench_run.resolve(bench, CELL, rehearse=False)
    arch = serve_arch.arch_files(config["arch"])
    model, flops = serve_arch.model_of(config), arch.flops
    assert arch.weights.count_params(model) == 2_931_873_280
    matmul = flops.total_params(model)
    assert 0 < arch.weights.count_params(model) - matmul < 1e-3 * matmul
    assert flops.layer_counts(model) == (2, 4, 1, 5)
    assert flops.attn_params(model, False) == 89_128_960
    assert flops.attn_params(model, True) == 94_371_840
    spec = arch.program.serving_programs(
        model, "bfloat16", 48, 256, 5377, 112)[0]
    assert spec.bytes_per_token == 5120 \
        == 2 * flops.cache_bytes_per_token_layer(model, 2)
    assert spec.bytes_per_slot == 2_621_440 \
        == 4 * flops.ring_bytes_per_slot_layer(model, 2)
    assert spec.dense_bytes_per_token == 25_600
    assert [spec.shape(a, 5377, 256) for a in spec.arrays] == [
        (2, 5377, 4, 256, 128), (2, 5377, 4, 64, 256),
        (2, 5377, 4, 256, 128), (4, 48, 8, 192, 128), (4, 48, 8, 128, 128)]
    assert flops.non_expert_weight_bytes(model, 2) == 1_680_867_328
    step = flops.decode_step_bytes(model, 580_000, 5 * 16 * 0.79, 2, 48)
    assert 7.6e9 < step < 8.4e9
    ops, nbytes = flops.moe_experts_cost(model, 48, 12.6, 2)
    assert ops / 197e12 < nbytes / 819e9    # the bytes bound the kernel
    ops, nbytes = flops.full_attn_cost(model, 580_000, 48, 2)
    assert ops / 197e12 < nbytes / 819e9
    ops, nbytes = flops.window_attn_cost(model, 48, 2)
    assert nbytes == 48 * (655_360 + 5_120 + 64 * 320 * 2)
    assert ops / 197e12 < nbytes / 819e9
