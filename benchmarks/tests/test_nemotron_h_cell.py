"""The ``nemotron_h`` cell at its tiny ``rehearse`` sizes on the CPU:
the plain reference against the program, the sound rehearsal of the
whole cell, and ``correct``'s teeth (the float8 control, and a timed
path broken underneath: a slot's state not cleared on reuse).

The limits used here are read off these sizes (float32 on both sides),
as the cell's own are read off the chip (PERF.md).
"""
import jax.numpy as jnp
import numpy as np

from benchmarks import harness
from benchmarks import run as bench_run
from benchmarks.planes import serve_arch

CELL = "nemotron3_nano_pp4ep2.reasoning_decode_resident"
SEEDS = (1, 2, 3)


def _cell():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.resolve(bench, CELL, rehearse=True)
    return config, traffic, serve_arch.arch_files(config["arch"])


def test_reference_agrees_with_the_program_at_rehearse_sizes():
    """Eager logits of the program's model class around the benchmark's
    weights against the reference's, all three kinds of layer, the held
    half of the experts, an expert width that is no multiple of 128."""
    config, _, arch = _cell()
    model = serve_arch.model_of(config)
    assert set(model["hybrid_override_pattern"]) == set("ME*")
    assert model["n_routed_experts"] < model["router_experts"]
    assert model["moe_intermediate_size"] % 128
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


def test_a_state_not_cleared_on_reuse_is_not_correct(monkeypatch, capsys):
    """Every prefill starts from what the slot's last owner left (the
    warm-up's requests, then sessions that ended) instead of zeros: the
    served tokens leave the reference's."""
    from paddle_tpu.serving.paged import hybrid_programs as hp
    real = hp.PagedAccess.ssm_init

    def stale(self, state, mi, start, b):
        return real(self, state, mi, jnp.int32(1), b)
    monkeypatch.setattr(hp.PagedAccess, "ssm_init", stale)
    rc = bench_run.main(["--workload", CELL, "--seed", "4", "--seconds",
                         "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct": false' in out
    assert "check served_logit_gap" in out and "NOT CORRECT" in out


def test_byte_counts_agree_with_the_program_at_the_real_sizes():
    """Arithmetic only, at the configuration's own sizes: the weights
    file, ``flops_nemotron_h.py`` and the program's cache spec count the
    same parameters, the same bytes a cached token and the same bytes of
    state a slot; a decode step's bytes are what PERF.md reckons."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, _ = bench_run.resolve(bench, CELL, rehearse=False)
    arch = serve_arch.arch_files(config["arch"])
    model, flops = serve_arch.model_of(config), arch.flops
    assert arch.weights.count_params(model) == 4_278_340_096
    matmul = flops.total_params(model)
    assert 0 < arch.weights.count_params(model) - matmul < 1e-3 * matmul
    assert flops.layer_counts(model) == (6, 5, 2)
    spec = arch.program.serving_programs(
        model, "bfloat16", 128, 256, 6145, 48)[0]
    assert spec.bytes_per_token == 2048 \
        == 2 * flops.cache_bytes_per_token_layer(model, 2)
    assert spec.bytes_per_slot == 12_804_096 \
        == 6 * flops.state_bytes_per_slot_layer(model, 2)
    assert [spec.shape(a, 6145, 256) for a in spec.arrays] == [
        (2, 6145, 2, 256, 128), (2, 6145, 2, 256, 128), (6, 128, 18432),
        (6, 128, 32, 128, 128)]
    step = flops.decode_step_bytes(model, 128 * 3000, 5 * 63.9, 2, 128)
    assert 11.5e9 < step < 12.5e9
    ops, nbytes = flops.moe_experts_cost(model, 128, 63.9, 2)
    assert ops / 197e12 < nbytes / 819e9    # the bytes bound the kernel
