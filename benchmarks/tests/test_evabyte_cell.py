"""The ``evabyte`` cell at its tiny ``rehearse`` sizes on the CPU: the
plain reference against the program, the sound rehearsal of the whole
cell, ``correct``'s teeth (the float8 control, and a timed path broken
underneath: a window that is never compacted), the byte counts against
the arrays' own, and the configuration's keys against the catalog's row.

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
from benchmarks.planes import serve_arch

CELL = "evabyte_6p5b_pp4.longdoc_decode_resident"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEEDS = (1, 2, 3)


def _cell(rehearse=True):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    _, config, traffic = bench_run.resolve(bench, CELL, rehearse=rehearse)
    return config, traffic, serve_arch.arch_files(config["arch"])


def test_reference_agrees_with_the_program_at_rehearse_sizes():
    """Eager logits of the program's model class around the benchmark's
    weights against the reference's, every prediction head, over a
    sequence that ends mid-chunk in its third window."""
    config, _, arch = _cell()
    model = serve_arch.model_of(config)
    W, C = model["window_size"], model["chunk_size"]
    T = 2 * W + C + 1
    for seed in SEEDS:
        w = arch.weights.make(seed, model, "float32")
        net = arch.program.build_model(model, "float32", w)
        ids = np.random.default_rng(seed).integers(
            0, model["vocab_size"], size=(1, T))
        got = np.asarray(net.forward_heads(ids).value)[0]
        want = arch.reference.logits(w, jnp.asarray(ids[0], jnp.int32),
                                     model)
        assert got.shape == (T, model["num_pred_heads"],
                             model["vocab_size"])
        assert np.abs(got - np.asarray(want)).max() < 2e-5


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
    rc = bench_run.main(["--workload", CELL, "--seed", "3700000005",
                         "--seconds", "2", "--trace", "0", "--rehearse"])
    assert rc == 0, capsys.readouterr().out


def test_a_window_never_compacted_is_not_correct(monkeypatch, capsys):
    """The step loop's compaction dispatch does nothing: a finished
    window's raw entries stay where its summaries are read from, and the
    served tokens leave the reference's."""
    from paddle_tpu.serving import engine as eng

    def skip(self, snapshot):
        spec, W = self.cache_spec, self._window[0]
        for slot in snapshot:
            t = self._hpos[slot] = self._hpos[slot] + 1
            if t % W == 0:
                self.pool.shrink(slot, spec.entries(t))
    monkeypatch.setattr(eng.ServingEngine, "_after_decode", skip)
    rc = bench_run.main(["--workload", CELL, "--seed", "4", "--seconds",
                         "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and "check served_logit_gap" in out
    assert "NOT CORRECT" in out


def test_byte_counts_agree_with_the_arrays_at_the_real_sizes():
    """Arithmetic only, at the configuration's own sizes: the weights
    file, ``flops_evabyte.py`` and the program's cache spec count the
    same parameters and the same bytes an entry; a decode step's bytes
    are what PERF.md reckons; the pool the sizing asks for is 10.41 GB."""
    config, _, arch = _cell(rehearse=False)
    model, flops = serve_arch.model_of(config), arch.flops
    shapes = arch.weights.leaf_shapes(model)
    assert arch.weights.count_params(model) == 1_630_932_992 \
        == flops.total_params(model)
    assert flops.layer_params(model) == 202_391_552
    nbytes = sum(int(np.prod(s)) * 2 for s, _ in shapes.values())
    assert nbytes == 2 * 1_630_932_992
    # what a step reads of them: all but the embedding and heads 1-7
    assert nbytes - flops.step_weight_bytes(model, 2) \
        == 2 * 4096 * (320 + 7 * 320)
    sz = config["sizing"]
    spec = arch.program.serving_programs(model, "bfloat16", sz["num_slots"],
                                         sz["block_size"], 1241, 62)[0]
    assert spec.window == (2048, 16)
    assert spec.bytes_per_token == 131_072 \
        == 8 * flops.entry_bytes_per_layer(model, 2)
    assert spec.capacity(sz["max_len"]) == 3968 == 62 * sz["block_size"]
    assert [spec.shape(a, 1241, 64) for a in spec.arrays] \
        == [(8, 1241, 32, 64, 128)] * 2
    pool = 2 * int(np.prod((8, 1241, 32, 64, 128))) * 2
    assert pool == 1241 * 64 * spec.bytes_per_token
    assert 10.40e9 < pool < 10.42e9
    for t in (0, 2047, 2048, 20000, 32767):
        assert flops.entries(model, t) == spec.entries(t)
    ops, moved = flops.compact_cost(model, 2)
    assert moved == 8 * (2048 + 128) * 16384
    step = flops.decode_step_bytes(model, 20 * 2280, 2, 0.01)
    assert 9.1e9 < step < 9.3e9
    ops, nb = flops.attn_decode_cost(model, 20 * 2280, 2)
    assert ops / 197e12 < nb / 819e9    # the bytes bound the kernel


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is not on this machine")
def test_the_configuration_keeps_the_catalog_rows_keys():
    """Every number of the catalog row's ``config`` is this file's
    top-level key with the same value, except the keys in ``reduced``,
    whose published values the file states."""
    config, _, _ = _cell(rehearse=False)
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "EvaByte")
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "evabyte_6p5b_pp4")
    assert entry["source"] == row["source_url"] == config["source"]
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value
        else:
            assert config[key] == value, key
