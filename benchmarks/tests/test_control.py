"""`correct` has teeth: the control (the reference in float8, the
nearest precision below the bfloat16 the configurations state) fails,
and a run whose timed path is broken underneath comes out not correct.

Sizes are the files' tiny ``rehearse`` sizes (a test run holds them);
the limits used against the control are read off these sizes, as the
cells' own limits are read off the chip (PERF.md).
"""
import numpy as np

from benchmarks import harness
from benchmarks import run as bench_run
from benchmarks.tools import control

SEEDS = (1, 2, 3)


def _cell(name):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    return bench_run.resolve(bench, name, rehearse=True)


def test_train_control_fails_where_sound_runs_pass():
    """Tiny-size readings (CPU, seeds 1-3): sound grad_norm_gap <=
    0.0044, float8's >= 0.033, so 0.015 separates them with room."""
    _, config, traffic = _cell("gpt2_124m.pretrain_1k")
    rows = [control.train_seed(config, traffic, s) for s in SEEDS]
    limit = 0.015
    assert max(r["sound"]["grad_norm_gap"] for r in rows) < limit
    assert min(r["control"]["grad_norm_gap"] for r in rows) > limit


def test_serve_control_fails_where_the_served_path_passes():
    """At this size every token the bf16 engine serves is the float32
    reference's first choice (gap 0), while float8's first choices lie
    up to some hundredths below it."""
    import jax.numpy as jnp
    from benchmarks import weights
    from benchmarks.reference import gpt as ref
    _, config, traffic = _cell("gpt3_1p3b.chat_steady")
    sound = [control.serve_seed(config, traffic, s, 2.0, control=False)
             for s in SEEDS]
    assert all(r["failed"] == 0 and r["served_tokens"] > 20 for r in sound)
    assert max(r["sound"]["served_logit_gap"] for r in sound) <= 1e-3
    model = config["model"]
    nh = model["num_attention_heads"]
    for seed in SEEDS:
        w = weights.gpt_weights(seed, model, config["precision"])
        ids = jnp.asarray(np.random.default_rng(seed).integers(
            0, model["vocab_size"], (config["sizing"]["max_len"],)),
            jnp.int32)
        _, _, first = ref.score(w, ids, ids, nh, control.CONTROL)
        best, at, _ = ref.score(w, ids, first, nh, "float32")
        assert float((best - at).max()) > 5e-3


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(
        monkeypatch, capsys):
    import jax.numpy as jnp
    from paddle_tpu.optimizer.optimizers import Adam

    def frozen(self, p, g):
        """Makes the optimizer's state, updates nothing."""
        shape = tuple(p.aval_shape())
        for kind in ("moment1", "moment2"):
            self._acc(kind, p, shape=shape, dtype=jnp.float32)
        for kind in ("beta1_pow", "beta2_pow"):
            self._acc(kind, p, init=lambda: jnp.ones((), jnp.float32))
    monkeypatch.setattr(Adam, "_apply_one", frozen)
    rc = bench_run.main(["--workload", "gpt2_124m.pretrain_1k", "--seed",
                         "4", "--seconds", "1", "--trace", "0",
                         "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct": false' in out
    assert "check delta_norm_gap: 1.0" in out and "NOT CORRECT" in out


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, capsys):
    from paddle_tpu.serving.engine import ServingEngine
    real = ServingEngine._read_back
    calls = {"n": 0}

    def altered(self, device_vals):
        vals = real(self, device_vals)
        calls["n"] += 1
        if calls["n"] % 7 == 0 and not isinstance(vals, tuple):
            vals = (np.asarray(vals) + 1) % 500
        return vals
    monkeypatch.setattr(ServingEngine, "_read_back", altered)
    rc = bench_run.main(["--workload", "gpt3_1p3b.chat_steady", "--seed",
                         "4", "--seconds", "3", "--trace", "0",
                         "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct": false' in out
    assert "check served_logit_gap" in out


def test_the_sound_rehearsal_is_correct(capsys):
    for cell in ("gpt2_124m.pretrain_1k", "gpt3_1p3b.chat_backlog"):
        rc = bench_run.main(["--workload", cell, "--seed", "5", "--seconds",
                             "2", "--trace", "0", "--rehearse"])
        assert rc == 0, capsys.readouterr().out
