"""The serving plane for any architecture (``planes/serve_arch.py``),
its ``deepseek_v3`` files, the ``resident_decode`` generator and the new
metric readers, at ``--rehearse`` sizes on the CPU."""
import json
import os
import time

import numpy as np
import pytest

from benchmarks import harness
from benchmarks import run as bench_run
from benchmarks.generators import resident_decode
from benchmarks.planes import serve, serve_arch

CELL = "kanana2_30b_a3b_pp8.longctx_decode_resident"
CONFIG = harness.load_json(harness.HERE, "configs",
                           "kanana2_30b_a3b_pp8.json")
TRAFFIC = harness.load_json(harness.HERE, "traffic",
                            "longctx_decode_resident.json")
MODEL = serve_arch.model_of(CONFIG)
ARCH = serve_arch.arch_files("deepseek_v3")


# ----------------------------------------------------- the configuration
def test_config_keeps_every_published_width():
    want = {"hidden_size": 2048, "num_attention_heads": 32,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "intermediate_size": 6144, "moe_intermediate_size": 768,
            "n_routed_experts": 128, "num_experts_per_tok": 6,
            "n_shared_experts": 2, "vocab_size": 128256,
            "routed_scaling_factor": 2.448, "first_k_dense_replace": 1,
            "num_hidden_layers": 6}
    assert {k: MODEL[k] for k in want} == want
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if "kanana-2-30b-a3b-instruct-2601" in line)
        assert CONFIG["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items()
                   if CONFIG.get(k, "missing") != v}
        assert differs == set(CONFIG["reduced"])


# --------------------------------------- operations and bytes from shapes
def test_counts_against_hand_calculations_at_the_published_sizes():
    f = ARCH.flops
    # W_q 2048x6144 + W_kva 2048x576 + W_kvb 512x8192 + W_o 4096x2048
    assert f.attn_params(MODEL) == 12582912 + 1179648 + 4194304 + 8388608 \
        == 26345472
    assert f.expert_params(MODEL) == 3 * 2048 * 768 == 4718592
    assert f.cache_bytes_per_token_layer(MODEL, 2) == (512 + 64) * 2 == 1152
    assert f.shared_params(MODEL) == 9437184
    assert f.router_params(MODEL) == 262144
    assert f.dense_mlp_params(MODEL) == 37748736
    # every weight but the norms' gains and the router's bias
    gains = 6 * (2048 * 2 + 512) + 2048 + 5 * 128
    assert f.total_params(MODEL) + gains \
        == ARCH.weights.count_params(MODEL) == 3789584000
    # one cached position, one layer: 32 heads x (2x576 + 2x512)
    assert f.mla_decode_attn_cost(MODEL, 1, 2) == (69632, 1152)
    ops, nbytes = f.moe_experts_cost(MODEL, 32, 100, 2)
    assert ops == 2 * 32 * 6 * 4718592
    assert nbytes == 100 * 4718592 * 2 + 32 * 2048 * 6
    fixed = 2 * (6 * 26345472 + 37748736 + 5 * (9437184 + 262144)
                 + 2048 * 128256)
    assert f.non_expert_weight_bytes(MODEL, 2) == fixed
    assert f.decode_step_bytes(MODEL, 320000, 500, 2) \
        == fixed + 500 * 4718592 * 2 + 320000 * 6 * 1152


def test_weights_repeat_by_seed_and_differ_between_seeds():
    small = serve_arch.model_of(harness.rehearsed(CONFIG))
    a = ARCH.weights.make(2 ** 31 + 5, small, "float32")
    b = ARCH.weights.make(2 ** 31 + 5, small, "float32")
    c = ARCH.weights.make(6, small, "float32")
    assert np.array_equal(a["moe"]["wq"], b["moe"]["wq"])
    assert not np.array_equal(a["moe"]["wq"], c["moe"]["wq"])
    assert a["experts"]["gate"].shape == (2 * 8, 128, 128)
    assert float(np.abs(a["moe"]["router_b"]).max()) == 0.0
    assert abs(float(np.mean(a["dense"]["norm1"])) - 1.0) < 0.02


# ----------------------------------------------------------- the traffic
PARAMS = dict(TRAFFIC, num_slots=32, max_len=16384)


def test_resident_traffic_is_one_multiset_in_seeded_order():
    a = resident_decode.build(PARAMS, 3, 40.0, 128256)
    b = resident_decode.build(PARAMS, 2 ** 31 + 4, 40.0, 128256)
    assert len(a) == len(b) == 64
    plen = lambda reqs: [len(r["prompt"]) for r in reqs]  # noqa: E731
    assert sorted(plen(a[:32])) == sorted(plen(a[32:])) \
        == sorted(plen(b[:32]))
    assert plen(a[:32]) != plen(b[:32])
    assert 6144 <= min(plen(a)) and max(plen(a)) <= 10240
    assert len(set(plen(a[:32]))) == 32
    assert all(r["max_new"] == 16384 - len(r["prompt"]) for r in a)
    assert max(int(r["prompt"].max()) for r in a) > 120000   # whole vocab


class _Req:
    def __init__(self):
        self.done = False
        self.t_prefill_dispatched = self.t_done = None


class _Client:
    """Stamps a token every 5 ms for every resident session but the
    ``mute`` ones."""
    num_slots = 4

    def __init__(self, mute=()):
        self.mute, self.recs, self.halted = set(mute), [], False

    def record(self, spec, due):
        return serve.Rec(spec, due)

    def preload(self, recs):
        self.recs = recs
        for r in recs:
            r.req = _Req()
            r.due = r.sent = time.perf_counter()

    def tick(self):
        for i, r in enumerate(self.recs[:self.num_slots]):
            if i not in self.mute:
                r.stamps.append(time.perf_counter())

    def halt(self):
        self.halted = True


def _drive(mute, ramp_max_s):
    import threading
    params = {"ramp_s": 0.05, "ramp_max_s": ramp_max_s,
              "settle_tokens": 3}
    client = _Client(mute)
    specs = [{"phase": "resident", "prompt": np.zeros(4, np.int64),
              "max_new": 10 ** 6} for _ in range(8)]
    stop = threading.Event()

    def ticker():
        while not stop.is_set():
            client.tick()
            time.sleep(0.005)
    t = threading.Thread(target=ticker, daemon=True)
    t.start()
    marks = []
    run = resident_decode.drive(client, specs, params, 0.1,
                                lambda: marks.append("open"),
                                lambda: marks.append("close"))
    stop.set()
    t.join()
    assert marks == ["open", "close"] and client.halted
    return run, resident_decode.account(run, params)


def test_window_opens_once_every_resident_session_has_settled():
    run, acct = _drive(mute=(), ramp_max_s=5.0)
    assert acct["checks"] == [("resident_short", 0, 0)]
    assert len(acct["attempted"]) == 4 and not acct["failed"]
    assert acct["prefills_in_window"] == acct["ended_in_window"] == 0
    assert run["ramp_took_s"] < 2.0


def test_resident_short_fires_when_a_session_has_no_token_at_the_opening():
    run, acct = _drive(mute=(2,), ramp_max_s=0.3)
    assert acct["checks"] == [("resident_short", 1, 0)]
    assert run["ramp_took_s"] >= 0.3          # it waited out ramp_max_s


# ------------------------------------------------------------ the readers
NEW_METRICS = ["mla_moe_decode_roofline", "mla_decode_attn_roofline",
               "moe_experts_roofline", "mla_decode_attn_dev_ms_per_step",
               "moe_experts_dev_ms_per_step", "moe_experts_hit_pct",
               "moe_load_imbalance", "decode_dev_ms.tput"]


def _reader(name):
    return harness.load_module(harness.find_by_name("metrics", name),
                               "m_" + name.replace(".", "_"))


def _moe(tokens, hits, steps):
    return {"expert_tokens": tokens, "experts_hit": hits,
            "layer_steps": steps}


def _ctx(trace):
    """100 decode steps of 32 slots at 10,000 live positions each; 5
    expert layers hit 100 experts a step; in the trace the attention
    kernel took 0.6 s, the experts' 0.8 s and a decode execution 17 ms."""
    rec = serve.Rec({"prompt": np.zeros(9999, np.int64), "max_new": 9},
                    0.0)
    rec.stamps = [0.5, 1.5]            # the second token saw 10,000
    zero = _moe([[0] * 128] * 5, [0] * 5, [0] * 5)
    tokens = [[150] * 64 + [0] * 64] * 5
    ops = {"%mla_paged_decode_attn.3 = f32[32,32,512] custom-call(...)":
           {"seconds": 0.6, "calls": 600},
           "%moe_experts_swiglu_decode.4 = f32[32,2048] custom-call(...)":
           {"seconds": 0.8, "calls": 500},
           "%fusion.9 = bf16[32,2048] fusion(...)":
           {"seconds": 0.3, "calls": 100}}
    programs = {"jit_paged_decode": {"calls": 100, "seconds": 1.7,
                                     "durations_s": [0.017] * 100}}
    return {"trace": {"ops": ops, "programs": programs} if trace else None,
            "trace_bounds": (1.0, 2.0) if trace else None,
            "programs": ARCH.program.PROGRAMS,
            "kernels": ARCH.program.KERNELS, "flops": ARCH.flops,
            "model": MODEL, "num_slots": 32, "weight_bytes": 2,
            "kv_bytes_per_value": 2,
            "peaks": harness.peaks_for("TPU v5 lite"),
            "run": {"t_open": 1.0, "t_close": 2.0, "recs": [rec],
                    "before": {"moe": zero, "decode_steps": 0},
                    "after": {"moe": _moe(tokens, [10000] * 5, [100] * 5),
                              "decode_steps": 100}}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_returns_none_without_a_trace_or_counters(name):
    read = _reader(name).read
    ctx = _ctx(trace=False)
    if name in ("moe_experts_hit_pct", "moe_load_imbalance"):
        assert read(ctx) is not None       # counters need no trace
        ctx["run"]["after"]["moe"] = ctx["run"]["before"]["moe"] = None
    assert read(ctx) is None


def test_readers_against_a_hand_calculation():
    ctx = _ctx(trace=True)
    val = {n: _reader(n).read(ctx) for n in NEW_METRICS}
    assert val["decode_dev_ms.tput"] == pytest.approx(17.0)
    assert val["mla_decode_attn_dev_ms_per_step"] == pytest.approx(6.0)
    assert val["moe_experts_dev_ms_per_step"] == pytest.approx(8.0)
    assert val["moe_experts_hit_pct"] == pytest.approx(100 * 100 / 128)
    assert val["moe_load_imbalance"] == pytest.approx(2.0)
    live = 32 * 10000
    attn_ms = 1e3 * live * 6 * 1152 / 819e9        # memory-bound
    assert 1e3 * live * 6 * 69632 / 197e12 < attn_ms
    assert val["mla_decode_attn_roofline"] == pytest.approx(
        100 * attn_ms / 6.0)
    moe_ms = 1e3 * 5 * (100 * 4718592 * 2 + 32 * 2048 * 6) / 819e9
    assert val["moe_experts_roofline"] == pytest.approx(100 * moe_ms / 8.0)
    step = ARCH.flops.decode_step_bytes(MODEL, live, 500, 2)
    assert val["mla_moe_decode_roofline"] == pytest.approx(
        100 * 1e3 * step / 819e9 / 17.0)
    assert all(v < 100 for k, v in val.items() if "roofline" in k)


# -------------------------------------------------------------- the plane
def test_the_cell_rehearses_end_to_end_and_is_correct(capsys):
    assert bench_run.main(["--workload", CELL, "--seed", str(2 ** 31 + 7),
                           "--seconds", "1.5", "--rehearse"]) == 0
    out = capsys.readouterr().out
    assert "check served_logit_gap" in out and "NOT CORRECT" not in out
    assert "check resident_short: 0" in out


def test_control_precision_is_not_correct_at_rehearse_sizes():
    """The reference computed in float8 puts other tokens first than the
    float32 reference: the comparison that decides ``correct`` would
    refuse a program that computed in it."""
    import jax.numpy as jnp
    cfg = harness.rehearsed(CONFIG)
    model = serve_arch.model_of(cfg)
    w = ARCH.weights.make(3, model, "float32")
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 512, 128),
                      jnp.int32)
    _, _, first8 = ARCH.reference.score(w, ids, ids, model, "float8")
    best, at, first = ARCH.reference.score(w, ids, first8, model,
                                           "float32")
    assert float((best - at).max()) > 10 * cfg["correct_limits"][
        "served_logit_gap"]
    best, at, _ = ARCH.reference.score(w, ids, first, model, "float32")
    assert float((best - at).max()) == 0.0


def test_stalls_are_read_from_one_sessions_stamps_and_gc_is_watched():
    import gc
    rec = serve.Rec({"prompt": np.zeros(4, np.int64), "max_new": 99}, 0.0)
    rec.stamps = [1.0 + 0.01 * i for i in range(50)]
    rec.stamps = rec.stamps[:30] + [s + 0.2 for s in rec.stamps[30:]]
    got = serve_arch.stalls({"recs": [rec], "t_open": 1.0, "t_close": 2.0})
    assert got["stalls"] == 1
    assert got["step_gap_median_ms"] == pytest.approx(10.0)
    assert got["step_gap_max_ms"] == pytest.approx(210.0)
    assert got["stalled_s"] == pytest.approx(0.2)
    watch = serve_arch.GcWatch()
    watch.start()
    gc.collect()
    seen = watch.stop()
    assert seen["gc_full_collections"] == 1 and seen["gc_pause_s"] > 0
    assert watch._on not in gc.callbacks
