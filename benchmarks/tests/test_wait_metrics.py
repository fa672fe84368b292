"""The readers of the program's own waits (PR 25): each on a hand-made
``ctx`` (present, and absent -> None, which is what the parent of the PR
that added them gives), on the recorded serve cut, and through the
rehearsal of each cell."""
import os
import types

import pytest

from benchmarks import harness, trace_reduce
from benchmarks import run as bench_run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _reader(name):
    return harness.load_module(harness.find_by_name("metrics", name),
                               "m_" + name.replace(".", "_")).read


def _serve_ctx(span_before, span_after, steps=(100, 300)):
    return {"run": {"before": {"decode_steps": steps[0],
                               "span_s": span_before},
                    "after": {"decode_steps": steps[1],
                              "span_s": span_after}}}


@pytest.mark.parametrize("name", ["host_outside_step_ms_per_step.gap",
                                  "host_outside_step_ms_per_step.tput"])
def test_host_outside_step_is_drive_minus_step_per_decode_step(name):
    read = _reader(name)
    ctx = _serve_ctx({"serving/drive": 1.0, "serving/step": 0.9},
                     {"serving/drive": 21.0, "serving/step": 20.5})
    assert read(ctx) == pytest.approx(1e3 * (20.0 - 19.6) / 200)
    absent = _serve_ctx({"serving/step": 0.9}, {"serving/step": 20.5})
    assert read(absent) is None
    assert read(_serve_ctx({}, {"serving/drive": 1.0, "serving/step": 1.0},
                           steps=(5, 5))) is None


@pytest.mark.parametrize("name", ["health_tick_ms_per_step.gap",
                                  "health_tick_ms_per_step.tput"])
def test_health_tick_per_decode_step(name):
    read = _reader(name)
    ctx = _serve_ctx({"serving/health_tick": 0.5},
                     {"serving/health_tick": 0.7})
    assert read(ctx) == pytest.approx(1e3 * 0.2 / 200)
    assert read(_serve_ctx({}, {"serving/step": 3.0})) is None


def _rec(prompt_len, t_admitted, **req_fields):
    req = types.SimpleNamespace(t_admitted=t_admitted, **req_fields)
    return types.SimpleNamespace(spec={"prompt": [0] * prompt_len},
                                 req=req)


def test_prefill_pad_pct_over_the_requests_admitted_in_the_window():
    read = _reader("prefill_pad_pct")
    recs = [_rec(100, 10.5, prefill_tokens_dispatched=128),
            _rec(300, 11.0, prefill_tokens_dispatched=512),
            _rec(700, 9.0, prefill_tokens_dispatched=1024),    # ramp
            _rec(50, None, prefill_tokens_dispatched=0),       # queued
            types.SimpleNamespace(spec={"prompt": [0]}, req=None)]
    ctx = {"run": {"recs": recs, "t_open": 10.0, "t_close": 12.0}}
    assert read(ctx) == pytest.approx(100.0 * (1 - 400 / 640))
    # a program that does not stamp its requests
    old = {"run": {"recs": [_rec(100, 10.5)], "t_open": 10.0,
                   "t_close": 12.0}}
    assert read(old) is None
    assert read({"run": {"recs": [], "t_open": 0.0,
                         "t_close": 1.0}}) is None


@pytest.fixture
def ring():
    from paddle_tpu.observability import default_recorder
    rec = default_recorder()
    rec.clear()
    yield rec
    rec.clear()


def test_training_readers_take_the_rings_spans_of_the_traced_steps(ring):
    for k in range(4):                      # four steps, 10 s apart
        t = 100.0 + 10 * k
        ring.record("io/next", t, 0.002)
        ring.record("jit/enqueue", t + 1.1, 0.003)
        ring.record("jit/call", t + 1.0, 0.0045)
    ctx = {"trace_bounds": (105.0, 135.0), "traced_steps": 3}
    assert _reader("loader_next_ms_per_step")(ctx) == pytest.approx(2.0)
    assert _reader("to_static_enqueue_ms_per_step")(ctx) \
        == pytest.approx(3.0)
    assert _reader("to_static_wrap_ms_per_step")(ctx) \
        == pytest.approx(1.5)


@pytest.mark.parametrize("name", ["loader_next_ms_per_step",
                                  "to_static_enqueue_ms_per_step",
                                  "to_static_wrap_ms_per_step"])
def test_training_readers_find_nothing_to_read(ring, name):
    read = _reader(name)
    ctx = {"trace_bounds": (105.0, 135.0), "traced_steps": 3}
    assert read(ctx) is None                          # an empty ring
    ring.record("optimizer/step", 110.0, 0.1)
    assert read(ctx) is None                          # no such span
    assert read({"trace_bounds": None, "traced_steps": None}) is None


def test_a_ring_that_dropped_spans_of_the_window_reads_none(monkeypatch):
    from paddle_tpu.observability import tracing
    small = tracing.HostSpanRecorder(capacity=4)
    monkeypatch.setattr(tracing, "_default_recorder", small)
    read = _reader("loader_next_ms_per_step")
    ctx = {"trace_bounds": (100.0, 200.0), "traced_steps": 4}
    for k in range(4):
        small.record("io/next", 110.0 + k, 0.002)
    assert read(ctx) == pytest.approx(2.0)
    small.record("io/next", 120.0, 0.002)   # overwrites one of them
    assert small.dropped == 1
    assert read(ctx) is None
    # drops from before the window do not matter
    assert read({"trace_bounds": (111.5, 200.0), "traced_steps": 4}) \
        == pytest.approx(1.5)


@pytest.mark.parametrize("name,needle,want", [
    ("flash_fwd_dev_ms_per_step", "flash_fwd", 1e3 * (0.5 + 0.25) / 2),
    ("flash_bwd_dev_ms_per_step", "flash_bwd_", 1e3 * (0.75 + 1.0) / 2)])
def test_flash_kernel_readers_match_the_instructions_own_name(name, needle,
                                                             want):
    read = _reader(name)
    call = ' = (bf16[2]) custom-call(bf16[2] %x), custom_call_target=' \
           '"tpu_custom_call"'
    red = {"ops": {"%jvp_flash_fwd_.12" + call: {"seconds": 0.5, "calls": 2},
                   "%flash_fwd.3" + call: {"seconds": 0.25, "calls": 2},
                   "%flash_bwd_dq.1" + call: {"seconds": 0.75, "calls": 2},
                   "%flash_bwd_dkv.1" + call: {"seconds": 1.0, "calls": 2},
                   # an operand's name is not the op's own
                   "%fusion.9 = f32[2] fusion(f32[2] %flash_fwd.3)":
                       {"seconds": 9.0, "calls": 2}},
           "programs": {"jit_compiled_fn": {"calls": 2, "seconds": 3.0,
                                            "durations_s": [1.5, 1.5]}}}
    ctx = {"trace": red, "programs": {"train_step": "compiled_fn"}}
    assert read(ctx) == pytest.approx(want)
    assert read(dict(ctx, trace=None)) is None
    # the recorded cut is of a program whose kernels had no name
    old = trace_reduce.reduce(os.path.join(DATA, "train_cut.xplane.pb"),
                              ("bench/",))
    assert read(dict(ctx, trace=old)) is None


NEW = {
    "gpt3_1p3b.chat_steady": ["host_outside_step_ms_per_step.gap",
                              "health_tick_ms_per_step.gap",
                              "prefill_pad_pct"],
    "gpt3_1p3b.chat_backlog": ["host_outside_step_ms_per_step.tput",
                               "health_tick_ms_per_step.tput"],
    "gpt2_124m.pretrain_1k": ["loader_next_ms_per_step",
                              "to_static_enqueue_ms_per_step",
                              "to_static_wrap_ms_per_step"],
}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_traced_rehearsal_of_each_cell_lists_the_new_metrics(
        cell, capsys):
    """(The device-trace readers have nothing to read on the CPU.)"""
    rc = bench_run.main(["--workload", cell, "--seed", "2147483905",
                         "--seconds", "3", "--trace", "1", "--rehearse"])
    last = capsys.readouterr().out.splitlines()[-1]
    assert rc == 0 and "rehearsal only" in last
    for name in NEW[cell]:
        assert f'"{name}"' in last, last
