"""The reduction from a trace to numbers, pinned on two short cuts of
traces recorded on a TPU v5e in PR 24 (tools/cut_trace.py):

serve_cut  the events that START in 0.4 s of gpt3_1p3b.chat_steady: five
           decode executions and one prefill (the last decode's ops are
           cut off, which leaves 15 ms with no op: the one idle gap), and
           the engine's own ``serving/*`` host spans
train_cut  likewise 0.2 s of gpt2_124m.pretrain_1k at batch 16: one whole
           step (its first ops cut off), the benchmark's ``bench/*``
           spans, the flash kernels
"""
import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def serve():
    return tr.reduce(os.path.join(DATA, "serve_cut.xplane.pb"),
                     ("serving/", "bench/"))


@pytest.fixture(scope="module")
def train():
    return tr.reduce(os.path.join(DATA, "train_cut.xplane.pb"), ("bench/",))


def test_serve_cut_busy_idle_and_programs(serve):
    assert serve["chips"] == 1
    assert serve["window_s"] == pytest.approx(0.462696, abs=1e-5)
    assert serve["busy_s"] == pytest.approx(0.447838, abs=1e-5)
    sec, calls, durs = tr.program_seconds(serve, "paged_decode")
    assert calls == 5 and sec == pytest.approx(0.45341, abs=1e-4)
    assert len(durs) == 5
    assert all(d == pytest.approx(0.09068, abs=2e-5) for d in durs)
    sec, calls, durs = tr.program_seconds(serve, "paged_prefill")
    assert calls == 1 and durs == [pytest.approx(0.009266, abs=1e-6)]
    assert tr.program_seconds(serve, "no_such_program")[1] == 0


def test_serve_cut_gaps_go_to_the_engines_own_spans(serve):
    idle = serve["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(
        serve["window_s"] - serve["busy_s"], rel=1e-6)
    # the 15 ms without an op lie under the engine's wait for the device
    assert idle["serving/sync"] == pytest.approx(0.014857, abs=1e-5)
    assert max(idle, key=idle.get) == "serving/sync"


def test_serve_cut_breakdown_names_the_kv_copy(serve):
    b = tr.breakdown(serve)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    names = " ".join(name for name, _ in b["device_ops"])
    assert "bf16[24,1537,16,16,128]" in names      # the whole KV pool
    assert all(len(name) <= 120 for name, _ in b["device_ops"])
    total = sum(v["seconds"] for v in serve["ops"].values())
    assert total == pytest.approx(serve["busy_s"], rel=0.02)


def test_train_cut_finds_the_flash_kernels(train):
    kernels = {k: v for k, v in train["ops"].items()
               if 'custom_call_target="tpu_custom_call"' in k}
    assert len(kernels) == 48          # 12 layers x (2 forward + dq + dkv)
    assert sum(v["seconds"] for v in kernels.values()) == pytest.approx(
        0.074996, abs=1e-5)
    assert tr.op_label(next(iter(kernels))).split()[1] \
        == "custom-call:tpu_custom_call"
    sec, calls, durs = tr.program_seconds(train, "compiled_fn")
    assert calls == 1 and durs == [pytest.approx(0.186408, abs=1e-5)]
    assert train["busy_s"] == pytest.approx(0.193564, abs=1e-5)


def test_union_self_time_and_gap_attribution_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    events = [(0.0, 100.0, "while"), (10.0, 40.0, "a"), (50.0, 90.0, "b"),
              (60.0, 70.0, "b.inner"), (200.0, 250.0, "a")]
    own = tr.self_times(events)
    assert own["while"] == [pytest.approx(30e-9), 1]
    assert own["a"] == [pytest.approx(80e-9), 2]
    assert own["b"] == [pytest.approx(30e-9), 1]
    spans = [(0.0, 1000.0, "step"), (100.0, 300.0, "sync"),
             (2000.0, 3000.0, "step")]
    got = tr.attribute_gaps([(150.0, 250.0), (900.0, 1100.0),
                             (5000.0, 5100.0)], spans)
    assert got == {"sync": pytest.approx(100e-9),
                   "step": pytest.approx(200e-9),
                   "(no host span)": pytest.approx(100e-9)}
