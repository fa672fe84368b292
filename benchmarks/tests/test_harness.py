"""A configuration, a traffic mix, a per-layer metric and a cell added
as NEW FILES and NEW ENTRIES only are found by name; nothing that was
there is edited."""
import json
import os
import shutil

import pytest

from benchmarks import harness
from benchmarks import run as bench_run


@pytest.fixture
def copy_of_benchmark(tmp_path, monkeypatch):
    shutil.copytree(harness.HERE, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "HERE", str(tmp_path / "benchmarks"))
    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    return tmp_path


def test_new_files_and_entries_are_found_by_name(copy_of_benchmark, capsys):
    root = copy_of_benchmark
    here = root / "benchmarks"
    config = json.loads((here / "configs" / "gpt2_124m.json").read_text())
    config["rehearse"]["sizing"]["batch"] = 2
    (here / "configs" / "gpt2_small_batch.json").write_text(
        json.dumps(config))
    traffic = json.loads((here / "traffic" / "pretrain_1k.json").read_text())
    traffic["settle_steps"] = 1
    (here / "traffic" / "pretrain_brief.json").write_text(
        json.dumps(traffic))
    (here / "metrics" / "steps_counted.py").write_text(
        "def read(ctx):\n    return ctx['steps']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "gpt2_small_batch", "source": "test",
        "file": "benchmarks/configs/gpt2_small_batch.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "gpt2_small_batch.pretrain_brief",
        "config": "gpt2_small_batch", "traffic": "pretrain_brief",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("gpt2_small_batch.pretrain_brief")
    bench["per_layer"].append({
        "name": "steps_counted.train", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "model step",
        "moves": "train_tokens_per_s",
        "workloads": ["gpt2_small_batch.pretrain_brief"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell, config, traffic = bench_run.resolve(
        bench, "gpt2_small_batch.pretrain_brief", rehearse=True)
    assert config["sizing"]["batch"] == 2 and traffic["settle_steps"] == 1
    names = [m["name"] for m in bench_run.cell_metrics(
        bench, "per_layer", cell["name"])]
    assert names == ["steps_counted.train"]
    bench_run.main(["--workload", "gpt2_small_batch.pretrain_brief",
                    "--seed", "3", "--seconds", "1", "--trace", "1",
                    "--rehearse"])
    out = capsys.readouterr().out
    assert "steps_counted.train" in out.splitlines()[-1]
    assert '"batch": 2' in out


def test_split_metric_names_share_their_stems_reader():
    path = harness.find_by_name("metrics", "device_idle_pct.gap")
    assert path.endswith("device_idle_pct.py")
    with pytest.raises(FileNotFoundError):
        harness.find_by_name("metrics", "no_such_metric.gap")


def test_every_entry_of_benchmark_json_has_its_files():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    for conf in bench["configs"]:
        config = harness.load_json(harness.ROOT, conf["file"])
        harness.find_by_name("planes", config["plane"])
        assert conf["source"] == config["source"]
        assert conf["reduced"] == config["reduced"]
    for cell in bench["workloads"]:
        traffic = harness.load_json(
            harness.find_by_name("traffic", cell["traffic"], ".json"))
        harness.find_by_name("generators", traffic["generator"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        harness.find_by_name("metrics", m["name"])
        assert m["moves"] in e2e


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def test_without_a_chip_the_measuring_path_prints_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        bench_run.main(["--workload", "gpt2_124m.pretrain_1k", "--seed",
                        "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out


def test_train_mfu_is_the_traced_steps_over_the_traced_seconds():
    """The profiler's stop falls inside a traced run's window; the rate
    behind the MFU ends where the steps were blocked on, before it."""
    from benchmarks import flops
    mod = harness.load_module(
        harness.find_by_name("metrics", "train_mfu_pct"), "mfu")
    model = harness.load_json(harness.HERE, "configs",
                              "gpt2_124m.json")["model"]
    peaks = harness.peaks_for("TPU v5 lite")
    ctx = {"peaks": peaks, "model": model, "seq_len": 1024,
           "tokens_per_step": 24 * 1024, "traced_steps": 14,
           "traced_seconds": 4.0, "steps": 130, "seconds": 43.0}
    want = 100.0 * flops.train_ops_per_token(model, 1024) \
        * 14 * 24 * 1024 / 4.0 / peaks["bf16_flops_per_s"]
    assert mod.read(ctx) == pytest.approx(want)
    assert mod.read(dict(ctx, traced_steps=None)) is None


def test_a_backlog_that_runs_dry_is_not_correct(copy_of_benchmark, capsys):
    path = copy_of_benchmark / "benchmarks" / "traffic" / "chat_backlog.json"
    traffic = json.loads(path.read_text())
    traffic["rehearse"].update(blocks=1, block_requests=60)
    path.write_text(json.dumps(traffic))
    rc = bench_run.main(["--workload", "gpt3_1p3b.chat_backlog", "--seed",
                         "6", "--seconds", "3", "--trace", "0",
                         "--rehearse"])
    out = capsys.readouterr().out
    assert rc != 0 and '"correct": false' in out
    assert "check backlog_short: 4 against limit 0: NOT CORRECT" in out
