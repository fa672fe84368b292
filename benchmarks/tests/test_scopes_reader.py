"""The readers of device time by scope (PR 39): ``metrics/_scopes.py``
on a hand-made ``ctx["trace"]["ops"]`` + table, each metric file through
the harness's loader, ``tools/scope_report.py`` on a hand-made
reduction, and every new ``BENCHMARK.json`` entry finding its file."""
import io
import json

import pytest

from benchmarks import harness
from benchmarks.metrics import _scopes
from benchmarks.tools import scope_report
from paddle_tpu.observability import watchdog as wd

J = "jit(paged_decode)/jit(main)/while/body/closed_call/"


def _ev(name, shape="f32[24,2048]{1,0:T(8,128)}", opcode="fusion"):
    """A trace event's name: the whole instruction, operands typed."""
    return (f"%{name} = {shape} {opcode}(f32[24]{{0:T(128)}} %p.1), "
            f"kind=kLoop, calls=%fused_computation.1")


def _key(name, shape="f32[24,2048]", opcode="fusion"):
    return f"%{name} = {shape} {opcode}"


DECODE = {
    _key("fusion.1"): J + "attn/dot_general",
    _key("fusion.2"): J + "attn/kv_write/dynamic_update_slice",
    _key("fusion.3"): J + "mlp/dot_general",
    _key("fusion.4"): "jit(paged_decode)/jit(main)/lm_head/dot_general",
    _key("fusion.5"): J + "paged_attn/add",          # no group's scope
    _key("paged_decode_attn.7", opcode="custom-call"): J + "attn/pallas_call",
    _key("copy.9", opcode="copy"): "",
    _key("fusion.6"): J + "attn/add",                # ambiguous below
    _key("while.1", opcode="while"): "jit(paged_decode)/jit(main)/while",
}
PREFILL = {
    _key("fusion.6"): "jit(paged_prefill)/jit(main)/mlp/add",
    _key("fusion.77"): "jit(paged_prefill)/jit(main)/mlp/dot_general",
}
OPS = {
    _ev("fusion.1"): 0.40, _ev("fusion.2"): 0.10, _ev("fusion.3"): 0.80,
    _ev("fusion.4"): 0.05, _ev("fusion.5"): 0.02,
    _ev("paged_decode_attn.7", opcode="custom-call"): 1.00,
    _ev("copy.9", opcode="copy"): 0.03, _ev("fusion.6"): 0.04,
    _ev("while.1", opcode="while"): 0.01,
    _ev("fusion.77"): 5.0,                 # the prefill program's own
    _ev("fusion.999"): 7.0,                # in no program's table
}


def _table():
    return {"('decode',)": {"module": "jit_paged_decode",
                            "instructions": DECODE},
            "('paged_prefill', 128)": {"module": "jit_paged_prefill",
                                       "instructions": PREFILL}}


def _ctx(monkeypatch, table=None, kernels=None, programs=None):
    monkeypatch.setattr(wd, "program_scopes",
                        lambda: _table() if table is None else table)
    red = {"ops": {k: {"seconds": v, "calls": 10} for k, v in OPS.items()},
           "programs": {
               "jit_paged_decode": {"calls": 10, "seconds": 2.5,
                                    "durations_s": [0.25] * 10},
               "jit_paged_prefill": {"calls": 2, "seconds": 5.0,
                                     "durations_s": [2.5] * 2}}}
    ctx = {"trace": red,
           "programs": programs or {"decode": "paged_decode",
                                    "prefill": "paged_prefill"}}
    if kernels:
        ctx["kernels"] = kernels
    return ctx


@pytest.mark.parametrize("path,group", [
    (("attn",), "mixer_proj"),
    (("paged_attn",), None),                       # whole components
    (("attn", "kv_write"), "cache_write"),         # the innermost decides
    (("eva", "attn"), "mixer_proj"),
    (("attn", "paged", "kv_write"), "cache_write"),
    (("moe", "experts"), "ffn"),
    (("experts",), None),
    (("lm_head",), "lm_head"),
    ((), None),
])
def test_group_of_matches_whole_components_innermost(path, group):
    assert _scopes.group_of(path, _scopes.SERVE_GROUPS) == group


@pytest.mark.parametrize("path,group", [
    (("bwd", "block", "mlp"), "block_mlp"),
    (("bwd", "block", "attn", "bwd", "block", "attn"), "block_attn_proj"),
    (("bwd", "lm_head", "loss"), "lm_head_loss"),
    (("optimizer", "step"), "optimizer"),
    (("block",), None), (("bwd",), None), (("embed",), None),
])
def test_backward_counts_for_its_forwards_group(path, group):
    assert _scopes.group_of(path, _scopes.TRAIN_GROUPS) == group


def test_groups_kernels_and_unscoped_add_up_to_the_programs_op_time(
        monkeypatch):
    ctx = _ctx(monkeypatch)
    sp, steps = _scopes._decode(ctx)
    assert steps == 10
    assert sp["total"] == pytest.approx(2.45)      # not 5.0, not 7.0
    assert sp["kernels"] == pytest.approx(1.00)    # counted once
    assert sp["groups"] == pytest.approx(
        {"mixer_proj": 0.40, "cache_write": 0.10, "ffn": 0.80,
         "lm_head": 0.05})
    # paged_attn (no group), the copy without metadata, the ambiguous
    # fusion.6, the while's own time
    assert sp["unscoped"] == pytest.approx(0.02 + 0.03 + 0.04 + 0.01)
    assert sp["kernels"] + sum(sp["groups"].values()) + sp["unscoped"] \
        == pytest.approx(sp["total"])
    assert _scopes.decode_group_ms(ctx, "mixer_proj") == pytest.approx(40.0)
    assert _scopes.decode_unscoped_pct(ctx) == pytest.approx(
        100 * 0.10 / 2.45)


def test_an_op_another_executed_program_holds_too_is_listed(
        monkeypatch, capsys):
    """Same instruction, same scope, in a prefill bucket: the trace's
    ops sum both programs' seconds under the one name, so the op stays
    in its group and the run's log names it beside the residual."""
    table = _table()
    table["('paged_prefill', 128)"] = {
        "module": "jit_paged_prefill", "instructions": dict(
            PREFILL, **{_key("fusion.3"): "jit(paged_prefill)/jit(main)/"
                                          "mlp/dot_general"})}
    ctx = _ctx(monkeypatch, table=table)
    sp, _ = _scopes._decode(ctx)
    assert sp["groups"]["ffn"] == pytest.approx(0.80)
    # fusion.6 (ambiguous, unscoped) is another program's too
    assert sp["shared_ops"] == pytest.approx(
        {_ev("fusion.3"): 0.80, _ev("fusion.6"): 0.04})
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("# scopes paged_decode"))
    logged = json.loads(line.split(" ", 3)[3])
    assert logged["shared_ms"] == pytest.approx(84.0)
    assert logged["residual_pct"] == pytest.approx(100 * (2.45 / 2.5 - 1))
    assert logged["shared_top_ms"][0][1] == pytest.approx(80.0)
    del ctx["trace"]["programs"]["jit_paged_prefill"]   # never executed
    ctx.pop("_scopes")
    assert _scopes._decode(ctx)[0]["shared_ops"] == {}


def test_ambiguity_is_judged_among_the_programs_that_ran(monkeypatch):
    ctx = _ctx(monkeypatch)
    del ctx["trace"]["programs"]["jit_paged_prefill"]   # never executed
    sp, _ = _scopes._decode(ctx)
    assert sp["groups"]["mixer_proj"] == pytest.approx(0.44)
    assert sp["unscoped"] == pytest.approx(0.06)


def test_the_planes_kernel_names_decide_what_a_kernel_is(monkeypatch):
    ctx = _ctx(monkeypatch, kernels={"x": "some_other_kernel"})
    sp, _ = _scopes._decode(ctx)
    assert sp["kernels"] == 0.0
    assert sp["groups"]["mixer_proj"] == pytest.approx(1.40)


def test_none_without_a_trace_a_table_or_the_groups_scope(monkeypatch):
    ctx = _ctx(monkeypatch)
    ctx["trace"] = None                               # --rehearse, the CPU
    assert _scopes.decode_group_ms(ctx, "ffn") is None
    assert _scopes.decode_unscoped_pct(ctx) is None
    no_mlp = {k: v for k, v in DECODE.items() if "/mlp/" not in v}
    ctx = _ctx(monkeypatch, table={"('decode',)": {
        "module": "jit_paged_decode", "instructions": no_mlp}})
    assert _scopes.decode_group_ms(ctx, "ffn") is None     # no such scope
    assert _scopes.decode_group_ms(ctx, "lm_head") == pytest.approx(5.0)
    ctx = _ctx(monkeypatch, table={})                 # no such program
    assert _scopes.decode_unscoped_pct(ctx) is None
    monkeypatch.delattr(wd, "program_scopes")         # the parent commit
    assert _scopes.decode_group_ms(_ctx_raw(), "ffn") is None


def _ctx_raw():
    return {"trace": {"ops": {}, "programs": {"jit_paged_decode": {
        "calls": 1, "seconds": 1.0, "durations_s": [1.0]}}},
        "programs": {"decode": "paged_decode"}}


SERVE = ["mixer_proj_dev_ms_per_step", "ffn_dev_ms_per_step",
         "lm_head_dev_ms_per_step", "cache_write_dev_ms_per_step",
         "decode_unscoped_pct"]
TRAIN = ["lm_head_loss_dev_ms_per_step", "optimizer_dev_ms_per_step",
         "block_mlp_dev_ms_per_step", "block_attn_proj_dev_ms_per_step",
         "train_unscoped_pct"]


@pytest.mark.parametrize("name", [s + e for s in SERVE
                                  for e in (".gap", ".tput")])
def test_every_serving_entry_finds_its_file_and_reads(monkeypatch, name):
    read = harness.load_module(harness.find_by_name("metrics", name),
                               "m_" + name.replace(".", "_")).read
    value = read(_ctx(monkeypatch))
    assert value is not None and value > 0
    ctx = _ctx(monkeypatch)
    ctx["trace"] = None
    assert read(ctx) is None


@pytest.mark.parametrize("name", TRAIN)
def test_every_training_entry_finds_its_file_and_reads(monkeypatch, name):
    J2 = "jit(compiled_fn)/jit(main)/"
    table = {"('to_static', 'step_fn')": {
        "module": "jit_compiled_fn", "instructions": {
            _key("fusion.1"): J2 + "bwd/block/mlp/transpose(jvp())/dot",
            _key("fusion.2"): J2 + "block/attn/dot_general",
            _key("fusion.3"): J2 + "bwd/lm_head/loss/transpose(jvp())/dot",
            _key("fusion.4"): J2 + "optimizer/step/mul",
            _key("fusion.5"): J2 + "embed/gather",
            _key("jvp_flash_fwd_.12", opcode="custom-call"):
                J2 + "block/attn/pallas_call"}}}
    ctx = _ctx(monkeypatch, table=table,
               programs={"train_step": "compiled_fn"})
    ctx["trace"]["programs"] = {"jit_compiled_fn": {
        "calls": 4, "seconds": 2.0, "durations_s": [0.5] * 4}}
    ctx["trace"]["ops"][_ev("jvp_flash_fwd_.12", opcode="custom-call")] = \
        {"seconds": 1.0, "calls": 4}
    read = harness.load_module(harness.find_by_name("metrics", name),
                               "m_" + name).read
    want = {"lm_head_loss_dev_ms_per_step": 1e3 * 0.80 / 4,
            "optimizer_dev_ms_per_step": 1e3 * 0.05 / 4,
            "block_mlp_dev_ms_per_step": 1e3 * 0.40 / 4,
            "block_attn_proj_dev_ms_per_step": 1e3 * 0.10 / 4,
            "train_unscoped_pct": 100 * 0.02 / 2.37}[name]
    assert read(ctx) == pytest.approx(want)


def test_benchmark_json_lists_the_fifteen_entries_with_their_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    by = {m["name"]: m for m in bench["per_layer"]}
    tput = [c for c in by["decode_dev_ms.tput"]["workloads"]] \
        + ["gpt3_1p3b.chat_backlog"]
    for stem in SERVE:
        assert by[stem + ".gap"]["workloads"] == ["gpt3_1p3b.chat_steady"]
        assert by[stem + ".gap"]["moves"] == "gap_mean_ms"
        assert sorted(by[stem + ".tput"]["workloads"]) == sorted(tput)
        assert by[stem + ".tput"]["moves"] == "serve_tokens_per_s"
    for name in TRAIN:
        assert by[name]["workloads"] == ["gpt2_124m.pretrain_1k"]
        assert by[name]["moves"] == "train_tokens_per_s"
    new = [m for m in bench["per_layer"]
           if m["name"].rsplit(".", 1)[0] in SERVE + TRAIN]
    assert len(new) == 15 and bench["per_layer"][-15:] == new
    assert all(m["source"] == "device_trace" for m in new)


def test_scope_report_on_a_hand_made_reduction():
    ops = {"jit_paged_decode": {k: [v, 10] for k, v in OPS.items()
                                if "fusion.77" not in k},
           "jit_paged_prefill": {_ev("fusion.77"): [5.0, 2],
                                 _ev("fusion.6"): [0.5, 2]}}
    runs = {"jit_paged_decode": [10, 2.5], "jit_paged_prefill": [2, 5.6]}
    rows = scope_report.report(ops, runs, _table())
    assert [r["program"] for r in rows] == ["jit_paged_decode",
                                            "jit_paged_prefill"]
    dec = dict(rows[0]["scopes"])
    assert dec["attn"] == pytest.approx(0.40 + 1.00 + 0.04)
    assert dec["attn/kv_write"] == pytest.approx(0.10)
    assert dec["(not in the table)"] == pytest.approx(7.0)
    assert dec["(none)"] == pytest.approx(0.03 + 0.01)
    # the same instruction name in the prefill program is the prefill's
    assert dict(rows[1]["scopes"])["mlp"] == pytest.approx(5.5)
    out = io.StringIO()
    scope_report.show(rows, out)
    text = out.getvalue()
    assert "jit_paged_decode: 10 executions" in text
    assert "attn/kv_write" in text and "unscoped" in text
