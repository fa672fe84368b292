"""The programs' table (``observability.watchdog.program_scopes``): what
each compiled program's instructions belong to, by the
``profiler.device_scope`` names the models emit; the tape's backward
under its forward's scope; ``GET /debug/programs``."""
import gc
import json
import os
import sys
import threading
import urllib.request
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import profiler  # noqa: E402
from paddle_tpu.observability import watchdog as wd  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.text.models import (GPTForCausalLM,  # noqa: E402
                                    TransformerLMConfig)


def _gpt():
    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m, 97, dict(num_slots=2, block_size=8)


def _deepseek():
    import test_deepseek_v3 as t
    m, _ = t._model()
    return m, 96, dict(num_slots=2, block_size=8, max_len=64,
                       buckets=[16, 32])


def _nemotron():
    import test_nemotron_h as t
    m, _, _ = t._model()
    return m, 96, dict(num_slots=2, block_size=8, max_len=64,
                       buckets=[16, 32])


def _evabyte():
    import test_evabyte as t
    m, _, _ = t._model()
    return m, 64, dict(num_slots=2, block_size=4, max_len=128)


ARCHS = {
    "gpt": (_gpt, ("embed", "attn", "kv_write", "mlp", "lm_head",
                   "sample")),
    "deepseek_v3": (_deepseek, (
        "embed", "mla/q_absorb", "mla/attn", "mla/out", "kv_write", "mlp",
        "moe/router", "moe/experts", "moe/shared", "lm_head", "sample")),
    "nemotron_h": (_nemotron, (
        "embed", "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/out",
        "attn/qkv", "attn/paged", "attn/out", "kv_write", "state_write",
        "moe/router", "moe/experts", "moe/shared", "lm_head", "sample")),
    "evabyte": (_evabyte, ("embed", "eva/qkv", "eva/attn", "eva/out",
                           "eva/compact", "kv_write", "mlp", "lm_head",
                           "sample")),
}


def _has(paths, scope):
    want = tuple(scope.split("/"))
    n = len(want)
    return any(p[i:i + n] == want for p in paths
               for i in range(len(p) - n + 1))


@pytest.fixture(scope="module", params=sorted(ARCHS))
def served(request):
    """One tiny engine of the architecture, driven past a window's end,
    closed and dropped; what is left: its compile events, a weak
    reference to its decode executable, and the table as it read right
    after (a later engine's programs take the same keys)."""
    build, scopes = ARCHS[request.param]
    model, vocab, kw = build()
    eng = ServingEngine(model, **kw)
    rng = np.random.default_rng(0)
    for n, k in ((5, 4), (11, 3), (40 if request.param == "evabyte"
                                   else 9, 6)):
        eng.add_request(rng.integers(0, vocab, size=n), max_new_tokens=k)
    eng.run()
    events, owner = eng.watchdog.events(), eng.watchdog.id
    ref = weakref.ref(eng._exec[("decode",)])
    eng.close()
    del eng, model
    gc.collect()
    mine = {rec["key"]: rec for rec in wd.program_scopes().values()
            if rec["owner"] == owner}
    return {"arch": request.param, "scopes": scopes, "events": events,
            "exec_ref": ref,
            "table": {e["key"]: mine.get(e["key"]) for e in events}}


def test_table_holds_every_program_the_watchdog_recorded(served):
    assert served["events"]
    for e in served["events"]:
        rec = served["table"][e["key"]]
        assert rec is not None and rec["error"] is None, e["key"]
        assert rec["instructions"], e["key"]
        assert rec["module"].startswith("jit_")
        assert rec["signature"] == e["signature"]
        # facts of the build only: no clock's reading in the table
        assert not any("seconds" in k or k.endswith("_s") for k in rec)
    modules = {r["module"] for r in served["table"].values()}
    assert "jit_paged_decode" in modules


def test_every_scope_of_the_model_appears(served):
    paths = {wd.scope_path(op) for rec in served["table"].values()
             for op in rec["instructions"].values()}
    missing = [s for s in served["scopes"] if not _has(paths, s)]
    assert not missing, (served["arch"], missing)


def test_table_survives_close_and_pins_no_executable(served):
    """The fixture read the table after ``close()`` and after the engine
    was dropped; the decode executable is gone by then."""
    assert served["exec_ref"]() is None
    rec = next(r for r in served["table"].values()
               if r["module"] == "jit_paged_decode")
    assert len(rec["instructions"]) > 10


def test_debug_programs_lists_instructions_by_scope():
    model, vocab, kw = _gpt()
    eng = ServingEngine(model, **kw)
    eng.add_request(np.arange(5) % vocab, max_new_tokens=2)
    eng.run()
    server = eng.serve_metrics()
    try:
        port = server.server_address[1]
        idx = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/", timeout=10).read())
        assert "/debug/programs" in idx["routes"]
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/programs", timeout=10).read())
    finally:
        eng.close()
    keys = {e["key"] for e in eng.watchdog.events()}
    assert set(body["programs"]) == keys
    dec = body["programs"][repr(("decode",))]
    assert dec["module"] == "jit_paged_decode"
    assert dec["instructions"] == sum(
        dec["instructions_by_scope"].values())
    assert any(k.split("/")[0] == "attn"
               for k in dec["instructions_by_scope"])
    assert "(none)" in dec["instructions_by_scope"]
    # seconds-free: facts of the build only
    assert not any("seconds" in k or k.endswith("_s") for k in dec)


# ------------------------------------------------------------ the tape
@pytest.fixture(scope="module")
def train_step():
    paddle.seed(3)
    cfg = TransformerLMConfig(vocab_size=128, hidden_size=32, num_layers=2,
                              num_heads=2, max_seq_len=16, dropout=0.0)
    net = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=net.parameters(),
                                 weight_decay=0.01)

    def tiny_train_step(ids, labels):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = net(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    step = paddle.jit.to_static(tiny_train_step)
    x = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 128, (2, 16)).astype("int64"))
    losses = [float(step(x, x).numpy()) for _ in range(4)]
    (entry,) = step.entries.values()
    return step, x, losses, entry["program"]


def _lazy_step(name="lazy_text_step"):
    """A small recorded step of its own (no other test asks for the
    table between its compiled calls and the look at its record), the
    Linear it trains, and the count of runs of its Python body."""
    lin = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(0.1, parameters=lin.parameters())
    runs = [0]

    def body(x):
        runs[0] += 1
        with profiler.device_scope("block/mlp"):
            loss = (lin(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    body.__name__ = name
    return paddle.jit.to_static(body), lin, runs


def test_training_text_is_produced_only_on_request():
    paddle.seed(5)
    step, _lin, runs = _lazy_step()
    x = paddle.to_tensor(np.ones((2, 8), "float32"))
    losses = [float(step(x).numpy()) for _ in range(4)]
    # eager, record, and ONE trace under jit: handing the table the
    # lowering of the first compiled call traced nothing a second time
    assert runs[0] == 3
    (entry,) = step.entries.values()
    key = entry["program"]
    rec = wd._programs[key]
    assert rec["key"] == repr(("to_static", "lazy_text_step"))
    # two more compiled calls have run: no text yet, nothing parsed
    assert rec["producer"] is not None and rec["text"] is None
    assert rec["instructions"] is None
    table = wd.program_scopes()
    assert rec["producer"] is None and rec["text"] is None
    assert table[key]["error"] is None
    assert table[key]["module"] == "jit_compiled_fn"
    paths = {wd.scope_path(op)
             for op in table[key]["instructions"].values()}
    assert _has(paths, "block/mlp") and _has(paths, "bwd/block/mlp")
    # and the step goes on as before, still on its one trace
    assert float(step(x).numpy()) < losses[0]
    assert runs[0] == 3


def test_a_dropped_step_leaves_its_pairs_and_pins_nothing():
    """Without anyone asking for the table: once the step and its model
    are dropped, no parameter's array is alive, and the record holds
    the parsed pairs (the benchmark reads it after ``close()``), not
    its producer."""
    paddle.seed(6)
    step, lin, _runs = _lazy_step("dropped_step")
    x = paddle.to_tensor(np.ones((2, 8), "float32"))
    for _ in range(3):
        step(x)
    (entry,) = step.entries.values()
    rec = wd._programs[entry["program"]]
    assert rec["producer"] is not None
    weight = weakref.ref(lin.weight.value)
    tensor = weakref.ref(lin.weight)
    del step, lin, entry, _runs
    gc.collect()
    assert weight() is None and tensor() is None
    assert rec["producer"] is None and rec["error"] is None
    paths = {wd.scope_path(op) for op in rec["instructions"].values()}
    assert _has(paths, "bwd/block/mlp")


def test_steps_of_one_name_keep_a_record_each():
    paddle.seed(8)
    x = paddle.to_tensor(np.ones((2, 8), "float32"))
    keys = []
    for _ in range(2):
        step, lin, _runs = _lazy_step("forward")
        for _ in range(3):
            step(x)
        (entry,) = step.entries.values()
        keys.append(entry["program"])
    assert keys[0] != keys[1]
    table = wd.program_scopes()
    assert all(table[k]["key"] == repr(("to_static", "forward"))
               and table[k]["instructions"] for k in keys)


def test_two_engines_keep_their_own_decode_record():
    """``("decode",)`` of one engine does not take the other's place,
    and ``/debug/programs`` of each shows its own."""
    engines = []
    for hidden in (32, 48):
        paddle.seed(7)
        cfg = TransformerLMConfig(vocab_size=97, hidden_size=hidden,
                                  num_layers=1, num_heads=4,
                                  max_seq_len=64, dropout=0.0)
        m = GPTForCausalLM(cfg)
        m.eval()
        eng = ServingEngine(m, num_slots=2, block_size=8)
        eng.add_request(np.arange(5) % 97, max_new_tokens=2)
        eng.run()
        engines.append(eng)
    try:
        table = wd.program_scopes()
        reports = [e.programs_report()["programs"] for e in engines]
    finally:
        for e in engines:
            e.close()
    key = repr(("decode",))
    owners = {rec["owner"] for rec in table.values() if rec["key"] == key}
    assert {e.watchdog.id for e in engines} <= owners
    sigs = [r[key]["signature"] for r in reports]
    assert sigs[0] != sigs[1]
    for eng, rep in zip(engines, reports):
        events = {e["key"]: e for e in eng.watchdog.events()}
        assert set(rep) == set(events)
        assert rep[key]["signature"] == events[key]["signature"]


def test_the_table_keeps_the_newest_records_only(monkeypatch):
    monkeypatch.setattr(wd, "_programs", {})
    monkeypatch.setattr(wd, "_PROGRAMS_MAX", 2)
    keys = [wd.note_program(("t_cap", i), text="") for i in range(4)]
    assert list(wd._programs) == keys[2:]
    # a replacement counts as the newest
    wd.note_program(("t_cap", 2), text="")
    wd.note_program(("t_cap", 4), text="")
    assert list(wd._programs) == [keys[2], repr(("t_cap", 4))]


def test_training_table_holds_the_backward_under_its_forward(train_step):
    key = train_step[3]
    paths = {wd.scope_path(op) for op in
             wd.program_scopes()[key]["instructions"].values()}
    for scope in ("block/attn", "block/mlp", "bwd/block/mlp",
                  "bwd/block/attn", "optimizer/step"):
        assert _has(paths, scope), scope
    assert _has(paths, "bwd/lm_head") or _has(paths, "bwd/loss")
    # a backward op is under "bwd" first, then the forward's names
    assert any(p[:3] == ("bwd", "block", "mlp") for p in paths)


def test_eager_nodes_keep_no_scope_and_jit_nodes_their_forwards():
    from paddle_tpu.core import trace as trace_mod
    a = paddle.to_tensor(np.ones((2, 2), "float32"), stop_gradient=False)
    with profiler.device_scope("outer"):
        b = a * 2.0
    assert b._grad_node[0].scope is None
    with trace_mod.trace_guard(trace_mod.TraceContext("jit")):
        with profiler.device_scope("outer"), profiler.device_scope("in"):
            c = a * 2.0
            d = c + 1.0
    assert c._grad_node[0].scope == ("outer", "in")
    assert d._grad_node[0].scope is c._grad_node[0].scope   # shared


def test_device_scope_keeps_a_stack_per_thread():
    assert profiler.current_scopes() == ()
    seen = {}
    with profiler.device_scope("a"):
        with profiler.device_scope("b/c"):
            seen["in"] = profiler.current_scopes()
            t = threading.Thread(target=lambda: seen.update(
                other=profiler.current_scopes()))
            t.start()
            t.join()
        seen["mid"] = profiler.current_scopes()
    assert seen == {"in": ("a", "b/c"), "other": (), "mid": ("a",)}
    assert profiler.current_scopes() == ()
    with profiler.record_scope("optimizer/step"):
        assert profiler.current_scopes() == ("optimizer/step",)

    def f(x):
        with profiler.device_scope("named"):
            return jnp.sin(x)
    text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    assert "named/sin" in text


# ------------------------------------------------------------ the parser
EVENT = ("%fusion.95 = s32[24,1,1]{0,2,1:T(1,128)S(1)} fusion(s32[24]{0:"
         "T(128)} %pos.1), kind=kLoop, calls=%fused_computation.142")
LINE = ("  ROOT %fusion.95 = s32[24,1,1]{0,2,1:T(1,128)S(1)} fusion(%pos.1)"
        ", kind=kLoop, calls=%fused_computation.142, metadata={op_name="
        "\"jit(paged_decode)/jit(main)/attn/kv_write/add\" stack_frame_id=4}")


def test_instruction_key_of_a_text_line_meets_a_trace_events():
    assert wd.instruction_key(EVENT) == wd.instruction_key(LINE) \
        == "%fusion.95 = s32[24,1,1] fusion"
    tup = ("%copy-start = (bf16[8,2048]{1,0:T(8,128)(2,1)S(1)}, bf16[8,2048]"
           "{1,0}, u32[]{:S(2)}) copy-start(bf16[8,2048]{1,0} %p), x=1")
    assert wd.instruction_key(tup) == \
        "%copy-start = (bf16[8,2048], bf16[8,2048], u32[]) copy-start"
    assert wd.instruction_key("HloModule jit_f, entry={...}") is None
    assert wd.instruction_key("}") is None


@pytest.mark.parametrize("op_name,path", [
    ("jit(paged_decode)/jit(main)/while/body/closed_call/attn/kv_write/"
     "dynamic_update_slice", ("attn", "kv_write")),
    ("jit(compiled_fn)/jit(main)/bwd/block/mlp/transpose(jvp())/"
     "dot_general", ("bwd", "block", "mlp")),
    ("jit(f)/jit(main)/embed/jvp(jit(_take))/gather", ("embed",)),
    ("jit(f)/mlp/jit(gelu)/tanh", ("mlp",)),
    ("mut_cap_arrays[3]", ()),
    ("", ()),
])
def test_scope_path_leaves_out_what_is_jaxs_own(op_name, path):
    assert wd.scope_path(op_name) == path


def test_parse_walks_loop_bodies_and_stays_out_of_fusions():
    def f(x, w):
        with profiler.device_scope("attn"):
            y = x @ w
            with profiler.device_scope("kv_write"):
                z = jnp.tanh(y) + 1

        def body(c, _):
            with profiler.device_scope("mlp"):
                return jnp.sin(c) @ w, None
        c, _ = jax.lax.scan(body, z, None, length=3)
        return c.sum()
    text = jax.jit(f).lower(jnp.ones((8, 8)), jnp.ones((8, 8))) \
        .compile().as_text()
    module, ins = wd.parse_program_text(text)
    assert module == "jit_f"
    paths = {k: wd.scope_path(v) for k, v in ins.items()}
    assert ("mlp",) in paths.values()            # inside the while body
    assert ("attn", "kv_write") in paths.values()
    # instructions INSIDE a fused computation are no ops of their own
    assert not any(k.startswith("%tanh") for k in ins)
    assert any(k.endswith(" while") for k in ins)
    assert any(v == "" for v in ins.values())    # known, no scope


def test_two_scopes_for_one_instruction_read_ambiguous():
    key = "%fusion.7 = f32[8] fusion"
    wd.note_program("t_a", text=None, producer=lambda: "")
    table = {
        "a": {"module": "jit_a", "instructions": {
            key: "jit(a)/attn/add", "%x = f32[] add": "jit(a)/mlp/add"}},
        "b": {"module": "jit_b", "instructions": {
            key: "jit(b)/mlp/add", "%x = f32[] add": "jit(b)/mlp/add"}},
    }
    assert wd.ambiguous_instructions(table) == {key}
    report = wd.programs_report()
    assert report["programs"]["t_a"]["instructions"] == 0
    wd._programs.pop("t_a")


def test_a_later_program_takes_its_keys_place():
    text = jax.jit(lambda x: x + 1).lower(jnp.ones(4)).compile().as_text()
    wd.note_program(("t_replace",), text="not a program")
    wd.note_program(("t_replace",), text=text, signature="sig")
    rec = wd.program_scopes()[repr(("t_replace",))]
    assert rec["signature"] == "sig" and rec["instructions"]
    # a producer that fails leaves an error and no instructions
    wd.note_program(("t_replace",), producer=lambda: 1 / 0)
    rec = wd.program_scopes()[repr(("t_replace",))]
    assert rec["instructions"] == {} and "ZeroDivisionError" in rec["error"]
    wd._programs.pop(repr(("t_replace",)))
