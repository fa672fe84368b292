"""The one shell of the paged programs (``serving/paged/shell.py``):
every program a served architecture's builder hands the engine is, as
a jaxpr, what it was when each builder wrote the shell out for itself;
it opens the ``device_scope`` names it opened then; it is named as the
benchmark finds it; and the kernels of a decode program are resolved in
one place."""
import hashlib
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from paddle_tpu.observability.watchdog import scope_path  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402


# ----------------------------------------- the seven models, tiny sizes
def _gpt():
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig
    cfg = TransformerLMConfig(vocab_size=64, hidden_size=64, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    return GPTForCausalLM(cfg), dict(num_slots=3, block_size=8,
                                     max_len=64, buckets=[16])


def _latent():
    from tests.test_deepseek_v3 import _model
    return _model()[0], dict(num_slots=3, block_size=8, max_len=64,
                             buckets=[16])


def _hybrid():
    from tests.test_nemotron_h import _model
    return _model()[0], dict(num_slots=3, block_size=8, max_len=64,
                             buckets=[16])


def _mixed():
    from tests.test_mimo_v2 import _model
    return _model()[0], dict(num_slots=3, block_size=8, max_len=64,
                             buckets=[16])


def _looped():
    from tests.test_ouro import _model
    return _model(2)[0], dict(num_slots=3, block_size=8, max_len=64,
                              buckets=[16])


def _parallel():
    from tests.test_falcon_h1 import _model
    return _model()[0], dict(num_slots=3, block_size=8, max_len=64,
                             buckets=[16])


def _eva():
    from tests.test_evabyte import _model
    return _model()[0], dict(num_slots=2, block_size=4, max_len=128)


MODELS = {"gpt": _gpt, "latent": _latent, "hybrid": _hybrid,
          "eva": _eva, "mixed": _mixed, "looped": _looped,
          "parallel": _parallel}


def _program(model, program, sampling=False):
    """``(fn, args)``: a program as the engine holds it and the
    arguments the engine dispatches it with (``ServingEngine
    ._run_chunks``, ``._decode_dispatch_args``, ``._compact``)."""
    build, kw = MODELS[model]()
    eng = ServingEngine(build, sampling=sampling, **kw)
    pool = eng.pool
    if program == "decode":
        return eng._decode_fn, eng._decode_dispatch_args(pool)[0]
    if program == "compact":
        return eng._compact_fn, (eng.params, np.int32(0),
                                 pool.table_row(0)) + tuple(pool.arrays)
    # one bucket: the window of a model prefilled by windows, or the
    # one the engine was given
    width = eng.chunk_len or kw["buckets"][0]
    args = (eng.params, np.zeros((1, width), np.int32), np.int32(5),
            np.int32(0), np.int32(1), np.int32(1), pool.table_row(1),
            eng._toks, eng._pos) + tuple(pool.arrays)
    if sampling:
        args += (np.int32(7), np.float32(0.8), np.int32(4),
                 np.float32(0.9))
    return eng._prefill_fn, args


def _digest(fn, *args):
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]


# sha256(str(jax.make_jaxpr(program)(*args)))[:16] at the sizes above,
# taken on the parent commit (b5618e4: every builder with a shell of its
# own) with this same test code
DIGESTS = {
    ("gpt", "prefill", False): "6b1cf7b7ada52916",
    ("gpt", "prefill", True): "bb82e5beb0140390",
    ("gpt", "decode", False): "bfc0be992ec17538",
    ("gpt", "decode", True): "f2ac1337f0cf7faf",
    ("latent", "prefill", False): "3f1b5a985bf40e04",
    ("latent", "prefill", True): "8e721e2688d39e17",
    # PR 49's own (a MEANT change: the decode program forms a length
    # that counts only held blocks and a live write position, as the
    # other five do, and writes through ``latent_write_attention``)
    ("latent", "decode", False): "31433cb513ed06f1",
    ("latent", "decode", True): "7e62ef3c2c2e9129",
    ("hybrid", "prefill", False): "2211a68f08327650",
    ("hybrid", "prefill", True): "3b4cbbf1bcab0710",
    ("hybrid", "decode", False): "a18b63eca0d7b956",
    ("hybrid", "decode", True): "d8439a47c4ae3628",
    ("eva", "prefill", False): "892886c5379b6abe",
    ("eva", "prefill", True): "76089c8919a36773",
    ("eva", "decode", False): "9750eb155ccd3949",
    ("eva", "decode", True): "7e763818afc411c1",
    ("eva", "compact", False): "5523bd83534f25a4",
    ("mixed", "prefill", False): "a58ee6bb3286b5ba",
    ("mixed", "prefill", True): "15964d87194a5ef1",
    ("mixed", "decode", False): "8a49eeea53aeaf53",
    ("mixed", "decode", True): "1f18265550d6a17d",
    ("looped", "prefill", False): "78baa21f75ddffdd",
    ("looped", "prefill", True): "e5068df3c10ffa8b",
    ("looped", "decode", False): "debd7a7438ea6318",
    ("looped", "decode", True): "b4a9975f0d4b9f1c",
    # text.falcon_h1 through hybrid_programs.py (PR 48 brought the
    # program: these four are that PR's own, and the twelve of "hybrid"
    # and "looped" above are what shows that making hybrid_programs.py
    # take the block from the configuration changed neither's program)
    ("parallel", "prefill", False): "dd0d537655ae68a0",
    ("parallel", "prefill", True): "31a212ca68f8be36",
    ("parallel", "decode", False): "fed044f9e82da53d",
    ("parallel", "decode", True): "e67b858c3ccfbe81",
}


@pytest.mark.parametrize("model,program,sampling", sorted(DIGESTS))
def test_program_is_the_parents(model, program, sampling):
    fn, args = _program(model, program, sampling)
    # a jitted function's module is named after it, and the benchmark
    # finds a program's device time by its module (PERF.md section 3)
    assert fn.__name__ == "paged_" + program
    assert _digest(fn, *args) == DIGESTS[model, program, sampling]


# ----------------------------------------------------------- the scopes
def _walk(jaxpr, above=()):
    for eqn in jaxpr.eqns:
        name = f"{eqn.source_info.name_stack}/{eqn.primitive.name}"
        # less the names ``jnp.einsum`` gives its own products
        here = above + tuple(c for c in scope_path(name) if "->" not in c)
        yield here
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, here)


def _scopes(fn, args):
    """``{device_scope path: equations staged under it}`` of a program
    (``str(jaxpr)`` prints no scope): every equation's name stack below
    those of the loops and calls it sits in, which is what lowering
    writes into an instruction's ``op_name``, read as the programs'
    table reads that (``watchdog.scope_path``)."""
    paths = ["/".join(p) for p in _walk(jax.make_jaxpr(fn)(*args).jaxpr)]
    return {p: paths.count(p) for p in sorted(set(paths)) if p}


# what each program stages under which scope on the parent commit
# (b5618e4), by this same reading
SCOPES = {
    ("gpt", "prefill"): {
        "attn": 119, "attn/kv_gather": 14, "attn/kv_write": 55,
        "embed": 16, "lm_head": 24, "mlp": 31, "sample": 19},
    ("gpt", "decode"): {
        "attn": 54, "attn/kv_gather": 18, "attn/kv_write": 77,
        "embed": 12, "kv_write": 6, "lm_head": 17, "mlp": 31,
        "sample": 2},
    ("latent", "prefill"): {
        "embed": 5, "lm_head": 9, "mla/attn": 98,
        "mla/attn/kv_gather": 26, "mla/attn/kv_write": 26, "mla/out": 6,
        "mla/q_absorb": 148, "mlp": 16, "moe/experts": 162,
        "moe/router": 25, "moe/shared": 7, "sample": 19},
    # PR 49's own: the ``jnp`` block write (the CPU's arm) is staged
    # whole under ``kv_write``, the write position with it
    ("latent", "decode"): {
        "embed": 5, "lm_head": 10, "mla/attn": 66,
        "mla/attn/kv_gather": 26, "mla/attn/kv_write": 158,
        "mla/out": 12, "mla/q_absorb": 154, "mlp": 16,
        "moe/experts": 34, "moe/router": 25, "moe/shared": 7,
        "sample": 2},
    ("hybrid", "prefill"): {
        "attn/out": 6, "attn/paged": 98, "attn/paged/kv_gather": 28,
        "attn/paged/kv_write": 28, "attn/qkv": 30, "embed": 5,
        "lm_head": 10, "moe/experts": 310, "moe/router": 50,
        "moe/shared": 10, "sample": 19, "ssm/conv": 231,
        "ssm/in_proj": 87, "ssm/out": 66, "ssm/scan": 324,
        "ssm/scan/state_write": 42, "state_write": 10},
    ("hybrid", "decode"): {
        "attn/out": 6, "attn/paged": 66, "attn/paged/kv_gather": 36,
        "attn/paged/kv_write": 166, "attn/qkv": 30, "embed": 5,
        "lm_head": 10, "moe/experts": 54, "moe/router": 50,
        "moe/shared": 10, "sample": 2, "ssm/in_proj": 87, "ssm/out": 66,
        "ssm/scan": 261},
    ("eva", "prefill"): {
        "embed": 5, "eva/attn": 126, "eva/attn/eva/compact": 37,
        "eva/attn/kv_gather": 30, "eva/attn/kv_write": 14, "eva/out": 2,
        "eva/qkv": 54, "lm_head": 12, "mlp": 17, "sample": 19},
    ("eva", "decode"): {
        "embed": 5, "eva/attn": 60, "eva/attn/kv_gather": 18,
        "eva/attn/kv_write": 83, "eva/out": 2, "eva/qkv": 54,
        "lm_head": 12, "mlp": 17, "sample": 2},
    ("eva", "compact"): {
        "eva/compact": 35, "kv_gather": 14, "kv_write": 16},
    ("mixed", "prefill"): {
        "attn/out": 9, "attn/paged": 359, "attn/paged/kv_gather": 34,
        "attn/paged/kv_write": 208, "attn/qkv": 180, "embed": 5,
        "kv_write": 10, "lm_head": 10, "mlp": 16, "moe/experts": 324,
        "moe/router": 50, "sample": 19},
    ("mixed", "decode"): {
        "attn/out": 9, "attn/paged": 100, "attn/paged/kv_gather": 58,
        "attn/paged/kv_write": 210, "attn/paged/window": 41,
        "attn/paged/window/kv_write": 22, "attn/qkv": 180, "embed": 5,
        "lm_head": 10, "mlp": 16, "moe/experts": 68, "moe/router": 50,
        "sample": 2},
    ("looped", "prefill"): {
        "attn/out": 12, "attn/paged": 49, "attn/paged/kv_gather": 14,
        "attn/paged/kv_write": 14, "attn/qkv": 53, "embed": 5,
        "lm_head": 1, "loop/gate": 34, "loop/norm": 9, "mlp": 25,
        "sample": 19},
    ("looped", "decode"): {
        "attn/out": 12, "attn/paged": 31, "attn/paged/kv_gather": 18,
        "attn/paged/kv_write": 83, "attn/qkv": 53, "embed": 5,
        "lm_head": 1, "loop/gate": 59, "loop/norm": 9, "mlp": 25,
        "sample": 2},
    # PR 48's own: each branch under its enclosing scope, the accepted
    # scope names inside it
    ("parallel", "prefill"): {
        "branch/attn/attn/out": 3, "branch/attn/attn/paged": 49,
        "branch/attn/attn/paged/kv_gather": 14,
        "branch/attn/attn/paged/kv_write": 14, "branch/attn/attn/qkv": 46,
        "branch/mix": 2, "branch/ssm/ssm/conv": 77,
        "branch/ssm/ssm/in_proj": 31, "branch/ssm/ssm/out": 22,
        "branch/ssm/ssm/scan": 108, "branch/ssm/ssm/scan/state_write": 14,
        "embed": 6, "lm_head": 11, "mlp": 19, "sample": 19,
        "state_write": 10},
    ("parallel", "decode"): {
        "branch/attn/attn/out": 3, "branch/attn/attn/paged": 33,
        "branch/attn/attn/paged/kv_gather": 18,
        "branch/attn/attn/paged/kv_write": 83, "branch/attn/attn/qkv": 46,
        "branch/mix": 2, "branch/ssm/ssm/in_proj": 31,
        "branch/ssm/ssm/out": 22, "branch/ssm/ssm/scan": 87, "embed": 6,
        "lm_head": 11, "mlp": 19, "sample": 2},
}


@pytest.mark.parametrize("model,program", sorted(SCOPES))
def test_program_opens_the_parents_scopes(model, program):
    assert _scopes(*_program(model, program)) == SCOPES[model, program]


# ------------------------------------------------- the kernels, resolved
def _checks(monkeypatch, fails=()):
    """Three checks over real ops modules, none forced; the ones whose
    index is in ``fails`` do not hold."""
    from paddle_tpu.ops import moe_experts, paged_attention, ssm
    mods = (paged_attention, ssm, moe_experts)
    for ops in mods:
        monkeypatch.setattr(ops, "_FORCE_INTERPRET", [False])
    return mods, [
        (ops, f"kernel_{i}", "slots, width", (8 + i, "bfloat16"),
         lambda i=i: i not in fails) for i, ops in enumerate(mods)]


def test_resolve_the_cpu_runs_no_kernel_and_asks_no_check(monkeypatch):
    from paddle_tpu.serving.paged.shell import resolve_decode_kernels
    _, checks = _checks(monkeypatch)
    asked = []
    checks = [c[:4] + (lambda: asked.append(1),) for c in checks]
    assert resolve_decode_kernels(checks) is False and not asked


@pytest.mark.parametrize("forced", [0, 1, 2])
def test_resolve_an_interpreted_op_turns_the_kernels_on(monkeypatch,
                                                        forced):
    from paddle_tpu.serving.paged.shell import resolve_decode_kernels
    mods, checks = _checks(monkeypatch)
    monkeypatch.setattr(mods[forced], "_FORCE_INTERPRET", [True])
    assert resolve_decode_kernels(checks) is True


@pytest.mark.parametrize("fails", [(), (0,), (1,), (2,), (0, 2), (1, 2)])
def test_resolve_names_the_first_kernel_that_cannot(monkeypatch, fails):
    from paddle_tpu.serving.paged.shell import resolve_decode_kernels
    mods, checks = _checks(monkeypatch, fails)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if not fails:
        assert resolve_decode_kernels(checks) is True
        return
    first = fails[0]
    module = mods[first].__name__.rpartition(".")[2]
    with pytest.raises(ValueError) as e:
        resolve_decode_kernels(checks)
    assert str(e.value) == (
        f"kernel_{first} cannot take (slots, width) = ({8 + first}, "
        f"bfloat16): ops.{module}.kernel_viable")


def _builder_kernels(model):
    """A model's own resolution at its tests' tiny configuration."""
    from paddle_tpu.serving.paged import (eva_programs, hybrid_programs,
                                          latent_programs,
                                          looped_programs, mixed_programs)
    cfg = MODELS[model]()[0].cfg
    return {"latent": lambda: latent_programs.decode_kernels(cfg, 3, 8),
            "hybrid": lambda: hybrid_programs.decode_kernels(cfg, 3, 8),
            "parallel": lambda: hybrid_programs.decode_kernels(cfg, 3, 8),
            "mixed": lambda: mixed_programs.decode_kernels(cfg, 3, 8),
            "eva": lambda: eva_programs.decode_kernel(cfg, 4),
            "looped": lambda: looped_programs.decode_kernel(cfg, 8)}[model]


@pytest.mark.parametrize("model", sorted(set(MODELS) - {"gpt"}))
def test_a_builder_resolves_its_kernels_through_the_shell(monkeypatch,
                                                          model):
    """The builders that choose their kernels themselves, for every
    model that runs through one (the GPT's are the engine's choice): none on the CPU; where there is
    Mosaic, a tiny shape is refused in the shell's words."""
    resolve = _builder_kernels(model)
    assert resolve() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match=r"^\w+ cannot take \(.+\) = "
                       r"\(.+\): ops\.\w+\.kernel_viable$"):
        resolve()
