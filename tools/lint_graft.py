#!/usr/bin/env python
"""Self-lint the repo's jitted entry points (paddle_tpu.analysis).

Builds the three kinds of compiled programs this framework ships —

  * ``serving_decode``   — a ServingEngine on a tiny GPT, drained once
    over shared-prefix prompts and warm-declared, linted via
    ``engine.lint()`` (f64-upcast / host-callback / donation over the
    decode jaxpr, dynamic-shape-risk over the engine's compile
    watchdog). The decode jaxpr threads the int32 block table, and
    the f64-upcast + donation passes must stay clean with that
    argument (the table is small and host-authored — donating it
    would be noise, and the donation pass's size floor keeps it
    silent);
  * ``paged_decode_pallas`` — the same engine with the Pallas
    paged decode-attention kernel as its decode attention (interpret
    mode forced, so ``kernel_viable`` chooses it on this CPU lint run):
    the decode jaxpr now embeds the ``pallas_call`` and the f64-upcast
    + donation passes must stay clean across its boundary (the kernel
    traces in 32-bit mode — pallas_compat — so an f64 leak here is a
    real finding, not noise);
  * ``chunked_prefill``  — a chunked-prefill + per-slot-sampling
    engine (``prefill_chunk=``, ``sampling=True``): the sampling
    decode of an engine that really chunked, linted via
    ``engine.lint()`` — it must stay f64/donation clean (a chunk is a
    prefill dispatch; ``lint`` has no prefill target);
  * ``spec_verify``      — a speculative-decoding engine
    (``speculative=True``): the k-token verify program
    (``engine.lint(program="spec_verify")``) and the plain decode it
    falls back to must all stay f64/donation clean — the verify
    flavor donates kc/vc/pos exactly like decode, shifted past the
    drafts/dlen host inputs;
  * ``kv_wire``          — a disaggregated KV handoff between a
    prefill-role and a decode-role engine: the ``kv_import``
    program is linted like any other jitted entry point, a SECOND
    handoff after ``declare_warmup`` must not compile (export/import
    are dispatch-only on the steady-state hot path), and the export
    program's device->host transfer must stay per-slot sized — an
    export whose outputs approach the full pool is a ``device_get``
    of the whole KV cache wearing a trench coat (error severity);
  * ``hapi_train_step``  — a hapi.Model static-adapter train step
    (forward + loss + backward + optimizer captured as ONE to_static
    program), linted via ``TracedFunction.lint()``;
  * ``to_static_sample`` — a @to_static function with tensor-bound
    control flow (the dy2static while/cond lowering path), linted the
    same way —

and prints every finding as JSON on stdout. Exit status: 0 when no
error-severity findings (warnings are reported but don't fail),
1 otherwise — wired into tier-1 via tests/test_analysis.py so the repo
stays self-clean.

Usage: python tools/lint_graft.py [--pretty]
"""
import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def lint_serving_decode():
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = ServingEngine(model, num_slots=4, block_size=8)
    rs = np.random.RandomState(0)
    shared = rs.randint(0, 97, (16,)).astype(np.int64)
    for n in (5, 9):
        engine.add_request(
            np.concatenate([shared,
                            rs.randint(0, 97, (n,)).astype(np.int64)]),
            max_new_tokens=4)
    engine.run()
    engine.declare_warmup()
    assert engine.metrics.snapshot()["prefix_cache"]["hits"] >= 1, \
        "decode lint target never exercised the prefix cache"
    return engine.lint()


def lint_paged_decode_pallas():
    import paddle_tpu as paddle
    from paddle_tpu.ops import paged_attention as paged_attn
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    # force interpret so the kernel_viable guard admits the kernel on
    # this CPU run and the decode program embeds the real pallas_call
    paged_attn._FORCE_INTERPRET[0] = True
    try:
        engine = ServingEngine(model, num_slots=4, block_size=8)
        rs = np.random.RandomState(0)
        for n in (5, 9):
            engine.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                               max_new_tokens=4)
        engine.run()
        engine.declare_warmup()
        assert engine.decode_layout == "paged_pallas", \
            "pallas lint target fell back to the XLA gather path"
        return engine.lint()
    finally:
        paged_attn._FORCE_INTERPRET[0] = False


def lint_chunked_prefill():
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = ServingEngine(model, num_slots=4, prefill_chunk=8,
                           sampling=True)
    rs = np.random.RandomState(0)
    for n in (5, 23, 40):       # two chunked, one grouped
        engine.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                           max_new_tokens=4)
    engine.add_request(rs.randint(0, 97, (30,)).astype(np.int64),
                       max_new_tokens=4, temperature=0.8, top_k=10)
    engine.run()
    engine.declare_warmup()
    sched = engine.metrics.snapshot()["scheduler"]
    assert sched["prefill_chunks"] >= 4, \
        "chunked-prefill lint target never actually chunked"
    # the sampling decode must stay f64/donation clean
    return engine.lint()


def lint_spec_verify():
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    paddle.seed(7)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = ServingEngine(model, num_slots=4, block_size=8,
                           speculative=True, spec_k=4)
    rs = np.random.RandomState(0)
    for n in (5, 9, 17):
        # greedy tiny-model decoding locks into cycles within a
        # few tokens — 16 new tokens reliably gives the n-gram
        # drafter self-matches, so verify steps actually dispatch
        engine.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                           max_new_tokens=16)
    engine.run()
    engine.declare_warmup()
    spec = engine.metrics.snapshot()["perf"]["spec"]
    assert spec["verify_steps"] >= 1, \
        "spec lint target never dispatched a verify step"
    # the verify program AND the plain-decode fallback it shares the
    # steady state with must both stay f64/donation clean
    return engine.lint(program="spec_verify") + engine.lint()


def lint_kv_wire():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.analysis import Finding
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving import engine as engine_mod
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    def build(role):
        paddle.seed(7)
        cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                                  num_layers=2, num_heads=4,
                                  max_seq_len=64, dropout=0.0)
        model = GPTForCausalLM(cfg)
        model.eval()
        return ServingEngine(model, num_slots=4, bucket_min=8,
                             block_size=8, role=role)

    pe, de = build("prefill"), build("decode")
    rs = np.random.RandomState(0)

    def handoff(n):
        req = pe.add_request(rs.randint(0, 97, (n,)).astype(np.int64),
                             max_new_tokens=1, hold_kv=True)
        pe.run()
        payload = pe.export_kv(req.rid)
        dreq = de.import_kv(payload, max_new_tokens=4)
        de.run()
        assert dreq.state == "done" and len(dreq.generated) == 4, \
            "kv_wire lint target never completed an imported decode"
        return payload

    handoff(13)                 # warm both tiers' handoff programs
    pe.warmup_kv_handoff()
    de.warmup_kv_handoff()
    pe.declare_warmup()
    de.declare_warmup()
    findings = []
    c0 = (pe.metrics.compiles, de.metrics.compiles)
    handoff(14)                 # different length, same prefill bucket
    c1 = (pe.metrics.compiles, de.metrics.compiles)
    if c1 != c0:
        findings.append(Finding(
            "kv_wire_steady_state", "error",
            "ServingEngine.export_kv/import_kv",
            f"a steady-state handoff compiled (prefill {c0[0]}->{c1[0]}, "
            f"decode {c0[1]}->{c1[1]}) — the KV wire path must be "
            f"dispatch-only after warmup_kv_handoff"))
    # the export program's device->host transfer must be ONE slot's
    # blocks, never the pool: abstract-eval the export and compare its
    # output bytes against the pool it reads from
    pool = pe.pool
    idx = np.zeros((pool.blocks_per_slot,), np.int32)
    out = jax.eval_shape(engine_mod._kv_export_fn, pool.kc, pool.vc, idx)
    out_bytes = sum(int(np.prod(o.shape)) * o.dtype.itemsize
                    for o in jax.tree_util.tree_leaves(out))
    pool_bytes = pool.kc.nbytes + pool.vc.nbytes
    if out_bytes * 2 > pool_bytes:
        findings.append(Finding(
            "kv_wire_transfer", "error",
            "serving.engine._kv_export_fn",
            f"export fetches {out_bytes} bytes against a "
            f"{pool_bytes}-byte pool — a per-slot slice should be a "
            f"small fraction; this is a device_get of the pool"))
    # the import program is a jitted entry point like any other: the
    # f64-upcast / host-callback / donation passes must stay clean
    findings += de.lint(program="kv_import")
    pe.close()
    de.close()
    return findings


def lint_hapi_train_step():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    paddle.seed(7)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10))
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.Adam(1e-3, parameters=net.parameters()),
        nn.CrossEntropyLoss())
    paddle.enable_static()
    try:
        rs = np.random.RandomState(7)
        for _ in range(3):  # eager -> record -> compiled
            x = rs.randn(8, 16).astype("float32")
            y = rs.randint(0, 10, (8, 1)).astype("int64")
            model.train_batch([x], [y])
        step = model._static_steps["train"]
        assert any(e["compiled"] is not None
                   for e in step.entries.values()), \
            "hapi train step never reached the compiled phase"
        return step.lint()
    finally:
        paddle.disable_static()


def lint_to_static_sample():
    import paddle_tpu as paddle

    @paddle.jit.to_static
    def sample(x, n):
        s = x * 0.0
        for _ in range(n):  # tensor bound -> ONE lax.while_loop program
            if s.sum() < 100.0:  # tensor pred -> lax.cond
                s = s + x
        return s

    xp = paddle.to_tensor(np.full((8,), 0.5, np.float32))
    for _ in range(3):  # eager -> record -> compiled
        sample(xp, paddle.to_tensor(np.int64(6)))
    assert any(e["compiled"] is not None
               for e in sample.entries.values()), \
        "to_static sample never reached the compiled phase"
    return sample.lint()


def lint_concurrency():
    """Static concurrency audit over the serving stack: cross-role
    unlocked writes (thread-role auditor) + live-buffer-to-dispatch
    (snapshot discipline, the PR-6 bug class). Pure AST — no engine
    builds, no jax dispatches."""
    from paddle_tpu.analysis import concurrency as cc

    return cc.audit_default()


TARGETS = {
    "serving_decode": lint_serving_decode,
    "paged_decode_pallas": lint_paged_decode_pallas,
    "chunked_prefill": lint_chunked_prefill,
    "spec_verify": lint_spec_verify,
    "kv_wire": lint_kv_wire,
    "hapi_train_step": lint_hapi_train_step,
    "to_static_sample": lint_to_static_sample,
    "concurrency": lint_concurrency,
}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--pretty", action="store_true",
                        help="indent the JSON report")
    parser.add_argument("--targets", nargs="*", choices=sorted(TARGETS),
                        default=sorted(TARGETS),
                        help="subset of entry points to lint")
    args = parser.parse_args(argv)

    from paddle_tpu.analysis import SEVERITIES, lint_passes

    findings = []
    for name in args.targets:
        for f in TARGETS[name]():
            d = f.to_dict()
            d["target"] = name
            findings.append(d)
    counts = {sev: sum(1 for f in findings if f["severity"] == sev)
              for sev in SEVERITIES}
    report = {
        "targets": list(args.targets),
        "passes": lint_passes(),
        "findings": findings,
        "counts": counts,
        "ok": counts.get("error", 0) == 0,
    }
    print(json.dumps(report, indent=2 if args.pretty else None))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
