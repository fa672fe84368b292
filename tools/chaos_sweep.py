#!/usr/bin/env python
"""Seeded chaos sweep over the serving engine: N seeds x fault kinds,
exit nonzero on any leak / hang / parity break.

Every cell of the matrix runs the SAME smoke workload on a hardened
engine (bounded retry + supervisor) under one armed fault site (plus
an "all" cell arming the default mix), then verifies the contract the
resilience layer owes:

  * **no hang** — the drain finishes within a step budget;
  * **no leak** — every slot free afterwards, and a full
    ``check_conservation()`` audit of the pool's blocks passes;
  * **parity** — every completed request's token stream is bit-exact
    with the unfaulted reference drain (greedy replay correctness
    through rollback, retry and supervisor restart);
  * **determinism** — the cell is re-run at the same seed and must
    reproduce the identical fault log and streams.

Output: one JSON line per cell plus a summary line; exit 1 on any
failure (the CI gate). Tier-1 self-runs ``--fast`` (one seed) via
tests/test_resilience.py; a nightly can widen ``--seeds``.

Usage: python tools/chaos_sweep.py [--seeds N] [--fast]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the per-site arming each cell uses: rates high enough that every
# recovery path actually runs during a ~40-request smoke drain
_SITE_RATES = {
    "prefill_dispatch": 0.25,
    "chunk_dispatch": 0.25,
    "decode_dispatch": 0.10,
    "transfer": 0.10,
    "block_exhaustion": 0.15,
    "callback": 0.30,
    "step_latency": {"rate": 0.05, "latency_s": 0.001},
}
_MAX_STEPS = 3000      # hang budget: a clean drain needs ~100 steps


def _build_model():
    import paddle_tpu as paddle
    from paddle_tpu.text.models import (GPTForCausalLM,
                                        TransformerLMConfig)
    paddle.seed(11)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _workload(n_requests=16):
    import numpy as np
    rs = np.random.RandomState(5)
    lengths = rs.randint(3, 20, n_requests)
    return [(rs.randint(0, 97, (int(n),)).astype(np.int64),
             int(rs.randint(3, 8))) for n in lengths]


def _drain(model, specs, chaos=None, chunk=None, spec=False):
    """One engine drain; returns (streams, engine, steps, fault_log).
    The engine's decode attention is its own choice (the Pallas
    kernel wherever ``kernel_viable`` says yes: here, under forced
    interpret)."""
    from paddle_tpu.serving import ServingEngine
    eng = ServingEngine(
        model, num_slots=4, bucket_min=8, speculative=spec,
        prefill_chunk=chunk, chaos=chaos, max_dispatch_retries=3,
        supervisor_cooldown_s=0.0, health_audit_every=8)
    reqs = [eng.add_request(p, max_new_tokens=k,
                            on_token=lambda r, t: None)
            for p, k in specs]
    steps = 0
    while eng.step():
        steps += 1
        if steps > _MAX_STEPS:
            return None, eng, steps, None   # hang
    streams = [list(r.generated) for r in reqs]
    log = eng.chaos.fault_log() if eng.chaos is not None else None
    return streams, eng, steps, log


def _check_cell(site, seed, model, specs, reference, chunk,
                spec=False):
    """Run one (site, seed) cell twice; returns a result dict with
    ok=False and a reason on any contract break."""
    from paddle_tpu.serving.resilience import FaultPlan
    faults = dict(_SITE_RATES) if site == "all" \
        else {site: _SITE_RATES[site]}

    def plan():
        return FaultPlan(seed=seed, faults=faults)

    out = {"site": site, "seed": seed, "spec": spec, "ok": True}
    streams, eng, steps, log = _drain(model, specs, chaos=plan(),
                                      chunk=chunk, spec=spec)
    out["steps"] = steps
    out["paged_attn"] = eng.paged_attn
    if streams is None:
        return dict(out, ok=False, reason=f"hang: > {_MAX_STEPS} steps")
    res = eng.metrics.snapshot()["resilience"]
    out["faults"] = res["faults_injected"]
    out["retries"] = res["dispatch_retries"]
    out["restarts"] = res["supervisor_restarts"]
    # leak checks: every slot free, block conservation intact
    if eng.pool.free_count + len(eng.pool.quarantined) \
            != eng.pool.num_slots:
        return dict(out, ok=False, reason="slot leak after drain")
    try:
        eng.pool.check_conservation()
    except AssertionError as e:
        return dict(out, ok=False, reason=f"block conservation: {e}")
    if eng.pool.live_blocks > 0:
        return dict(out, ok=False, reason="live blocks at idle")
    # parity: completed requests match the unfaulted reference
    bad = [i for i, (got, want) in enumerate(zip(streams, reference))
           if got and got != want]
    if bad:
        return dict(out, ok=False,
                    reason=f"parity break on requests {bad}")
    incomplete = sum(1 for got, want in zip(streams, reference)
                     if got != want)
    out["incomplete"] = incomplete   # aborted-after-retries allowed,
    if incomplete > len(specs) // 4:  # but not wholesale failure
        return dict(out, ok=False,
                    reason=f"{incomplete}/{len(specs)} incomplete")
    # determinism: same seed => identical fault log and streams
    streams2, _, _, log2 = _drain(model, specs, chaos=plan(),
                                  chunk=chunk, spec=spec)
    if log2 != log:
        return dict(out, ok=False, reason="fault log not deterministic")
    if streams2 != streams:
        return dict(out, ok=False, reason="streams not deterministic")
    return out


def _check_handoff_cell(seed, model, specs, reference):
    """Disaggregated KV-handoff cell (ISSUE 17): every request
    prefills on a prefill-role engine, crosses the wire as a
    serialized block payload, and decodes on a decode-role engine —
    with a seeded fraction of payloads corrupted in flight (digest
    flip / dropped frame / garbled base64). The contract: corruption
    raises the TYPED wire error and never poisons the decode pool (a
    clean retry of the same handoff must succeed and stay bit-exact
    with the monolithic reference), both tiers end block-clean, and
    the same seed reproduces the same corruption schedule and
    streams."""
    import copy

    import numpy as np

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.kv_wire import KVWireError

    def corrupt(rs, payload):
        bad = copy.deepcopy(payload)
        kind = int(rs.randint(3))
        if kind == 0:
            f = bad["frames"][int(rs.randint(len(bad["frames"])))]
            f["digest"] = (f["digest"] + 1) % (1 << 32)
        elif kind == 1:
            bad["frames"].pop()
        else:
            bad["frames"][0]["k"] = "!!notb64"
        return bad

    def run_once():
        pe = ServingEngine(model, num_slots=4, bucket_min=8,
                           role="prefill")
        de = ServingEngine(model, num_slots=4, bucket_min=8,
                           role="decode")
        rs = np.random.RandomState(seed)
        streams, faults = [], 0
        try:
            for p, k in specs:
                req = pe.add_request(p, max_new_tokens=1, hold_kv=True)
                pe.run()
                payload = pe.export_kv(req.rid)
                if rs.rand() < 0.4:
                    faults += 1
                    try:
                        de.import_kv(corrupt(rs, payload),
                                     max_new_tokens=int(k))
                    except KVWireError:
                        pass
                    else:
                        return None, faults, \
                            "corrupted import did not raise KVWireError"
                dreq = de.import_kv(payload, max_new_tokens=int(k))
                de.run()
                streams.append(list(dreq.generated))
            for eng, tier in ((pe, "prefill"), (de, "decode")):
                if eng._held_exports:
                    return None, faults, f"held-export leak: {tier}"
                try:
                    eng.pool.check_conservation()
                except AssertionError as e:
                    return None, faults, \
                        f"{tier} block conservation: {e}"
                if eng.pool.live_blocks > 0:
                    return None, faults, f"live blocks at idle: {tier}"
        finally:
            pe.close()
            de.close()
        return streams, faults, None

    out = {"site": "kv_handoff", "seed": seed, "ok": True}
    streams, faults, reason = run_once()
    out["faults"] = {"kv_wire_corruption": faults}
    if reason:
        return dict(out, ok=False, reason=reason)
    bad = [i for i, (got, want) in enumerate(zip(streams, reference))
           if got != want]
    if bad:
        return dict(out, ok=False,
                    reason=f"handoff parity break on requests {bad}")
    streams2, faults2, reason2 = run_once()
    if reason2:
        return dict(out, ok=False, reason=f"rerun: {reason2}")
    if faults2 != faults:
        return dict(out, ok=False,
                    reason="corruption schedule not deterministic")
    if streams2 != streams:
        return dict(out, ok=False, reason="streams not deterministic")
    return out


def _patrolled(check, *args, **kwargs):
    """Run one cell with the lock patrol armed: every seeded fault
    schedule doubles as a race/deadlock drill. A lock-order or
    held-across-dispatch finding fails the cell with the finding JSON
    in the cell row."""
    from paddle_tpu.analysis import lock_patrol

    with lock_patrol() as patrol:
        result = check(*args, **kwargs)
        findings = patrol.findings()
    if findings:
        patrol_json = [f.to_dict() for f in findings]
        if result.get("ok"):
            result = dict(result, ok=False,
                          reason="lock patrol findings",
                          patrol=patrol_json)
        else:   # keep the cell's own failure reason, attach the drill
            result = dict(result, patrol=patrol_json)
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    parser.add_argument("--seeds", type=int, default=3)
    parser.add_argument("--fast", action="store_true",
                        help="one seed, reduced site matrix (tier-1)")
    args = parser.parse_args(argv)

    sites = ["prefill_dispatch", "decode_dispatch", "transfer",
             "callback", "block_exhaustion", "chunk_dispatch", "all"]
    seeds = [1] if args.fast else list(range(1, args.seeds + 1))
    if args.fast:
        sites = ["prefill_dispatch", "decode_dispatch", "chunk_dispatch",
                 "all"]

    model = _build_model()
    specs = _workload(12 if args.fast else 16)
    # one long prompt so chunk_dispatch cells exercise real chunking
    chunk = 8
    import numpy as np
    rs = np.random.RandomState(9)
    specs = specs + [(rs.randint(0, 97, (28,)).astype(np.int64), 4)]

    failures = 0
    cells = 0
    reference, _, _, _ = _drain(model, specs, chunk=chunk)
    assert reference is not None, "reference drain hung"
    for seed in seeds:
        for site in sites:
            cells += 1
            result = _patrolled(_check_cell, site, seed, model,
                                specs, reference, chunk)
            print(json.dumps(result), flush=True)
            if not result["ok"]:
                failures += 1
    # one decode-faulted cell per seed with the Pallas paged decode
    # kernel as the engine's choice (forced interpret makes
    # kernel_viable say yes on the CPU): retry/restart replay must stay
    # bit-exact through the kernel path too, against an unfaulted
    # reference on the same path
    from paddle_tpu.ops import paged_attention as paged_attn_mod
    paged_attn_mod._FORCE_INTERPRET[0] = True
    try:
        pallas_ref, eng, _, _ = _drain(model, specs, chunk=chunk)
        assert pallas_ref is not None, "pallas reference drain hung"
        assert eng.decode_layout == "paged_pallas", eng.decode_layout
        for seed in seeds:
            cells += 1
            result = _patrolled(_check_cell, "decode_dispatch", seed,
                                model, specs, pallas_ref, chunk)
            print(json.dumps(result), flush=True)
            if not result["ok"]:
                failures += 1
    finally:
        paged_attn_mod._FORCE_INTERPRET[0] = False
    # speculation-enabled cells per seed: decode faults now
    # hit k-token verify dispatches too (same "decode_dispatch" site),
    # and retry / supervisor-restart replay must stay bit-exact against
    # a SPEC-ENABLED unfaulted reference (which itself is bit-exact
    # with the plain reference by the acceptance construction — both
    # invariants break loudly here if either drifts). Longer
    # generations so the n-gram drafter actually proposes and verify
    # dispatches really carry drafts when the faults land.
    spec_specs = [(p, k + 8) for p, k in specs]
    spec_ref, _, _, _ = _drain(model, spec_specs, chunk=chunk,
                               spec=True)
    assert spec_ref is not None, "spec reference drain hung"
    for seed in seeds:
        cells += 1
        result = _patrolled(_check_cell, "decode_dispatch", seed,
                            model, spec_specs, spec_ref, chunk,
                            spec=True)
        print(json.dumps(result), flush=True)
        if not result["ok"]:
            failures += 1
    # disaggregated KV-handoff cells (ISSUE 17): seeded in-flight
    # corruption must surface as the typed wire error without
    # poisoning the decode pool, clean retries stay bit-exact with the
    # monolithic reference, and both tiers end block-clean
    for seed in seeds:
        cells += 1
        result = _patrolled(_check_handoff_cell, seed, model, specs,
                            reference)
        print(json.dumps(result), flush=True)
        if not result["ok"]:
            failures += 1
    print(json.dumps({"summary": True, "cells": cells,
                      "failures": failures}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
