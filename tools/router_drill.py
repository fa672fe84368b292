#!/usr/bin/env python
"""router_drill — the kill-a-replica gate for the fleet router.

Spawns N replica subprocesses (tests/router_replica_worker.py: same
seeded tiny GPT each, EngineGateway + ``POST /v1/generate``), routes
seeded traffic over the wire, and proves the router's failover
promise the hard way:

  1. **reference wave** — all replicas up; every request completes;
     its greedy streams are the parity oracle;
  2. **failover wave** — identical traffic with seeded PR-9
     ``router_dispatch`` faults armed, and one replica SIGKILLed the
     moment it has requests in flight. PASS iff 100% of admitted,
     non-shed requests complete, every stream is bit-exact vs the
     reference, the survivors end with zero queued requests / zero
     occupied slots, and their compile counters did not move (zero
     steady-state compiles under failover). The wave also audits the
     distributed traces (ISSUE 18): every failed-over request must
     remain ONE trace — the replay's survivor-side spans land under
     the ORIGINAL trace id with a router/failover annotation;
  3. **no-failover baseline** — the same kill against a
     ``max_retries=0`` router: the drill DEMANDS lost requests here
     (if losing a replica were free, the failover machinery would be
     dead weight) and names the lost rids.

Exit 0 iff completion 100% + parity + no leaks (and the baseline
demonstrably lost the dead replica's in-flight work); exit 1 names
the lost/mismatched rids. One JSON line per wave on stdout, RESULT
line last — the same scriptable-gate discipline as chaos_sweep.py.

``--kill prefill`` runs the DISAGGREGATED flavor: replica 0 comes up
as the prefill tier, the rest as decode (warmed KV
export/import programs), wave 1 must complete through real handoffs
(``disagg.handoffs > 0``), and wave 2 SIGKILLs the PREFILL replica
mid-handoff — every request must still complete bit-exact via the
journaled first token (or a full monolithic replay on a decode
survivor when hop 1 never finished), with zero leaked blocks and
zero steady-state compiles on both tiers.

    python tools/router_drill.py              # 3 replicas, 12 reqs
    python tools/router_drill.py --fast       # the tier-1 cell
    python tools/router_drill.py --fast --kill prefill   # disagg cell
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_WORKER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "router_replica_worker.py")


def _spawn(idx, role=None):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["ROUTER_REPLICA_ID"] = f"dr{idx}"
    env.setdefault("ROUTER_PORT", "0")
    if role is not None:
        env["ROUTER_ROLE"] = role
    # stderr goes to a file, not a pipe: nobody reads it while the
    # worker lives, and a pipe that fills (a runtime that warns once
    # per program it loads from the compile cache fills 64 KB before
    # the ready line) blocks the worker for good
    err = tempfile.TemporaryFile(mode="w+")
    proc = subprocess.Popen(
        [sys.executable, _WORKER], env=env, stdout=subprocess.PIPE,
        stderr=err, text=True)
    proc.err_file = err
    return proc


def _ready(proc, timeout=180.0):
    box = {}

    def read():
        box["line"] = proc.stdout.readline()

    t = threading.Thread(target=read, daemon=True)
    t.start()
    t.join(timeout)
    line = box.get("line")
    if not line:
        proc.kill()
        proc.wait()
        proc.err_file.seek(0)
        err = proc.err_file.read()[-2000:]
        raise RuntimeError(
            f"replica worker never became ready:\n{err}")
    return json.loads(line)


def _get(url, path, timeout=3.0):
    with urllib.request.urlopen(url + path, timeout=timeout) as resp:
        return json.loads(resp.read().decode("utf-8"))


def _compiles(url):
    """Sum of the replica's ``serving_compiles_total`` series from its
    /metrics.json (``{name: {values: {labelkey: value}}}`` shape)."""
    fam = _get(url, "/metrics.json").get("serving_compiles_total")
    if fam is None:
        raise RuntimeError(
            "replica exposes no serving_compiles_total — the "
            "steady-state compile audit has nothing to audit")
    return sum(fam["values"].values())


def _prompts(seed, n, vocab=97):
    import numpy as np
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab, (int(rs.randint(4, 8)),))
            .astype(int).tolist() for _ in range(n)]


def _route_wave(router, prompts, max_new, timeout=600.0):
    tickets = [router.submit(p, max_new) for p in prompts]
    return [t.result(timeout=timeout) for t in tickets]


def _wait_inflight(urls, deadline_s=30.0):
    """Block until SOME replica has occupied slots — the moment a
    SIGKILL is guaranteed to strand in-flight requests. Returns its
    url."""
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        for u in urls:
            try:
                st = _get(u, "/debug/state", timeout=1.0)
            except Exception:   # noqa: BLE001 - replica mid-warmup
                continue
            if st.get("slot_occupancy", 0) > 0 \
                    or st.get("queue_depth", 0) > 0:
                return u
        time.sleep(0.01)
    return None


def _leak_audit(url, rid, failures):
    st = _get(url, "/debug/state")
    if st.get("queue_depth", 0) != 0 \
            or st.get("slot_occupancy", 0) != 0 \
            or st.get("held_exports", 0) != 0:
        failures.append(
            f"leak on {rid}: queue_depth={st.get('queue_depth')} "
            f"slot_occupancy={st.get('slot_occupancy')} "
            f"held_exports={st.get('held_exports')}")
    pool = (st.get("prefix_cache") or {}).get("pool") or {}
    # indexed prefix blocks are CACHE, not leaks — live counts
    # only blocks some slot still references
    if pool.get("live_blocks", 0) != 0:
        failures.append(
            f"leaked blocks on {rid}: "
            f"live_blocks={pool.get('live_blocks')}")


def run_drill(replicas=3, requests=12, max_new=16, seed=5,
              fault_rate=0.1, kill="replica", out=sys.stdout):
    from paddle_tpu.serving.resilience.chaos import (FaultPlan,
                                                     FaultSpec)
    from paddle_tpu.serving.router import (HTTPTransport, Router,
                                           RouterConfig)

    disagg = kill == "prefill"
    roles = (["prefill"] + ["decode"] * (replicas - 1)) if disagg \
        else [None] * replicas
    procs = [_spawn(i, role=r) for i, r in enumerate(roles)]
    failures = []
    try:
        infos = [_ready(p) for p in procs]
        urls = [f"http://127.0.0.1:{i['port']}" for i in infos]
        rids = [i["replica_id"] for i in infos]
        by_url = dict(zip(urls, rids))
        prompts = _prompts(seed, requests)

        def transports(active_urls):
            return [HTTPTransport(u, replica_id=by_url[u],
                                  timeout_s=120.0)
                    for u in active_urls]

        def cfg(max_retries):
            return RouterConfig(max_retries=max_retries,
                                refresh_s=0.1, backoff_base_s=0.05,
                                backoff_max_s=0.5, seed=seed)

        # in disagg mode the steady-state compile audit covers the
        # HANDOFF traffic too: baseline every replica before wave 1
        compiles_w0 = {u: _compiles(u) for u in urls} if disagg \
            else {}

        # ---- wave 1: reference (no kill) — the parity oracle
        router = Router(transports(urls), config=cfg(max_retries=3))
        ref = _route_wave(router, prompts, max_new)
        w1_state = router.state()
        router.close()
        ref_ok = sum(1 for r in ref if r["ok"])
        w1_line = {"wave": "reference", "ok": ref_ok,
                   "total": requests}
        if disagg:
            w1_line["handoffs"] = w1_state["disagg"]["handoffs"]
            w1_line["wire_bytes"] = w1_state["disagg"]["wire_bytes"]
        print(json.dumps(w1_line), file=out, flush=True)
        if ref_ok != requests:
            bad = [(r["rid"], r.get("reason")) for r in ref
                   if not r["ok"]]
            failures.append(
                f"reference wave incomplete: {ref_ok}/{requests} "
                f"{bad}")
            return failures
        if disagg:
            if w1_state["disagg"]["handoffs"] == 0:
                failures.append(
                    "disagg reference wave completed without a "
                    "single KV handoff — the two-hop path never ran")
            # the prefill tier is about to die: audit it NOW (zero
            # leaked blocks, zero steady-state compiles under
            # handoff traffic)
            _leak_audit(urls[0], rids[0], failures)
            after0 = _compiles(urls[0])
            if after0 != compiles_w0[urls[0]]:
                failures.append(
                    f"steady-state compiles on prefill tier "
                    f"{rids[0]}: {compiles_w0[urls[0]]} -> {after0}")
        ref_streams = [r["tokens"] for r in ref]

        # ---- wave 2: failover — SIGKILL mid-traffic + seeded
        # router_dispatch faults; identical prompts, 100% + parity
        # demanded
        survivors = urls[1:]
        compiles_before = {u: compiles_w0[u] for u in survivors} \
            if disagg else {u: _compiles(u) for u in survivors}
        plan = FaultPlan(seed=seed, faults={
            "router_dispatch": FaultSpec(rate=fault_rate)})
        router = Router(transports(urls), config=cfg(max_retries=4),
                        chaos=plan)
        tickets = [router.submit(p, max_new) for p in prompts]
        victim = urls[0]
        # kill the victim the moment it holds in-flight work (it is
        # a placement target like any other; if traffic hasn't hit
        # it yet, wait for the router to load-balance onto it)
        _wait_inflight([victim], deadline_s=30.0)
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait(timeout=30)
        res = [t.result(timeout=600.0) for t in tickets]
        state = router.state()
        router.close()
        ok = [r for r in res if r["ok"]]
        shed = [r for r in res if r.get("shed")]
        lost = [r["rid"] for r in res
                if not r["ok"] and not r.get("shed")]
        mismatch = [r["rid"] for i, r in enumerate(res)
                    if r["ok"] and r["tokens"] != ref_streams[i]]
        failmoves = state["counters"]["failovers"]
        w2_line = {
            "wave": "failover", "ok": len(ok), "shed": len(shed),
            "lost": lost, "parity_mismatch": mismatch,
            "failovers": failmoves,
            "retries": state["counters"]["retries"],
            "killed": by_url[victim]}
        if disagg:
            w2_line["handoffs"] = state["disagg"]["handoffs"]
            w2_line["handoff_failures"] = \
                state["disagg"]["handoff_failures"]
        # distributed-trace audit (ISSUE 18): a failed-over request
        # must remain ONE trace — the replay's spans land under the
        # ORIGINAL trace id (minted at admission, carried by the
        # journal through every dispatch attempt), annotated with a
        # router/failover span. The victim's ring died with it, so
        # assembly joins the router's recorder with the SURVIVORS'
        # /debug/traces — the spans of an attempt that moved TO a
        # survivor must appear under the same id (an attempt that
        # moved to the victim, after a seeded dispatch fault and
        # before the kill landed, left its spans in the ring that
        # died). A survivor that cannot be scraped is its own
        # failure, not a forked trace.
        from paddle_tpu.observability.trace import TraceAssembler
        asm = TraceAssembler()
        asm.add_recorder(router.trace)
        for u in survivors:
            try:
                asm.scrape(u, timeout=120.0)
            except Exception as e:   # noqa: BLE001 - named below
                failures.append(
                    f"could not scrape {by_url[u]}/debug/traces: {e}")
        failed_over = [t for t in asm.assemble_all()
                       if any(s["name"] == "router/failover"
                              for s in t.spans)]
        w2_line["traced_failovers"] = len(failed_over)
        if failmoves and not failed_over:
            failures.append(
                f"router counted {failmoves} failovers but no "
                f"assembled trace carries a router/failover span")
        survivor_rids = {by_url[u] for u in survivors}
        for t in failed_over:
            moved_to = {s.get("attrs", {}).get("to") for s in t.spans
                        if s["name"] == "router/failover"}
            if moved_to & survivor_rids and not (
                    {s["replica"] for s in t.spans} & survivor_rids):
                failures.append(
                    f"failed-over trace {t.trace_id} has no "
                    f"survivor-side spans under the original trace "
                    f"id — the replay forked the trace")
        print(json.dumps(w2_line), file=out, flush=True)
        if lost:
            failures.append(f"failover wave lost rids: {lost}")
        if mismatch:
            failures.append(
                f"greedy parity broken for rids: {mismatch}")
        if len(ok) + len(shed) != requests:
            failures.append("failover wave accounting does not add up")
        # leak + steady-state-compile audit on the survivors
        for u in survivors:
            _leak_audit(u, by_url[u], failures)
            after = _compiles(u)
            if after != compiles_before[u]:
                failures.append(
                    f"steady-state compiles on {by_url[u]}: "
                    f"{compiles_before[u]} -> {after}")

        # ---- wave 3: no-failover baseline — the kill MUST hurt
        base_urls = survivors
        router = Router(transports(base_urls),
                        config=cfg(max_retries=0))
        tickets = [router.submit(p, max_new) for p in prompts]
        victim = base_urls[0]
        _wait_inflight([victim], deadline_s=30.0)
        procs[1].send_signal(signal.SIGKILL)
        procs[1].wait(timeout=30)
        res = [t.result(timeout=600.0) for t in tickets]
        router.close()
        base_lost = [r["rid"] for r in res
                     if not r["ok"] and not r.get("shed")]
        print(json.dumps({
            "wave": "baseline_no_failover",
            "ok": sum(1 for r in res if r["ok"]),
            "shed": sum(1 for r in res if r.get("shed")),
            "lost": base_lost, "killed": by_url[victim]}),
            file=out, flush=True)
        if not base_lost:
            failures.append(
                "baseline (max_retries=0) lost nothing — the kill "
                "was not observed mid-flight; drill inconclusive")
        return failures
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:   # noqa: BLE001 - teardown
                pass


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="kill-a-replica drill: exit 0 iff 100% "
                    "completion + greedy parity + no leaks")
    parser.add_argument("--replicas", type=int, default=3)
    parser.add_argument("--requests", type=int, default=12)
    parser.add_argument("--max-new", type=int, default=16)
    parser.add_argument("--seed", type=int, default=5)
    parser.add_argument("--fault-rate", type=float, default=0.1,
                        help="seeded router_dispatch fault rate for "
                             "the failover wave")
    parser.add_argument("--kill", choices=("replica", "prefill"),
                        default="replica",
                        help="replica: SIGKILL a monolithic replica "
                             "(the classic drill); prefill: 1P+ND "
                             "disaggregated topology, SIGKILL the "
                             "prefill tier mid-handoff")
    parser.add_argument("--fast", action="store_true",
                        help="the tier-1 cell: 3 replicas, fewer/"
                             "shorter requests")
    args = parser.parse_args(argv)
    if args.fast:
        args.requests = min(args.requests, 8)
        args.max_new = min(args.max_new, 12)
    if args.replicas < 3:
        parser.error("the drill needs >= 3 replicas (one killed per "
                     "chaos wave, one survivor to finish the work)")
    t0 = time.monotonic()
    failures = run_drill(replicas=args.replicas,
                         requests=args.requests,
                         max_new=args.max_new, seed=args.seed,
                         fault_rate=args.fault_rate, kill=args.kill)
    verdict = "PASS" if not failures else "FAIL"
    print(json.dumps({"result": verdict,
                      "failures": failures,
                      "wall_s": round(time.monotonic() - t0, 1)}),
          flush=True)
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
