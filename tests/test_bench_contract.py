"""Emission contract of the repo's chip-facing scripts on a host with
no chip (this suite runs on the CPU): bench.py, bench_serving.py (full
mode) and chip_smoke.py exit non-zero and print NO result — there is no
cached number and no fallback backend. bench_serving.py --smoke is the
CPU rehearsal of the serving sections: it runs, and its line names the
CPU as its device."""
import glob
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, script), *args],
        env=env, capture_output=True, text=True, timeout=170)


@pytest.mark.parametrize("script", ["bench.py", "bench_serving.py"])
def test_bench_refuses_without_chip_no_metric_line(script):
    """No chip -> non-zero exit and not one JSON line on stdout (the
    removed behavior: a cached value first, again on failure, rc 0)."""
    res = _run(script)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert not [ln for ln in res.stdout.splitlines()
                if ln.strip().startswith("{")], res.stdout


def test_chip_smoke_fails_on_cpu_without_verdict():
    res = _run("chip_smoke.py")
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok": true' not in res.stdout
    assert not res.stdout.strip()


def test_bench_serving_smoke_emits_contract_line_rc0(tmp_path):
    """bench_serving.py --smoke: a live CPU measurement in seconds,
    emitting the serving_decode_tokens_per_sec JSON line in bench.py's
    artifact-backed format (value > 0, vs_baseline = engine over
    sequential generate, artifact path on disk), rc 0."""
    smoke_glob = os.path.join(_ROOT, "bench_artifacts",
                              "serving_smoke_*.json")
    before = set(glob.glob(smoke_glob))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # fast beats so the run is long enough to capture several ledger-
    # attributed heartbeat lines (the wedge-attribution satellite)
    env["BENCH_HEARTBEAT_SECS"] = "2"
    # this bench run shares the host with the rest of tier-1, so its
    # wall clocks measure suite contention — the rows go to a scratch
    # ledger (asserted below), never into the repo ledger that
    # tools/perf_diff.py gates real runs against
    scratch_ledger = tmp_path / "perf_ledger.jsonl"
    env["BENCH_LEDGER_PATH"] = str(scratch_ledger)
    _repo_ledger = os.path.join(_ROOT, "bench_artifacts",
                                "perf_ledger.jsonl")
    repo_size = os.path.getsize(_repo_ledger) \
        if os.path.exists(_repo_ledger) else None
    try:
        res = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "bench_serving.py"),
             "--smoke"],
            env=env, capture_output=True, text=True, timeout=240)
        assert res.returncode == 0, res.stderr[-500:]
        lines = [json.loads(ln) for ln in res.stdout.splitlines()
                 if ln.strip().startswith("{")]
        assert lines, res.stdout
        last = lines[-1]
        assert last["metric"] == "serving_decode_tokens_per_sec"
        assert last["unit"] == "tokens/sec" and last["value"] > 0
        assert last["source"] == "live-smoke"
        # the rehearsal says where it ran
        assert last["device"] == {"platform": "cpu", "kind": "cpu",
                                  "count": 8}
        assert last["vs_baseline"] > 0
        art = os.path.join(_ROOT, last["artifact"])
        with open(art) as fh:
            evidence = json.load(fh)
        assert evidence["tokens_per_sec"] == last["value"]
        assert evidence["workload"]["tokens"] > 0
        # serving hot-path observability (PR 2): grouped prefill,
        # KV-donation status, dispatch-vs-sync wall split — in the
        # engine snapshot AND the deep-queue scenario section
        snap = evidence["serving_metrics"]
        assert set(snap["kv_donation"]) >= {"enabled", "effective"}
        assert snap["dispatch_s"] >= 0 and snap["sync_s"] >= 0
        assert snap["prefill_requests"] >= snap["prefills"] > 0
        # PR 3 observability sections: latency percentiles from the
        # bounded reservoirs, and the attributed compile log
        lp = evidence["latency_percentiles"]
        assert set(lp) == {"ttft", "request_latency", "queue_wait"}
        for entry in lp.values():
            assert set(entry) == {"count", "p50_ms", "p90_ms", "p99_ms"}
            assert entry["count"] > 0
            assert entry["p50_ms"] <= entry["p90_ms"] <= entry["p99_ms"]
        wd = evidence["watchdog"]
        assert wd["compiles_total"] == snap["compiles"] > 0
        assert all(e["call_site"] and e["signature"]
                   for e in wd["events"])   # every compile attributed
        # PR 4 request-level sections: SLO/goodput accounting under
        # the configured targets...
        slo = evidence["slo"]
        assert set(slo) >= {"config", "requests", "attained",
                            "attainment", "violations",
                            "goodput_tokens", "total_tokens",
                            "goodput_fraction", "window"}
        assert slo["config"]["slo_ttft_ms"] is not None
        assert slo["requests"] == snap["requests_completed"] > 0
        assert 0 <= slo["goodput_tokens"] <= slo["total_tokens"]
        assert slo["total_tokens"] == snap["tokens_generated"]
        assert set(slo["window"]) == {"ttft", "tpot", "request_latency"}
        for entry in slo["window"].values():
            assert set(entry) == {"count", "p50_ms", "p90_ms", "p99_ms"}
        # ...the device cost model (graceful nulls on non-reporting
        # backends — flops/bytes DO report on CPU)...
        cm = evidence["cost_model"]
        assert set(cm) >= {"device", "executables",
                           "executables_with_cost",
                           "decode_flops_per_step", "peak_flops",
                           "estimated_mfu", "device_memory"}
        assert len(cm["executables"]) == wd["compiles_total"]
        assert cm["executables_with_cost"] > 0
        assert cm["decode_flops_per_step"] > 0
        # ...and sampled flight-recorder lifecycle traces with the
        # full enqueue->retire event chain
        traces = evidence["request_traces"]
        assert traces
        for tr in traces:
            assert tr["reason"] in ("eos", "max_tokens")
            names = [e["event"] for e in tr["events"]]
            assert names[0] == "enqueued" and names[-1] == "retired"
            assert "first_token" in names and "admitted" in names
            ts = [e["t"] for e in tr["events"]]
            assert ts == sorted(ts)          # lifecycle is monotone
        # PR 6 shared-prefix scenario: the paged pool's radix prefix
        # cache vs the legacy pool on identical prefix-sharing
        # traffic — the acceptance bar is >= 1.3x TTFT, the cache
        # counters must show the tail-only prefill actually happened,
        # and the timed wave must stay zero-recompile under paging
        sp = evidence["shared_prefix"]
        assert set(sp) >= {"requests", "prefix_tokens",
                           "paged_ttft_p50_ms", "nonpaged_ttft_p50_ms",
                           "ttft_improvement", "paged_tokens_per_sec",
                           "nonpaged_tokens_per_sec",
                           "goodput_improvement", "prefix_cache",
                           "prefill_accounting",
                           "steady_state_new_compiles", "watchdog"}
        assert sp["ttft_improvement"] >= 1.3, sp
        pc = sp["prefix_cache"]
        assert pc["hits"] > 0 and pc["cached_tokens"] > 0
        assert pc["cached_tokens"] > pc["computed_tokens"]
        assert pc["pool"]["indexed_blocks"] > 0
        acct = sp["prefill_accounting"]
        assert acct["prefix_cached_tokens"] == pc["cached_tokens"]
        assert sp["steady_state_new_compiles"] == 0
        assert sp["watchdog"]["warmed"] is True
        assert last["shared_prefix_ttft_x"] == sp["ttft_improvement"]
        # PR 13 cache observatory section: measured hit rate, the MRC
        # with its predicted-vs-measured agreement at current capacity
        # (the estimator's live acceptance check), hot-prefix digest,
        # savings attribution, and the probe-measured admission cost
        cache = sp["cache"]
        assert set(cache) >= {"hit_rate", "mrc",
                              "predicted_hit_rate_at_capacity",
                              "predicted_vs_measured_abs_err",
                              "heat_top", "savings", "evictions",
                              "thrash_reinserts", "sampled",
                              "overhead"}
        assert cache["hit_rate"] > 0.5   # shared prefix = mostly hits
        assert [p["factor"] for p in cache["mrc"]] == \
            [0.5, 1.0, 2.0, 4.0]
        # the MRC estimate at CURRENT capacity must agree with the
        # live measured hit rate (tolerance covers the spatial
        # sampler's small-population noise on the smoke workload)
        assert cache["predicted_vs_measured_abs_err"] is not None
        assert cache["predicted_vs_measured_abs_err"] <= 0.15, cache
        assert cache["heat_top"], "the shared prefix must rank hot"
        assert cache["heat_top"][0]["tokens_saved"] > 0
        assert cache["savings"]["saved_tokens"] > 0
        assert cache["savings"]["saved_ttft_ms"] > 0
        cache_over = cache["overhead"]
        assert cache_over["per_admission_us"] > 0
        assert cache_over["overhead_frac"] is not None
        assert cache_over["overhead_frac"] < 0.05   # the contract bar
        # healthy drain: no eviction-then-reinsert churn
        assert cache["thrash_reinserts"] == 0
        # PR 7 overload scenario: identical oversubscribed traffic
        # (chunked long prompts + sampled fraction) under FIFO vs the
        # SLO-feedback load-shedding policy — the acceptance bars are
        # >= 1.3x goodput (SLO-met tokens/sec) and a materially
        # reduced TTFT tail (p99 cut >= 1.3x, p99/p50 spread smaller),
        # with zero steady-state recompiles under chunked prefill on
        # BOTH engines (watchdog-verified)
        ovl = evidence["overload"]
        assert set(ovl) >= {"requests", "oversubscription",
                            "capacity_rps", "arrival_rate_rps",
                            "slo_ttft_ms", "prefill_chunk", "fifo",
                            "slo_feedback", "goodput_improvement",
                            "ttft_p99_improvement",
                            "ttft_tail_improvement"}
        assert 2.0 <= ovl["oversubscription"] <= 10.0
        assert ovl["goodput_improvement"] >= 1.3, ovl
        assert ovl["ttft_p99_improvement"] >= 1.3, ovl
        fifo_sec, fb_sec = ovl["fifo"], ovl["slo_feedback"]
        # the material-tail bar, sample-size-robust form: the
        # policy's WORST served TTFT sits at (or below) FIFO's
        # MEDIAN — the whole served distribution moved, not just the
        # p99 point (the p99/p50 spread ratios are reported in the
        # artifact; their pointwise comparison is too noisy to pin on
        # ~25 served CPU-smoke samples)
        assert fb_sec["ttft_p99_ms"] < fifo_sec["ttft_p50_ms"] * 1.15
        assert ovl["ttft_tail_improvement"] is not None
        # the policy sheds under overload, FIFO never does; shed
        # requests are the goodput trade the scheduler section owns
        assert fb_sec["shed_requests"] > 0
        assert fifo_sec["shed_requests"] == 0
        assert fb_sec["scheduler"]["policy"] == "slo_feedback"
        assert fifo_sec["scheduler"]["policy"] == "fifo"
        assert fb_sec["scheduler"]["shed_total"] == \
            fb_sec["shed_requests"]
        # chunked prefill actually ran on both engines, and the
        # steady state stayed compile-free under it
        for sec in (fifo_sec, fb_sec):
            assert sec["scheduler"]["chunked_requests"] > 0
            assert sec["scheduler"]["prefill_chunks"] > \
                sec["scheduler"]["chunked_requests"]
            assert sec["steady_state_new_compiles"] == 0
            assert sec["watchdog"]["warmed"] is True
        assert last["overload_goodput_x"] == \
            ovl["goodput_improvement"]
        # PR 9 chaos scenario: identical traffic + identical seeded
        # fault schedule, hardened vs unhardened. The acceptance bars:
        # the hardened engine completes >= 95% of requests bit-exact
        # with the unfaulted reference (parity through rollback /
        # retry / supervisor restart), leaks zero slots/blocks with
        # the conservation audit passing after every recovery
        # (health_audit_every=1), and shows zero steady-state compiles
        # outside supervisor restarts — while the unhardened baseline
        # demonstrably wedges AND leaks on the same seed
        cz = evidence["chaos"]
        assert set(cz) >= {"requests", "seed", "fault_plan",
                           "hardened", "unhardened",
                           "completion_rate", "parity_ok"}
        assert cz["fault_plan"]["seed"] == cz["seed"]
        hz = cz["hardened"]
        assert hz["wedged"] is False
        assert hz["completion_rate"] >= 0.95, hz
        assert cz["completion_rate"] == hz["completion_rate"]
        assert hz["parity_ok"] is True and cz["parity_ok"] is True
        assert sum(hz["faults_injected"].values()) > 0   # chaos ran
        assert hz["slots_leaked"] == 0
        assert hz["live_blocks_at_idle"] == 0
        assert hz["conservation_ok"] is True
        # the deterministic decode-failure burst forces at least one
        # supervisor recovery, and steady state stays compile-free
        # outside the restart's reopened warmup window
        assert hz["supervisor_restarts"] >= 1
        assert hz["steady_state_new_compiles"] == 0
        assert hz["health"]["detectors"]["kv_block_leak"] == 0
        assert hz["health"]["restarts"] == hz["supervisor_restarts"]
        uz = cz["unhardened"]
        assert uz["wedged"] is True and uz["error"]
        assert uz["completion_rate"] < hz["completion_rate"]
        assert uz["slots_leaked"] > 0 or uz["live_blocks_leaked"] > 0
        assert last["chaos_completion_rate"] == cz["completion_rate"]
        # PR 8 health observatory: a clean smoke bench must fire ZERO
        # anomalies across every scenario engine (the false-positive
        # acceptance bar), the per-scenario rollups must be present,
        # and the observatory's measured step-time overhead must stay
        # small (<2% is the target; the CI bound is loose because CPU
        # timers are noisy)
        health = evidence["health"]
        assert set(health) >= {"anomalies_total", "scenarios",
                               "incident_dir", "incidents", "overhead"}
        assert health["anomalies_total"] == 0, health
        scen = health["scenarios"]
        assert {"headline", "deep_queue_grouped", "deep_queue_pr1",
                "shared_prefix_paged", "shared_prefix_nonpaged",
                "overload_fifo", "overload_slo_feedback"} <= set(scen)
        for name, s in scen.items():
            assert s["enabled"] is True, name
            assert s["healthy"] is True and s["anomalies_total"] == 0, \
                (name, s)
            assert s["ledger_steps"] > 0, name
        ohd = health["overhead"]
        assert ohd["health_on_s"] > 0 and ohd["health_off_s"] > 0
        # direct per-tick measurement over a representative low-ms
        # step: the target is <2% (measured ~1.5% on the smoke
        # runner); the CI bound carries slack for shared-runner noise
        assert ohd["overhead_frac"] < 0.05, ohd
        assert ohd["per_step_overhead_us"] < 150, ohd
        assert ohd["step_wall_us"] > 1000, ohd   # representative step
        # the headline snapshot carries the same health rollup
        assert snap["health"]["enabled"] is True
        assert snap["health"]["anomalies_total"] == 0
        # PR 11 fleet observatory: three in-process replicas under a
        # live FleetPoller — all up and healthy, zero fleet anomalies,
        # bucket-wise merged percentiles populated, and the probe-
        # measured scrape-side + engine-side poll costs under the
        # same <2%-of-step bar as the health tick (<5% with runner
        # slack)
        fp = evidence["fleet_poll"]
        assert set(fp) >= {"replicas", "interval_s", "polls",
                           "verdicts", "fleet", "latency",
                           "anomalies_total", "detectors", "overhead"}
        assert fp["replicas"] == 3 and fp["polls"] > 0
        assert fp["fleet"]["up"] == 3 and fp["fleet"]["down"] == 0
        assert fp["fleet"]["healthy"] is True
        assert all(v == "up" for v in fp["verdicts"].values())
        assert fp["anomalies_total"] == 0, fp["detectors"]
        assert fp["fleet"]["tokens_generated"] > 0
        lat = fp["latency"]["ttft"]
        assert lat["count"] > 0 and lat["p50_ms"] <= lat["p99_ms"]
        fohd = fp["overhead"]
        assert fohd["scrape_side_per_poll_ms"] > 0
        assert fohd["engine_side_per_poll_us"] > 0
        assert fohd["overhead_frac"] < 0.05, fohd
        # the headline snapshot carries the replica identity section
        assert snap["replica"]["replica_id"]
        assert snap["replica"]["uptime_s"] > 0
        # PR 14 fleet router: goodput over 1/2/3 in-process replicas,
        # the kill-a-replica drill (routed journal-replay failover =
        # 100% completion with greedy parity; the max_retries=0
        # baseline records what the dead replica's in-flight work
        # cost), and the self-timed dispatch overhead under the same
        # <5%-with-runner-slack bar as every observatory probe
        rt = evidence["router"]
        assert set(rt) >= {"replicas", "requests",
                           "goodput_tokens_per_sec", "goodput_x",
                           "goodput_attempts", "failover",
                           "no_failover_baseline", "overhead"}
        assert rt["replicas"] == 3
        assert set(rt["goodput_tokens_per_sec"]) == {"1", "2", "3"}
        assert all(v > 0 for v in
                   rt["goodput_tokens_per_sec"].values())
        # the noise re-measure loop ran 1-3 scaling attempts and
        # kept the best ratio
        assert 1 <= len(rt["goodput_attempts"]) <= 3
        # in-process replicas share one CPU: the bar is sanity (the
        # router must not DESTROY throughput), not linear scaling
        assert rt["goodput_x"] > 0.5, rt
        fo = rt["failover"]
        assert fo["completion"] == 1.0, fo   # nothing lost, ever
        assert fo["lost"] == []
        assert fo["parity_ok"] is True       # bit-exact continuation
        assert fo["failovers"] >= 1          # the kill actually moved
        assert fo["killed"]
        base = rt["no_failover_baseline"]
        assert 0.0 <= base["completion"] <= 1.0
        assert base["completion"] <= fo["completion"]
        rohd = rt["overhead"]
        assert rohd["seconds_total"] >= 0 and rohd["ops"] > 0
        assert rohd["overhead_frac"] is not None
        assert rohd["overhead_frac"] < 0.05, rohd
        assert last["router_failover_completion"] == fo["completion"]
        # PR 15 decode-kernel A/B probe: the paged_xla arm vs the
        # Pallas paged-attention arm on identical traffic — streams
        # bit-exact (the greedy contract; on CPU the kernel runs in
        # interpret mode, so speed is not pinned, parity is), both
        # arms report their honest roofline layout, and the headline
        # line carries the speedup ratio
        dk = evidence["decode_kernel"]
        assert set(dk) >= {"interpret", "requests", "parity_ok",
                           "xla", "pallas", "speedup_x"}
        assert dk["parity_ok"] is True
        assert dk["requests"] > 0 and dk["speedup_x"] > 0
        assert dk["xla"]["layout"] == "paged_xla"
        assert dk["pallas"]["layout"] == "paged_pallas"
        assert dk["pallas"]["model_gather_factor"] == 1.0
        for arm in (dk["xla"], dk["pallas"]):
            assert arm["decode_avg_ms"] > 0
            # the smoke runs on the CPU, which has no peaks: a
            # device-referenced fraction is never reported there
            assert arm["roofline_fraction"] is None
        # interpret-mode runs emit the A/B ratio under an honest key
        # ("speedup" is reserved for real-backend runs) — the smoke
        # runner is CPU, so the interpret key is the expected one
        dk_key = ("decode_kernel_interp_ratio_x" if dk["interpret"]
                  else "decode_kernel_speedup_x")
        assert last[dk_key] == dk["speedup_x"]
        assert ("decode_kernel_speedup_x" in last) != dk["interpret"]
        # PR 16 speculative decoding A/B: the spec arm vs plain decode
        # on identical shared-prefix traffic — greedy streams bit-exact
        # between the arms (the hard contract), real drafting on the
        # structured smoke traffic (acceptance > 0), tokens-per-
        # dispatch at least break-even, and BOTH arms hold the
        # zero-steady-state-compile invariant under watchdog raise.
        # The 1.3x-effective / 1.2x-goodput bench-run bars live in
        # ROADMAP, not here: CI pins what must never regress, the
        # ledger tracks the trajectory.
        sv = evidence["speculative"]
        assert set(sv) >= {"requests", "new_tokens", "spec_k",
                           "parity_ok", "off", "spec",
                           "acceptance_rate",
                           "effective_tokens_per_dispatch",
                           "goodput_x"}
        assert sv["parity_ok"] is True
        assert sv["acceptance_rate"] is not None
        assert sv["acceptance_rate"] > 0
        assert sv["effective_tokens_per_dispatch"] is not None
        assert sv["effective_tokens_per_dispatch"] >= 1.0
        assert sv["goodput_x"] > 0
        for arm in (sv["off"], sv["spec"]):
            assert arm["warmed"] is True
            assert arm["steady_state_compiles"] == 0
            assert arm["tokens_per_sec"] > 0
        assert sv["spec"]["verify_steps"] > 0
        assert sv["spec"]["drafted_tokens"] > 0
        assert sv["spec"]["drafted_tokens"] == \
            sv["spec"]["accepted_tokens"] + sv["spec"]["rejected_tokens"]
        assert last["spec_goodput_x"] == sv["goodput_x"]
        # PR 17 prefill/decode disaggregation: the SAME long-prompt/
        # short-decode wave through 1P+2D (KV-block streaming over
        # the router's two-hop path) vs 3 monolithic replicas — the
        # disagg arm must beat the monolithic arm on BOTH TTFT p99
        # and decode goodput, every request must ride a real KV
        # handoff, and the wire unit (bytes per prefill token) is a
        # shape-determined constant the ledger tracks
        dz = evidence["disagg"]
        assert set(dz) >= {"topology", "requests", "monolithic",
                           "disagg", "ttft", "decode_goodput_x",
                           "wire", "attempts"}
        # the noise re-measure loop ran 1-3 paired attempts and kept
        # the best pair; each attempt reports [ttft_x, goodput_x]
        assert 1 <= len(dz["attempts"]) <= 3
        assert all(len(a) == 2 for a in dz["attempts"])
        assert dz["topology"] == {"prefill": 1, "decode": 2,
                                  "monolithic_baseline": 3}
        assert dz["ttft"]["improvement_x"] > 1.0, dz
        assert dz["decode_goodput_x"] > 1.0, dz
        assert dz["ttft"]["disagg_p99_ms"] > 0
        wire = dz["wire"]
        assert wire["handoffs"] >= dz["requests"]   # two-hop path ran
        assert wire["bytes_total"] > 0 and wire["tokens"] > 0
        assert wire["bytes_per_token"] > 0
        assert last["disagg_decode_goodput_x"] == \
            dz["decode_goodput_x"]
        # PR 18 distributed tracing: the disagg wave's TTFT must
        # explain itself — every measured request assembled into ONE
        # complete cross-replica trace (all nine canonical segments),
        # the unattributed gap under 10% of the trace window, and the
        # kv-handoff price (export+wire+import+decode-admission)
        # extracted for the ledger. The span-recording overhead probe
        # stays under the 5% bar (2% is the target on a quiet host).
        bd = dz["ttft_breakdown"]
        assert bd["enabled"] is True
        assert bd["count"] == bd["complete"] == dz["requests"]
        # the unattributed gap: <10% is the quiet-host target (the
        # bench re-measures attempts past it and keeps the cleanest
        # trace), but on a contended 1-core runner the gap measures
        # REAL scheduler stalls landing between segment boundaries —
        # observed regimes: ~0.03 quiet, 0.11-0.31 under suite/host
        # contention with the segments and completeness intact. The
        # contract bar carries that runner slack; a genuine
        # attribution break (an unspanned wire edge, a lost segment)
        # reads ~0.5+ and the per-segment count pins below stay exact.
        assert bd["gap_frac"] < 0.35, bd
        assert bd["kv_handoff_overhead_ms"] > 0
        segs = bd["segments"]
        for name in ("router/queue", "router/dispatch",
                     "prefill/queue", "prefill/compute", "kv/export",
                     "kv/wire", "kv/import", "decode/queue",
                     "decode/first_step"):
            assert segs[name]["count"] == dz["requests"], name
        assert bd["span_overhead"]["frac_of_ttft"] < 0.05, bd
        assert last["kv_handoff_overhead_ms"] == \
            bd["kv_handoff_overhead_ms"]
        # PR 19 tenant observatory: fair and adversarial two-tenant
        # arms through live engines + fleet pollers — per-tenant sums
        # equal the global counters EXACTLY on both pool kinds, the
        # noisy_neighbor detector fires on the adversarial arm and
        # ONLY there (the false-positive bar), a 10k-unique-id flood
        # stays bounded at max_tenants+1 series, and the per-request
        # attribution cost stays under the probe bar (<2% target,
        # <5% contract-tested with runner slack)
        tz = evidence["tenants"]
        assert tz["conservation_ok"] is True
        assert tz["conservation_ok_frac"] == 1.0
        arms = tz["arms"]
        assert arms["fair"]["pool"] == "legacy"
        assert arms["adversarial"]["pool"] == "paged"
        for arm in arms.values():
            assert arm["conservation"] and \
                all(arm["conservation"].values()), arm["conservation"]
        det = tz["detector"]
        assert det["fired_only_adversarial"] is True
        assert det["fair_noisy_fired"] == 0
        assert det["adversarial_noisy_fired"] >= 1
        assert arms["adversarial"]["last_verdicts"][
            "noisy_neighbor"]["tenant"] == "hog"
        fl = tz["flood"]
        assert fl["bounded_ok"] is True
        assert fl["series_per_family"] == fl["max_tenants"] + 1
        ov = tz["overhead"]
        assert ov["per_request_us"] > 0
        assert ov["overhead_frac"] is not None
        assert ov["overhead_frac"] < 0.05, ov
        assert last["tenant_conservation_ok"] is True
        # heartbeat wedge attribution: beats name the last ledger step
        # and the phase-relative step rate
        beats = [ln for ln in res.stderr.splitlines()
                 if ln.startswith("# heartbeat") and " step=" in ln]
        assert beats, res.stderr[-2000:]
        assert all("step_rate=" in ln for ln in beats)
        dq = evidence["deep_queue"]
        assert dq["group_sizes_used"] and \
            max(dq["group_sizes_used"]) > 1   # grouped prefill fired
        assert set(dq["kv_donation"]) >= {"enabled", "effective"}
        assert dq["dispatch_s"] >= 0 and dq["sync_s"] >= 0
        assert dq["vs_pr1_engine"] > 0
        assert dq["steady_state_new_compiles"] == 0
        assert last["deep_queue_vs_pr1"] == dq["vs_pr1_engine"]
        # the deep-queue engine declared warmup after its first drain,
        # so its watchdog section IS the zero-recompile invariant
        dq_wd = dq["watchdog"]
        assert dq_wd["warmed"] is True
        assert dq_wd["steady_state_compiles"] == 0
        assert dq["latency_percentiles"]["ttft"]["count"] > 0
        # any earlier lines are provisional cached ones, marked so
        for ln in lines[:-1]:
            assert ln["source"] == "cached" and "note" in ln
        # the run's perf-ledger rows landed in the scratch ledger —
        # valid rows, attributed to this run — and the repo ledger
        # was not touched (suite-contention wall clocks must never
        # enter the gated cross-run trajectory)
        from paddle_tpu.observability.perf import read_rows
        lrows, lskipped = read_rows(str(scratch_ledger))
        assert lrows and lskipped == 0
        assert all(r["run_id"] == os.path.basename(art)
                   for r in lrows)
        # the two PR-19 tenant rows made it into the ledger: the
        # overhead probe and the exact-conservation verdict (the
        # latter deterministic — counter math carries no host noise,
        # any move off 1.0 is an attribution leak)
        by_metric = {r["metric"]: r for r in lrows}
        assert by_metric["tenant_attribution_overhead_frac"][
            "scenario"] == "tenants"
        cons_row = by_metric["tenant_conservation_ok"]
        assert cons_row["value"] == 1.0
        assert cons_row["measurement"] == "deterministic"
        repo_ledger = os.path.join(_ROOT, "bench_artifacts",
                                   "perf_ledger.jsonl")
        if repo_size is not None:
            assert os.path.getsize(repo_ledger) == repo_size
    finally:
        for f in set(glob.glob(smoke_glob)) - before:
            os.unlink(f)  # this test's artifact is noise in git
