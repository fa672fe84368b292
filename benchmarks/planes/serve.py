"""Serving cells: a GPT behind ``ServingEngine`` + ``EngineGateway``,
driven from the client's side in one process.

The traffic's generator (``generators/<name>.py``) owns the loop: it
offers its requests through this plane's client methods, which call
``EngineGateway.submit(..., on_token=...)`` and stamp every token in the
callback while the gateway's own drive thread steps the engine (the path
``InProcessTransport`` and the router use). Latencies are taken from the
moment a request was DUE.

Only sizing comes from the configuration file; every tuning option of
the engine stays at the program's default.
"""
import contextlib
import gc
import threading
import time

import numpy as np

from benchmarks import harness, weights
from benchmarks.planes import gpt_program

# names the program gives its compiled serving programs (jit_<fn>);
# the trace reduction finds their executions by these
PROGRAMS = {"prefill": "paged_prefill", "decode": "paged_decode"}
HOST_SPANS = ("serving/", "bench/")


class Rec:
    """One request as the client sees it."""
    __slots__ = ("spec", "phase", "due", "sent", "accepted", "req",
                 "stamps", "error")

    def __init__(self, spec, due):
        self.spec, self.due = spec, due
        self.phase = spec.get("phase", "window")
        self.sent = self.accepted = self.req = self.error = None
        self.stamps = []

    @property
    def finished(self):
        return self.req is not None and self.req.done \
            and len(self.stamps) == self.spec["max_new"]


class ServeProgram:
    """The system under test, set up and warm."""

    def __init__(self, config, seed):
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.serving import ServingEngine
        from paddle_tpu.serving.router.transport import EngineGateway
        self.model_cfg, self.sizing = config["model"], config["sizing"]
        t0 = time.perf_counter()
        net = gpt_program.build_model(self.model_cfg)
        net.eval()
        paddle.amp.decorate(net, level="O2", dtype=config["precision"])
        w = weights.gpt_weights(seed, self.model_cfg, config["precision"])
        gpt_program.set_weights(net, w)
        del w
        t1 = time.perf_counter()
        self.engine = ServingEngine(net, **self.sizing)
        del net
        gc.collect()
        self.gateway = EngineGateway(self.engine)
        self.pool = self.engine.pool
        self.blocks_peak = 0
        self._submitters = []
        t2 = time.perf_counter()
        self._warm(seed)
        jax.block_until_ready(self.pool.kc)
        harness.log("serve set-up", model_s=t1 - t0, engine_s=t2 - t1,
                    warm_s=time.perf_counter() - t2,
                    decode_layout=self.engine.decode_layout,
                    kv_dtype=str(self.pool.kc.dtype),
                    pool_blocks=self.pool.num_blocks,
                    pool_bytes=self.pool.nbytes())

    def _warm(self, seed):
        """One synthetic admission per prefill bucket plus decode, then
        declare_warmup(): from here a compile is a violation."""
        rng = np.random.default_rng([abs(int(seed)), 7])
        limit = self.model_cfg["vocab_size"]
        cap = self.sizing["max_len"]
        reqs = []
        for b in self.sizing["buckets"]:
            n = min(b, cap - 4)
            reqs.append(self.gateway.submit(
                rng.integers(0, limit, size=n, dtype=np.int64), 3))
        for r in reqs:
            if not self.gateway.wait(r, timeout=1500.0):
                raise RuntimeError("warm-up request did not finish")
        self.engine.declare_warmup()

    # ----------------------------------------- the generator's client
    @property
    def num_slots(self):
        return self.sizing["num_slots"]

    def record(self, spec, due):
        return Rec(spec, due)

    def _callback(self, rec):
        def on_token(request, token):
            rec.stamps.append(time.perf_counter())
            live = self.pool.live_blocks
            if live > self.blocks_peak:
                self.blocks_peak = live
        return on_token

    def _offer(self, rec):
        try:
            rec.req = self.gateway.submit(rec.spec["prompt"],
                                          rec.spec["max_new"],
                                          on_token=self._callback(rec))
        except Exception as e:  # noqa: BLE001 - a refused request fails
            rec.error = repr(e)
        rec.accepted = time.perf_counter()

    def submit(self, rec):
        """Offer one request: stamped as sent by the generator, then
        submitted from a thread of its own (as the gateway's HTTP server
        gives every request a handler thread), so that a submit() that
        waits for the gateway's lock delays this request and not the
        generator's clock."""
        rec.sent = time.perf_counter()
        t = threading.Thread(target=self._offer, args=(rec,),
                             name="bench-submit", daemon=True)
        self._submitters.append(t)
        t.start()

    def join_submitters(self, timeout):
        for t in self._submitters:
            t.join(timeout=timeout)
        if any(t.is_alive() for t in self._submitters):
            raise RuntimeError("a submit() did not return")
        self._submitters = []

    def preload(self, recs):
        """Queue a whole backlog before any of it is served. The
        gateway's drive thread runs from its construction (the warm-up
        went through it), so the only way to keep it out is the lock it
        steps under: taken one by one, each submit() would wait seconds
        for that lock (PERF.md) and the queue would fill at the lock's
        pace. Which way was taken, and how long it took, is logged."""
        lock = getattr(self.gateway, "_lock", None)
        t0 = time.perf_counter()
        with lock if lock is not None else contextlib.nullcontext():
            for rec in recs:
                rec.due = rec.sent = time.perf_counter()
                self._offer(rec)
        harness.log("preload", requests=len(recs),
                    refused=sum(1 for r in recs if r.error),
                    how="drive thread held out by the gateway's lock"
                    if lock is not None else "plain submit() calls",
                    seconds=time.perf_counter() - t0)

    def halt(self):
        """Stop serving at once: what is queued or decoding is cut
        short. Nothing calls back after this returns."""
        self.gateway.close()

    def counters(self):
        M = self.engine.metrics
        return {"decode_steps": M.decode_steps, "prefills": M.prefills,
                "tokens_generated": M.tokens_generated,
                "requests_admitted": M.requests_admitted,
                "span_s": dict(M.span_s),
                "steady_state_compiles":
                    self.engine.watchdog.report()["steady_state_compiles"]}

    def drive(self, module, params, seed, seconds, on_open=None,
              on_close=None):
        """One ramp + window (+ drain) of the generator's traffic. The
        generator drives and accounts (attempted, failed, checks of its
        own); the counters are read here as the window opens and
        closes."""
        specs = module.build(params, seed, seconds,
                             self.model_cfg["vocab_size"])
        self.blocks_peak = 0
        ends = {}

        def opened():
            ends["before"] = self.counters()
            if on_open:
                on_open()

        def closed():
            ends["after"] = self.counters()
            if on_close:
                on_close()

        run = module.drive(self, specs, params, seconds, opened, closed)
        self.join_submitters(30.0)
        run.update(ends, blocks_peak=self.blocks_peak,
                   account=module.account(run, params))
        return run

    def close(self):
        """Stop the drive thread and free the device state (the
        reference runs after this, in the memory it leaves)."""
        self.gateway.close()
        for a in [self.pool.kc, self.pool.vc] + _leaves(self.engine.params):
            try:
                a.delete()
            except Exception:  # noqa: BLE001 - already donated/deleted
                pass
        self.engine = self.gateway = self.pool = None
        gc.collect()


def _leaves(tree):
    import jax
    return jax.tree_util.tree_leaves(tree)


# ---------------------------------------------------------------- metrics
def client_metrics(run, seconds):
    """What the client saw, from its own stamps. Which requests were
    attempted and which failed is the generator's account (a failed
    request misses every limit: ``failed`` must be 0 for ``correct``)."""
    t_open, t_close = run["t_open"], run["t_close"]
    acct = run["account"]
    bad = {id(r) for r in acct["failed"]}
    ok = [r for r in acct["attempted"] if id(r) not in bad]
    out = {"attempted": len(acct["attempted"]), "failed": len(bad),
           "values": {}, "ok": ok}
    tokens_in_window = sum(1 for r in run["recs"] for s in r.stamps
                           if t_open <= s < t_close)
    out["values"]["serve_tokens_per_s"] = tokens_in_window / seconds
    if ok:
        ttft = [(r.stamps[0] - r.due) * 1e3 for r in ok]
        gaps = [(b - a) * 1e3 for r in ok
                for a, b in zip(r.stamps, r.stamps[1:])]
        out["values"]["ttft_p95_ms"] = harness.percentile(ttft, 95)
        if gaps:
            out["values"]["gap_p95_ms"] = harness.percentile(gaps, 95)
            out["values"]["gap_mean_ms"] = sum(gaps) / len(gaps)
        out["ttft_ms"], out["gap_ms"] = ttft, gaps
    return out


def _p(values, q):
    return harness.percentile(values, q) if values else None


def describe(run, cm):
    """The earlier line that carries what BENCHMARK.json does not bound:
    the lengths drawn, and the first-token latency with the waits it is
    made of (PERF.md 2a: a thread race on this gateway, so logged and
    held under a ceiling, not a metric)."""
    recs, ok = run["recs"], cm["ok"]
    window = [r for r in recs if r.phase == "window"]
    info = {"requests": len(recs), "attempted": cm["attempted"],
            "failed": cm["failed"], "finished": len(ok),
            "prompt_len_p50": _p([len(r.spec["prompt"]) for r in recs], 50),
            "prompt_len_max": max(len(r.spec["prompt"]) for r in recs),
            "output_len_p50": _p([r.spec["max_new"] for r in recs], 50),
            "output_len_max": max(r.spec["max_new"] for r in recs),
            "blocks_peak": run["blocks_peak"],
            "serve_tokens_per_s": cm["values"]["serve_tokens_per_s"]}
    for k, v in run["account"].items():
        if not isinstance(v, list):
            info[k] = v
    if "ttft_ms" in cm:
        info.update(ttft_p50_ms=_p(cm["ttft_ms"], 50),
                    ttft_p95_ms=cm["values"]["ttft_p95_ms"],
                    ttft_max_ms=max(cm["ttft_ms"]),
                    ttft_samples=len(cm["ttft_ms"]))
    if cm.get("gap_ms"):
        info.update(gap_p50_ms=_p(cm["gap_ms"], 50),
                    gap_p95_ms=cm["values"]["gap_p95_ms"],
                    gap_mean_ms=cm["values"]["gap_mean_ms"],
                    gap_samples=len(cm["gap_ms"]))
    late = [(r.sent - r.due) * 1e3 for r in window if r.sent is not None]
    waits = [(r.accepted - r.sent) * 1e3 for r in window
             if r.accepted is not None]
    queue = [(r.req.t_admitted - r.due) * 1e3 for r in window
             if r.req is not None and r.req.t_admitted is not None]
    info.update(gen_late_p95_ms=_p(late, 95),
                submit_wait_p50_ms=_p(waits, 50),
                submit_wait_p95_ms=_p(waits, 95),
                queue_wait_p50_ms=_p(queue, 50),
                queue_wait_p95_ms=_p(queue, 95))
    return {k: v for k, v in info.items() if v is not None}


# ---------------------------------------------------------------- correct
def served_gap(sample, seed, model, precision, max_len, control=None):
    """Widest gap, over every served token of the sample, by which the
    token's reference logit lies below the reference's best at that
    position (float32 reference over prompt + served tokens, once per
    request). With ``control`` set the tokens judged are those that the
    reference computed in that lower precision puts first."""
    import jax.numpy as jnp
    from benchmarks.reference import gpt as ref
    w = weights.gpt_weights(seed, model, precision)
    nh = model["num_attention_heads"]
    widest, n_tok = 0.0, 0
    for r in sample:
        prompt = np.asarray(r.spec["prompt"], np.int32)
        served = np.asarray(r.req.generated, np.int32)
        p, g = len(prompt), len(served)
        ids = np.zeros((max_len,), np.int32)
        ids[:p + g - 1] = np.concatenate([prompt, served[:-1]])
        probe = np.zeros((max_len,), np.int32)
        probe[p - 1:p - 1 + g] = served
        if control:
            _, _, first = ref.score(w, jnp.asarray(ids), jnp.asarray(probe),
                                    nh, control)
            probe = np.asarray(first)
        best, at, _ = ref.score(w, jnp.asarray(ids), jnp.asarray(probe),
                                nh, "float32")
        gap = np.asarray(best - at)[p - 1:p - 1 + g]
        widest = max(widest, float(gap.max()))
        n_tok += g
    return widest, n_tok


# -------------------------------------------------------------------- run
def run(ctx):
    config, traffic = ctx["config"], ctx["traffic"]
    module = harness.load_module(
        harness.find_by_name("generators", traffic["generator"]),
        "bench_generator")
    counter = harness.LoweringCounter()
    prog = ServeProgram(config, ctx["seed"])
    pool_blocks = prog.pool.num_blocks
    tracer = harness.Tracer(ctx["cell"]["name"]) if ctx["trace"] else None
    devs = ctx["devices"]
    marks = {}

    def on_open():
        marks["lowered"] = counter.n
        marks["setup_s"] = time.perf_counter() - ctx["t0"]
        if tracer:
            tracer.start()
            marks["timer"] = threading.Timer(
                min(ctx["seconds"], float(traffic["trace_s"])), tracer.stop)
            marks["timer"].start()

    def on_close():
        marks["lowered_in_window"] = counter.n - marks["lowered"]
        marks["memory_peak_bytes"] = harness.memory_peak_bytes(devs)
        marks["memory_stats"] = devs[0].memory_stats()
        if tracer:
            marks["timer"].join()

    run_ = prog.drive(module, traffic, ctx["seed"], ctx["seconds"],
                      on_open, on_close)
    harness.log("memory", stats=marks["memory_stats"])
    cm = client_metrics(run_, ctx["seconds"])
    harness.log("traffic", **describe(run_, cm))
    compiles = run_["after"]["steady_state_compiles"]
    prog.close()

    sample = cm["ok"]    # every finished request is checked
    t_ref = time.perf_counter()
    gap, n_tok = served_gap(sample, ctx["seed"], config["model"],
                            config["precision"],
                            config["sizing"]["max_len"]) \
        if sample else (float("inf"), 0)
    limits = ctx["limits"]
    checks = [
        ("served_logit_gap", gap, limits["served_logit_gap"]),
        ("lowered_in_window", marks["lowered_in_window"], 0),
        ("steady_state_compiles", compiles, 0),
        ("failed", cm["failed"], 0),
    ] + list(run_["account"]["checks"])
    harness.log("reference", requests=len(sample), served_tokens=n_tok,
                seconds=time.perf_counter() - t_ref)
    cm["values"]["setup_s"] = marks["setup_s"]
    itemsize = np.dtype(config["precision"]).itemsize \
        if config["precision"] != "bfloat16" else 2
    return {"checks": checks, "attempted": cm["attempted"],
            "failed": cm["failed"], "values": cm["values"],
            "memory_peak_bytes": marks["memory_peak_bytes"],
            "tracer": tracer,
            "programs": PROGRAMS, "host_spans": HOST_SPANS,
            "readings": {"run": run_, "client": cm,
                         "num_slots": config["sizing"]["num_slots"],
                         "pool_blocks": pool_blocks, "model": config["model"],
                         "seconds": ctx["seconds"],
                         "kv_bytes_per_value": itemsize,
                         "weight_bytes": itemsize}}
