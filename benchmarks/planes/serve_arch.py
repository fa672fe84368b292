"""Serving cells of ANY architecture: the serving plane of
``planes/serve.py`` (its client, its drive, its client metrics and its
``correct``) with everything that knows the model found by the
configuration's ``arch``:

  ``planes/<arch>_program.py``   builds the program's model class around
                                 the seeded weights, names its compiled
                                 programs and kernels, reads its counters,
                                 builds its programs from sizes alone
                                 (``tools/aot_compile_arch.py``);
  ``weights_<arch>.py``          ``make(seed, model, dtype)`` in one jitted
                                 call on the device, ``leaf_shapes``,
                                 ``count_params``;
  ``reference/<arch>.py``        the plain reference: ``score(w, ids,
                                 probe, model, precision)``;
  ``flops_<arch>.py``            operations and bytes from shapes, for the
                                 metric readers.

A configuration for this plane keeps the model's published ``config.json``
keys at the top level of its file (where the benchmark's contract
compares them); ``model_of`` takes every key that is not the benchmark's
own as the model. The generator is handed ``num_slots`` and ``max_len``
beside its traffic parameters. Metric readers get the same ``readings``
as from ``serve.py`` plus ``arch``, ``flops`` (the module), ``kernels``
and the program's expert counters at both ends of the window
(``run["before"]["moe"]``, ``run["after"]["moe"]``).
"""
import gc
import time
import types

import numpy as np

from benchmarks import harness
from benchmarks.planes import serve

HOST_SPANS = serve.HOST_SPANS
_OWN = {"source", "plane", "arch", "precision", "reduced", "published",
        "deployment", "assumed", "sizing", "rehearse", "correct_limits"}


def model_of(config):
    """The model's sizes: the configuration's top-level keys that are
    not the benchmark's own."""
    return {k: v for k, v in config.items() if k not in _OWN}


def arch_files(arch):
    """The four files of an architecture, by name."""
    def load(kind, name):
        return harness.load_module(
            harness.find_by_name(kind, name), f"bench_{arch}_{kind or 'x'}"
            f"_{name}".replace("/", "_"))
    return types.SimpleNamespace(
        program=load("planes", f"{arch}_program"),
        weights=load("", f"weights_{arch}"),
        reference=load("reference", arch),
        flops=load("", f"flops_{arch}"))


class ServeArchProgram(serve.ServeProgram):
    """The system under test, set up and warm: ``ServeProgram`` with the
    model built by the architecture's program file."""

    def __init__(self, config, seed, arch):
        import jax
        from paddle_tpu.serving import ServingEngine
        from paddle_tpu.serving.router.transport import EngineGateway
        self.arch = arch
        self.model_cfg, self.sizing = model_of(config), config["sizing"]
        # a program that lacks the architecture fails HERE, at once, and
        # not after the weights are made
        arch.program.model_config(self.model_cfg, config["precision"])
        t0 = time.perf_counter()
        w = arch.weights.make(seed, self.model_cfg, config["precision"])
        net = arch.program.build_model(self.model_cfg,
                                       config["precision"], w)
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
        jax.block_until_ready(arch.program.cache_arrays(self.engine))
        harness.log("serve set-up", arch=config["arch"], model_s=t1 - t0,
                    engine_s=t2 - t1, warm_s=time.perf_counter() - t2,
                    pool_blocks=self.pool.num_blocks,
                    block_size=self.pool.block_size,
                    pool_bytes=self.pool.nbytes(),
                    kv_bytes_per_token=self.engine.cache_spec
                    .bytes_per_token,
                    kv_donation=dict(self.engine.metrics.kv_donation))

    def counters(self):
        out = super().counters()
        out["moe"] = self.arch.program.moe_counts(self.engine)
        return out

    def drive(self, module, params, seed, seconds, on_open=None,
              on_close=None):
        params = dict(params, num_slots=self.sizing["num_slots"],
                      max_len=self.sizing["max_len"])
        return super().drive(module, params, seed, seconds, on_open,
                             on_close)

    def close(self):
        self.gateway.close()
        for a in self.arch.program.cache_arrays(self.engine) \
                + serve._leaves(self.engine.params):
            try:
                a.delete()
            except Exception:  # noqa: BLE001 - already donated/deleted
                pass
        self.engine = self.gateway = self.pool = None
        gc.collect()


def token_gaps(sample, w, model, arch, pad_to, control=None):
    """For each session of the sample, the gaps by which its served
    tokens' reference logits lie below the reference's best at their
    positions (float32 reference over prompt + served tokens, once a
    session). With ``control`` set the tokens judged are those that the
    reference computed in that lower precision puts first. Every
    sequence is padded to ONE length (the longest, rounded up to
    ``pad_to``), so the reference compiles once."""
    import jax.numpy as jnp
    longest = max(len(r.spec["prompt"]) + len(r.req.generated)
                  for r in sample)
    T = -(-longest // pad_to) * pad_to
    for r in sample:
        prompt = np.asarray(r.spec["prompt"], np.int32)
        served = np.asarray(r.req.generated, np.int32)
        p, g = len(prompt), len(served)
        ids = np.zeros((T,), np.int32)
        ids[:p + g - 1] = np.concatenate([prompt, served[:-1]])
        probe = np.zeros((T,), np.int32)
        probe[p - 1:p - 1 + g] = served
        if control:
            _, _, first = arch.reference.score(
                w, jnp.asarray(ids), jnp.asarray(probe), model, control)
            probe = np.asarray(first)
        best, at, _ = arch.reference.score(
            w, jnp.asarray(ids), jnp.asarray(probe), model, "float32")
        yield np.asarray(best - at)[p - 1:p - 1 + g]


def served_gap(sample, seed, model, precision, arch, pad_to):
    """(widest, mean, tokens) of ``token_gaps`` over the sample, with
    the weights rebuilt from the seed."""
    w = arch.weights.make(seed, model, precision)
    gaps = np.concatenate(list(token_gaps(sample, w, model, arch, pad_to)))
    return float(gaps.max()), float(gaps.mean(dtype=np.float64)), \
        len(gaps)


class GcWatch:
    """Seconds the interpreter's garbage collector held the process
    between ``start`` and ``stop`` (every thread waits for it: a full
    collection of a large heap is a stall of the step loop)."""

    def __init__(self):
        self.pauses, self._t0 = [], None

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0, self._t0))
            self._t0 = None

    def start(self):
        gc.callbacks.append(self._on)

    def stop(self):
        gc.callbacks.remove(self._on)
        full = [d for g, d, _ in self.pauses if g == 2]
        return {"gc_collections": len(self.pauses),
                "gc_full_collections": len(full),
                "gc_pause_s": sum(d for _, d, _ in self.pauses),
                "gc_longest_pause_s": max((d for _, d, _ in self.pauses),
                                          default=0.0)}

    def spans(self):
        """The pauses as (start, end, name) on ``perf_counter``."""
        return [(t, t + d, f"gc/gen{g}") for g, d, t in self.pauses]


def stalls(run, host_spans=(), top=6):
    """Where the window's steps were not a step apart: over the token
    stamps of one resident session (they all ride the same steps), the
    gaps longer than twice the median; the longest ``top`` of them with
    the host spans (start, end, name: the program's ring, the collector's
    pauses) that cover a tenth of the gap or more."""
    recs = [r for r in run["recs"] if len(r.stamps) > 2]
    if not recs:
        return {}
    stamps = [s for s in max(recs, key=lambda r: len(r.stamps)).stamps
              if run["t_open"] <= s < run["t_close"]]
    gaps = np.diff(stamps)
    if not len(gaps):
        return {}
    med = float(np.median(gaps))
    long_ = gaps[gaps > 2 * med]
    worst = []
    for i in np.argsort(-gaps)[:top]:
        if gaps[i] <= 2 * med:
            break
        lo, hi = stamps[i], stamps[i + 1]
        over = sorted(((min(hi, e) - max(lo, b), n) for b, e, n in
                       host_spans if e > lo and b < hi), reverse=True)
        worst.append({"at_s": round(lo - run["t_open"], 3),
                      "ms": round(1e3 * float(gaps[i]), 1),
                      "under": [[n, round(1e3 * c, 1)] for c, n in over
                                if c >= 0.1 * gaps[i]][:5]})
    return {"step_gap_median_ms": 1e3 * med,
            "step_gap_max_ms": 1e3 * float(gaps.max()),
            "stalls": int(len(long_)),
            "stalled_s": float((long_ - med).sum()), "worst": worst}


def checked_sample(ok, traffic, seed):
    """The sessions whose served tokens are checked: all of them, or a
    seeded subset of ``check_sessions`` whole ones where the traffic
    file says that all would take too long."""
    n = int(traffic.get("check_sessions", 0))
    if not n or n >= len(ok):
        return list(ok)
    rng = np.random.default_rng([abs(int(seed)), 11])
    return [ok[i] for i in sorted(rng.choice(len(ok), n, replace=False))]


def run(ctx):
    import threading
    config, traffic = ctx["config"], ctx["traffic"]
    arch = arch_files(config["arch"])
    module = harness.load_module(
        harness.find_by_name("generators", traffic["generator"]),
        "bench_generator")
    counter = harness.LoweringCounter()
    prog = ServeArchProgram(config, ctx["seed"], arch)
    pool_blocks = prog.pool.num_blocks
    kv_bytes_per_token = prog.engine.cache_spec.bytes_per_token
    tracer = harness.Tracer(ctx["cell"]["name"]) if ctx["trace"] else None
    devs = ctx["devices"]
    marks = {}
    gc_watch = GcWatch()

    def on_open():
        gc_watch.start()
        marks["lowered"] = counter.n
        marks["setup_s"] = time.perf_counter() - ctx["t0"]
        if tracer:
            tracer.start()
            marks["timer"] = threading.Timer(
                min(ctx["seconds"], float(traffic["trace_s"])), tracer.stop)
            marks["timer"].start()

    def on_close():
        marks["gc"] = gc_watch.stop()
        marks["lowered_in_window"] = counter.n - marks["lowered"]
        marks["memory_peak_bytes"] = harness.memory_peak_bytes(devs)
        marks["memory_stats"] = devs[0].memory_stats()
        if tracer:
            marks["timer"].join()

    run_ = prog.drive(module, traffic, ctx["seed"], ctx["seconds"],
                      on_open, on_close)
    harness.log("memory", stats=marks["memory_stats"])
    from paddle_tpu.observability import default_recorder
    ring = [(sp.t0, sp.t0 + sp.dur, sp.name)
            for sp in default_recorder().spans()]
    harness.log("steadiness", **stalls(run_, ring + gc_watch.spans()),
                **marks["gc"])
    cm = serve.client_metrics(run_, ctx["seconds"])
    harness.log("traffic", **serve.describe(run_, cm))
    compiles = run_["after"]["steady_state_compiles"]
    prog.close()

    sample = checked_sample(cm["ok"], traffic, ctx["seed"])
    model = model_of(config)
    t_ref = time.perf_counter()
    gap, mean_gap, n_tok = served_gap(
        sample, ctx["seed"], model, config["precision"], arch,
        int(traffic.get("reference_pad", 2048))) \
        if sample else (float("inf"), float("inf"), 0)
    limits = ctx["limits"]
    checks = [
        ("served_logit_gap", gap, limits["served_logit_gap"]),
        # where routers sit on near ties the widest gap of a sound bf16
        # program is a heavy tail; the MEAN over the served tokens is
        # what tells a lower precision apart (PERF.md section 6)
        ("served_logit_gap_mean", mean_gap,
         limits["served_logit_gap_mean"]),
        ("lowered_in_window", marks["lowered_in_window"], 0),
        ("steady_state_compiles", compiles, 0),
        ("failed", cm["failed"], 0),
    ] + list(run_["account"]["checks"])
    harness.log("reference", sessions=len(sample), of=len(cm["ok"]),
                served_tokens=n_tok, seconds=time.perf_counter() - t_ref)
    cm["values"]["setup_s"] = marks["setup_s"]
    itemsize = 2 if config["precision"] == "bfloat16" \
        else np.dtype(config["precision"]).itemsize
    return {"checks": checks, "attempted": cm["attempted"],
            "failed": cm["failed"], "values": cm["values"],
            "memory_peak_bytes": marks["memory_peak_bytes"],
            "tracer": tracer,
            "programs": arch.program.PROGRAMS, "host_spans": HOST_SPANS,
            "readings": {"run": run_, "client": cm,
                         "num_slots": config["sizing"]["num_slots"],
                         "pool_blocks": pool_blocks, "model": model,
                         "seconds": ctx["seconds"],
                         "kv_bytes_per_value": itemsize,
                         "weight_bytes": itemsize,
                         "arch": config["arch"], "flops": arch.flops,
                         "kernels": arch.program.KERNELS,
                         "sizing": config["sizing"],
                         "kv_bytes_per_token": kv_bytes_per_token}}
