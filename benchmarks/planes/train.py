"""Training cells: ``to_static(forward + backward + AdamW)`` under AMP
O2 bf16, fed by ``paddle.io.DataLoader`` (chip_smoke.py's job).

Set-up builds ONE compiled step with its state, runs the eager and
record passes at batch 1 (an eager call at the real batch keeps GiBs of
autograd graph alive), puts the state back to the seeded weights and a
fresh optimizer, and drives the compiled step through its first steps on
the window's own feed; the window then continues with that same object.
The plain reference follows those first steps afterwards, once the
program's state is freed.
"""
import gc
import time

import numpy as np

from benchmarks import harness, weights
from benchmarks.planes import gpt_program

PROGRAMS = {"train_step": "compiled_fn"}
HOST_SPANS = ("bench/", "optimizer/")
# the step's only Mosaic kernels are the flash-attention ones, and the
# trace shows them by their custom-call target alone (no name= is set)
FLASH_KERNELS = ('custom_call_target="tpu_custom_call"',)


class TrainProgram:
    def __init__(self, config, traffic, seed, spans):
        import paddle_tpu as paddle
        self.model_cfg, self.sizing = config["model"], config["sizing"]
        self.hp = config["optimizer"]
        self.spans = spans
        self.seed = seed
        B, T = self.sizing["batch"], self.sizing["seq_len"]
        t0 = time.perf_counter()
        self.net = gpt_program.build_model(self.model_cfg)
        self.opt = paddle.optimizer.AdamW(
            self.hp["lr"], beta1=self.hp["beta1"], beta2=self.hp["beta2"],
            epsilon=self.hp["eps"], parameters=self.net.parameters(),
            weight_decay=self.hp["weight_decay"])
        net, opt, precision = self.net, self.opt, config["precision"]

        def step_fn(ids, labels):
            with paddle.amp.auto_cast(level="O2", dtype=precision):
                loss = net(ids, labels=labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        self.step = paddle.jit.to_static(step_fn)

        gen = harness.load_module(
            harness.find_by_name("generators", traffic["generator"]),
            "bench_generator")
        self.gen = gen
        # eager + record at batch 1, on a row the feed never serves
        row = gen.row(seed, -1 & 0xFFFFFF, T, self.sizing["token_id_limit"])
        x1 = paddle.to_tensor(row[None, :-1])
        y1 = paddle.to_tensor(row[None, 1:])
        for _ in range(2):
            float(self.step(x1, y1).numpy())
        t1 = time.perf_counter()
        self._reset_state()
        loader = paddle.io.DataLoader(
            gen.build(traffic, seed, T, self.sizing["token_id_limit"]),
            batch_size=B, shuffle=False, drop_last=True)
        self.feed = iter(loader)
        # the compiled step's first steps, through the window's own
        # call and feed; the first of them compiles
        n = int(traffic["checked_steps"])
        self.first = {"losses": []}
        for k in range(n):
            loss = self.one_step()
            self.first["losses"].append(float(loss.numpy()))
            if k == 0:
                self.first["grad_norms"] = self._first_grad_norms()
        self.first["delta_norms"] = self._delta_norms()
        for _ in range(int(traffic["settle_steps"])):
            float(self.one_step().numpy())
        harness.log("train set-up", eager_record_s=t1 - t0,
                    compile_and_first_steps_s=time.perf_counter() - t1,
                    batch=B, seq_len=T, first_losses=self.first["losses"])

    def one_step(self):
        with self.spans.span("data_wait"):
            x, y = next(self.feed)
        with self.spans.span("dispatch"):
            return self.step(x, y)

    # ------------------------------------------------ state, by leaf
    def _seeded(self):
        return weights.gpt_weights(self.seed, self.model_cfg, "float32")

    def _reset_state(self):
        """Seeded weights, zero moments, step count 0: the state the
        reference starts from."""
        import jax.numpy as jnp
        gpt_program.set_weights(self.net, self._seeded())
        for name, t in self.opt.state_dict().items():
            if name == "LR_Scheduler":
                continue
            if name.endswith(("_moment1", "_moment2")):
                t.value = jnp.zeros_like(t.value)
            elif name.endswith(("_beta1_pow", "_beta2_pow")):
                t.value = jnp.ones_like(t.value)
            else:
                raise RuntimeError(f"optimizer state {name!r} is not "
                                   f"AdamW's: the reference cannot "
                                   f"follow it")

    def _first_grad_norms(self):
        """Norm of the first gradient as the optimizer got it: after one
        step from zero moments, moment1 = (1 - beta1) * g."""
        import jax
        import jax.numpy as jnp
        by_id = {id(p): (leaf, layer) for p, leaf, layer in
                 gpt_program.param_leaves(self.net)}
        m1 = self.opt._accumulators["moment1"]
        arrays = {by_id[pid]: t.value for pid, t in m1.items()}
        norms = jax.jit(lambda a: {k: jnp.sqrt((v * v).sum())
                                   for k, v in a.items()})(arrays)
        scale = 1.0 - self.hp["beta1"]
        return {k: float(v) / scale for k, v in norms.items()}

    def _delta_norms(self):
        import jax
        import jax.numpy as jnp
        w0 = self._seeded()
        now, was = {}, {}
        for p, leaf, layer in gpt_program.param_leaves(self.net):
            now[(leaf, layer)] = p.value
            was[(leaf, layer)] = w0[leaf] if layer is None \
                else w0[leaf][layer]
        norms = jax.jit(lambda a, b: {
            k: jnp.sqrt(((a[k].astype(jnp.float32) - b[k]) ** 2).sum())
            for k in a})(now, was)
        return {k: float(v) for k, v in norms.items()}

    def close(self):
        for p in self.net.parameters():
            p.value.delete()
        for store in self.opt._accumulators.values():
            for t in store.values():
                t.value.delete()
        self.net = self.opt = self.step = self.feed = None
        gc.collect()


# ---------------------------------------------------------------- correct
def reference_first_steps(config, traffic, seed, gen, precision="float32"):
    import jax.numpy as jnp
    from benchmarks.reference import gpt as ref
    sz = config["sizing"]
    w0 = weights.gpt_weights(seed, config["model"], "float32")
    batches = []
    for k in range(int(traffic["checked_steps"])):
        x, y = gen.batch(seed, k, sz["batch"], sz["seq_len"],
                         sz["token_id_limit"])
        batches.append((jnp.asarray(x, jnp.int32), jnp.asarray(y, jnp.int32)))
    out = ref.train_steps(w0, batches, config["model"]["num_attention_heads"],
                          config["optimizer"], precision,
                          row_block=int(traffic.get("reference_row_block",
                                                    4)))
    flat = {}
    for key in ("grad_norms", "delta_norms"):
        flat[key] = {}
        for leaf, v in out[key].items():
            v = np.asarray(v)
            if v.ndim == 0:
                flat[key][(leaf, None)] = float(v)
            else:
                for i, x in enumerate(v):
                    flat[key][(leaf, i)] = float(x)
    flat["losses"] = out["losses"]
    return flat


def worst_leaf_gap(got, want):
    """Largest |got - want| over the leaves, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    median = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for k, w in want.items():
        gap = abs(got[k] - w) / max(w, median)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(first, want):
    loss_gap = max(abs(a - b) for a, b in zip(first["losses"],
                                              want["losses"]))
    g_gap, g_at = worst_leaf_gap(first["grad_norms"], want["grad_norms"])
    d_gap, d_at = worst_leaf_gap(first["delta_norms"], want["delta_norms"])
    return {"loss_gap": loss_gap, "grad_norm_gap": g_gap,
            "delta_norm_gap": d_gap}, {"grad_at": g_at, "delta_at": d_at}


# -------------------------------------------------------------------- run
def run(ctx):
    import jax
    config, traffic = ctx["config"], ctx["traffic"]
    counter = harness.LoweringCounter()
    spans = harness.Spans()
    prog = TrainProgram(config, traffic, ctx["seed"], spans)
    B, T = config["sizing"]["batch"], config["sizing"]["seq_len"]
    tracer = harness.Tracer(ctx["cell"]["name"]) if ctx["trace"] else None
    trace_s = min(ctx["seconds"], float(traffic["trace_s"]))
    in_flight = int(traffic["in_flight"])

    lowered0 = counter.n
    spans0 = spans.snapshot()
    t_open = time.perf_counter()
    setup_s = t_open - ctx["t0"]
    if tracer:
        tracer.start()
    pending, steps, traced_steps, last = [], 0, None, None
    while True:
        last = prog.one_step()
        pending.append(last)
        steps += 1
        if len(pending) > in_flight:
            # the loss of `in_flight` steps back: the host never runs
            # further ahead of the device than that
            pending.pop(0).value.block_until_ready()
        now = time.perf_counter()
        if tracer and tracer.t_stop is None and now - t_open >= trace_s:
            for p in pending:
                p.value.block_until_ready()
            pending = []
            tracer.stop()
            traced_steps = steps
        if now - t_open >= ctx["seconds"]:
            break
    for p in pending:
        p.value.block_until_ready()
    last_loss = float(last.numpy())
    elapsed = time.perf_counter() - t_open
    lowered = counter.n - lowered0
    spans1 = spans.snapshot()
    peak = harness.memory_peak_bytes(ctx["devices"])
    harness.log("memory", stats=ctx["devices"][0].memory_stats())
    traced_seconds = tracer.t_stop - tracer.t_start if traced_steps \
        else None
    harness.log("window", steps=steps, seconds=elapsed,
                step_ms=1e3 * elapsed / steps, last_loss=last_loss,
                lowered_in_window=lowered, traced_steps=traced_steps,
                traced_seconds=traced_seconds)
    first, gen = prog.first, prog.gen
    prog.close()

    t_ref = time.perf_counter()
    want = reference_first_steps(config, traffic, ctx["seed"], gen)
    gaps, where = compare(first, want)
    harness.log("reference", seconds=time.perf_counter() - t_ref,
                losses=want["losses"], program_losses=first["losses"],
                **{k: str(v) for k, v in where.items()})
    limits = ctx["limits"]
    checks = [(k, v, limits[k]) for k, v in gaps.items()]
    checks.append(("lowered_in_window", lowered, 0))
    checks.append(("finite_last_loss",
                   0 if np.isfinite(last_loss) else 1, 0))
    tokens = steps * B * T
    return {"checks": checks, "attempted": steps, "failed": 0,
            "values": {"train_tokens_per_s": tokens / elapsed,
                       "setup_s": setup_s},
            "memory_peak_bytes": peak, "tracer": tracer,
            "programs": PROGRAMS, "host_spans": HOST_SPANS,
            "readings": {"steps": steps, "seconds": elapsed,
                         "traced_steps": traced_steps,
                         "traced_seconds": traced_seconds,
                         "tokens_per_step": B * T, "batch": B, "seq_len": T,
                         "spans": {"before": spans0, "after": spans1},
                         "model": config["model"],
                         "flash_kernels": FLASH_KERNELS}}
