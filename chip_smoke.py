"""Chip smoke: both planes of paddle_tpu, end to end, on one TPU chip.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # builder-run: ONLY the dp2 x mp2
                                    # sharded train step + its one-device
                                    # comparison

One process, the entry points a user would call, GPT-124M at full width
(TransformerLMConfig(50304, 768, 12 layers, 12 heads)), random weights
from --seed. Phases (one JSON line each, the verdict line LAST):

  0 device  jax.devices() must be a TPU in the peak tables
  1 train   to_static(fwd + bwd + AdamW step), seq 1024 x batch 8, AMP
            O2 bf16: finite falling loss, state on the TPU, the Pallas
            flash kernel in the compiled step, no compile after step 3;
            plus eager ops and one eager (lazy micro-trace) train step
  2 serve   ServingEngine + paged KV pool behind EngineGateway.serve(),
            real POST /v1/generate requests over localhost; tokens ==
            model.generate(temperature=0), KV donation effective, zero
            steady-state compiles; the engine's own choice of decode
            attention: XLA gather at heads of 64, the Pallas paged
            kernel at heads of 128 on an f32 and on a bf16 pool

Any failed check raises: non-zero exit, no verdict line. With no TPU it
stops in phase 0. ``--rehearse`` is the CPU rehearsal (tiny width,
device-only checks skipped); it never prints the ``ok`` verdict.
"""
import argparse
import gc
import json
import sys
import threading
import time
import urllib.request

import numpy as np

FULL = dict(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12)
TINY = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4)
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(cond, what):
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


class LoweringCounter:
    """Counts every new program jax lowers (a cache hit on the
    persistent compile cache still lowers first, so this sees every new
    specialization whether or not XLA had to compile it)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == _LOWERING:
            self.n += 1


def sync(t):
    t.value.block_until_ready()
    return t


# ------------------------------------------------------------------ phase 0
def phase_device(want_count, rehearse):
    import jax
    import jaxlib
    devs = jax.devices()
    dev = devs[0]
    if not rehearse and dev.platform != "tpu":
        sys.exit(f"chip_smoke: jax found no TPU ({dev.platform}:"
                 f"{dev.device_kind}) — nothing was run")
    require(len(devs) == want_count,
            f"{want_count} device(s) wanted, jax sees {len(devs)}")
    import paddle_tpu  # noqa: F401  (places the compile cache)
    from paddle_tpu.core import native
    from paddle_tpu.observability import hbm_bps_for
    from paddle_tpu.serving.engine import _peak_flops_for
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - informational only
        libtpu = None
    # an unknown TPU kind raises here rather than being priced as a v5e
    peaks = {"peak_flops": _peak_flops_for(dev.device_kind),
             "hbm_bps": hbm_bps_for(dev.device_kind)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    emit("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu,
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         runtime_cpp_built=native.available(), **peaks)
    return device


# ------------------------------------------------------------------ phase 1
def build_gpt(paddle, width, seq, seed, use_mp=False):
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig
    paddle.seed(seed)
    cfg = TransformerLMConfig(max_seq_len=seq, dropout=0.0,
                              use_flash_attention=True, use_mp=use_mp,
                              **width)
    return GPTForCausalLM(cfg)


def make_train_step(paddle, model, opt):
    def step_fn(ids, labels):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss
    return paddle.jit.to_static(step_fn)


def train_steps(train_step, ids, labels, steps, counter, warm=3,
                after_record=None):
    """Run ``steps`` calls (1 eager, 2 record, 3+ compiled), each timed
    to block_until_ready. Returns (losses, seconds, programs lowered
    after the first ``warm`` calls)."""
    losses, secs = [], []
    after_warm = None
    for i in range(steps):
        if i == 2 and after_record is not None:
            after_record()
        if i == warm:
            after_warm = counter.n
        t0 = time.perf_counter()
        loss = sync(train_step(ids, labels))
        secs.append(time.perf_counter() - t0)
        # read it now and let the Tensor go: a live eager loss keeps its
        # whole autograd graph (GiBs of activations at this size) alive
        losses.append(float(loss.numpy()))
    return losses, secs, counter.n - after_warm


def compiled_hlo(train_step, ids, labels):
    """Optimized HLO of the compiled step (the same jitted callable the
    step dispatches, lowered on the same arguments)."""
    from paddle_tpu.jit.to_static import captured_arrays
    entry = next(e["compiled"] for e in train_step.entries.values()
                 if e["compiled"])
    return entry["jitted"].lower([ids.value, labels.value],
                                 *captured_arrays(entry)).compile().as_text()


def phase_train(paddle, width, seq, batch, steps, seed, counter, rehearse):
    model = build_gpt(paddle, width, seq, seed)
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.01)
    train_step = make_train_step(paddle, model, opt)
    rs = np.random.RandomState(seed)
    ids_np = rs.randint(0, width["vocab_size"], (batch, seq)).astype("int64")
    ids, labels = paddle.to_tensor(ids_np), paddle.to_tensor(ids_np.copy())

    losses, secs, late = train_steps(train_step, ids, labels, steps, counter)
    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(all(b < a for a, b in zip(losses, losses[1:])),
            f"loss not falling across eager->record->compiled: {losses}")
    require(late == 0, f"{late} new program(s) lowered after step 3")
    params = [p.value for p in model.parameters()]
    state = [t.value for store in opt._accumulators.values()
             for t in store.values()]
    require(state, "optimizer holds no state")
    f64 = [str(a.dtype) for a in params + state
           if a.dtype in (np.float64, np.complex128)]
    require(not f64, f"f64 arrays in the compiled step's state: {f64[:4]}")
    hlo = compiled_hlo(train_step, ids, labels)
    require(" f64[" not in hlo, "an f64 value reached the compiled step")
    if not rehearse:
        require(all(d.platform == "tpu" for a in params + state
                    for d in a.devices()),
                "parameters / optimizer state are not on the TPU")
        require("tpu_custom_call" in hlo,
                "no Pallas flash-attention kernel in the compiled step")
    emit("train", losses=losses, eager_s=secs[0], record_s=secs[1],
         compile_s=secs[2], steady_step_ms=1e3 * float(np.median(secs[3:])),
         lowerings_after_step3=late,
         pallas_custom_calls=hlo.count("tpu_custom_call"),
         tokens_per_step=batch * seq)
    return model


def phase_eager(paddle, seed):
    """Default dygraph mode on the device: plain eager ops, and one
    eager train step of a small Layer (lazy micro-trace engine)."""
    import paddle_tpu.nn as nn
    paddle.seed(seed)
    a = paddle.to_tensor(np.arange(12, dtype="float32").reshape(3, 4))
    b = paddle.ones([4, 3])
    got = (paddle.matmul(a, b) + 1.0).sum().numpy()
    require(float(got) == 3 * (66.0 + 3.0), f"eager matmul/sum gave {got}")
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 4))
    opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
    x = paddle.to_tensor(np.random.RandomState(seed).randn(8, 16)
                         .astype("float32"))
    y = paddle.to_tensor(np.arange(8, dtype="int64") % 4)
    losses = []
    for _ in range(3):
        loss = nn.functional.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    require(np.isfinite(losses).all() and losses[-1] < losses[0],
            f"eager train step did not learn: {losses}")
    emit("eager", losses=losses,
         device=str(next(iter(net.parameters()[0].value.devices()))))


# ------------------------------------------------------------------ phase 2
def post_generate(port, prompt, max_new_tokens):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps({"prompt": [int(t) for t in prompt],
                         "max_new_tokens": int(max_new_tokens)}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read().decode())


def reference_tokens(model, prompts, new_tokens):
    """model.generate(temperature=0) per prompt-length group (one
    compiled program per length); greedy decoding is prefix-stable, so
    each request compares its own first max_new_tokens of it."""
    out = [None] * len(prompts)
    n_new = max(new_tokens)
    for length in sorted({len(p) for p in prompts}):
        idx = [i for i, p in enumerate(prompts) if len(p) == length]
        ids = np.stack([prompts[i] for i in idx])
        full = np.asarray(model.generate(ids, max_new_tokens=n_new,
                                         temperature=0.0).numpy())
        for row, i in zip(full, idx):
            out[i] = [int(t) for t in row[length:length + new_tokens[i]]]
    return out


def serve_wave(port, prompts, new_tokens, concurrent):
    """POST every request; the first ``concurrent`` at once, the rest
    one after another. Returns the token lists in request order."""
    got = [None] * len(prompts)

    def one(i):
        got[i] = post_generate(port, prompts[i], new_tokens[i])

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(concurrent)]
    for t in threads:
        t.start()
    for i in range(concurrent, len(prompts)):
        one(i)
    for t in threads:
        t.join()
    for i, body in enumerate(got):
        require(body is not None and not body.get("shed_reason")
                and len(body["tokens"]) == new_tokens[i],
                f"request {i} did not complete: {body}")
    return [body["tokens"] for body in got]


def divergence_is_tie(paddle, model, prompt, got, ref):
    """Greedy decoding forks when two logits tie to within rounding.
    At the first token where ``got`` leaves ``ref``, score the shared
    prefix with the model's ordinary forward (a third implementation)
    and accept the fork only if it separates the two candidates by no
    more than two ulps of the weights' dtype at the logits' scale."""
    import jax.numpy as jnp
    j = next(i for i, (g, r) in enumerate(zip(got, ref)) if g != r)
    ids = np.concatenate([prompt, np.asarray(ref[:j], "int64")])[None]
    with paddle.no_grad():
        row = model(paddle.to_tensor(ids)).numpy()[0, -1].astype("float64")
    dtype = model.parameters()[0].value.dtype
    tol = 2 * float(jnp.finfo(dtype).eps) * float(np.abs(row).max())
    if dtype == jnp.float32:
        # at default precision the MXU rounds f32 OPERANDS to bf16, in
        # generate() and in this forward alike, while the paged kernel
        # multiplies f32 pools at HIGHEST: the implementations differ
        # by bf16's rounding of every product, so one bf16 ulp at the
        # logits' scale is the tie (half a bf16 model's allowance).
        # The arm's first runs on a chip (PR 30) forked at 1.4e-4 and
        # 1.2e-3 of that scale
        tol = max(tol, float(jnp.finfo(jnp.bfloat16).eps)
                  * float(np.abs(row).max()))
    gap = abs(row[got[j]] - row[ref[j]])
    return gap <= tol, {"at": j, "gap": gap, "tol": tol}


def serve_arm(paddle, name, model, waves, refs, kernel, rehearse):
    """One engine behind the HTTP gateway: a warm-up wave, then a
    steady wave on fresh prompts of the same lengths. ``kernel`` is
    what the engine must have chosen for this model's head width (the
    Pallas paged decode kernel or the XLA gather): nobody sets it."""
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.router.transport import EngineGateway
    eng = ServingEngine(model, num_slots=8, bucket_min=16,
                        block_size=16)
    gateway = EngineGateway(eng)
    handle = gateway.serve()
    try:
        answers = []
        for w, (prompts, new_tokens) in enumerate(waves):
            t0 = time.perf_counter()
            answers.append(serve_wave(handle.port, prompts, new_tokens,
                                      concurrent=len(prompts) // 2))
            wall = time.perf_counter() - t0
            if w == 0:
                eng.declare_warmup()
        snap = eng.metrics.snapshot()
        steady = eng.watchdog.report()["steady_state_compiles"]
        decode_hlo = eng._exec[("decode",)].as_text()
    finally:
        gateway.close()
    forks = []
    for (prompts, _), got_w, ref_w in zip(waves, answers, refs):
        for prompt, got, ref in zip(prompts, got_w, ref_w):
            if got != ref:
                tie, where = divergence_is_tie(paddle, model, prompt,
                                               got, ref)
                require(tie, f"{name}: tokens differ from generate() "
                             f"beyond a rounding tie: {where}")
                forks.append(where)
    require(steady == 0, f"{name}: {steady} steady-state compile(s)")
    if not rehearse:    # (the CPU has no Mosaic: there it is the gather)
        require(eng.paged_attn == bool(kernel),
                f"{name}: engine.paged_attn is {eng.paged_attn}")
        require(snap["kv_donation"]["effective"],
                f"{name}: KV donation is not effective on this backend")
        require(("tpu_custom_call" in decode_hlo) == bool(kernel),
                f"{name}: Pallas decode kernel presence != {bool(kernel)}")
    n_req = sum(len(w[0]) for w in waves)
    emit("serve", arm=name, requests=n_req,
         equal_to_generate=n_req - len(forks), forked_at_rounding_tie=forks,
         tokens=int(sum(sum(w[1]) for w in waves)),
         kv_dtype=str(eng.pool.kc.dtype), decode_layout=eng.decode_layout,
         kv_donation=snap["kv_donation"], compiles=snap["compiles"],
         steady_state_compiles=steady, steady_wave_s=wall)


def phase_serve(paddle, model, width, seq, seed, rehearse):
    model.eval()
    rs = np.random.RandomState(seed + 1)
    lengths = [16, 16, 16, 72, 72, 72, 200, 200]
    new_tokens = [32, 96, 48, 64, 40, 96, 80, 56]
    if rehearse:
        lengths = [min(n, seq // 2) for n in lengths]
        new_tokens = [min(k, seq // 4) for k in new_tokens]
    waves = [([rs.randint(0, width["vocab_size"], (n,)).astype("int64")
               for n in lengths], new_tokens) for _ in range(2)]

    def arm(model, tag, kernel):
        refs = [reference_tokens(model, p, k) for p, k in waves]
        serve_arm(paddle, f"{tag}-{'pallas' if kernel else 'xla-gather'}",
                  model, waves, refs, kernel, rehearse)

    # heads of 64 do not fill the kernel's lanes: the engine keeps the
    # gather for the trained model, and takes the kernel for the same
    # width cut into heads of 128
    arm(model, "f32-hd64", False)
    heads = max(1, width["hidden_size"] // 128)
    wide = build_gpt(paddle, dict(width, num_heads=heads), seq, seed)
    wide.eval()
    arm(wide, "f32-hd128", True)
    # a bf16 model serves from a bf16 KV pool
    paddle.amp.decorate(wide, level="O2", dtype="bfloat16")
    arm(wide, "bf16-hd128", True)


# ---------------------------------------------------------------- --chips 4
def phase_sharded(paddle, width, seq, batch, steps, seed, counter, rehearse,
                  tol=0.07):
    """dp2 x mp2 over the four local chips vs the same step on one
    device: same seed, same data, loss by loss. Under AMP O2 the loss
    comes out in bf16, whose ulp between 8 and 16 is 0.0625: ``tol`` is
    one such step."""
    import jax
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.distributed.fleet import DistributedStrategy

    rs = np.random.RandomState(seed)
    ids_np = rs.randint(0, width["vocab_size"], (batch, seq)).astype("int64")

    def run(sharded):
        model = build_gpt(paddle, width, seq, seed, use_mp=sharded)
        inner = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                       weight_decay=0.01)
        if sharded:
            model = fleet.distributed_model(model)
            opt = fleet.distributed_optimizer(inner)
        else:
            opt = inner
        step = make_train_step(paddle, model, opt)
        ids, labels = (paddle.to_tensor(ids_np),
                       paddle.to_tensor(ids_np.copy()))
        # the sharded step lowers twice: call 3 sees the state where
        # eager left it (one device), call 4 sees it as call 3's
        # outputs left it (sharded); from call 5 on nothing may change
        def eager_stayed_on_one_device():
            # the framework's rule: only the compiled step spans the
            # mesh (a multi-device eager step cannot hold a Mosaic
            # kernel, and this is how a CPU rehearsal sees that)
            spread = [n for n, p in model.named_parameters()
                      if len(p.value.devices()) > 1]
            require(not spread, f"eager phases left {len(spread)} "
                                f"parameter(s) multi-device: {spread[:3]}")

        losses, secs, late = train_steps(
            step, ids, labels, steps, counter, warm=4,
            after_record=eager_stayed_on_one_device)
        require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
        require(late == 0, f"{late} new program(s) lowered after step 4")
        return model, losses, secs

    def bytes_in_use():
        return [d.memory_stats()["bytes_in_use"] if d.memory_stats()
                else None for d in jax.devices()]

    # the sharded run first, on four empty chips; the one-device run it
    # is compared with follows once this one's state has been dropped
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh
    require(int(mesh.shape["dp"]) == 2 and int(mesh.shape["mp"]) == 2,
            f"mesh is {dict(mesh.shape)}")
    model, sharded, secs = run(sharded=True)

    # the mp-sharded weights must really be split over the four chips
    placed = []
    for name, p in model.named_parameters():
        spec = getattr(p, "tp_spec", None)
        if not spec or "mp" not in spec:
            continue
        shards = p.value.addressable_shards
        want = tuple(s // 2 if ax == "mp" else s
                     for s, ax in zip(p.value.shape, spec))
        require(len({s.device for s in shards}) == 4,
                f"{name}: shards sit on "
                f"{sorted(str(s.device) for s in shards)}")
        require(all(s.data.shape == want for s in shards),
                f"{name}: shard shapes {[s.data.shape for s in shards]} "
                f"!= {want}")
        placed.append(name)
    require(placed, "the model has no mp-sharded parameter")
    gc.collect()
    mem = bytes_in_use()
    if not rehearse:
        require(min(mem) * 4 >= max(mem),
                f"device memory is lopsided across the chips: {mem}")
    emit("sharded", mesh={k: int(v) for k, v in mesh.shape.items()},
         losses=sharded, mp_sharded_params=len(placed), bytes_in_use=mem,
         compile_s=[secs[2], secs[3]],
         steady_step_ms=1e3 * float(np.median(secs[4:])))

    topology._HYBRID = None
    del model
    gc.collect()
    before = bytes_in_use()
    _, single, _ = run(sharded=False)
    diffs = [abs(a - b) for a, b in zip(single, sharded)]
    emit("single_device", losses=single, max_abs_loss_diff=max(diffs),
         tolerance=tol, bytes_in_use_before=before)
    require(max(diffs) <= tol,
            f"sharded vs single-device losses differ by {max(diffs)} "
            f"(> {tol}): {single} vs {sharded}")


# --------------------------------------------------------------------- main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny width; no ok verdict")
    args = ap.parse_args()

    device = phase_device(args.chips, args.rehearse)
    import paddle_tpu as paddle
    counter = LoweringCounter()
    width, seq, batch = (TINY, 128, 4) if args.rehearse else (FULL, 1024, 8)
    if args.chips == 4:
        phase_sharded(paddle, width, seq, batch, 6, args.seed, counter,
                      args.rehearse)
    else:
        model = phase_train(paddle, width, seq, batch, 6, args.seed,
                            counter, args.rehearse)
        phase_eager(paddle, args.seed)
        phase_serve(paddle, model, width, seq, args.seed, args.rehearse)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
