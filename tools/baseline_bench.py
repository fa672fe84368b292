"""Measure the training baseline configs on the real chip.

Config 1: LeNet/MNIST dygraph — eager step time AND to_static step time
          (the eager-vs-compiled gap is SURVEY §7 hard-part 1).
Config 3: BERT-base pretraining (MLM+NSP), bf16 AMP, to_static.

Prints one JSON line per measurement. Run: python tools/baseline_bench.py
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _sync(t):
    # wait for the device (jax returns before it finishes), then read
    t.value.block_until_ready()
    return float(np.asarray(t.value).reshape(-1)[0])


def bench_lenet():
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    net = LeNet()
    opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
    loss_fn = nn.CrossEntropyLoss()
    batch = 64
    x = paddle.to_tensor(
        np.random.randn(batch, 1, 28, 28).astype("float32"))
    y = paddle.to_tensor(np.random.randint(0, 10, (batch,)).astype("int64"))

    def step():
        loss = loss_fn(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    n = 20

    def time_eager():
        for _ in range(3):
            _sync(step())  # warm executable caches
        t0 = time.perf_counter()
        for _ in range(n):
            loss = step()
        _sync(loss)
        return (time.perf_counter() - t0) / n * 1000

    # eager with lazy micro-tracing (the default: core/lazy.py defers
    # ops and flushes each step as one cached executable)
    paddle.set_flags({"FLAGS_lazy_eager": True})
    eager_lazy_ms = time_eager()
    # eager immediate (per-op dispatch — the r2 baseline mode)
    paddle.set_flags({"FLAGS_lazy_eager": False})
    eager_imm_ms = time_eager()
    paddle.set_flags({"FLAGS_lazy_eager": True})

    compiled = paddle.jit.to_static(step)
    for _ in range(3):
        _sync(compiled())
    t0 = time.perf_counter()
    for _ in range(n):
        loss = compiled()
    _sync(loss)
    comp_ms = (time.perf_counter() - t0) / n * 1000

    print(json.dumps({
        "config": 1, "model": "LeNet/MNIST", "batch": batch,
        "eager_step_ms": round(eager_lazy_ms, 3),
        "eager_immediate_step_ms": round(eager_imm_ms, 3),
        "to_static_step_ms": round(comp_ms, 3),
        "eager_over_compiled": round(eager_lazy_ms / comp_ms, 1),
        "samples_per_sec_compiled": round(batch / comp_ms * 1000, 1),
    }), flush=True)


def bench_bert(batch=32, seq=128, steps=20):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn  # noqa: F401
    from paddle_tpu.text.models import bert_base

    paddle.seed(0)
    model = bert_base(max_seq_len=seq, dropout=0.0)
    n_params = sum(int(np.prod(p.aval_shape()))
                   for p in model.parameters())
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01)

    def step_fn(ids, tok, mlm, nsp):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            loss = model(ids, tok, mlm, nsp)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step = paddle.jit.to_static(step_fn)

    def data(b):
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 30522, (b, seq)).astype("int64")
        tok = np.zeros((b, seq), "int64")
        mlm = np.where(rs.rand(b, seq) < 0.15,
                       rs.randint(0, 30522, (b, seq)), -1).astype("int64")
        nsp = rs.randint(0, 2, (b, 1)).astype("int64")
        return tuple(paddle.to_tensor(a) for a in (ids, tok, mlm, nsp))

    # discovery at tiny batch, then shape-polymorphic compile at target
    small = data(2)
    for _ in range(3):
        _sync(train_step(*small))
    for b in (batch, batch // 2, batch // 4):
        try:
            args = data(b)
            t0 = time.perf_counter()
            _sync(train_step(*args))
            print(f"# bert compile (batch {b}): "
                  f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
            # chained steps with ONE final sync: it waits for the
            # whole dependency chain (params thread step-to-step)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = train_step(*args)
            _sync(loss)
            dt = (time.perf_counter() - t0) / steps
            step_ms = dt * 1000
            sps = b / dt
            tokens_per_sec = sps * seq
            # training FLOPs ~ 6 * params per token
            mfu = 6.0 * n_params * tokens_per_sec / 197e12
            print(json.dumps({
                "config": 3, "model": "BERT-base pretrain",
                "batch": b, "seq": seq,
                "params_m": round(n_params / 1e6, 1),
                "step_ms": round(step_ms, 2),
                "samples_per_sec": round(sps, 1),
                "tokens_per_sec": round(tokens_per_sec, 0),
                "mfu_vs_v5e_peak_bf16": round(mfu, 3),
                "final_loss": round(float(loss.numpy()), 4),
            }), flush=True)
            return
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) \
                    and "ResourceExhausted" not in str(e):
                raise
            print(f"# bert batch {b} OOM, retrying", file=sys.stderr)
    print(json.dumps({"config": 3, "model": "BERT-base pretrain",
                      "error": "all batch sizes OOMed"}), flush=True)


def bench_gpt(batch=8, seq=1024, steps=20, amp_level=None):
    """GPT-2-small-scale (124M) causal-LM training on one chip: the
    flagship LLM path — Pallas flash attention fwd+bwd, AdamW, bf16.
    Reference flagship analogue: GPT pretraining under hybrid_parallel
    (the single-chip slice of the hybrid-parallel GPT config).

    Knobs (also see tools/gpt_mfu_sweep.py): batch/seq from argv,
    GPT_AMP_LEVEL=O1|O2 (O2 = pure-bf16 compute, fp32 master weights in
    the optimizer — halves the cast traffic)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import TransformerLMConfig, GPTForCausalLM

    amp_level = amp_level or os.environ.get("GPT_AMP_LEVEL", "O1")
    paddle.seed(0)
    cfg = TransformerLMConfig(
        vocab_size=50304, hidden_size=768,
        num_layers=12, num_heads=12,
        max_seq_len=seq, dropout=0.0, use_flash_attention=True,
        recompute=os.environ.get("GPT_RECOMPUTE", "0") == "1")
    model = GPTForCausalLM(cfg)
    n_params = sum(int(np.prod(p.aval_shape()))
                   for p in model.parameters())
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01)

    def step_fn(ids, labels):
        with paddle.amp.auto_cast(level=amp_level, dtype="bfloat16"):
            loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step = paddle.jit.to_static(step_fn)

    def data(b):
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 50304, (b, seq)).astype("int64")
        return (paddle.to_tensor(ids), paddle.to_tensor(ids.copy()))

    small = data(1)
    for _ in range(3):
        _sync(train_step(*small))
    for b in (batch, batch // 2, batch // 4):
        if b < 1:
            continue  # caller-chosen small batches: never "train" on b=0
        try:
            args = data(b)
            t0 = time.perf_counter()
            _sync(train_step(*args))
            print(f"# gpt compile (batch {b}): "
                  f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = train_step(*args)
            _sync(loss)  # ONE final D2H sync (see bench_bert note)
            dt = (time.perf_counter() - t0) / steps
            tokens_per_sec = b * seq / dt
            mfu = 6.0 * n_params * tokens_per_sec / 197e12
            # true-FLOPs MFU as well: 6N ignores the attention
            # quadratic. Causal fwd score+value matmuls are 2*s*d
            # FLOPs/token/layer; fwd+bwd ~3x that -> 6*L*s*d extra,
            # no longer negligible at seq >= 1024
            attn_extra = 6.0 * cfg.num_layers * seq * cfg.hidden_size
            mfu_true = ((6.0 * n_params + attn_extra)
                        * tokens_per_sec / 197e12)
            print(json.dumps({
                "config": 5, "model": "GPT-124M causal LM (flash attn)",
                "batch": b, "seq": seq, "amp": amp_level,
                "params_m": round(n_params / 1e6, 1),
                "step_ms": round(dt * 1000, 2),
                "tokens_per_sec": round(tokens_per_sec, 0),
                "mfu_vs_v5e_peak_bf16": round(mfu, 3),
                "mfu_incl_attention_flops": round(mfu_true, 3),
                "final_loss": round(float(loss.numpy()), 4),
            }), flush=True)
            prof_dir = os.environ.get("GPT_PROFILE_DIR")
            if prof_dir and b != batch:
                # an OOM fallback batch is NOT the headline workload —
                # a ceiling analysis on it would be misattributed
                print(f"# skipping profile: measured batch {b} != "
                      f"requested {batch}", file=sys.stderr)
                prof_dir = None
            if prof_dir:
                # XPlane capture of 5 steady-state steps for the MFU
                # ceiling analysis (VERDICT r4 item 1); best-effort —
                # a failed capture must not sink the measurement above
                try:
                    import jax
                    # perfetto trace = gzipped JSON, parseable without
                    # the TF profiler stack (XPlane .pb is not)
                    with jax.profiler.trace(prof_dir,
                                            create_perfetto_trace=True):
                        for _ in range(5):
                            loss = train_step(*args)
                        _sync(loss)
                    print(f"# profile captured to {prof_dir}",
                          file=sys.stderr)
                except Exception as pe:  # noqa: BLE001
                    print(f"# profile capture failed: {pe}",
                          file=sys.stderr)
            return
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) \
                    and "ResourceExhausted" not in str(e):
                raise
            print(f"# gpt batch {b} OOM, retrying", file=sys.stderr)
    print(json.dumps({"config": 5, "model": "GPT-124M causal LM",
                      "error": "all batch sizes OOMed"}), flush=True)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "lenet"):
        bench_lenet()
    if which in ("all", "bert"):
        bench_bert()
    if which in ("all", "gpt"):
        kw = {}
        if len(sys.argv) > 2:
            kw["batch"] = int(sys.argv[2])
        if len(sys.argv) > 3:
            kw["seq"] = int(sys.argv[3])
        bench_gpt(**kw)


if __name__ == "__main__":
    main()
