"""Analytical roofline budget for the GPT-124M single-chip train step
AND (--decode) the serving decode step.

Computes, from first principles, where the step time HAS to go on a
v5e-class chip (197 TFLOP/s bf16 MXU, ~819 GB/s HBM): dense matmul
FLOPs, attention FLOPs (causal-halved), LM-head cost (fused vs
unfused), optimizer + parameter HBM traffic, and activation traffic.
Pairs with tools/mfu_analysis.py's measured perfetto breakdown: the
measured bucket that most exceeds its roofline line is the next lever.

``--decode`` switches to the serving decode-step HBM model (ROADMAP
direction #2's "roofline first" step, shared with the engine's
snapshot()["perf"] via paddle_tpu/observability/perf/roofline.py,
loaded directly by file so this tool never imports jax): KV-read
bytes per token as a function of batch, context length, heads and
layout (paged_xla / paged_pallas), the parameter re-read
every step pays, and the resulting per-step floor — printed for
both layouts so the XLA gather-materialization tax, and what the
Pallas paged-attention kernel (ops.paged_attention) buys back by
deleting it, are numbers, not vibes.

Usage: python tools/gpt_roofline.py [batch seq]           (train step)
       python tools/gpt_roofline.py --decode [batch ctx]  (decode step)
"""
import importlib.util
import json
import os
import sys

PEAK_FLOPS = 197e12        # v5e bf16
HBM_BPS = 819e9            # v5e HBM bandwidth

# GPT-124M
L, H, V, HEADS = 12, 768, 50304, 12
MAX_SEQ = 1024


def budget(batch, seq, mxu_eff=1.0, hbm_eff=1.0):
    t = batch * seq
    # dense body matmuls: qkv+proj (4H^2/layer) + mlp (8H^2/layer),
    # fwd + 2x bwd
    body_params = L * 12 * H * H
    body_flops = 6.0 * body_params * t
    # attention score+value matmuls: 2 matmuls x 2*T*seq*H per layer
    # fwd, 2x that bwd; causal -> half the blocks are skipped
    attn_flops = 0.5 * 3 * 2 * 2 * t * seq * H * L
    # LM head (tied embedding): fwd logits + bwd dx + bwd dW
    head_flops = 3 * 2.0 * t * H * V
    head_flops_fused_pallas = 5 * 2.0 * t * H * V  # +2 recomputes
    # optimizer/params HBM (O2: bf16 weights, f32 master+moments):
    # fwd read Wbf16, bwd read Wbf16 + write Gbf16, opt reads
    # G+m+v+master, writes m+v+master+Wbf16
    n_params = body_params + V * H + seq * H
    opt_bytes = n_params * (2 + 2 + 2 + 4 * 4 + 4 * 3 + 2)
    # activation traffic: ~10 layer-intermediate [T, H] bf16 tensors
    # per layer written fwd + read bwd
    act_bytes = 2 * 10 * L * t * H * 2
    # unfused head logits traffic: write [T, V] bf16 + read in
    # softmax-CE fwd, dlogits write + 2 reads bwd
    logits_bytes = 5 * t * V * 2

    ms = lambda fl, by: round(max(fl / (PEAK_FLOPS * mxu_eff),
                                  by / (HBM_BPS * hbm_eff)) * 1e3, 2)
    rows = {
        "body_matmuls": ms(body_flops, 0),
        "attention(causal)": ms(attn_flops, 0),
        "head_unfused": ms(head_flops, logits_bytes),
        "head_fused_pallas(2 recomputes)": ms(head_flops_fused_pallas, 0),
        "optimizer+params_hbm": ms(0, opt_bytes),
        "activations_hbm": ms(0, act_bytes),
    }
    floor_unfused = (rows["body_matmuls"] + rows["attention(causal)"]
                     + rows["head_unfused"]
                     + rows["optimizer+params_hbm"])
    model_flops = 6.0 * (n_params) * t + attn_flops
    return {
        "config": {"batch": batch, "seq": seq,
                   "mxu_eff": mxu_eff, "hbm_eff": hbm_eff},
        "per_component_ms": rows,
        "step_floor_ms_unfused_head": round(floor_unfused, 2),
        "mfu_at_floor": round(
            model_flops / (floor_unfused / 1e3) / PEAK_FLOPS, 3),
    }


def _load_roofline_module():
    """Load observability/perf/roofline.py by file path: pure stdlib
    module, no paddle_tpu (= no jax) import at tool startup."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "paddle_tpu", "observability", "perf",
                        "roofline.py")
    spec = importlib.util.spec_from_file_location("_ptpu_roofline",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def decode_budget(batch, ctx):
    """Decode-step HBM model for GPT-124M at (batch slots, ctx cached
    positions), both attention paths over the paged pool — the
    XLA-composed gather and the in-place Pallas kernel — bf16
    params/KV on the v5e reference chip."""
    rf = _load_roofline_module()
    n_params = L * 12 * H * H + V * H + MAX_SEQ * H
    out = {"config": {"batch": batch, "ctx": ctx, "model": "gpt-124m",
                      "peak_flops": PEAK_FLOPS, "hbm_bps": HBM_BPS}}
    for layout in rf.LAYOUTS:
        m = rf.decode_step_model(
            batch=batch, kv_len=ctx, num_layers=L, num_heads=HEADS,
            head_dim=H // HEADS, n_params=n_params, param_bytes=2,
            kv_bytes=2, layout=layout, live_kv_len=ctx,
            peak_flops=PEAK_FLOPS, hbm_bps=HBM_BPS)
        out[layout] = {
            "gather_factor": m["gather_factor"],
            "kv_read_bytes_per_token": m["kv_read_bytes_per_token"],
            "bytes_total": m["bytes_total"],
            "flops": m["flops"],
            "arithmetic_intensity": round(m["arithmetic_intensity"], 4),
            "floor_us_per_step": round(m["floor_s"] * 1e6, 3),
            "tokens_per_sec_at_floor": round(
                batch / m["floor_s"], 1),
            "bound": m["bound"],
        }
    # what the Pallas kernel buys back at the floor: the gather tax
    out["pallas_vs_paged_xla_x"] = round(
        out["paged_xla"]["floor_us_per_step"]
        / out["paged_pallas"]["floor_us_per_step"], 3)
    return out


def main():
    args = [a for a in sys.argv[1:] if a != "--decode"]
    if "--decode" in sys.argv[1:]:
        batch = int(args[0]) if args else 8
        ctx = int(args[1]) if len(args) > 1 else 1024
        print(json.dumps(decode_budget(batch, ctx)))
        return
    batch = int(args[0]) if args else 8
    seq = int(args[1]) if len(args) > 1 else 1024
    # ideal floor and a realistic-efficiency scenario
    for mxu, hbm in ((1.0, 1.0), (0.6, 0.7)):
        print(json.dumps(budget(batch, seq, mxu, hbm)))


if __name__ == "__main__":
    main()
