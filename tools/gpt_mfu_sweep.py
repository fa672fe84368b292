"""GPT-124M MFU sweep.

Runs tools/baseline_bench.py's GPT config across the tuning axes that
matter on one chip — AMP level (O1 per-op autocast vs O2 pure-bf16),
the fused head, and the seq 2048/4096 extension points (the flash
kernels pick their own tiles from the shape: ops/attention._block) —
each in a FRESH SUBPROCESS, one after
another (the env knobs are read at import, and the chip belongs to one
process at a time; this parent never touches jax). Every result line
is appended to an artifact in chiprun_out/ (what a chip run brings
back).

Usage:  python tools/gpt_mfu_sweep.py [quick|full]
  quick: amp and head sweep at seq 1024
  full:  + seq 2048/4096 points
"""
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ART = os.path.join(_ROOT, "chiprun_out")


def run_config(tag, batch, seq, env_extra, timeout=900):
    env = dict(os.environ)
    env.update(env_extra)
    cmd = [sys.executable, os.path.join(_ROOT, "tools",
                                        "baseline_bench.py"),
           "gpt", str(batch), str(seq)]
    t0 = time.time()
    try:
        res = subprocess.run(cmd, env=env, capture_output=True,
                             text=True, timeout=timeout)
        stdout, stderr, rc = res.stdout, res.stderr, res.returncode
        hung = None
    except subprocess.TimeoutExpired as e:
        # the measurement JSON may already be out (e.g. a hang during
        # the post-measurement profile capture) — salvage it
        stdout = e.stdout or ""
        if isinstance(stdout, bytes):
            stdout = stdout.decode("utf-8", "replace")
        stderr, rc = "", -1
        hung = f"hung >{timeout}s"
    line = None
    for ln in stdout.splitlines():
        ln = ln.strip()
        if ln.startswith("{"):
            line = ln
    if line is None:
        return {"tag": tag,
                "error": hung or (stderr or "no output")[-400:],
                "rc": rc}
    out = json.loads(line)
    out["tag"] = tag
    out["wall_s"] = round(time.time() - t0, 1)
    if hung:
        out["note"] = ("measurement line salvaged; process " + hung)
    return out


def main():
    mode = sys.argv[1] if len(sys.argv) > 1 else "quick"
    os.makedirs(_ART, exist_ok=True)
    # FIXED per-mode artifact so a re-run after an interrupted sweep
    # resumes at the first config with no successful line instead of
    # restarting from config 1
    art = os.path.join(_ART, f"gpt_mfu_sweep_{mode}.jsonl")
    done = set()
    prior_best = None
    if os.path.exists(art):
        with open(art) as f:
            for ln in f:
                try:
                    rec = json.loads(ln)
                except ValueError:
                    continue
                if "tokens_per_sec" in rec:
                    done.add(rec["tag"])
                    if rec.get("seq") == 1024 and (
                            prior_best is None or rec["tokens_per_sec"]
                            > prior_best["tokens_per_sec"]):
                        prior_best = rec
                elif rec.get("rc", -1) != -1:
                    # a real exit code = deterministic failure (compile
                    # error, OOM at every batch) — reproduces on retry,
                    # skip it; a hang (rc -1) is retried
                    done.add(rec["tag"])

    configs = [
        ("baseline_O1", 8, 1024, {"GPT_AMP_LEVEL": "O1"}),
        ("O2_pure_bf16", 8, 1024, {"GPT_AMP_LEVEL": "O2"}),
        # ablation: the fused linear+CE head OFF (logits round-trip
        # HBM) — the delta vs O2_pure_bf16 is the fused-CE win
        ("O2_unfused_ce", 8, 1024, {"GPT_AMP_LEVEL": "O2",
                                    "PADDLE_FUSED_CE_DISABLE": "1"}),
        # bigger token tile: halves the per-token-block W streaming
        ("O2_ce_bt512", 8, 1024, {"GPT_AMP_LEVEL": "O2",
                                  "PADDLE_FUSED_CE": "1",
                                  "PADDLE_FUSED_CE_BLOCK_T": "512"}),
        # the ceiling-analysis capture runs right after the head
        # decision configs — it is the "45% MFU or a profile-backed
        # ceiling analysis" deliverable and must not sit behind the
        # block sweep on a short window
        ("O2_nf_profiled", 8, 1024,
         {"GPT_AMP_LEVEL": "O2",
          "PADDLE_FUSED_CE_DISABLE": "1",
          "GPT_PROFILE_DIR": os.path.join(_ART, "gpt_profile")}),
        # LAST in the quick list (the longest compile); unfused so the
        # batch-scaling axis is clean of the head question
        ("O2_nf_batch16", 16, 1024, {"GPT_AMP_LEVEL": "O2",
                                     "PADDLE_FUSED_CE_DISABLE": "1"}),
    ]
    if mode == "full":
        configs += [
            ("O2_nf_seq2048", 4, 2048, {"GPT_AMP_LEVEL": "O2",
                                        "PADDLE_FUSED_CE_DISABLE": "1"}),
            ("O2_nf_seq4096", 2, 4096, {"GPT_AMP_LEVEL": "O2",
                                        "PADDLE_FUSED_CE_DISABLE": "1"}),
            # fused head at seq 4096: the memory-bound config where
            # not materializing [T, V] logits should actually matter
            ("O2_seq4096_fused", 2, 4096, {"GPT_AMP_LEVEL": "O2",
                                           "PADDLE_FUSED_CE": "1"}),
            ("O2_nf_seq4096_rc_b4", 4, 4096, {"GPT_AMP_LEVEL": "O2",
                                              "PADDLE_FUSED_CE_DISABLE": "1",
                                              "GPT_RECOMPUTE": "1"}),
            # fused head at batch 16: if nf_batch16 OOMs back to batch
            # 8, this measures whether the no-logits-in-HBM head buys
            # the batch the unfused one can't fit
            ("O2_batch16_fused", 16, 1024, {"GPT_AMP_LEVEL": "O2",
                                            "PADDLE_FUSED_CE": "1"}),
        ]

    best = prior_best
    with open(art, "a") as f:
        for tag, batch, seq, env in configs:
            if tag in done:
                print(f"# {tag}: done in a previous attempt, skipping",
                      file=sys.stderr)
                continue
            print(f"# running {tag} (batch {batch} seq {seq}) ...",
                  file=sys.stderr)
            out = run_config(tag, batch, seq, env)
            f.write(json.dumps(out) + "\n")
            f.flush()
            print(json.dumps(out), flush=True)
            if "error" in out:
                # stop at the first failure; a re-run resumes here
                # (finished tags are skipped)
                print("# config failed; stopping", file=sys.stderr)
                sys.exit(1)
            if "tokens_per_sec" in out and (
                    best is None
                    or out["tokens_per_sec"] > best["tokens_per_sec"]):
                if out.get("seq") == 1024:
                    best = out
    if best:
        print(json.dumps({"best_1024": best,
                          "artifact": os.path.relpath(art, _ROOT)}),
              flush=True)


if __name__ == "__main__":
    main()
