"""Compile the cells' programs at their real sizes for a DESCRIBED TPU
v5e (no chip attached) and print each program's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/aot_compile.py \
        [config[:num_slots or batch] ...]

What the chip's compiler refuses (a program that does not fit beside
the KV pool, a kernel Mosaic rejects) shows here at no chip time.
Nothing runs: this prints sizes, never a time or a device metric.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks import harness, weights  # noqa: E402

GB = 1e9


def _report(name, compiled):
    m = compiled.memory_analysis()
    print(f"{name}: arguments {m.argument_size_in_bytes / GB:.3f} GB, "
          f"outputs {m.output_size_in_bytes / GB:.3f} GB, "
          f"aliased {m.alias_size_in_bytes / GB:.3f} GB, "
          f"temporaries {m.temp_size_in_bytes / GB:.3f} GB, "
          f"kernels {compiled.as_text().count('tpu_custom_call')}",
          flush=True)
    return m


def serve_programs(config, chip):
    from paddle_tpu.serving.paged.programs import build_paged_fns
    from paddle_tpu.text.models import TransformerLMConfig
    m, sz = config["model"], config["sizing"]
    dtype = jnp.dtype(config["precision"])
    cfg = TransformerLMConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_layers=m["num_hidden_layers"],
        num_heads=m["num_attention_heads"],
        intermediate_size=m["intermediate_size"],
        max_seq_len=m["max_position_embeddings"], dropout=0.0)
    S, BS = sz["num_slots"], sz["block_size"]
    MB = -(-sz["max_len"] // BS)
    NB = S * MB + 1

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    leaves = {k: sds(s, dtype)
              for k, (s, _) in weights.leaf_shapes(m).items()}
    layer = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
             "ln2_w", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
    params = {"stacked": {k: leaves[k] for k in layer},
              "wemb": leaves["wemb"], "pemb": leaves["pemb"],
              "lnf_w": leaves["lnf_w"], "lnf_b": leaves["lnf_b"],
              "head": sds(leaves["wemb"].shape[::-1], dtype)}
    hd = m["hidden_size"] // m["num_attention_heads"]
    kv = sds((m["num_hidden_layers"], NB, m["num_attention_heads"], BS, hd),
             dtype)
    i32 = jnp.int32
    toks, pos = sds((S,), i32), sds((S,), i32)
    prefill, decode = build_paged_fns(cfg, S, BS, NB, MB)
    pool_gb = 2 * kv.size * dtype.itemsize / GB
    w_gb = weights.count_params(m) * dtype.itemsize / GB
    print(f"weights {w_gb:.3f} GB, KV pool {pool_gb:.3f} GB "
          f"({NB} blocks of {BS} tokens, {S} slots)")
    worst = 0
    c = jax.jit(decode, donate_argnums=(2, 4, 5)).lower(
        params, toks, pos, sds((S, MB), i32), kv, kv).compile()
    worst = max(worst, _report("paged_decode", c).temp_size_in_bytes)
    scalar = sds((), i32)
    for b in sz["buckets"]:
        c = jax.jit(prefill, donate_argnums=(8, 9, 10)).lower(
            params, sds((1, b), i32), scalar, scalar, scalar, scalar,
            sds((MB,), i32), toks, pos, kv, kv).compile()
        worst = max(worst, _report(f"paged_prefill[{b}]",
                                   c).temp_size_in_bytes)
    print(f"steady state: weights + head copy + pool + worst temporaries "
          f"= {w_gb + pool_gb + worst / GB + 0.21:.2f} GB of 16")


def train_program(config, chip, batches):
    """The to_static step: its eager and record passes run here on the
    CPU at batch 1 (they only discover the step's state), then the
    captured step is lowered at each real batch for the described chip.
    The kernel gates ask the process's backend, so it is patched to
    "tpu" for the lowering (here, not in the program)."""
    from unittest import mock

    from benchmarks.planes import train
    from paddle_tpu.jit.to_static import captured_arrays
    traffic = {"generator": "token_rows", "checked_steps": 0,
               "settle_steps": 0}
    small = dict(config, sizing=dict(config["sizing"], batch=1))
    prog = train.TrainProgram(small, traffic, 0, harness.Spans())
    entry = next(e["compiled"] for e in prog.step.entries.values()
                 if e["compiled"])

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
    mut, ro = captured_arrays(entry)
    mut, ro = [sds(a) for a in mut], [sds(a) for a in ro]
    T = config["sizing"]["seq_len"]
    state = sum(a.size * a.dtype.itemsize for a in mut + ro)
    print(f"captured state {state / GB:.3f} GB")
    for b in batches:
        ids = jax.ShapeDtypeStruct((b, T), jnp.int64, sharding=chip)
        try:
            with mock.patch.object(jax, "default_backend",
                                   lambda: "tpu"):
                c = entry["jitted"].lower([ids, ids], mut, ro).compile()
            m = _report(f"train_step[batch {b}]", c)
            total = (m.argument_size_in_bytes + m.temp_size_in_bytes
                     + m.output_size_in_bytes - m.alias_size_in_bytes)
            print(f"   batch {b}: {total / GB:.2f} GB of 16.9 usable")
        except jax.errors.JaxRuntimeError as e:
            print(f"train_step[batch {b}] DOES NOT FIT:",
                  str(e).split("\n")[0][:300])


def main(argv):
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    names = argv or ["gpt3_1p3b"]
    for name in names:
        name, _, slots = name.partition(":")
        config = harness.load_json(harness.HERE, "configs", name + ".json")
        if slots:   # try another size without editing the file
            config["sizing"]["num_slots"] = int(slots)
        print(f"== {name} ({config['plane']})")
        if config["plane"] == "serve":
            try:
                serve_programs(config, chip)
            except jax.errors.JaxRuntimeError as e:
                print("DOES NOT FIT:", str(e).split("\n")[0])
        else:
            train_program(config, chip,
                          [int(slots)] if slots else [16, 64, 96, 112, 128])


if __name__ == "__main__":
    main(sys.argv[1:])
