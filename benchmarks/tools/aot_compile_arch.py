"""Compile a served architecture's programs (a configuration whose
``plane`` is ``serve_arch``) at their real sizes for a DESCRIBED TPU v5e
(no chip attached) and print each program's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/aot_compile_arch.py \
        [config[:max_len] ...]

What the chip's compiler refuses (a kernel Mosaic rejects, a program
that does not fit beside the weights and the pool) shows here at no chip
time. Nothing runs: this prints sizes, never a time or a device metric.
The program's kernel gates ask the process's backend, so it is patched
to "tpu" for the build (here, not in the program).
"""
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks import harness  # noqa: E402
from benchmarks.planes import serve_arch  # noqa: E402
from benchmarks.tools.aot_compile import GB, _report  # noqa: E402


def programs(config, chip):
    arch = serve_arch.arch_files(config["arch"])
    model, sz = serve_arch.model_of(config), config["sizing"]
    dtype = jnp.dtype(config["precision"])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=chip)

    params = {}
    for path, (shape, kind) in arch.weights.leaf_shapes(model).items():
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = sds(shape, jnp.float32 if kind == "z" else dtype)
    S, BS = sz["num_slots"], sz["block_size"]
    MB = -(-sz["max_len"] // BS)
    NB = S * MB + 1
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        spec, prefill, decode = arch.program.serving_programs(
            model, config["precision"], S, BS, NB, MB)
    pool = [sds(spec.shape(a, NB, BS), a.dtype) for a in spec.arrays]
    state = [sds(shape, dt) for _, shape, dt in spec.state]
    i32 = jnp.int32
    toks, pos = sds((S,), i32), sds((S,), i32)
    w_gb = arch.weights.count_params(model) * dtype.itemsize / GB
    useful = spec.bytes_per_token * S * sz["max_len"] / GB
    print(f"weights {w_gb:.3f} GB ({arch.weights.count_params(model):,} "
          f"parameters); cache {spec.bytes_per_token} B a token, "
          f"{useful:.3f} GB useful for {S} x {sz['max_len']} positions "
          f"({NB} blocks of {BS})")
    n = len(pool)
    c = jax.jit(decode, donate_argnums=(2,) + tuple(range(4, 4 + n))) \
        .lower(params, toks, pos, sds((S, MB), i32), *pool, *state) \
        .compile()
    m = _report("paged_decode", c)
    worst = m.temp_size_in_bytes
    args = m.argument_size_in_bytes
    scalar = sds((), i32)
    for b in sz["buckets"]:
        c = jax.jit(prefill, donate_argnums=tuple(range(8, 9 + n))).lower(
            params, sds((1, b), i32), scalar, scalar, scalar, scalar,
            sds((MB,), i32), toks, pos, *pool).compile()
        worst = max(worst, _report(f"paged_prefill[{b}]",
                                   c).temp_size_in_bytes)
    print(f"steady state: decode's arguments (weights + pool as the "
          f"device lays it out) + worst temporaries = "
          f"{(args + worst) / GB:.2f} GB of 16")


def main(argv):
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    for name in argv or ["kanana2_30b_a3b_pp8"]:
        name, _, max_len = name.partition(":")
        config = harness.load_json(harness.HERE, "configs", name + ".json")
        if max_len:   # try another size without editing the file
            config["sizing"]["max_len"] = int(max_len)
        print(f"== {name} ({config['plane']}, arch {config['arch']})")
        try:
            programs(config, chip)
        except jax.errors.JaxRuntimeError as e:
            print("DOES NOT FIT / REFUSED:", str(e)[:2000])


if __name__ == "__main__":
    main(sys.argv[1:])
