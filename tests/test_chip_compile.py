"""AOT-compile the main path's Pallas kernels for a described TPU v5e.

The sandbox has no chip, but the chip's compiler is installed: a
``v5e:2x2`` topology can be DESCRIBED and programs compiled against its
devices, which raises what Mosaic would raise on the real chip (things
interpret mode cannot see: tiling-misaligned slices, unsupported shape
casts, VMEM overflow). Nothing runs — these tests assert only that the
compile succeeds and that the kernel (``tpu_custom_call``) is in the
program.

The topology is described inside a module-scoped fixture, never at
import time: only one process may hold libtpu, and every xdist worker
imports every test file. Keep all such tests in THIS file so they land
on one worker (``--dist loadfile``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import attention, fused_ce, paged_attention

# GPT-124M widths (TransformerLMConfig(50304, 768, 12, 12))
HEADS, HEAD_DIM, HIDDEN, VOCAB = 12, 64, 768, 50304
TOKENS = 8192  # batch 8 x seq 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one — keep these
    # compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compile ``fn`` for the described devices the args' shardings
    name; the kernel must be in the optimized program."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _qkv(one_chip, batch, seq, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((batch, HEADS, seq, HEAD_DIM), dtype,
                                sharding=one_chip)


@pytest.mark.parametrize("batch,seq", [(8, 1024), (1, 8192)])
def test_flash_fwd_compiles(one_chip, batch, seq):
    q = _qkv(one_chip, batch, seq)
    _compile(lambda q, k, v: attention._pallas_flash_fwd(
        q, k, v, HEAD_DIM ** -0.5, True), q, q, q)


@pytest.mark.parametrize("batch,seq", [(8, 1024), (1, 8192)])
def test_flash_bwd_compiles(one_chip, batch, seq):
    q = _qkv(one_chip, batch, seq)
    lse = jax.ShapeDtypeStruct((batch, HEADS, 1, seq), jnp.float32,
                               sharding=one_chip)
    _compile(lambda q, k, v, o, lse, g: attention._pallas_flash_bwd(
        q, k, v, o, lse, g, HEAD_DIM ** -0.5, True), q, q, q, q, lse, q)


def _ce_args(sharding_x, sharding_w, sharding_t, vocab=VOCAB):
    x = jax.ShapeDtypeStruct((TOKENS, HIDDEN), jnp.bfloat16,
                             sharding=sharding_x)
    w = jax.ShapeDtypeStruct((vocab, HIDDEN), jnp.bfloat16,
                             sharding=sharding_w)
    lab = jax.ShapeDtypeStruct((TOKENS,), jnp.int32, sharding=sharding_t)
    return x, w, lab


def test_fused_ce_fwd_compiles(one_chip):
    x, w, lab = _ce_args(one_chip, one_chip, one_chip)
    _compile(lambda x, w, lab: fused_ce._pallas_fwd(x, w, lab, -100),
             x, w, lab)


def test_fused_ce_bwd_compiles(one_chip):
    x, w, lab = _ce_args(one_chip, one_chip, one_chip)
    vec = jax.ShapeDtypeStruct((TOKENS,), jnp.float32, sharding=one_chip)
    _compile(lambda x, w, lab, lse, g: fused_ce._pallas_bwd(
        x, w, lab, lse, g, -100), x, w, lab, vec, vec)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_decode_compiles(one_chip, dtype, monkeypatch):
    slots, block, blocks, per_slot = 8, 16, 256, 64
    # kernel_viable asks the PROCESS's default backend (cpu here); the
    # program is compiled for the described TPU, so answer for it
    monkeypatch.setattr(paged_attention.jax, "default_backend",
                        lambda: "tpu")
    assert paged_attention.kernel_viable(HEADS, HEAD_DIM, block, dtype)
    q = jax.ShapeDtypeStruct((slots, HEADS, HEAD_DIM), dtype,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((blocks, HEADS, block, HEAD_DIM), dtype,
                              sharding=one_chip)
    bt = jax.ShapeDtypeStruct((slots, per_slot), jnp.int32,
                              sharding=one_chip)
    ln = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    _compile(paged_attention.paged_decode_attention, q, kv, kv, bt, ln)


@pytest.mark.parametrize("vocab,kernel", [(50432, True), (VOCAB, False)])
def test_tp_fused_ce_shard_map_compiles(topo, monkeypatch, vocab, kernel):
    """The vocab-sharded fused-CE head (fwd + bwd) on a dp2 x mp2 mesh
    of the described chips; the compiler inserts the mp/dp collectives.
    With the vocab padded to a multiple of 128*mp (50432) each shard
    runs the Pallas kernels. At GPT-124M's own 50304 the per-shard
    vocab is 25152 = 196.5 lanes, which ``_use_pallas`` refuses: the
    shard_map then carries the XLA composition (pinned here so the
    refusal is a recorded fact, not a surprise on the chip)."""
    # same steering as above, for fused_ce._use_pallas
    monkeypatch.setattr(fused_ce.jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    x, w, lab = _ce_args(NamedSharding(mesh, P("dp", None)),
                         NamedSharding(mesh, P("mp", None)),
                         NamedSharding(mesh, P("dp")), vocab=vocab)

    def loss_and_grads(x, w, lab):
        def loss(x, w):
            return fused_ce._fused_tp_core(
                x, w, lab, fused_ce._register_mesh(mesh), -100).sum()
        return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    text = jax.jit(loss_and_grads).lower(x, w, lab).compile().as_text()
    assert "all-reduce" in text
    assert ("tpu_custom_call" in text) == kernel
