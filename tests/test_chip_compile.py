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
import re
import sys
from unittest import mock

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


def _instruction_names(text):
    """Names of the optimized program's instructions: a Pallas kernel's
    ``name=`` becomes its custom call's (``%flash_fwd = ...``), which
    is what the benchmark's per-kernel readers match in the trace."""
    return re.findall(r"%([\w.-]+) = ", text)


# (24, 1024) is the training cell's own shape: one tile a head, one
# backward kernel; (1, 8192) streams K/V tile by tile through the grid
# and takes the backward's two kernels
FLASH_SHAPES = [(8, 1024), (24, 1024), (1, 8192)]


@pytest.mark.parametrize("batch,seq", FLASH_SHAPES)
def test_flash_fwd_compiles(one_chip, batch, seq):
    q = _qkv(one_chip, batch, seq)
    text = _compile(lambda q, k, v: attention._pallas_flash_fwd(
        q, k, v, HEAD_DIM ** -0.5, True), q, q, q)
    # benchmarks/metrics/flash_fwd_dev_ms_per_step.py reads this name
    assert any("flash_fwd" in n for n in _instruction_names(text))


@pytest.mark.parametrize("batch,seq", FLASH_SHAPES)
def test_flash_bwd_compiles(one_chip, batch, seq):
    q = _qkv(one_chip, batch, seq)
    lse = jax.ShapeDtypeStruct((batch, HEADS, 1, seq), jnp.float32,
                               sharding=one_chip)
    text = _compile(lambda q, k, v, o, lse, g: attention._pallas_flash_bwd(
        q, k, v, o, lse, g, HEAD_DIM ** -0.5, True), q, q, q, q, lse, q)
    # ... and flash_bwd_dev_ms_per_step.py this prefix, on every kernel
    # of the backward
    kernels = [n for n in _instruction_names(text) if "flash" in n]
    assert kernels and all("flash_bwd_" in n for n in kernels), kernels
    # gradients leave the kernels in the inputs' dtype: no f32 copy of
    # them, and no fusion that casts one
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert calls and not any(
        f"f32[{batch},{HEADS},{seq},{HEAD_DIM}]" in l for l in calls)


def test_replayed_flash_fwd_merges_with_the_first(one_chip):
    """The tape's backward runs jax.vjp of an op's forward again
    (core/engine.py), so a step holds every layer's forward twice. The
    kernels' wrappers are jitted: the two calls are then one computation
    on the same operands to XLA, which keeps one."""
    q = _qkv(one_chip, 24, 1024)

    def taped(q, k, v, g):
        core = lambda *a: attention._flash_attention_core(
            *a, HEAD_DIM ** -0.5, True)
        out = core(q, k, v)                       # the forward pass
        _, vjp = jax.vjp(core, q, k, v)           # the tape's replay
        return out, vjp(g)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = _compile(taped, q, q, q, q)
    kernels = [n for line in text.splitlines() if "tpu_custom_call" in line
               for n in _instruction_names(line)]
    assert sorted(n.split(".")[0] for n in kernels) == [
        "flash_bwd_dqkv", "flash_fwd"], kernels


# what else runs the kernels (nn.functional.scaled_dot_product_attention
# for every model): wider heads, f32 operands (no scale folded at 128:
# 1/sqrt(128) is no power of two), no causal mask
@pytest.mark.parametrize("d,dtype,causal", [
    (128, jnp.bfloat16, True), (256, jnp.bfloat16, True),
    (64, jnp.float32, True), (128, jnp.float32, False),
    (64, jnp.bfloat16, False)],
    ids=["d128", "d256", "f32", "f32-d128-full", "full"])
def test_flash_compiles_at_other_shapes(one_chip, d, dtype, causal):
    batch, seq = 2, 2048
    q = jax.ShapeDtypeStruct((batch, HEADS, seq, d), dtype,
                             sharding=one_chip)

    def both(q, k, v, g):
        o, lse = attention._pallas_flash_fwd(q, k, v, d ** -0.5, causal)
        return attention._pallas_flash_bwd(q, k, v, o, lse, g, d ** -0.5,
                                           causal)
    _compile(both, q, q, q, q)


# the packed entry (q, k, v read from the qkv projection's output where
# it lies): the training cell's own shape, two heads of 64 a grid step,
# and the 1.3B GPT's heads of 128, one a step
PACKED_SHAPES = [(24, 1024, 12, 64), (8, 1024, 16, 128)]


def _packed(one_chip, batch, seq, heads, d):
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    return (sds((batch, seq, 3 * heads * d)), sds((batch, seq, heads * d)),
            sds((batch, heads, 1, seq), jnp.float32))


@pytest.mark.parametrize("batch,seq,heads,d", PACKED_SHAPES)
def test_packed_flash_fwd_compiles(one_chip, batch, seq, heads, d):
    qkv, _, _ = _packed(one_chip, batch, seq, heads, d)
    text = _compile(lambda x: attention._pallas_flash_qkv_fwd(
        x, heads, d ** -0.5, True), qkv)
    assert any("flash_fwd" in n for n in _instruction_names(text))


@pytest.mark.parametrize("batch,seq,heads,d", PACKED_SHAPES)
def test_packed_flash_bwd_compiles(one_chip, batch, seq, heads, d):
    qkv, o, lse = _packed(one_chip, batch, seq, heads, d)
    text = _compile(lambda x, o, lse, g: attention._pallas_flash_qkv_bwd(
        x, o, lse, g, heads, d ** -0.5, True), qkv, o, lse, o)
    kernels = [n for n in _instruction_names(text) if "flash" in n]
    assert kernels and all("flash_bwd_" in n for n in kernels), kernels
    # the kernel writes dqkv itself: nothing else is in the program
    assert not re.search(r" (fusion|copy|transpose|concatenate)\(", text)


def test_packed_layer_holds_no_head_split(one_chip):
    """qkv projection -> packed attention -> output projection, forward
    and vjp, at the training cell's shape: between the matmuls and the
    kernels no copy or transpose, no [.., heads, seq, d] or [.., 3,
    heads, d] array, no f32 array of an operand's size."""
    batch, seq = 24, 1024
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)

    def layer(x, wq, bq, wo, bo):
        o = attention._flash_qkv_op.fn(x @ wq + bq, heads=HEADS,
                                       causal=True)
        return o @ wo + bo

    def step(g, *args):
        out, vjp = jax.vjp(layer, *args)
        return out, vjp(g)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = _compile(step, sds(batch, seq, HIDDEN), sds(batch, seq, HIDDEN),
                        sds(HIDDEN, 3 * HIDDEN), sds(3 * HIDDEN),
                        sds(HIDDEN, HIDDEN), sds(HIDDEN))
    entry = text[text.index("ENTRY"):]
    for line in entry.splitlines():
        shapes = re.findall(r"(\w+)\[([\d,]+)\]", line.split(" = ")[-1]
                            .split("(")[0])
        for dt, shape in shapes:
            dims = shape.split(",")
            assert dims[-2:] != [str(HEADS), str(HEAD_DIM)] \
                and dims[-3:-1] != [str(HEADS), str(seq)], line
            assert not (dt == "f32" and len(dims) >= 3
                        and dims[:2] == [str(batch), str(seq)]), line
        assert not re.search(r" (copy|transpose)\(", line) \
            or "copy-" in line, line
    kernels = [n for line in entry.splitlines() if "tpu_custom_call" in line
               for n in _instruction_names(line)]
    assert sorted(n.split(".")[0] for n in kernels) == [
        "flash_bwd_dqkv", "flash_fwd"], kernels


def test_replayed_packed_flash_fwd_merges_with_the_first(one_chip):
    """As above for the packed entry: the tape's replayed forward is the
    first forward to XLA, one `flash_fwd` a layer."""
    qkv, o, _ = _packed(one_chip, 24, 1024, HEADS, HEAD_DIM)

    def taped(x, g):
        core = lambda x_: attention._flash_qkv_core(
            x_, HEADS, HEAD_DIM ** -0.5, True)
        out = core(x)
        _, vjp = jax.vjp(core, x)
        return out, vjp(g)
    text = _compile(taped, qkv, o)
    kernels = [n for line in text.splitlines() if "tpu_custom_call" in line
               for n in _instruction_names(line)]
    assert sorted(n.split(".")[0] for n in kernels) == [
        "flash_bwd_dqkv", "flash_fwd"], kernels


def _ce_args(sharding_x, sharding_w, sharding_t, vocab=VOCAB,
             tokens=TOKENS):
    x = jax.ShapeDtypeStruct((tokens, HIDDEN), jnp.bfloat16,
                             sharding=sharding_x)
    w = jax.ShapeDtypeStruct((vocab, HIDDEN), jnp.bfloat16,
                             sharding=sharding_w)
    lab = jax.ShapeDtypeStruct((tokens,), jnp.int32, sharding=sharding_t)
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


def _fusions_over(text, shape):
    """(name, kind) of the entry computation's fusions that take or give
    an array of ``shape``."""
    bodies = dict(re.findall(r"^%?([\w.-]+) \([^\n]*\{\n(.*?)^\}", text,
                             re.M | re.S))
    entry = re.search(r"^ENTRY [^\n]*\n(.*?)^\}", text, re.M | re.S).group(1)
    found = []
    for line in entry.splitlines():
        m = re.search(r"%([\w.-]+) = (.*?) fusion\(.*kind=(\w+), "
                      r"calls=%([\w.-]+)", line)
        if m is None:
            continue
        name, gives, kind, body = m.groups()
        takes = any(" parameter(" in ln and shape in ln
                    for ln in bodies[body].splitlines())
        if takes or shape in gives:
            found.append((name, kind))
    return found


def test_differentiated_head_walks_the_logits_three_times(one_chip):
    """The training cell's head and loss at its own shape (24 x 1024
    tokens), as the tape runs them: the forward call AND the replay of
    its rule for the backward, in one program. XLA must merge the two
    and keep three dense passes over the f32 logits (the logits with
    their row maximum; `exp(logits - m) @ [W | 1]` = dx and the softmax's
    sum; dW), none of them a reduce-only loop fusion: what the ledger's
    `breakdown.device_ops` shows of the cell. Beside them only the
    label's lookup, a gather of one element a row."""
    tokens = 24 * 1024
    x, w, lab = _ce_args(one_chip, one_chip, one_chip, tokens=tokens)

    def step(x, w, lab):
        def head(x, w):
            return fused_ce._fused_core(x, w, lab, -100, True)
        loss = head(x, w)
        _, vjp = jax.vjp(head, x, w)
        g = jnp.full((tokens,), 1.0 / tokens, jnp.float32)
        return loss.sum(), vjp(g)
    compiled = jax.jit(step).lower(x, w, lab).compile()
    over = _fusions_over(compiled.as_text(), f"f32[{tokens},{VOCAB}]")
    dense = [(n, k) for n, k in over if k != "kCustom"]
    assert len(dense) == 3 and {k for _, k in dense} == {"kOutput"}, over
    assert len(over) - len(dense) == 1, over      # the label's gather
    assert compiled.memory_analysis().temp_size_in_bytes < 5.1e9


# the 1.3B serving cell's attention: 16 heads x 128, blocks of 16, 64 a
# slot (GPT-124M's heads of 64 do not fill the kernel's lanes: refused)
PAGED_HEADS, PAGED_HEAD_DIM, PAGED_BLOCK, PAGED_PER_SLOT = 16, 128, 16, 64


@pytest.fixture
def mosaic_backend(monkeypatch):
    """kernel_viable asks the PROCESS's default backend (cpu here); the
    programs below are compiled for the described TPU, so answer for
    it."""
    monkeypatch.setattr(paged_attention.jax, "default_backend",
                        lambda: "tpu")


def _compile_paged_decode_with_write(one_chip, dtype, slots, per_slot,
                                     nq, nkv, hd, block, rot=0):
    """``paged_decode_attn`` WITH the step's new entry placed in it (what
    the decode programs call), pools donated: Mosaic takes the tile's
    write-back (``[nkv, 16, hd]`` rows of a 16-bit pool, 8 of an f32
    one, 128 lanes of the transposed part), the pools are aliased onto
    the results and the call has no temporary."""
    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    blocks = slots * per_slot + 1
    pools = [sds((blocks, nkv, block, hd)), sds((blocks, nkv, block, hd))]
    new = [sds((slots, nkv, hd)), sds((slots, nkv, hd))]
    second = []
    if rot:
        second = [sds((slots, nq, rot))]
        pools.append(sds((blocks, nkv, rot, block)))
        new.append(sds((slots, nkv, rot)))
    scalars = [sds((slots, per_slot), jnp.int32), sds((slots,), jnp.int32),
               sds((slots,), jnp.int32)]

    def call(q, pools, new, bt, ln, wpos, *q_rot):
        return paged_attention.paged_write_attention(
            q, new, pools, bt, wpos, ln, True, *q_rot)
    compiled = jax.jit(call, donate_argnums=(1,)).lower(
        sds((slots, nq, hd)), tuple(pools), tuple(new), *scalars,
        *second).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attn" in text
    mem = compiled.memory_analysis()
    nbytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 1 << 20, mem.temp_size_in_bytes
    return text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_decode_compiles(one_chip, dtype, mosaic_backend):
    slots, blocks = 24, 24 * 64 + 1
    nh, hd, block, per_slot = (PAGED_HEADS, PAGED_HEAD_DIM, PAGED_BLOCK,
                               PAGED_PER_SLOT)
    assert paged_attention.kernel_viable(nh, hd, block, dtype)
    assert not paged_attention.kernel_viable(HEADS, HEAD_DIM, block, dtype)
    q = jax.ShapeDtypeStruct((slots, nh, hd), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((blocks, nh, block, hd), dtype,
                              sharding=one_chip)
    bt = jax.ShapeDtypeStruct((slots, per_slot), jnp.int32,
                              sharding=one_chip)
    ln = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    _compile(paged_attention.paged_decode_attention, q, kv, kv, bt, ln)
    _compile_paged_decode_with_write(one_chip, dtype, slots, per_slot,
                                     nh, nh, hd, block)


def _paged_gpt(one_chip, slots, L=8, ffn=512, vocab=512):
    """(config, parameter shapes, pool shape, shape maker) of a GPT of
    16 heads x 128 over blocks of 16, 64 a slot plus the trash block."""
    from paddle_tpu.text.models import TransformerLMConfig
    S, BS, MB, nh, hd = (slots, PAGED_BLOCK, PAGED_PER_SLOT, PAGED_HEADS,
                         PAGED_HEAD_DIM)
    NB, hidden = S * MB + 1, nh * hd
    cfg = TransformerLMConfig(vocab_size=vocab, hidden_size=hidden,
                              num_layers=L, num_heads=nh,
                              intermediate_size=ffn, max_seq_len=MB * BS,
                              dropout=0.0)

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    stacked = {"ln1_w": (hidden,), "ln1_b": (hidden,),
               "qkv_w": (hidden, 3 * hidden), "qkv_b": (3 * hidden,),
               "out_w": (hidden, hidden), "out_b": (hidden,),
               "ln2_w": (hidden,), "ln2_b": (hidden,),
               "fc1_w": (hidden, ffn), "fc1_b": (ffn,),
               "fc2_w": (ffn, hidden), "fc2_b": (hidden,)}
    params = {"stacked": {k: sds((L,) + v) for k, v in stacked.items()},
              "wemb": sds((vocab, hidden)), "pemb": sds((MB * BS, hidden)),
              "lnf_w": sds((hidden,)), "lnf_b": sds((hidden,)),
              "head": sds((hidden, vocab))}
    return cfg, params, (L, NB, nh, BS, hd), sds


def _paged_decode_program(one_chip, sampling, attn_kernel, slots=4):
    """(compiled paged_decode, pool shape), jitted with the engine's
    donation (pos, kc, vc), at sizes where the pool dominates the
    program: 8 layers of [16 heads, 16 tokens, 128] bf16 blocks, 64 a
    slot plus the trash block (135 MB for K at 4 slots, as much for V),
    narrow MLP and vocab. ``attn_kernel`` as the engine would choose it
    (``kernel_viable``) or refused."""
    from paddle_tpu.serving.paged.programs import build_paged_fns
    cfg, params, pool, sds = _paged_gpt(one_chip, slots)
    S, MB, NB, BS = slots, PAGED_PER_SLOT, pool[1], PAGED_BLOCK
    i32 = jnp.int32
    args = [params, sds((S,), i32), sds((S,), i32), sds((S, MB), i32),
            sds(pool), sds(pool)]
    if sampling:   # seeds, temps, top-k, top-p (sched.sampling)
        args += [sds((S,), jnp.uint32), sds((S,), jnp.float32),
                 sds((S,), i32), sds((S,), jnp.float32)]
    _, decode = build_paged_fns(cfg, S, BS, NB, MB, sampling=sampling,
                                attn_kernel=attn_kernel)
    return jax.jit(decode, donate_argnums=(2, 4, 5)).lower(
        *args).compile(), pool


def _pool_shaped(compiled, shapes, dtype="bf16", layout=False):
    """[(instruction name, opcode)] of the optimized program's
    instructions whose result has one of ``shapes`` (comma-joined
    dims); with ``layout`` [(name, layout and memory space, opcode)]."""
    import re
    inst = re.compile(
        rf"%([\w.\-]+) = {dtype}\[(?:{'|'.join(shapes)})\](\S*) ([\w\-]+)\(")
    found = [m.groups() for m in map(inst.search,
                                     compiled.as_text().splitlines()) if m]
    return found if layout else [(name, op) for name, _, op in found]


def _assert_pool_stays_put(compiled, pool):
    """No copy, dynamic-slice or dynamic-update-slice is left in the
    optimized program with the pool's shape or one layer's."""
    L, NB, nh, BS, hd = pool
    found = _pool_shaped(compiled, [
        f"{lead},{nh},{BS},{hd}" for lead in (
            f"{L},{NB}", f"1,{NB}", f"{L * NB}", f"{NB}")])
    assert found   # the pool is in the program under these shapes
    moving = ("copy", "dynamic-slice", "dynamic-update-slice")
    bad = [(name, op) for name, op in found
           if op in moving or any(w in name for w in moving)]
    assert not bad, bad


@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "sampling"])
def test_paged_decode_program_updates_pool_in_place(one_chip, sampling):
    """The GATHER decode program (the kernel refused: what the CPU and
    untileable shapes run) carries the donated KV pool through its layer
    loop in place: both pools aliased onto the results, temporaries
    under ONE pool half (a second pool beside the first would be two),
    and no copy / dynamic-slice / dynamic-update-slice left in the
    optimized program with the pool's shape or one layer's."""
    compiled, (L, NB, nh, BS, hd) = _paged_decode_program(
        one_chip, sampling, attn_kernel=False)
    half = L * NB * nh * BS * hd * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * half
    assert mem.temp_size_in_bytes < half, (mem.temp_size_in_bytes, half)
    _assert_pool_stays_put(compiled, (L, NB, nh, BS, hd))


def test_default_gpt_decode_program_reads_live_blocks_in_place(
        one_chip, mosaic_backend):
    """The decode program the engine builds by default for the 1.3B
    cell's shapes on a v5e (24 slots, 16 heads x 128, blocks of 16, 64 a
    slot, bf16; ``attn_kernel`` is ``kernel_viable``'s answer, as in
    ``ServingEngine``): ``paged_decode_attn`` is in it, both pools are
    aliased, the temporaries are under 1 MB (AOT, PR 43: 0.74), and
    nothing of a gathered view's shape is left: no ``[24,16,64,16,128]``
    (every slot's keys at capacity), no ``[1536,16,16,128]`` (the gather
    of 24 x 64 blocks) and no ``[24,16,16,128]`` (the block set the
    ``jnp`` write gathered, selected into and scattered back: the kernel
    places the entry) in any type. What is left of the write is one
    scalar fusion under ``kv_write``."""
    chosen = paged_attention.kernel_viable(
        PAGED_HEADS, PAGED_HEAD_DIM, PAGED_BLOCK, jnp.bfloat16)
    assert chosen
    compiled, (L, NB, nh, BS, hd) = _paged_decode_program(
        one_chip, sampling=False, attn_kernel=chosen, slots=24)
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attn" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 2 * L * NB * nh * BS * hd
    assert mem.temp_size_in_bytes < 1 << 20, mem.temp_size_in_bytes
    gathered = [f"24,{nh},{PAGED_PER_SLOT},{BS},{hd}",
                f"{24 * PAGED_PER_SLOT},{nh},{BS},{hd}",
                f"24,{nh},{BS},{hd}"]
    left = _pool_shaped(compiled, gathered, dtype=r"\w+")
    assert not left, left
    assert "/kv_write/" in text


@pytest.mark.parametrize("bucket", [128, 256, 512, 1024])
def test_paged_prefill_program_updates_pool_in_place(one_chip, bucket,
                                                     mosaic_backend):
    """The prefill program of the 1.3B cell (24 layers of 2048 / 8192,
    vocabulary 50304, 24 slots, 16 heads x 128, blocks of 16, 64 a
    slot, bf16) at each of its buckets, jitted with the engine's
    donation (pos, kc, vc): both pools aliased onto the results; no
    copy, dynamic-slice or dynamic-update-slice of the pool's shape or
    a layer's; nothing left of a slot's view at capacity
    (``[24,1,16,1024,128]``, 0.40 GB of temporaries until PR 38) nor of
    scores over it (``[16,bucket,1024]``); temporaries under 16 MB; the
    flash kernel in it from 256 on, the bucket of 128 in plain XLA."""
    from paddle_tpu.serving.paged.programs import build_paged_fns
    S, MB, BS = 24, PAGED_PER_SLOT, PAGED_BLOCK
    cfg, params, pool, sds = _paged_gpt(one_chip, S, L=24, ffn=8192,
                                        vocab=50304)
    L, NB, nh, _, hd = pool
    prefill, _ = build_paged_fns(cfg, S, BS, NB, MB, attn_kernel=True)
    i32 = jnp.int32
    compiled = jax.jit(prefill, donate_argnums=(8, 9, 10)).lower(
        params, sds((1, bucket), i32), sds((), i32), sds((), i32),
        sds((), i32), sds((), i32), sds((MB,), i32), sds((S,), i32),
        sds((S,), i32), sds(pool), sds(pool)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 2 * L * NB * nh * BS * hd
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    _assert_pool_stays_put(compiled, pool)
    C = MB * BS
    view = [f"{L},1,{nh},{C},{hd}", f"{L},{nh},{C},{hd}"]
    # (at the bucket of 1024 a layer's view would look like q, and the
    # scores over it like the run's own square, which the kernel keeps)
    if bucket < C:
        view += [f"{lead}{nh},{C},{hd}" for lead in ("1,", "")]
        view += [f"{lead}{nh},{bucket},{C}" for lead in ("1,", "")]
    left = _pool_shaped(compiled, view, dtype=r"\w+")
    assert not left, left
    text = compiled.as_text()
    assert ("flash_fwd" in text) == (bucket >= 256)
    assert ("tpu_custom_call" in text) == (bucket >= 256)


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


# ------------------------------------------------------------------------
# deepseek_v3 (latent attention + experts): kernels at the published
# widths of kanana-2-30b-a3b, and the whole decode program at sizes where
# the pool dominates

def _sds(one_chip):
    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=one_chip)
    return sds


@pytest.mark.parametrize("nh,r,BS,G", [(32, 512, 256, 4),
                                       (16, 256, 128, 16)],
                         ids=["kanana2", "h16-r256-bs128"])
def test_mla_paged_decode_attn_compiles(one_chip, nh, r, BS, G):
    """32 heads over a latent of 512 + a rotary key of 64, blocks of 256
    tokens, 32 slots of 64 blocks: Mosaic takes the kernel as the
    benchmark's cell runs it, at the blocks a chunk its shapes give;
    and at another head count, rank and block size, whose chunk holds
    another number of blocks."""
    from paddle_tpu.ops import mla_attention
    sds = _sds(one_chip)
    S, dr, MB = 32, 64, 64
    NB = 6 * (S * MB + 1)
    assert mla_attention.kernel_viable(BS, r, dr, jnp.bfloat16)
    assert mla_attention.blocks_per_chunk(BS, r, dr, MB, jnp.bfloat16) == G
    q = (sds((S, nh, r)), sds((S, nh, dr)))
    pools = (sds((NB, BS, r)), sds((NB, dr, BS)))
    scalars = (sds((S, MB), jnp.int32), sds((S,), jnp.int32))
    _compile(lambda *a: mla_attention.mla_paged_decode_attn(*a, 192 ** -0.5),
             *q, *pools, *scalars)

    # WITH the step's new entry placed in it (what the decode program
    # calls), pools donated: Mosaic takes the write-back of the latent's
    # [16, rank] tile and of 128 lanes of the rotary key's block, both
    # pools are aliased onto the results and the call has no temporary
    def call(q, pools, new, bt, ln, wpos):
        return mla_attention.latent_write_attention(
            *q, new, pools, bt, wpos, ln, 192 ** -0.5, True)
    compiled = jax.jit(call, donate_argnums=(1,)).lower(
        q, pools, (sds((S, r)), sds((S, dr))), *scalars,
        sds((S,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_paged_decode_attn" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * NB * BS * (r + dr)
    assert mem.temp_size_in_bytes < 1 << 20, mem.temp_size_in_bytes


def test_moe_experts_swiglu_decode_compiles(one_chip):
    """32 tokens through up to 128 experts of 2048 x 768, the matrices of
    5 layers stacked flat, a layer's row offset traced."""
    from paddle_tpu.ops import moe_experts
    sds = _sds(one_chip)
    T, h, f, E, Lm = 32, 2048, 768, 128, 5
    _compile(moe_experts.moe_experts_swiglu_decode,
             sds((T, h)), sds((Lm * E, h, f)), sds((Lm * E, h, f)),
             sds((Lm * E, f, h)), sds((T, E), jnp.float32),
             sds((), jnp.int32))


LATENT_SLOTS = 16


def _latent_decode_program(one_chip, sampling):
    """(compiled paged_decode, the two pool shapes) of a deepseek_v3
    model, jitted with the engine's donation (pos and the cache spec's
    arrays; the carried counters are not donated): published attention
    widths, 4 layers x 1025 blocks of 256 tokens (1.07 GB of latent, 134
    MB of rotary key: a smaller one XLA prefetches WHOLE into the 128
    MiB of VMEM, which no real pool fits), 16 slots, few narrow experts,
    small vocabulary."""
    from paddle_tpu.serving.paged.latent_programs import \
        build_paged_latent_fns
    from paddle_tpu.text import deepseek_v3 as ds
    S, BS, MB = LATENT_SLOTS, 256, 64
    NB = S * MB + 1
    cfg = ds.DeepseekV3Config(
        vocab_size=1024, hidden_size=1024, num_hidden_layers=4,
        num_attention_heads=16, intermediate_size=1024,
        moe_intermediate_size=256, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2,
        max_position_embeddings=MB * BS, dtype="bfloat16")
    sds = _sds(one_chip)
    params = {}
    for path, (shape, _, dt) in ds.param_shapes(cfg).items():
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = sds(shape, jnp.dtype(dt))
    spec = ds.latent_cache_spec(cfg)
    pool = [spec.shape(a, NB, BS) for a in spec.arrays]
    i32 = jnp.int32
    args = [params, sds((S,), i32), sds((S,), i32), sds((S, MB), i32)] \
        + [sds(p) for p in pool] \
        + [sds(shape, dt) for _, shape, dt in spec.state]
    if sampling:
        args += [sds((S,), jnp.uint32), sds((S,), jnp.float32),
                 sds((S,), i32), sds((S,), jnp.float32)]
    _, decode = build_paged_latent_fns(cfg, S, BS, NB, MB,
                                       sampling=sampling, kernels=True)
    return jax.jit(decode, donate_argnums=(2, 4, 5)).lower(
        *args).compile(), pool


@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "sampling"])
def test_latent_decode_program_updates_pool_in_place(one_chip, sampling):
    """The deepseek_v3 decode program carries the donated latent pool
    through both of its layer loops in place: both arrays aliased onto
    the results, temporaries far under the pool, both kernels in the
    program, no copy / dynamic-slice / dynamic-update-slice of the
    pool's shape (a rotary-key array with the key dim minor made XLA copy
    the whole array in front of the kernel, every layer: PERF.md) and no
    instruction of a block gather's shape (PR 49)."""
    import re
    compiled, pool = _latent_decode_program(one_chip, sampling)
    nbytes = sum(2 * int(np.prod(p)) for p in pool)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // 8, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "mla_paged_decode_attn" in text
    assert "moe_experts_swiglu_decode" in text
    shapes = "|".join(
        ",".join(str(d) for d in lead + p[2:])
        for p in pool for lead in ((p[0], p[1]), (1, p[1]),
                                   (p[0] * p[1],), (p[1],)))
    inst = re.compile(
        rf"%([\w.\-]+) = bf16\[(?:{shapes})\]\S* ([\w\-]+)\(")
    found = [m.groups() for m in map(inst.search, text.splitlines()) if m]
    assert found   # the pool is in the program under these shapes
    moving = ("copy", "dynamic-slice", "dynamic-update-slice")
    bad = [(name, op) for name, op in found
           if op in moving or any(w in name for w in moving)]
    assert not bad, bad
    # the kernel places the step's entry: no gather of every slot's
    # current block (``[S, BS, rank]``, ``[S, dr, BS]``) and no scatter
    # of it back is left in front of it
    blocks = "|".join(",".join(str(d) for d in (LATENT_SLOTS,) + p[2:])
                      for p in pool)
    left = re.findall(rf"%([\w.\-]+) = bf16\[(?:{blocks})\]", text)
    assert not left, left


# ------------------------------------------------ nemotron_h (PR 35)
# NVIDIA-Nemotron-3-Nano-30B-A3B's widths, cut in depth and slots only
NEMOTRON = dict(
    vocab_size=131072, hidden_size=2688, num_attention_heads=32,
    num_key_value_heads=2, head_dim=128, mamba_num_heads=64,
    mamba_head_dim=64, ssm_state_size=128, n_groups=8, conv_kernel=4,
    chunk_size=128, moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, n_routed_experts=8,
    router_experts=16, num_experts_per_tok=6, routed_scaling_factor=2.5,
    max_position_embeddings=4096)


def test_ssm_decode_step_compiles(one_chip):
    """The state-space decode kernel at the published widths, 128 slots,
    layer 3 of 6: the packed float32 state aliased in and out."""
    from paddle_tpu.ops import ssm
    S, H, P, G, N, Lm = 128, 64, 64, 8, 128, 6
    assert ssm.kernel_viable(H, P, N, G)
    f32 = jnp.float32

    def sds(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    state = sds((Lm * S,) + ssm.packed_shape(H, P, N, G))
    compiled = jax.jit(
        lambda st, xs, dt, A, B, C: ssm.ssm_state_step(
            st, jnp.int32(3), xs, dt, A, B, C, S),
        donate_argnums=(0,)).lower(
        state, sds((S, H, P), jnp.bfloat16), sds((S, H)), sds((H,)),
        sds((S, G, N), jnp.bfloat16), sds((S, G, N), jnp.bfloat16)
    ).compile()
    assert "ssm_decode_step" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= Lm * S * H * P * N * 4
    assert mem.temp_size_in_bytes < 64 << 20


def test_relu2_decode_compiles_at_a_width_off_the_lane_tile(one_chip):
    """relu-squared experts of width 1856 = 4 x 464 (no multiple of
    128) stored ``[f, h]``: the width rides the sublanes in tiles of 464
    rows."""
    from paddle_tpu.ops import moe_experts as moe
    T, h, f, E = 128, 2688, 1856, 16
    assert moe.kernel_viable(T, h, f, jnp.bfloat16, gated=False)
    assert not moe.kernel_viable(T, h, f, jnp.bfloat16)
    assert moe._f_rows(f, h, 2) == 464
    bf = jnp.bfloat16

    def sds(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compile(
        lambda x, up, down, cw: moe.moe_experts_relu2_decode(
            x, up, down, cw, jnp.int32(8)),
        sds((T, h)), sds((E, f, h)), sds((E, f, h)),
        sds((T, 8), jnp.float32))
    assert "moe_experts_relu2_decode" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_query_paged_decode_compiles(one_chip, dtype,
                                             mosaic_backend):
    """32 query heads over 2 KV heads of 128 in blocks of 256: the 16
    query heads of a group are the rows of the MXU tile."""
    slots, per_slot, nkv, nq, hd, block = 128, 48, 2, 32, 128, 256
    assert paged_attention.kernel_viable(nkv, hd, block, dtype)

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(paged_attention.paged_decode_attention, sds((slots, nq, hd)),
             sds((257, nkv, block, hd)), sds((257, nkv, block, hd)),
             sds((slots, per_slot), jnp.int32), sds((slots,), jnp.int32))
    # with the write, at 12 blocks a slot (a slot's last block is its own)
    _compile_paged_decode_with_write(one_chip, dtype, slots, 12, nq, nkv,
                                     hd, block)


def _hybrid_family_programs(one_chip, block, cfg, slots, max_len):
    """Both programs of ``hybrid_programs.py`` for a configuration of
    either family it serves (``block``: the module of its class), with
    the shapes they are lowered on."""
    from paddle_tpu.serving.paged.hybrid_programs import \
        build_paged_hybrid_fns
    BS = 256
    MB = max_len // BS
    NB = slots * MB + 1
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        prefill, decode = build_paged_hybrid_fns(cfg, slots, BS, NB, MB)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)
    params = {}
    for path, (shape, _, dt) in block.param_shapes(cfg).items():
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = sds(shape, dt)
    spec = block.hybrid_cache_spec(cfg).with_slots(slots)
    pool = [sds(spec.shape(a, NB, BS), a.dtype) for a in spec.arrays]
    state = [sds(shape, dt) for _, shape, dt in spec.state]
    i32 = jnp.int32
    toks, pos = sds((slots,), i32), sds((slots,), i32)
    nbytes = [int(np.prod(p.shape)) * p.dtype.itemsize for p in pool]
    return prefill, decode, params, pool, state, toks, pos, MB, nbytes


def _hybrid_programs(one_chip, pattern, slots, max_len):
    from paddle_tpu.text import nemotron_h as nh
    cfg = nh.NemotronHConfig.from_hf(
        dict(NEMOTRON, hybrid_override_pattern=pattern), dtype="bfloat16")
    return _hybrid_family_programs(one_chip, nh, cfg, slots, max_len)


def test_hybrid_decode_program_updates_both_kinds_of_state_in_place(
        one_chip):
    """The nemotron_h decode program (one layer of each kind in a
    repeated run, published widths, 16 slots) carries keys, values,
    convolution windows and recurrent state through its layer loop in
    place: all four aliased onto the results, temporaries under 2 MB,
    all three kernels in the program, no gathered block set in front of
    the attention kernel."""
    _, decode, params, pool, state, toks, pos, MB, nbytes = \
        _hybrid_programs(one_chip, "ME*ME*", 16, 1024)
    n = len(pool)
    tables = jax.ShapeDtypeStruct((16, MB), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        decode, donate_argnums=(2,) + tuple(range(4, 4 + n))).lower(
        params, toks, pos, tables, *pool, *state).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes)
    # AOT, PR 43: 1.81 MB
    assert mem.temp_size_in_bytes < 2 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    for kernel in ("ssm_decode_step", "moe_experts_relu2_decode",
                   "paged_decode_attn"):
        assert kernel in text
    # the kernel places the new entry: no set of 16 gathered blocks
    assert not _pool_shaped(compiled, ["16,2,256,128"], dtype=r"\w+")
    assert "/kv_write/" in text


def test_hybrid_prefill_program_copies_no_slot_state(one_chip):
    """The prefill program cuts ONE slot's state out before its layer
    loop and puts it back after: no copy of the whole recurrent state
    (with the state in the loop's carry XLA relaid all of it for the
    chunked scan's small transposes: PERF.md, PR 35)."""
    prefill, _, params, pool, _, toks, pos, MB, nbytes = \
        _hybrid_programs(one_chip, "ME*ME*", 16, 1024)
    n = len(pool)
    i32 = jnp.int32
    scalar = jax.ShapeDtypeStruct((), i32, sharding=one_chip)
    compiled = jax.jit(
        prefill, donate_argnums=tuple(range(8, 9 + n))).lower(
        params, jax.ShapeDtypeStruct((1, 512), i32, sharding=one_chip),
        scalar, scalar, scalar, scalar,
        jax.ShapeDtypeStruct((MB,), i32, sharding=one_chip), toks, pos,
        *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes)
    state = pool[3].shape
    shapes = "|".join(",".join(str(d) for d in s) for s in (
        state, (state[0] * state[1],) + state[2:]))
    moved = re.findall(rf"= f32\[(?:{shapes})\]\S* copy\(",
                       compiled.as_text())
    assert not moved, moved


# ----------------------------- evabyte: cache entries that are not positions
EVABYTE = dict(vocab_size=320, hidden_size=4096, num_hidden_layers=2,
               num_attention_heads=32, num_key_value_heads=32,
               intermediate_size=11008, window_size=2048, chunk_size=16,
               num_pred_heads=8, max_position_embeddings=32768,
               rope_theta=100000, init_std=0.01275)


def _eva_programs(one_chip, slots):
    """The three programs at the published widths (2 layers), blocks of
    64 entries, ``slots`` slots of 3,968 entries."""
    from paddle_tpu.serving.paged.eva_programs import build_paged_eva_fns
    from paddle_tpu.text import evabyte as eb
    cfg = eb.EvaByteConfig.from_hf(EVABYTE, dtype="bfloat16")
    spec = eb.eva_cache_spec(cfg)
    BS = 64
    MB = -(-spec.capacity(cfg.max_seq_len) // BS)
    NB = slots * MB + 1
    assert MB == 62
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        fns = build_paged_eva_fns(cfg, slots, BS, NB, MB)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)
    params = {}
    for path, (shape, _, dt) in eb.param_shapes(cfg).items():
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = sds(shape, dt)
    pool = [sds(spec.shape(a, NB, BS), a.dtype) for a in spec.arrays]
    nbytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pool)
    i32 = jnp.int32
    return fns, params, pool, sds((slots,), i32), sds((slots,), i32), \
        sds((slots, MB), i32), sds((MB,), i32), sds((), i32), nbytes


def test_paged_decode_kernel_compiles_at_32_heads(one_chip, mosaic_backend):
    """The GPT's kernel at 32 heads of 128 in bf16, read-only and with
    the write: a block of 64 entries is the largest its chunk buffers
    hold."""
    dtype = jnp.bfloat16
    assert paged_attention.kernel_viable(32, 128, 64, dtype)
    assert not paged_attention.kernel_viable(32, 128, 128, dtype)

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compile(paged_attention.paged_decode_attention,
                    sds((20, 32, 128)), sds((1241, 32, 64, 128)),
                    sds((1241, 32, 64, 128)), sds((20, 62), jnp.int32),
                    sds((20,), jnp.int32))
    assert "paged_decode_attn" in text
    _compile_paged_decode_with_write(one_chip, dtype, 20, 62, 32, 32, 128,
                                     64)


def test_eva_decode_program_aliases_the_whole_pool(one_chip):
    """The evabyte decode program carries the entry pool through its
    layer loop in place: all of it aliased onto the results, temporaries
    under 1 MB, the shared kernel in the program and no gathered block
    set in front of it."""
    (_, decode, _), params, pool, toks, pos, tables, _, _, nbytes = \
        _eva_programs(one_chip, 8)
    compiled = jax.jit(decode, donate_argnums=(2, 4, 5)).lower(
        params, toks, pos, tables, *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    # AOT, PR 43: 0.65 MB
    assert mem.temp_size_in_bytes < 1 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "paged_decode_attn" in text and "/kv_write/" in text
    # the kernel places the new entry: no set of 8 gathered blocks
    assert not _pool_shaped(compiled, ["8,32,64,128"], dtype=r"\w+")


def test_eva_compact_and_prefill_programs_update_the_pool_in_place(
        one_chip):
    """``paged_compact`` reads one window's raw entries and writes its
    summaries over the first of them: the pool aliased whole,
    temporaries a few windows' worth; the window-sized prefill aliases
    it too."""
    (prefill, _, compact), params, pool, toks, pos, _, row, scalar, \
        nbytes = _eva_programs(one_chip, 8)
    compiled = jax.jit(compact, donate_argnums=(3, 4)).lower(
        params, scalar, row, *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    window = 2048 * 32 * 128 * 2          # one window's keys, one layer
    assert mem.temp_size_in_bytes < 16 * window, mem.temp_size_in_bytes
    compiled = jax.jit(prefill, donate_argnums=(9, 10)).lower(
        params, jax.ShapeDtypeStruct((1, 2048), jnp.int32,
                                     sharding=one_chip),
        scalar, scalar, scalar, scalar, row, toks, pos, *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes


@pytest.mark.parametrize("model,shape", [("evabyte", "1,4096,4096"),
                                         ("latent", "1,1024,3072")])
def test_decode_projection_reads_the_stacked_weight_in_the_matmul(
        one_chip, model, shape):
    """A projection whose result is split into heads (EvaByte's ``wq``,
    ``wk``, ``wv``; the latent model's ``wq``) reads its layer's slice of
    the stacked weight from HBM inside the matmul's own fusion
    (``stacked_lm.project_heads``). With the split folded into the dot
    XLA sliced the layer's matrix into VMEM (``S(1)``) and relaid it
    there (a ``copy`` to ``{1,2,0}``) every layer of every step: 0.86 of
    a 13.49 ms step in the EvaByte cell (ledger, PR 44). No instruction
    of the slice's shape may be a ``copy``, lie in another layout or
    live in ``S(1)``; the one exception is the latent helper's DENSE
    stack, ONE layer deep and so of the slice's own shape, which XLA
    may prefetch whole (``copy-start`` / ``copy-done``, asynchronous,
    row-major: not a relayout)."""
    if model == "evabyte":
        (_, decode, _), params, pool, toks, pos, tables, _, _, _ = \
            _eva_programs(one_chip, 8)
        compiled = jax.jit(decode, donate_argnums=(2, 4, 5)).lower(
            params, toks, pos, tables, *pool).compile()
        prefetch = ()
    else:
        compiled, _ = _latent_decode_program(one_chip, False)
        prefetch = ("copy-start", "copy-done", "parameter",
                    "get-tuple-element")
    found = _pool_shaped(compiled, [shape], layout=True)
    assert found   # a layer's slice is in the program under this shape
    bad = [(name, layout, op) for name, layout, op in found
           if op == "copy" or not layout.startswith("{2,1,0")
           or ("S(1)" in layout and op not in prefetch)]
    assert not bad, bad


# -------------- mimo_v2: blocks in the full layers, rings in the window ones
def _mixed_programs(one_chip):
    """Both programs of the ``mimo_v2_flash_pp8ep16`` cell at its
    configuration's widths and its sizes (48 slots x 28,672 positions,
    blocks of 256), as ``tools/aot_compile_arch.py`` builds them."""
    import json
    import os
    from paddle_tpu.serving.paged.mixed_programs import \
        build_paged_mixed_fns
    from paddle_tpu.text import mimo_v2
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "mimo_v2_flash_pp8ep16.json")) as f:
        config = json.load(f)
    sz = config["sizing"]
    cfg = mimo_v2.MimoV2Config.from_hf(config, dtype="bfloat16")
    S, BS = sz["num_slots"], sz["block_size"]
    MB = sz["max_len"] // BS
    NB = S * MB + 1
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        prefill, decode = build_paged_mixed_fns(cfg, S, BS, NB, MB)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)
    params = {}
    for path, (shape, _, dt) in mimo_v2.param_shapes(cfg).items():
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = sds(shape, dt)
    spec = mimo_v2.mixed_cache_spec(cfg).with_slots(S)
    pool = [sds(spec.shape(a, NB, BS), a.dtype) for a in spec.arrays]
    state = [sds(shape, dt) for _, shape, dt in spec.state]
    toks, pos = sds((S,), jnp.int32), sds((S,), jnp.int32)
    nbytes = [int(np.prod(p.shape)) * p.dtype.itemsize for p in pool]
    return (prefill, decode, params, pool, state, toks, pos, sz, NB, MB,
            nbytes, sds)


def test_two_part_key_paged_decode_compiles(one_chip, mosaic_backend):
    """The kernel alone at the full layers' shape: 4 KV heads x 16 query
    heads, keys of 128 + 64 transposed, values of 128, blocks of 256."""
    S, nkv, BS, MB = 48, 4, 256, 112
    assert paged_attention.kernel_viable(nkv, 128, BS, jnp.bfloat16, 64)
    assert paged_attention.blocks_per_chunk(nkv, 128, BS, MB,
                                            jnp.bfloat16, 64) == 1

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    NB = S * MB + 1
    _compile(paged_attention.paged_decode_attention,
             sds((S, 64, 128)), sds((NB, nkv, BS, 128)),
             sds((NB, nkv, BS, 128)), sds((S, MB), jnp.int32),
             sds((S,), jnp.int32), sds((S, 64, 64)),
             sds((NB, nkv, 64, BS)))
    _compile_paged_decode_with_write(one_chip, jnp.bfloat16, S, MB, 64,
                                     nkv, 128, BS, rot=64)


# the cell's rings: 48 slots x 8 KV heads, a window of 128; keys of 192
# transposed, values of 128
RING_SHAPES = [(4, 48, 8, 192, 128), (4, 48, 8, 128, 128)]


def test_ring_decode_kernel_compiles(one_chip, mosaic_backend):
    """The window layers' kernel alone at the cell's shape: 48 slots,
    8 KV heads x 8 query heads, keys of 192, values of 128, a window of
    128, 4 layers' rings in bf16, donated: aliased onto the results, no
    temporary of a ring's size (the queries laid out by group and the
    new keys a slot a lane are all there is)."""
    from paddle_tpu.ops import slot_ring_decode
    S, nq, nkv, hd, dv = 48, 64, 8, 192, 128
    monkey = mock.patch.object(slot_ring_decode.jax, "default_backend",
                               lambda: "tpu")
    with monkey:
        assert slot_ring_decode.kernel_viable(nkv, hd, dv, 128,
                                              jnp.bfloat16)
    assert slot_ring_decode.slots_per_step(
        S, slot_ring_decode.slot_ring_bytes(
            nkv, hd, dv, 128, jnp.bfloat16)) == 4

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rings = tuple(sds(shape) for shape in RING_SHAPES)
    compiled = jax.jit(slot_ring_decode.ring_decode_attention,
                       donate_argnums=(3, 4)).lower(
        sds((S, nq, hd)), sds((S, nkv, hd)), sds((S, nkv, dv)), *rings,
        sds((), jnp.int32), sds((S, 128), jnp.int32), sds((S,), jnp.int32),
        sds((nq,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert any(n.startswith("ring_decode_attn")
               for n in _instruction_names(text))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        int(np.prod(r.shape)) * 2 for r in rings)
    assert mem.temp_size_in_bytes < 4 << 20, mem.temp_size_in_bytes


def test_mixed_decode_program_keeps_rings_and_no_copy_of_the_pool(
        one_chip):
    """The decode program of the cell: blocks and rings aliased onto the
    results and carried through the layer loop in place, all three
    kernels in it; NOTHING of a window layer grows with ``max_len`` (no
    array with the window layers' 8 KV heads has a block or position
    axis), no copy, slice or update of a pool-shaped array is left, and
    none of a RING-shaped one either: the window layers' kernel takes
    the rings whole and places the entry itself."""
    (_, decode, params, pool, state, toks, pos, sz, NB, MB, nbytes,
     sds) = _mixed_programs(one_chip)
    n = len(pool)
    compiled = jax.jit(
        decode, donate_argnums=(2,) + tuple(range(4, 4 + n))).lower(
        params, toks, pos, sds((sz["num_slots"], MB), jnp.int32), *pool,
        *state).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes)
    # AOT, PR 43: 2.24 MB (15.3 with the jnp block write in front)
    assert mem.temp_size_in_bytes < 4 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    for kernel in ("paged_decode_attn", "moe_experts_swiglu_decode",
                   "ring_decode_attn"):
        assert kernel in text
    # the kernel places the new entry: no set of 48 gathered blocks
    assert not _pool_shaped(compiled, ["48,4,256,128", "48,4,64,256"],
                            dtype=r"\w+")
    # every array that has an axis of the pool's (blocks, all layers'
    # blocks, positions) is one of the full layers' three: 4 KV heads
    grows = re.findall(
        rf"= \w+\[([\d,]*\b(?:{NB}|{2 * NB}|{sz['max_len']})\b[\d,]*)\]",
        text)
    assert grows
    full = {f"{lead},4,256,128" for lead in (f"2,{NB}", f"{2 * NB}")} \
        | {f"{lead},4,64,256" for lead in (f"2,{NB}", f"{2 * NB}")}
    assert set(grows) <= full, set(grows) - full
    # the rings are there, at their size, whatever max_len is
    for shape in RING_SHAPES:
        assert f"bf16[{','.join(map(str, shape))}]" in text
    moving = ("copy", "dynamic-slice", "dynamic-update-slice")
    bad = [(name, op) for name, op in _pool_shaped(compiled, sorted(full))
           if op in moving or any(w in name for w in moving)]
    assert not bad, bad
    # nor of a ring, all layers' or one layer's: nothing selects an
    # entry into a ring outside the kernel or moves a ring around it
    rings = [f"{lead}48,8,{rows},128" for lead in ("4,", "1,", "")
             for rows in (192, 128)]
    found = _pool_shaped(compiled, rings)
    assert found   # the rings are in the program under these shapes
    moving += ("select",)
    bad = [(name, op) for name, op in found
           if op in moving or any(w in name for w in moving)]
    assert not bad, bad


def test_mixed_prefill_program_updates_the_pool_in_place(one_chip):
    """The one prefill bucket of the cell: blocks and rings aliased,
    temporaries (the walk's scores over one block of keys) under a GB."""
    (prefill, _, params, pool, _, toks, pos, sz, _, MB, nbytes,
     sds) = _mixed_programs(one_chip)
    n = len(pool)
    scalar = sds((), jnp.int32)
    (bucket,) = sz["buckets"]
    compiled = jax.jit(
        prefill, donate_argnums=tuple(range(8, 9 + n))).lower(
        params, sds((1, bucket), jnp.int32), scalar, scalar, scalar,
        scalar, sds((MB,), jnp.int32), toks, pos, *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes)
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes


# ------- ouro: one stack of layers run 4 times, a cache entry for every pass
def _looped_programs(one_chip):
    """Both programs of the ``ouro_2p6b`` cell at its configuration's
    widths and depth (48 layers x 4 passes, nothing cut) and its sizes
    (2 slots x 2,560 positions, blocks of 64), as
    ``tools/aot_compile_arch.py`` builds them."""
    import json
    import os
    from paddle_tpu.serving.paged.looped_programs import \
        build_paged_looped_fns
    from paddle_tpu.text import ouro
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "ouro_2p6b.json")) as f:
        config = json.load(f)
    sys.path.insert(0, root)
    from benchmarks.planes import serve_arch
    sz = config["sizing"]
    cfg = ouro.OuroConfig.from_hf(serve_arch.model_of(config),
                                  dtype="bfloat16")
    S, BS = sz["num_slots"], sz["block_size"]
    MB = sz["max_len"] // BS
    NB = S * MB + 1
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        prefill, decode = build_paged_looped_fns(cfg, S, BS, NB, MB)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dt),
                                    sharding=one_chip)
    params = {}
    for path, (shape, _, dt) in ouro.param_shapes(cfg).items():
        node = params
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = sds(shape, dt)
    spec = ouro.looped_cache_spec(cfg)
    pool = [sds(spec.shape(a, NB, BS), a.dtype) for a in spec.arrays]
    state = [sds(shape, dt) for _, shape, dt in spec.state]
    toks, pos = sds((S,), jnp.int32), sds((S,), jnp.int32)
    nbytes = [int(np.prod(p.shape)) * p.dtype.itemsize for p in pool]
    return (prefill, decode, params, pool, state, toks, pos, sz, NB, MB,
            nbytes, sds)


def _weight_stack_moves(compiled):
    """Instructions of the optimized program that copy a leaf of the
    48-layer weight stack whole, or one layer's matrix of it."""
    mats = ["2048,6144", "2048,2048", "2048,5632", "5632,2048"]
    found = _pool_shaped(compiled, [f"48,{m}" for m in mats]
                         + [f"1,{m}" for m in mats] + mats)
    return [(name, op) for name, op in found
            if op == "copy" or "copy" in name]


def test_looped_decode_program_reads_one_stack_and_keeps_the_pool(
        one_chip):
    """The decode program of the cell: the pool of 192 cache layers
    (over 48 weight layers) aliased onto the results and carried through
    the layer loop AND the pass loop in place; ONE call site of the
    kernel (the layer body is traced once, whatever the passes) with the
    write inside; temporaries in MBs; no copy of the pool, of a leaf of
    the weight stack or of one layer's matrix."""
    (_, decode, params, pool, state, toks, pos, sz, NB, MB, nbytes,
     sds) = _looped_programs(one_chip)
    assert pool[0].shape == (192, 81, 16, 64, 128)
    assert params["layers"]["wqkv"].shape == (48, 2048, 6144)
    n = len(pool)
    compiled = jax.jit(
        decode, donate_argnums=(2,) + tuple(range(4, 4 + n))).lower(
        params, toks, pos, sds((sz["num_slots"], MB), jnp.int32), *pool,
        *state).compile()
    mem = compiled.memory_analysis()
    assert sum(nbytes) == 8_153_726_976
    assert mem.alias_size_in_bytes >= sum(nbytes)
    # AOT, PR 44: 0.77 MB
    assert mem.temp_size_in_bytes < 4 << 20, mem.temp_size_in_bytes
    # weights + pool as the device lays them out: 13.49 GB of 16
    assert mem.argument_size_in_bytes < 13.6e9
    text = compiled.as_text()
    assert "paged_decode_attn" in text
    assert text.count("tpu_custom_call") == 1
    # the kernel places the new entry: no set of 2 gathered blocks, and
    # the flat pool is the only thing with the pool's size
    assert not _pool_shaped(compiled, ["2,16,64,128"], dtype=r"\w+")
    _assert_pool_stays_put(compiled, (192, NB, 16, 64, 128))
    assert not _weight_stack_moves(compiled)


def test_looped_prefill_program_updates_the_pool_in_place(one_chip):
    """The one prefill bucket of the cell (512 positions through all
    four passes): the pool aliased, temporaries in MBs (a slot's 40
    blocks of one entry as a view, the blocked scores), no copy of the
    pool or of the weight stack."""
    (prefill, _, params, pool, _, toks, pos, sz, NB, MB, nbytes,
     sds) = _looped_programs(one_chip)
    n = len(pool)
    scalar = sds((), jnp.int32)
    (bucket,) = sz["buckets"]
    assert bucket == sz["prefill_chunk"] == 512
    compiled = jax.jit(
        prefill, donate_argnums=tuple(range(8, 9 + n))).lower(
        params, sds((1, bucket), jnp.int32), scalar, scalar, scalar,
        scalar, sds((MB,), jnp.int32), toks, pos, *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes)
    # AOT, PR 44: 11.5 MB
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    whole = _pool_shaped(compiled, [f"192,{NB},16,64,128",
                                    f"{192 * NB},16,64,128"])
    assert whole and not [(n_, op) for n_, op in whole
                          if op == "copy" or "copy" in n_]
    assert not _weight_stack_moves(compiled)


def test_the_aot_tool_compiles_the_looped_cell_unedited(topo, capsys):
    """``benchmarks/tools/aot_compile_arch.py ouro_2p6b``: the tool as
    it stands finds the architecture's four files by name, takes the
    pool's 192 layers from the cache spec and the weights' 48 from the
    weights file, and reports both programs."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmarks import harness
    from benchmarks.tools import aot_compile_arch
    config = harness.load_json(harness.HERE, "configs", "ouro_2p6b.json")
    aot_compile_arch.programs(config,
                              SingleDeviceSharding(topo.devices[0]))
    out = capsys.readouterr().out
    assert "2,667,974,657 parameters" in out
    assert "cache 1572864 B a token" in out and "81 blocks of 64" in out
    assert "paged_decode:" in out and "paged_prefill[512]:" in out
    assert "kernels 1" in out


# ---- falcon_h1: a state-space mixer AND attention in every layer (PR 48)
# Falcon-H1-34B-Instruct's widths and multipliers, cut in depth only
FALCON = dict(
    vocab_size=261120, hidden_size=5120, intermediate_size=21504,
    num_hidden_layers=2, num_attention_heads=20, num_key_value_heads=4,
    head_dim=128, mamba_n_heads=32, mamba_d_head=128, mamba_d_ssm=4096,
    mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
    mamba_chunk_size=128, rope_theta=1e11, max_position_embeddings=262144,
    attention_in_multiplier=1, attention_out_multiplier=0.0375,
    key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
    ssm_out_multiplier=0.08838834764831845,
    ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                     0.3535533905932738],
    mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
    embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125)


def test_ssm_decode_step_compiles_at_the_largest_state(one_chip):
    """The state-space decode kernel where ONE head fills a row of the
    packed state (32 heads of 128 x 256 in 2 groups: 4 MiB a slot a
    layer, exactly the kernel's limit; 16 MiB of its VMEM with both
    double buffers), 40 slots, layer 3 of 6: the packed float32 state
    aliased in and out."""
    from paddle_tpu.ops import ssm
    S, H, P, G, N, Lm = 40, 32, 128, 2, 256, 6
    assert ssm.kernel_viable(H, P, N, G)
    assert ssm.packed_shape(H, P, N, G) == (32, 256, 128)
    f32 = jnp.float32

    def sds(shape, dt=f32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    state = sds((Lm * S,) + ssm.packed_shape(H, P, N, G))
    compiled = jax.jit(
        lambda st, xs, dt, A, B, C: ssm.ssm_state_step(
            st, jnp.int32(3), xs, dt, A, B, C, S),
        donate_argnums=(0,)).lower(
        state, sds((S, H, P), jnp.bfloat16), sds((S, H)), sds((H,)),
        sds((S, G, N), jnp.bfloat16), sds((S, G, N), jnp.bfloat16)
    ).compile()
    assert "ssm_decode_step" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= Lm * S * H * P * N * 4
    assert mem.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_decode_compiles_at_a_query_group_of_five(one_chip, dtype,
                                                        mosaic_backend):
    """20 query heads over 4 KV heads of 128 in blocks of 256: a group
    of 5 rides in a tile of 8 rows (twice over for a 16-bit pool), 3 of
    them padding; read only, and placing the step's new entry."""
    slots, per_slot, nkv, nq, hd, block = 40, 24, 4, 20, 128, 256
    assert paged_attention.kernel_viable(nkv, hd, block, dtype)
    assert paged_attention._group_rows(5, jnp.dtype(dtype)) \
        == ((8, 8) if dtype == jnp.float32 else (16, 8))

    def sds(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(paged_attention.paged_decode_attention, sds((slots, nq, hd)),
             sds((961, nkv, block, hd)), sds((961, nkv, block, hd)),
             sds((slots, per_slot), jnp.int32), sds((slots,), jnp.int32))
    _compile_paged_decode_with_write(one_chip, dtype, slots, per_slot, nq,
                                     nkv, hd, block)


def _parallel_programs(one_chip, slots, max_len):
    from paddle_tpu.text import falcon_h1 as fh
    cfg = fh.FalconH1Config.from_hf(FALCON, dtype="bfloat16")
    return _hybrid_family_programs(one_chip, fh, cfg, slots, max_len)


def test_parallel_decode_program_updates_both_kinds_of_state_in_place(
        one_chip):
    """The falcon_h1 decode program (2 layers at the published widths
    and vocabulary, 16 slots) through ``hybrid_programs.py``: keys,
    values, convolution windows and recurrent state ride its ONE layer
    scan in place (all four aliased onto the results, temporaries of a
    few MB), both kernels in the program under their branch's scope, no
    expert kernel, no gathered block set in front of the attention
    kernel."""
    _, decode, params, pool, _, toks, pos, MB, nbytes = \
        _parallel_programs(one_chip, 16, 1024)
    n = len(pool)
    tables = jax.ShapeDtypeStruct((16, MB), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        decode, donate_argnums=(2,) + tuple(range(4, 4 + n))).lower(
        params, toks, pos, tables, *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes)
    # AOT, PR 48: 0.6 MB
    assert mem.temp_size_in_bytes < 8 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert "moe_experts" not in text
    for kernel, scope in (("ssm_decode_step", "branch/ssm/ssm/scan"),
                          ("paged_decode_attn", "branch/attn/attn/paged")):
        assert re.search(rf'{scope}[^"]*{kernel}', text), kernel
    assert not _pool_shaped(compiled, ["16,4,256,128"], dtype=r"\w+")
    assert "/kv_write/" in text and "branch/mix" in text


def test_parallel_prefill_program_copies_no_slot_state(one_chip):
    """Its prefill program at a bucket of 512: the pool aliased, and no
    copy of the whole recurrent state (the slot's is cut out before the
    layer scan and put back after it)."""
    prefill, _, params, pool, _, toks, pos, MB, nbytes = \
        _parallel_programs(one_chip, 16, 1024)
    n = len(pool)
    i32 = jnp.int32
    scalar = jax.ShapeDtypeStruct((), i32, sharding=one_chip)
    compiled = jax.jit(
        prefill, donate_argnums=tuple(range(8, 9 + n))).lower(
        params, jax.ShapeDtypeStruct((1, 512), i32, sharding=one_chip),
        scalar, scalar, scalar, scalar,
        jax.ShapeDtypeStruct((MB,), i32, sharding=one_chip), toks, pos,
        *pool).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(nbytes)
    state = pool[3].shape
    shapes = "|".join(",".join(str(d) for d in s) for s in (
        state, (state[0] * state[1],) + state[2:]))
    moved = re.findall(rf"= f32\[(?:{shapes})\]\S* copy\(",
                       compiled.as_text())
    assert not moved, moved
