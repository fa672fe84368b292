"""Pallas flash-attention BACKWARD kernels (O(seq) memory) vs the dense
reference — run in Pallas interpret mode on the CPU mesh; the same
kernels compile natively on TPU.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import attention as attn


@pytest.fixture(autouse=True)
def _interp():
    attn._FORCE_INTERPRET[0] = True
    yield
    attn._FORCE_INTERPRET[0] = False


def _qkv(s, d=64, b=1, h=2, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, h, s, d).astype("float32") * 0.3)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_matches_reference(causal):
    q, k, v = _qkv(256)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out, lse = attn._pallas_flash_fwd(q, k, v, scale, causal)
    ref = attn._reference_attention(q, k, v, None, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # lse really is the log-sum-exp of the score rows
    qk = np.einsum("bhsd,bhtd->bhst", q, k) * scale
    if causal:
        s_ = qk.shape[-1]
        m = np.tril(np.ones((s_, s_), bool))
        qk = np.where(m, qk, -1e30)
    ref_lse = np.log(np.exp(qk - qk.max(-1, keepdims=True)).sum(-1)) + \
        qk.max(-1)
    np.testing.assert_allclose(np.asarray(lse)[:, :, 0, :], ref_lse,
                               rtol=1e-4, atol=1e-4)


# (384, 64): the preferred tile does not divide the sequence (tiles of
# 128, two backward kernels); (1024, 64): the training cell's own shape
# (one tile a head, walked in strips up to the diagonal, one backward
# kernel); (512, 128): a head that fills the lanes, and a scale that
# does not fold into q; (2048, 64): two tiles a side, each of several
# strips (the clamped index maps and the diagonal walk together)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("seq,d", [(256, 64), (384, 64), (1024, 64),
                                   (512, 128), (2048, 64)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference_in_f32(causal, seq, d, dtype):
    """Output and dq / dk / dv of the kernels against the dense
    composition computed in f32 from the same (rounded) inputs, as a
    share of each array's largest entry: f32 inputs to rounding, bf16
    inputs to the rounding of p and ds that the MXU operands carry."""
    q, k, v = (x.astype(dtype) for x in _qkv(seq, d=d, seed=seq + d))
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape)
                    .astype("float32"))
    scale = 1.0 / np.sqrt(d)
    f32 = lambda x: x.astype(jnp.float32)

    def flash(q_, k_, v_):
        return f32(attn._flash_attention_core(q_, k_, v_, scale, causal))

    def dense(q_, k_, v_):
        return attn._reference_attention(f32(q_), f32(k_), f32(v_), None,
                                         scale, causal)

    got = (flash(q, k, v),) + jax.grad(
        lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(q, k, v)
    want = (dense(q, k, v),) + jax.grad(
        lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    limit = 2e-5 if dtype == jnp.float32 else 2e-2
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.dtype == (jnp.float32 if name == "out" else dtype)
        gap = float(jnp.max(jnp.abs(f32(a) - f32(b)))
                    / jnp.max(jnp.abs(f32(b))))
        assert gap < limit, (name, gap)


def _split_heads(qkv, heads):
    b, s, width = qkv.shape
    return jnp.moveaxis(qkv.reshape(b, s, 3, heads, width // (3 * heads)),
                        (2, 3), (0, 2))


# (12, 64): the training cell's heads, two a grid step side by side on
# the lanes; (16, 128): the 1.3B GPT's, one a step; (2, 64) at 1024:
# in f32 two tiles a side, so two backward kernels write one dqkv
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads,d,s", [(12, 64, 256), (16, 128, 256),
                                       (2, 64, 1024)])
@pytest.mark.parametrize("causal", [False, True])
def test_packed_flash_matches_reference_in_f32(causal, heads, d, s, dtype):
    """The packed entry (q, k, v read from the projection's [b, s, 3 *
    heads * d] where it lies, o and dqkv written in that layout)
    against the dense composition in f32 from the same inputs: the
    output and every third of dqkv, under the limits of the
    [b, h, s, d] entry above."""
    b, hidden = 2, heads * d
    rs = np.random.RandomState(heads + d)
    qkv = jnp.asarray(rs.randn(b, s, 3 * hidden).astype("float32")
                      * 0.3).astype(dtype)
    w = jnp.asarray(rs.randn(b, s, hidden).astype("float32"))
    f32 = lambda x: x.astype(jnp.float32)
    assert attn.packed_qkv_viable(qkv.shape, qkv.dtype, heads)

    def packed(x):
        return f32(attn._flash_qkv_core(x, heads, d ** -0.5, causal))

    def dense(x):
        o = attn._reference_attention(*_split_heads(f32(x), heads), None,
                                      d ** -0.5, causal)
        return jnp.swapaxes(o, 1, 2).reshape(b, s, hidden)

    got = (packed(qkv), jax.grad(lambda x: jnp.sum(packed(x) * w))(qkv))
    want = (dense(qkv), jax.grad(lambda x: jnp.sum(dense(x) * w))(qkv))
    assert got[1].dtype == dtype and got[1].shape == qkv.shape
    parts = [("out", got[0], want[0])] + [
        (name, got[1][..., i * hidden:(i + 1) * hidden],
         want[1][..., i * hidden:(i + 1) * hidden])
        for i, name in enumerate(("dq", "dk", "dv"))]
    limit = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b_ in parts:
        gap = float(jnp.max(jnp.abs(f32(a) - f32(b_)))
                    / jnp.max(jnp.abs(f32(b_))))
        assert gap < limit, (name, gap)


def _attention_layer(heads, hidden, causal=True):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import SelfAttention, TransformerLMConfig
    paddle.seed(0)
    return SelfAttention(TransformerLMConfig(
        hidden_size=hidden, num_heads=heads, dropout=0.0), causal)


def _loss_and_grads(layer, x, mask=None):
    """The loss and the gradient of the input and of every parameter,
    through the tape."""
    import paddle_tpu as paddle
    x = paddle.to_tensor(x, stop_gradient=False)
    loss = (layer(x, mask) ** 2).mean()
    loss.backward()
    grads = [x.grad.numpy()] + [p.grad.numpy() for p in layer.parameters()]
    for p in layer.parameters():
        p.clear_grad()
    return float(loss.numpy()), grads


@pytest.mark.parametrize("heads,hidden", [(12, 768), (2, 256)],
                         ids=["12x64", "2x128"])
def test_self_attention_packed_matches_head_split(heads, hidden,
                                                  monkeypatch):
    """SelfAttention end to end through the packed entry against
    today's head-split path (the same kernels over [b, h, s, d]): the
    same loss and the same gradient of the input and of every
    parameter."""
    layer = _attention_layer(heads, hidden)
    x = np.random.RandomState(1).randn(2, 256, hidden).astype("float32")
    calls = []
    real = attn.flash_attention_qkv
    monkeypatch.setattr(attn, "flash_attention_qkv",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    loss, grads = _loss_and_grads(layer, x)
    assert calls
    monkeypatch.setattr(attn, "packed_qkv_viable", lambda *a: False)
    calls.clear()
    loss_split, grads_split = _loss_and_grads(layer, x)
    assert not calls
    np.testing.assert_allclose(loss, loss_split, rtol=2e-4)
    for a, b_ in zip(grads, grads_split):
        np.testing.assert_allclose(a, b_, rtol=5e-3,
                                   atol=5e-4 * np.abs(b_).max())


@pytest.mark.parametrize("case", ["mask", "seq200", "13heads"])
def test_self_attention_keeps_the_head_split_where_packed_cannot(
        case, monkeypatch):
    """A masked call, a sequence the kernels refuse and an odd number
    of heads of 64 (two share a block's lanes) take today's path."""
    import paddle_tpu as paddle
    heads, seq = (13, 256) if case == "13heads" else (4, 256)
    seq = 200 if case == "seq200" else seq
    layer = _attention_layer(heads, heads * 64, causal=False)
    monkeypatch.setattr(attn, "flash_attention_qkv", lambda *a, **k: 1 / 0)
    x = np.random.RandomState(2).randn(1, seq, heads * 64).astype("float32")
    mask = paddle.to_tensor(np.zeros((1, 1, seq, seq), "float32")) \
        if case == "mask" else None
    loss, grads = _loss_and_grads(layer, x, mask)
    assert np.isfinite(loss) and all(np.isfinite(g).all() for g in grads)


@pytest.mark.parametrize("s,d,itemsize,block", [
    (1024, 64, 2, 1024),    # the training cell: one tile a head
    (8192, 64, 2, 1024),    # K/V streamed tile by tile
    (384, 64, 2, 128),      # 256 and up do not divide it
    (1024, 64, 4, 1024), (1024, 128, 4, 512),
    (2048, 256, 2, 512), (2048, 256, 4, 256)])
def test_tile_follows_the_shape(s, d, itemsize, block):
    assert attn._block(s, d, itemsize) == block
    assert block * d * itemsize <= 256 << 10 and s % block == 0


@pytest.mark.parametrize("d,exact", [(64, True), (256, True),
                                     (128, False), (96, False)])
def test_scale_folds_into_q_only_where_exact(d, exact):
    assert attn._exact_scale(d ** -0.5) == exact


@pytest.mark.parametrize("causal", [False, True])
def test_only_causal_kernels_build_a_mask(causal):
    # bidirectional attention keeps no mask at all: no iota, no select
    # in the forward or the backward kernel
    q = jnp.ones((1, 1, 256, 64), jnp.bfloat16)

    def loss(q_, k_, v_):
        return attn._flash_attention_core(q_, k_, v_, 0.125, causal).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    assert ("iota" in text and "select_n" in text) == causal


def test_flash_bwd_inside_train_step():
    # end to end: a tiny attention layer trains through the Pallas
    # forward + backward kernels
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.ops import manipulation

    paddle.seed(0)
    proj = nn.Linear(64, 64)
    opt = paddle.optimizer.SGD(0.1, parameters=proj.parameters())
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(1, 2, 128, 64).astype("float32"))
    losses = []
    from paddle_tpu.ops.attention import scaled_dot_product_attention
    for _ in range(4):
        hq = proj(x)
        out = scaled_dot_product_attention(hq, x, x, is_causal=True)
        loss = (out ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_ulysses_routes_through_flash_kernels():
    # Ulysses gathers full seq per head group and now calls the flash
    # core: verify parity vs dense attention with the kernels ACTIVE
    # (interpret mode) on the sp mesh, including gradients
    from paddle_tpu.distributed import topology, fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.ops.ring_attention import ulysses_attention

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "sp_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh
    try:
        rs = np.random.RandomState(0)
        b, h, s, d = 1, 4, 512, 64
        mk = lambda: jnp.asarray(rs.randn(b, h, s, d).astype("float32")
                                 * 0.3)
        q, k, v = mk(), mk(), mk()
        scale = 1.0 / np.sqrt(d)

        def f_ul(q_, k_, v_):
            return jnp.sum(ulysses_attention(q_, k_, v_, mesh,
                                             causal=True) ** 2)

        def f_ref(q_, k_, v_):
            return jnp.sum(attn._reference_attention(
                q_, k_, v_, None, scale, True) ** 2)

        out = ulysses_attention(q, k, v, mesh, causal=True)
        ref = attn._reference_attention(q, k, v, None, scale, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-4)
        g1 = jax.grad(f_ul, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-3, atol=5e-4)
    finally:
        topology._HYBRID = None


@pytest.mark.parametrize("b,h", [(4, 4), (3, 3)])
def test_flash_over_mesh_matches_reference(b, h):
    """Inside a multi-device program the kernels run under a shard_map
    (Mosaic cannot be partitioned automatically): batch over dp x
    sharding, heads over mp where they divide — (3, 3) divides neither
    and runs replicated. Values and gradients match dense attention."""
    from paddle_tpu.distributed import topology, fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "sharding_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh
    try:
        q, k, v = _qkv(256, b=b, h=h)
        scale = 1.0 / np.sqrt(q.shape[-1])

        def f_mesh(q_, k_, v_):
            return jnp.sum(attn._flash_over_mesh(q_, k_, v_, scale, True,
                                                 mesh) ** 2)

        def f_ref(q_, k_, v_):
            return jnp.sum(attn._reference_attention(
                q_, k_, v_, None, scale, True) ** 2)

        out = jax.jit(lambda *a: attn._flash_over_mesh(
            *a, scale, True, mesh))(q, k, v)
        ref = attn._reference_attention(q, k, v, None, scale, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)
        g1 = jax.jit(jax.grad(f_mesh, argnums=(0, 1, 2)))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=5e-3, atol=5e-4)
    finally:
        topology._HYBRID = None


def test_compiled_step_under_mesh_routes_flash_through_shard_map(
        monkeypatch):
    """to_static over an active multi-device mesh: the compiled step
    hands the kernel the mesh (eager warm-up/record stay single-device
    and call the core directly), and training still converges."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.distributed import topology, fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.ops.attention import scaled_dot_product_attention

    calls = []
    real = attn._flash_over_mesh
    monkeypatch.setattr(
        attn, "_flash_over_mesh",
        lambda *a: calls.append(a[-1].shape) or real(*a))
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(0)
        proj = nn.Linear(64, 64)
        opt = paddle.optimizer.SGD(0.1, parameters=proj.parameters())
        x = paddle.to_tensor(np.random.RandomState(0)
                             .randn(4, 2, 128, 64).astype("float32"))

        @paddle.jit.to_static
        def step(x):
            out = scaled_dot_product_attention(proj(x), x, x,
                                               is_causal=True)
            loss = (out ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        losses = []
        for i in range(4):
            losses.append(float(step(x).numpy()))
            assert bool(calls) == (i >= 2)   # compiled calls only
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
    finally:
        topology._HYBRID = None
