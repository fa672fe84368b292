"""Autograd engine tests — analytic grads vs numeric finite differences,
mirroring the reference OpTest.check_grad strategy (op_test.py:1409)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


from grad_check import numeric_grad


def check_grad(paddle_fn, x_np, rtol=1e-2, atol=1e-3):
    x = paddle.to_tensor(x_np.astype("float64"), stop_gradient=False)
    out = paddle_fn(x)
    loss = out.sum()
    loss.backward()
    analytic = x.grad.numpy()

    def f(a):
        t = paddle.to_tensor(a)
        return float(paddle_fn(t).sum().numpy())
    numeric = numeric_grad(f, x_np.astype("float64"))
    np.testing.assert_allclose(analytic, numeric, rtol=rtol, atol=atol)


@pytest.mark.parametrize("fn_name", [
    "exp", "tanh", "sigmoid", "sqrt_abs", "square", "relu_like", "log_abs",
])
def test_unary_grads(fn_name):
    x = np.random.uniform(0.5, 2.0, (3, 4))
    fns = {
        "exp": paddle.exp, "tanh": paddle.tanh,
        "sigmoid": paddle.sigmoid,
        "sqrt_abs": paddle.sqrt, "square": paddle.square,
        "relu_like": F.relu, "log_abs": paddle.log,
    }
    check_grad(fns[fn_name], x)


def test_matmul_grad():
    a_np = np.random.randn(3, 4)
    b_np = np.random.randn(4, 5)
    a = paddle.to_tensor(a_np, stop_gradient=False)
    b = paddle.to_tensor(b_np, stop_gradient=False)
    out = paddle.matmul(a, b)
    out.backward(paddle.ones_like(out))
    np.testing.assert_allclose(a.grad.numpy(),
                               np.ones((3, 5)) @ b_np.T, rtol=1e-6)
    np.testing.assert_allclose(b.grad.numpy(),
                               a_np.T @ np.ones((3, 5)), rtol=1e-6)


def test_softmax_cross_entropy_grad():
    logits = np.random.randn(4, 10)
    labels = np.random.randint(0, 10, (4,))

    def fn(x):
        return F.cross_entropy(x, paddle.to_tensor(labels))
    check_grad(fn, logits)


def test_conv2d_grad():
    x_np = np.random.randn(1, 2, 6, 6)
    w = paddle.to_tensor(np.random.randn(3, 2, 3, 3), stop_gradient=False)

    def fn(x):
        return F.conv2d(x, w)
    check_grad(fn, x_np, rtol=2e-2, atol=1e-2)


def test_grad_accumulation():
    x = paddle.to_tensor([1.0, 2.0], stop_gradient=False)
    (x * 2).sum().backward()
    (x * 3).sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [5.0, 5.0])
    x.clear_grad()
    assert x.grad is None


def test_stop_gradient_cut():
    x = paddle.to_tensor([1.0], stop_gradient=False)
    y = paddle.to_tensor([2.0], stop_gradient=True)
    (x * y).sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [2.0])
    assert y.grad is None


def test_detach_cuts_graph():
    x = paddle.to_tensor([3.0], stop_gradient=False)
    y = (x * x).detach()
    z = y * x
    z.backward()
    np.testing.assert_allclose(x.grad.numpy(), [9.0])  # only through z=y*x


def test_backward_twice_raises_without_retain():
    x = paddle.to_tensor([1.0], stop_gradient=False)
    y = x * x * x
    y.backward(retain_graph=True)
    y.backward()  # retain allowed it once more
    with pytest.raises(RuntimeError):
        y.backward()


def test_multi_output_op_grad():
    x = paddle.to_tensor(np.random.randn(5).astype("float64"),
                         stop_gradient=False)
    vals, idx = paddle.topk(x, k=2)
    vals.sum().backward()
    g = x.grad.numpy()
    top2 = np.argsort(-x.numpy())[:2]
    expected = np.zeros(5)
    expected[top2] = 1
    np.testing.assert_allclose(g, expected)


def test_paddle_grad_api():
    x = paddle.to_tensor([2.0], stop_gradient=False)
    y = x * x
    (g,) = paddle.grad(y, x)
    np.testing.assert_allclose(g.numpy(), [4.0])
    assert x.grad is None  # no side effect on .grad


def test_tensor_hook():
    x = paddle.to_tensor([1.0, 1.0], stop_gradient=False)
    h = x.register_hook(lambda g: g * 2)
    (x * 3).sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [6.0, 6.0])
    h.remove()


def test_pylayer():
    class Double(paddle.autograd.PyLayer):
        @staticmethod
        def forward(ctx, x):
            ctx.save_for_backward(x)
            return x * 2

        @staticmethod
        def backward(ctx, g):
            return g * 2

    x = paddle.to_tensor([1.5], stop_gradient=False)
    y = Double.apply(x)
    np.testing.assert_allclose(y.numpy(), [3.0])
    y.backward()
    np.testing.assert_allclose(x.grad.numpy(), [2.0])


def test_no_grad_context():
    x = paddle.to_tensor([1.0], stop_gradient=False)
    with paddle.no_grad():
        y = x * 2
    assert y._grad_node is None


def test_embedding_grad_scatter():
    w = paddle.to_tensor(np.random.randn(10, 4), stop_gradient=False)
    ids = paddle.to_tensor(np.array([1, 1, 3]))
    out = F.embedding(ids, w)
    out.sum().backward()
    g = w.grad.numpy()
    assert g[1].sum() == pytest.approx(8.0)  # row 1 hit twice
    assert g[3].sum() == pytest.approx(4.0)
    assert g[0].sum() == 0


def test_double_grad_scalar():
    """d2/dx2 of x^3 = 6x (reference: partial_grad_engine.cc create_graph)."""
    import numpy as np
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.float32(2.0))
    x.stop_gradient = False
    y = x * x * x
    (g,) = paddle.grad(y, x, create_graph=True)
    assert float(g.numpy()) == 12.0  # 3x^2
    assert not g.stop_gradient
    (g2,) = paddle.grad(g, x)
    assert float(g2.numpy()) == 12.0  # 6x


def test_double_grad_vector_and_gradient_penalty():
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    paddle.seed(11)
    net = nn.Linear(4, 1)
    x = paddle.to_tensor(np.random.randn(8, 4).astype("float32"))
    x.stop_gradient = False
    out = net(x).sum()
    (gx,) = paddle.grad(out, x, create_graph=True)
    # gradient penalty: ||dout/dx||^2 — backward through the grad
    gp = (gx * gx).sum()
    gp.backward()
    w = net.weight
    assert w.grad is not None
    # analytic: gx rows = w^T; gp = 8 * ||w||^2; d gp/d w = 16 w
    np.testing.assert_allclose(w.grad.numpy(),
                               16.0 * w.numpy(), rtol=1e-4, atol=1e-5)


def test_triple_grad():
    import numpy as np
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.float32(3.0))
    x.stop_gradient = False
    y = x ** 4
    (g1,) = paddle.grad(y, x, create_graph=True)       # 4x^3
    (g2,) = paddle.grad(g1, x, create_graph=True)      # 12x^2
    (g3,) = paddle.grad(g2, x)                         # 24x
    assert float(g1.numpy()) == 108.0
    assert float(g2.numpy()) == 108.0
    assert float(g3.numpy()) == 72.0


def test_pylayer_under_create_graph_cuts_cleanly():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.autograd import PyLayer

    class Double(PyLayer):
        @staticmethod
        def forward(ctx, x):
            return x * 2

        @staticmethod
        def backward(ctx, g):
            return g * 2

    x = paddle.to_tensor(np.float32(3.0))
    x.stop_gradient = False
    y = Double.apply(x) * x  # 2x^2
    (g,) = paddle.grad(y, x, create_graph=True)
    assert float(g.numpy()) == 12.0  # 4x


def test_double_grad_distinct_attrs_no_vjp_cache_collision():
    """Two same-named forward ops differing only in attrs (sum axis) must
    not share a vjp executable (regression: jit-cache collision)."""
    import numpy as np
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.arange(9, dtype="float32").reshape(3, 3))
    x.stop_gradient = False
    v = paddle.to_tensor(np.array([1.0, 2.0, 3.0], "float32"))

    y0 = (x.sum(axis=0) * v).sum()
    (g0,) = paddle.grad(y0, x, create_graph=True)
    y1 = (x.sum(axis=1) * v).sum()
    (g1,) = paddle.grad(y1, x, create_graph=True)
    # d(sum axis 0)/dx broadcasts v along rows; axis 1 along columns
    np.testing.assert_allclose(g0.numpy(), np.tile([[1, 2, 3]], (3, 1)))
    np.testing.assert_allclose(g1.numpy(),
                               np.tile([[1], [2], [3]], (1, 3)))


def test_hooks_with_create_graph_raise():
    import numpy as np
    import pytest
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.float32(2.0))
    x.stop_gradient = False
    y = x * x
    y.register_hook(lambda g: g)
    z = y * x
    with pytest.raises(NotImplementedError, match="create_graph"):
        paddle.grad(z, x, create_graph=True)


@pytest.fixture
def cache_flags(monkeypatch):
    """core.flags with no explicit FLAGS_compilation_cache_dir; the
    flag and jax's setting are put back afterwards."""
    import jax
    from paddle_tpu.core import flags
    key = "FLAGS_compilation_cache_dir"
    was_dir = jax.config.jax_compilation_cache_dir
    was_flag = flags._flags.pop(key, None)
    monkeypatch.delenv(key, raising=False)
    yield flags
    flags._flags.pop(key, None)
    if was_flag is not None:
        flags._flags[key] = was_flag
    jax.config.update("jax_compilation_cache_dir", was_dir)


def test_set_flags_reapplies_compilation_cache(cache_flags):
    import jax
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_compilation_cache_dir": ""})
    assert jax.config.jax_compilation_cache_dir is None
    paddle.set_flags({"FLAGS_compilation_cache_dir": "/tmp/ptpu_cache_t"})
    assert jax.config.jax_compilation_cache_dir == "/tmp/ptpu_cache_t"


def test_cache_dir_from_jax_env_is_not_overridden(cache_flags, monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR places the cache from outside: jax
    reads it itself and the framework sets no directory in code."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    cache_flags.init_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == "sentinel"
    # ... but the explicit user flag still wins over it
    import paddle_tpu as paddle
    paddle.set_flags({"FLAGS_compilation_cache_dir": ""})
    assert jax.config.jax_compilation_cache_dir is None


def test_cache_dir_default_is_fixed_path_in_checkout(cache_flags,
                                                     monkeypatch):
    import os
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_flags.init_compilation_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        repo, ".jax_cache")


def test_unusable_cache_dir_raises(cache_flags, tmp_path):
    import paddle_tpu as paddle
    blocker = tmp_path / "a_file"
    blocker.write_text("")
    with pytest.raises(OSError):
        paddle.set_flags(
            {"FLAGS_compilation_cache_dir": str(blocker / "sub")})


def test_grad_failure_restores_accumulated_grads():
    """paddle.grad must not wipe .grad when backward raises mid-run."""
    import numpy as np
    import pytest
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    x = paddle.to_tensor(np.float32(2.0))
    x.stop_gradient = False
    x._grad = Tensor(np.float32(5.0))  # pre-accumulated
    y = x * x
    y.register_hook(lambda g: g)
    z = y * x
    with pytest.raises(NotImplementedError):
        paddle.grad(z, x, create_graph=True)
    assert float(x.grad.numpy()) == 5.0


def test_double_grad_uses_forward_time_values():
    """vjp must see the forward-time param values even after in-place
    mutation (opt.step) before the create_graph backward."""
    import numpy as np
    import paddle_tpu as paddle
    w = paddle.to_tensor(np.float32(3.0))
    w.stop_gradient = False
    y = w * w  # dy/dw = 2w = 6 at forward time
    w.value = np.float32(100.0)  # simulate opt.step mutation
    (g,) = paddle.grad(y, w, create_graph=True)
    assert float(g.numpy()) == 6.0


def test_double_grad_analytic_sweep():
    """Second-order grads vs closed forms for transcendental and
    composite ops (reference: PartialGradEngine create_graph path —
    partial_grad_engine.cc double-grad)."""
    v = np.array([0.3, -0.7, 1.1], np.float32)

    cases = [
        # (fn, d2/dx2 closed form)
        (lambda t: t.tanh(),
         lambda x: -2 * np.tanh(x) * (1 - np.tanh(x) ** 2)),
        (lambda t: t.sigmoid(),
         lambda x: (s := 1 / (1 + np.exp(-x))) * (1 - s) * (1 - 2 * s)),
        (lambda t: t.exp(), np.exp),
        (lambda t: (t * t * t), lambda x: 6 * x),
        (lambda t: t.square().log(), lambda x: -2 / x ** 2),
    ]
    for fn, d2 in cases:
        x = paddle.to_tensor(v.copy())
        x.stop_gradient = False
        y = fn(x).sum()
        (g1,) = paddle.grad(y, x, create_graph=True)
        (g2,) = paddle.grad(g1.sum(), x)
        np.testing.assert_allclose(np.asarray(g2.numpy()), d2(v),
                                   rtol=2e-4, atol=1e-5)


def test_double_grad_matmul_mixed():
    """Mixed second-order through matmul: grad wrt B of sum(A@B * C)
    is A^T C; the grad wrt A of ||A^T C||^2 must equal the closed form
    2 C (A^T C)^T."""
    rs = np.random.RandomState(0)
    A = rs.randn(3, 4).astype(np.float32)
    B = rs.randn(4, 2).astype(np.float32)
    C = rs.randn(3, 2).astype(np.float32)

    a = paddle.to_tensor(A.copy()); a.stop_gradient = False
    bt = paddle.to_tensor(B.copy()); bt.stop_gradient = False
    c = paddle.to_tensor(C.copy())
    y = (a.matmul(bt) * c).sum()
    (gb,) = paddle.grad(y, bt, create_graph=True)   # = A^T @ C
    z = (gb * gb).sum()
    (ga,) = paddle.grad(z, a)                       # = 2 C @ (A^T C)^T
    expect = 2 * C @ (A.T @ C).T
    np.testing.assert_allclose(np.asarray(ga.numpy()), expect,
                               rtol=1e-4, atol=1e-5)
