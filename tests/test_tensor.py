"""Tensor basics (reference test analogue: unittests over VarBase/Tensor)."""
import numpy as np
import pytest

import paddle_tpu as paddle


def test_to_tensor_dtypes():
    t = paddle.to_tensor([1.0, 2.0])
    assert t.dtype == paddle.float32
    t2 = paddle.to_tensor([1, 2])
    assert t2.dtype == paddle.int64
    t3 = paddle.to_tensor(np.zeros((2, 2), np.float64))
    assert t3.dtype == paddle.float64
    t4 = paddle.to_tensor(3)
    assert t4.dtype == paddle.int64


def test_shape_numel_ndim():
    t = paddle.zeros([2, 3, 4])
    assert t.shape == [2, 3, 4]
    assert t.ndim == 3
    assert t.numel() == 24
    assert len(t) == 2


def test_numpy_roundtrip():
    arr = np.random.randn(3, 4).astype("float32")
    t = paddle.to_tensor(arr)
    np.testing.assert_array_equal(t.numpy(), arr)


def test_operators():
    a = paddle.to_tensor([1.0, 2.0, 3.0])
    b = paddle.to_tensor([4.0, 5.0, 6.0])
    np.testing.assert_allclose((a + b).numpy(), [5, 7, 9])
    np.testing.assert_allclose((a - b).numpy(), [-3, -3, -3])
    np.testing.assert_allclose((a * b).numpy(), [4, 10, 18])
    np.testing.assert_allclose((b / a).numpy(), [4, 2.5, 2])
    np.testing.assert_allclose((a ** 2).numpy(), [1, 4, 9])
    np.testing.assert_allclose((-a).numpy(), [-1, -2, -3])
    np.testing.assert_allclose((a + 1).numpy(), [2, 3, 4])
    np.testing.assert_allclose((2 * a).numpy(), [2, 4, 6])
    assert (a + 1).dtype == paddle.float32  # scalar keeps tensor dtype


def test_comparisons():
    a = paddle.to_tensor([1.0, 2.0, 3.0])
    b = paddle.to_tensor([3.0, 2.0, 1.0])
    np.testing.assert_array_equal((a < b).numpy(), [True, False, False])
    np.testing.assert_array_equal((a == b).numpy(), [False, True, False])


def test_matmul_operator():
    a = paddle.to_tensor(np.eye(3, dtype="float32"))
    b = paddle.to_tensor(np.random.randn(3, 3).astype("float32"))
    np.testing.assert_allclose((a @ b).numpy(), b.numpy())


def test_indexing():
    t = paddle.to_tensor(np.arange(24).reshape(2, 3, 4).astype("float32"))
    np.testing.assert_array_equal(t[0].numpy(), np.arange(12).reshape(3, 4))
    np.testing.assert_array_equal(t[:, 1].numpy(),
                                  np.arange(24).reshape(2, 3, 4)[:, 1])
    np.testing.assert_array_equal(t[0, 1, 2].numpy(), 6)
    np.testing.assert_array_equal(t[..., -1].numpy(),
                                  np.arange(24).reshape(2, 3, 4)[..., -1])
    idx = paddle.to_tensor(np.array([1, 0]))
    np.testing.assert_array_equal(t[idx].numpy(),
                                  np.arange(24).reshape(2, 3, 4)[[1, 0]])


def test_setitem_inplace():
    t = paddle.zeros([3, 3])
    t[1] = 5.0
    assert t.numpy()[1].tolist() == [5, 5, 5]
    t[0, 0] = -1.0
    assert t.numpy()[0, 0] == -1


def test_set_value_and_item():
    t = paddle.zeros([2, 2])
    t.set_value(np.ones((2, 2), np.float32))
    assert t.numpy().sum() == 4
    s = paddle.to_tensor(3.5)
    assert s.item() == pytest.approx(3.5)
    assert float(s) == pytest.approx(3.5)


def test_astype_cast():
    t = paddle.to_tensor([1.5, 2.5])
    i = t.astype("int32")
    assert i.dtype == paddle.int32
    assert i.numpy().tolist() == [1, 2]
    b = paddle.cast(t, "bfloat16")
    assert b.dtype == paddle.bfloat16


def test_detach_clone():
    t = paddle.to_tensor([1.0, 2.0], stop_gradient=False)
    d = t.detach()
    assert d.stop_gradient
    c = t.clone()
    np.testing.assert_array_equal(c.numpy(), t.numpy())


def test_methods():
    t = paddle.to_tensor(np.random.randn(2, 8).astype("float32"))
    assert t.sum().shape == []
    assert t.mean(axis=1).shape == [2]
    assert t.reshape([4, 4]).shape == [4, 4]
    assert t.transpose([1, 0]).shape == [8, 2]
    assert t.T.shape == [8, 2]
    assert t.unsqueeze(0).shape == [1, 2, 8]
    assert t.flatten().shape == [16]
    assert t.max().numpy() == t.numpy().max()


def test_repr_does_not_crash():
    assert "Tensor" in repr(paddle.ones([2]))
    assert "Parameter" in repr(paddle.Parameter(np.ones(2, np.float32)))


def test_tensor_iteration_protocol():
    # iterating without __iter__ used to loop forever (getitem clamps
    # instead of raising IndexError); 0-d iteration must raise at
    # iter() time
    t = paddle.to_tensor(np.asarray([[1.0, 2.0], [3.0, 4.0]], "float32"))
    rows = [r.numpy().tolist() for r in t]
    assert rows == [[1.0, 2.0], [3.0, 4.0]]
    assert len(t) == 2
    assert t.element_size() == 4
    assert t.ndimension() == 2
    s = paddle.to_tensor(np.asarray(1.0, "float32"))
    import pytest
    with pytest.raises(TypeError):
        iter(s)
    with pytest.raises(TypeError):
        len(s)


def test_set_device_tpu_without_a_tpu_is_an_error():
    """Asking for the TPU by name never hands out a quiet CPU place;
    the compat aliases ('gpu', ...) still map to whatever is there."""
    import pytest
    from paddle_tpu.core import device
    before = device._current_place
    try:
        with pytest.raises(RuntimeError, match="no TPU present"):
            paddle.set_device("tpu:0")
        assert paddle.set_device("gpu:0").is_cpu_place()
        assert paddle.set_device("cpu").is_cpu_place()
    finally:
        device._current_place = before
