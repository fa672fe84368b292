"""fluid-1.x program-construct control flow: While and StaticRNN
(reference: fluid/layers/control_flow.py:973 While, :451 StaticRNN —
the constructs book-era static-graph code trains with)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import fluid
from paddle_tpu.fluid import layers


@pytest.fixture
def static_mode():
    paddle.enable_static()
    yield
    paddle.disable_static()


def test_while_counter_loop(static_mode):
    """The reference's canonical While pattern: counter + cond updated
    in place via increment/less_than(cond=...)."""
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [2], "float32")
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 5)
        acc = layers.fill_constant([2], "float32", 0.0)
        cond = layers.less_than(i, n)
        w = layers.While(cond)
        with w.block():
            acc2 = acc + x
            layers.assign(acc2, output=acc)
            i = layers.increment(i, in_place=True)
            layers.less_than(i, n, cond=cond)
        out = acc * 1.0

    exe = paddle.static.Executor()
    xp = np.array([1.5, 2.0], np.float32)
    res, = exe.run(main, feed={"x": xp}, fetch_list=[out])
    np.testing.assert_allclose(res, xp * 5)


def test_while_data_dependent_bound(static_mode):
    """The trip count comes from a FEED value — one compiled program
    serves different bounds (lax.while_loop, no unrolling)."""
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        n = paddle.static.data("n", [1], "int64")
        i = layers.fill_constant([1], "int64", 0)
        s = layers.fill_constant([1], "float32", 0.0)
        cond = layers.less_than(i, n)
        w = layers.While(cond)
        with w.block():
            layers.assign(s + 2.0, output=s)
            i = layers.increment(i, in_place=True)
            layers.less_than(i, n, cond=cond)

    exe = paddle.static.Executor()
    for bound in (3, 7):
        res, = exe.run(main,
                       feed={"n": np.array([bound], np.int64)},
                       fetch_list=[s])
        np.testing.assert_allclose(res, [2.0 * bound])


def test_static_rnn_prefix_sum(static_mode):
    """StaticRNN accumulating its input: ys must be prefix sums."""
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [4, 2, 3], "float32")  # [T, B, D]
        rnn = layers.StaticRNN()
        with rnn.step():
            word = rnn.step_input(x)
            prev = rnn.memory(shape=[-1, 3], batch_ref=word)
            hidden = prev + word
            rnn.update_memory(prev, hidden)
            rnn.step_output(hidden)
        out = rnn()

    exe = paddle.static.Executor()
    xp = np.random.RandomState(0).randn(4, 2, 3).astype("float32")
    res, = exe.run(main, feed={"x": xp}, fetch_list=[out])
    np.testing.assert_allclose(res, np.cumsum(xp, axis=0), rtol=1e-6)


def test_static_rnn_trains_through_scan(static_mode):
    """append_backward differentiates THROUGH the recurrence (lax.scan
    is reverse-differentiable — the property While lacks)."""
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [3, 2, 1], "float32")
        w = paddle.to_tensor(np.array([2.0], np.float32),
                             stop_gradient=False)
        rnn = layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(x)
            prev = rnn.memory(shape=[-1, 1], batch_ref=xt)
            h = prev + xt * w
            rnn.update_memory(prev, h)
            rnn.step_output(h)
        out = rnn()
        loss = paddle.sum(out)
        grads = paddle.static.append_backward(loss)

    exe = paddle.static.Executor()
    xp = np.arange(6, dtype=np.float32).reshape(3, 2, 1)
    g_name = grads[0][1]
    loss_v, g = exe.run(main, feed={"x": xp}, fetch_list=[loss, g_name])
    # h_t = w * cumsum -> loss = w * sum_t (T - t) x_t; dl/dw analytic:
    weights = np.array([3, 2, 1], np.float32).reshape(3, 1, 1)
    expect_grad = float((xp * weights).sum())
    np.testing.assert_allclose(float(loss_v), 2.0 * expect_grad,
                               rtol=1e-6)
    np.testing.assert_allclose(float(np.asarray(g).sum()), expect_grad,
                               rtol=1e-6)


def test_static_rnn_with_initial_memory(static_mode):
    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [3, 2, 2], "float32")
        boot = paddle.static.data("boot", [2, 2], "float32")
        rnn = layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(x)
            prev = rnn.memory(init=boot)
            h = prev * 0.5 + xt
            rnn.update_memory(prev, h)
            rnn.step_output(h)
        out = rnn()

    exe = paddle.static.Executor()
    xp = np.ones((3, 2, 2), np.float32)
    bp = np.full((2, 2), 4.0, np.float32)
    res, = exe.run(main, feed={"x": xp, "boot": bp}, fetch_list=[out])
    h = bp.copy()
    expect = []
    for t in range(3):
        h = h * 0.5 + xp[t]
        expect.append(h)
    np.testing.assert_allclose(res, np.stack(expect), rtol=1e-6)


def test_descoped_constructs_say_so(static_mode):
    from paddle_tpu.core.errors import UnimplementedError
    for ctor in (layers.Switch, layers.IfElse, layers.DynamicRNN,
                 layers.reorder_lod_tensor_by_rank):
        with pytest.raises(UnimplementedError, match="explicitly descoped"):
            ctor()


def test_while_program_serialization_roundtrip(static_mode, tmp_path):
    """Programs containing the new control-flow records (While sub-
    blocks, aliases, consts) serialize and reload (reference:
    save/load_inference_model over ProgramDesc sub-blocks)."""
    from paddle_tpu.static.program import (_deserialize_program,
                                           _serialize_program)

    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [2], "float32")
        i = layers.fill_constant([1], "int64", 0)
        n = layers.fill_constant([1], "int64", 4)
        acc = layers.fill_constant([2], "float32", 0.0)
        cond = layers.less_than(i, n)
        w = layers.While(cond)
        with w.block():
            layers.assign(acc + x, output=acc)
            i = layers.increment(i, in_place=True)
            layers.less_than(i, n, cond=cond)
        out = acc * 2.0

    exe = paddle.static.Executor()
    xp = np.array([1.0, 3.0], np.float32)
    want, = exe.run(main, feed={"x": xp}, fetch_list=[out])

    blob = _serialize_program(main)
    import pickle
    prog2 = _deserialize_program(pickle.loads(pickle.dumps(blob)))
    got, = exe.run(prog2, feed={"x": xp},
                   fetch_list=[out.name])
    np.testing.assert_allclose(got, want)
    np.testing.assert_allclose(got, xp * 4 * 2)


def test_static_rnn_serialization_roundtrip(static_mode):
    from paddle_tpu.static.program import (_deserialize_program,
                                           _serialize_program)

    main = paddle.static.Program()
    with paddle.static.program_guard(main):
        x = paddle.static.data("x", [3, 2, 2], "float32")
        rnn = layers.StaticRNN()
        with rnn.step():
            xt = rnn.step_input(x)
            prev = rnn.memory(shape=[-1, 2], batch_ref=xt)
            h = prev + xt
            rnn.update_memory(prev, h)
            rnn.step_output(h)
        out = rnn()

    exe = paddle.static.Executor()
    xp = np.random.RandomState(0).randn(3, 2, 2).astype("float32")
    want, = exe.run(main, feed={"x": xp}, fetch_list=[out])

    import pickle
    prog2 = _deserialize_program(
        pickle.loads(pickle.dumps(_serialize_program(main))))
    got, = exe.run(prog2, feed={"x": xp}, fetch_list=[out.name])
    np.testing.assert_allclose(got, want)
