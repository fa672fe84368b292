"""DDP Reducer absorption proof (VERDICT r2 §2.4 partial row; reference:
paddle/fluid/imperative/reducer.h:84 — group_size_limits buckets grads
so many small allreduces amortize into few big ones, overlapped with
backward).

On TPU the compiled step makes the Reducer unnecessary BY CONSTRUCTION:
GSPMD inserts the cross-dp grad reductions and XLA's all-reduce
combiner + latency-hiding scheduler fuse and overlap them. These tests
pin that down by inspecting the optimized HLO: N per-parameter grad
all-reduces collapse into O(1) fused collectives — the optimal 'bucket'
the reference's 25MB heuristic approximates."""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn

def _mesh():
    return Mesh(np.array(jax.devices()), ("dp",))


class TestReducerAbsorbed:
    def test_substrate_combines_grad_allreduces(self):
        """12 parameters' dp-grad reductions -> ONE all-reduce in the
        optimized HLO (XLA all-reduce combiner)."""
        mesh = _mesh()
        rng = np.random.RandomState(0)
        params = [jnp.asarray(rng.randn(64, 64), jnp.float32)
                  for _ in range(12)]

        def loss_fn(params, x, y):
            h = x
            for w in params:
                h = jnp.tanh(h @ w)
            return jnp.mean((h - y) ** 2)

        def step(params, x, y):
            g = jax.grad(loss_fn)(params, x, y)
            return [p - 0.1 * gi for p, gi in zip(params, g)]

        shard = NamedSharding(mesh, P("dp"))
        repl = NamedSharding(mesh, P())
        x = jax.device_put(
            jnp.asarray(rng.randn(32, 64), jnp.float32), shard)
        y = jax.device_put(
            jnp.asarray(rng.randn(32, 64), jnp.float32), shard)
        ps = [jax.device_put(p, repl) for p in params]
        hlo = jax.jit(step).lower(ps, x, y).compile().as_text()
        n_ar = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
        assert n_ar >= 1, "grads never crossed the dp axis"
        assert n_ar <= 2, (
            f"{n_ar} all-reduces for 12 params — combiner not engaged")

    def test_paddle_dp_train_step_hlo(self):
        """The same property through the paddle surface: a DP train step
        (model + SGD via the op registry) compiles to O(1) fused grad
        all-reduces for its 6 parameters."""
        from conftest import make_traced_train_step

        mesh = _mesh()
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                            nn.Linear(32, 16), nn.ReLU(),
                            nn.Linear(16, 4))
        opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
        train_step, names, state = make_traced_train_step(
            net, opt, nn.CrossEntropyLoss())

        rng = np.random.RandomState(1)
        shard = NamedSharding(mesh, P("dp"))
        repl = NamedSharding(mesh, P())
        x = jax.device_put(
            jnp.asarray(rng.randn(16, 16), jnp.float32), shard)
        y = jax.device_put(
            jnp.asarray(rng.randint(0, 4, (16,)), jnp.int64), shard)
        pv = [jax.device_put(state[n].value, repl) for n in names]
        hlo = jax.jit(train_step).lower(pv, x, y).compile().as_text()
        n_ar = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
        assert n_ar >= 1, "grads never crossed the dp axis"
        assert n_ar <= 3, (
            f"{n_ar} all-reduces for {len(names)} params — combiner "
            "not engaged")
        # and the same step's math trains: FRESH instances (a model/
        # optimizer pair is traced exactly once in its lifetime — a
        # re-trace after accumulator creation bakes a different capture
        # set), mesh-free, one jit wrapper, second call a cache hit.
        paddle.seed(0)
        net2 = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                             nn.Linear(32, 16), nn.ReLU(),
                             nn.Linear(16, 4))
        opt2 = paddle.optimizer.SGD(0.1, parameters=net2.parameters())
        step2, names2, state2 = make_traced_train_step(
            net2, opt2, nn.CrossEntropyLoss())
        f = jax.jit(step2)
        pv0 = [state2[n].value for n in names2]
        x_h = jnp.asarray(np.asarray(x))
        y_h = jnp.asarray(np.asarray(y))
        loss1, pv1 = f(pv0, x_h, y_h)
        loss2, _ = f(pv1, x_h, y_h)
        assert float(loss2) < float(loss1)
