"""Test config: the suite runs on the CPU backend with 8 virtual
devices (SURVEY §4: multi-chip tests simulated on one host;
XLA_FLAGS=--xla_force_host_platform_device_count=8). The platform is
switched via jax.config before any backend is initialized.

Nothing here (or in any module a test file imports) may load libtpu:
every xdist worker imports this file. The one file that describes a TPU
topology, tests/test_chip_compile.py, does so inside a fixture.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_everything():
    import numpy as np
    import paddle_tpu as paddle
    np.random.seed(0)
    paddle.seed(1234)
    yield


@pytest.fixture(params=["gather", "kernel-interpret"])
def decode_attention(request):
    """Both decode attentions a ServingEngine can choose for a GPT:
    the XLA gather (the CPU's own choice) and the Pallas paged kernel,
    interpreted. The engine asks ``kernel_viable`` when it is built
    and the kernel is lowered when the decode program first compiles,
    so the switch holds for the whole test. ``engine.decode_layout``
    says which one a test got."""
    from paddle_tpu.ops import paged_attention as pa
    pa._FORCE_INTERPRET[0] = request.param == "kernel-interpret"
    yield {"gather": "paged_xla",
           "kernel-interpret": "paged_pallas"}[request.param]
    pa._FORCE_INTERPRET[0] = False


def make_traced_train_step(net, opt, loss_fn):
    """jax-jittable closure running one REAL paddle train step (model +
    optimizer via the op registry) under a TraceContext — shared by the
    HLO-inspection tests (DDP reducer / fused optimizer absorption).
    Signature: train_step(param_vals, x_arr, y_arr) -> (loss, params);
    optimizer accumulators created in-trace stay internal (compile-time
    state), only params thread through.
    """
    from paddle_tpu.core import trace as trace_mod
    from paddle_tpu.core.tensor import Tensor

    state = {t.name: t for t in net.parameters()}
    names = list(state)

    def train_step(param_vals, x_arr, y_arr):
        ctx = trace_mod.TraceContext("jit")
        with trace_mod.trace_guard(ctx):
            for n, v in zip(names, param_vals):
                ctx.bind(state[n], v)
            x = Tensor(x_arr)
            y = Tensor(y_arr)
            ctx.register_created(x)
            ctx.register_created(y)
            loss = loss_fn(net(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            new_params = [ctx.final_value(state[n]) for n in names]
            return loss.value, new_params

    return train_step, names, state
