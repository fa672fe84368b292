"""The plain reference against GPTForCausalLM at a tiny width, float32
on the CPU: forward logits, loss, and one AdamW step.

Tolerances: both sides compute in float32; they differ by summation
order only, so logits (of magnitude ~1) agree to 1e-5, the loss to 1e-5
and a step's parameter change (magnitude lr = 3e-4 an element) to 1e-3
of its norm, leaf by leaf.
"""
import numpy as np
import jax.numpy as jnp

from benchmarks import weights
from benchmarks.planes import gpt_program
from benchmarks.reference import gpt as ref

MODEL = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
             num_attention_heads=4, intermediate_size=256,
             max_position_embeddings=128)
HP = dict(lr=3e-4, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01)


def _program(seed):
    import paddle_tpu as paddle
    w = weights.gpt_weights(seed, MODEL, "float32")
    net = gpt_program.build_model(MODEL)
    gpt_program.set_weights(net, w)
    return paddle, net, w


def test_weights_repeat_and_take_large_seeds():
    a = weights.gpt_weights(2 ** 31 + 12345, MODEL, "float32")
    b = weights.gpt_weights(2 ** 31 + 12345, MODEL, "float32")
    c = weights.gpt_weights(12345, MODEL, "float32")
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wemb"], c["wemb"])
    assert weights.count_params(MODEL) == sum(int(np.prod(v.shape))
                                              for v in a.values())


def test_forward_logits_agree():
    paddle, net, w = _program(3)
    net.eval()
    ids = np.random.default_rng(0).integers(0, 512, (2, 48))
    with paddle.no_grad():
        got = net(paddle.to_tensor(ids.astype("int64"))).numpy()
    want = np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32), 4))
    assert np.abs(got - want).max() < 1e-5


def test_loss_and_adamw_step_agree():
    paddle, net, w = _program(4)
    rng = np.random.default_rng(1)
    x = rng.integers(0, 500, (4, 32))
    y = rng.integers(0, 500, (4, 32))
    opt = paddle.optimizer.AdamW(HP["lr"], parameters=net.parameters(),
                                 weight_decay=HP["weight_decay"])
    loss = net(paddle.to_tensor(x.astype("int64")),
               labels=paddle.to_tensor(y.astype("int64")))
    loss.backward()
    opt.step()
    out = ref.train_steps(w, [(jnp.asarray(x, jnp.int32),
                               jnp.asarray(y, jnp.int32))], 4, HP)
    assert abs(float(loss.numpy()) - out["losses"][0]) < 1e-5
    for p, leaf, layer in gpt_program.param_leaves(net):
        w0 = w[leaf] if layer is None else w[leaf][layer]
        got = float(jnp.sqrt(((p.value - w0) ** 2).sum()))
        want = np.asarray(out["delta_norms"][leaf])
        want = float(want if layer is None else want[layer])
        assert abs(got - want) <= 1e-3 * want, (leaf, layer, got, want)


def test_lower_precisions_move_the_logits_in_order():
    """float32 < bfloat16 < float8 in distance from the reference."""
    w = weights.gpt_weights(5, MODEL, "float32")
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 512, (1, 64)),
                      jnp.int32)
    exact = ref.logits(w, ids, 4)
    err = {p: float(jnp.abs(ref.logits(w, ids, 4, p) - exact).max())
           for p in ("bfloat16", "float8")}
    assert 0 < err["bfloat16"] < err["float8"]
