"""Fused linear+cross-entropy kernel (ops/fused_ce.py): the LM-head
matmul and softmax-CE as one vocab-tiled Pallas program. Interpret-mode
kernel parity vs the unfused composition, gradients included."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops import fused_ce


def _reference_loss_np(x, w_vh, labels, ignore=-100):
    logits = x.astype(np.float64) @ w_vh.astype(np.float64).T
    m = logits.max(-1, keepdims=True)
    lse = (m[:, 0] + np.log(np.exp(logits - m).sum(-1)))
    ll = logits[np.arange(len(labels)), np.clip(labels, 0, None)]
    out = lse - ll
    out[labels == ignore] = 0.0
    return out


def test_fused_ce_reference_path_matches_numpy():
    rs = np.random.RandomState(0)
    x = rs.randn(8, 16).astype(np.float32)
    w = rs.randn(32, 16).astype(np.float32)
    lab = rs.randint(0, 32, (8,))
    lab[2] = -100
    out = fused_ce.fused_linear_cross_entropy(
        paddle.to_tensor(x), paddle.to_tensor(w),
        paddle.to_tensor(lab.astype(np.int64)))
    np.testing.assert_allclose(out.numpy(),
                               _reference_loss_np(x, w, lab), rtol=1e-5)


@pytest.fixture
def interpret_kernels():
    fused_ce._FORCE_INTERPRET[0] = True
    yield
    fused_ce._FORCE_INTERPRET[0] = False


def test_pallas_kernel_parity_interpret(interpret_kernels):
    """The tiled online-logsumexp kernel (forced through the pallas
    path in interpret mode) matches the composition, including the
    ignore_index masking."""
    import jax.numpy as jnp
    rs = np.random.RandomState(1)
    t, h, v = 256, 128, 1024
    x = rs.randn(t, h).astype(np.float32) * 0.3
    w = rs.randn(v, h).astype(np.float32) * 0.3
    lab = rs.randint(0, v, (t,))
    lab[5] = -100
    assert fused_ce._use_pallas(jnp.asarray(x), jnp.asarray(w))
    loss, lse = fused_ce._pallas_fwd(jnp.asarray(x), jnp.asarray(w),
                                     jnp.asarray(lab.astype(np.int32)),
                                     -100)
    np.testing.assert_allclose(np.asarray(loss),
                               _reference_loss_np(x, w, lab),
                               rtol=2e-5, atol=2e-5)


def test_pallas_kernel_grads_interpret(interpret_kernels):
    """dx and dW from the recompute backward kernels match jax.grad of
    the unfused composition."""
    import jax
    import jax.numpy as jnp
    rs = np.random.RandomState(2)
    t, h, v = 128, 128, 1024
    x = jnp.asarray(rs.randn(t, h).astype(np.float32) * 0.3)
    w = jnp.asarray(rs.randn(v, h).astype(np.float32) * 0.3)
    lab_np = rs.randint(0, v, (t,))
    lab_np[3] = -100
    lab = jnp.asarray(lab_np.astype(np.int32))

    def fused(x_, w_):
        return fused_ce._fused_core(x_, w_, lab, -100).mean()

    def ref(x_, w_):
        return fused_ce._reference(x_, w_, lab, -100).mean()

    gx_f, gw_f = jax.grad(fused, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                               rtol=2e-4, atol=2e-6)


def _spread_case(rs, t, h, v, dtype):
    """Rows whose logits spread by 150 and more: every term of the
    softmax's sum but the maximum's underflows, so s is exactly 1."""
    import jax.numpy as jnp
    w = np.zeros((v, h), np.float32)
    for j in range(v):
        w[j, j % h] = 1.0 + j // h        # rows j, j + h, ... parallel
    hot = rs.randint(0, h, (t,))
    x = np.zeros((t, h), np.float32)
    x[np.arange(t), hot] = 150.0
    return jnp.asarray(x, dtype), jnp.asarray(w, dtype)


# name: (t, h, v, dtype, what the labels hold, cotangent, inputs)
_RULE_CASES = {
    "f32": (128, 128, 1024, "float32", "one_ignored", "mean", "randn"),
    "bf16": (128, 128, 1024, "bfloat16", "one_ignored", "mean", "randn"),
    "some_rows_ignored": (96, 32, 256, "float32", "third_ignored",
                          "mean", "randn"),
    "every_row_ignored": (64, 32, 256, "float32", "all_ignored", "mean",
                          "randn"),
    "label_on_last_vocab_row": (64, 32, 256, "float32", "last_row",
                                "mean", "randn"),
    "label_past_the_vocab_is_clipped": (64, 32, 256, "float32",
                                        "past_end", "mean", "randn"),
    "logits_spread_over_100": (64, 32, 160, "float32", "plain", "mean",
                               "spread"),
    "logits_spread_over_100_bf16": (64, 32, 160, "bfloat16",
                                    "one_ignored", "rows", "spread"),
    "t_off_128": (100, 32, 256, "float32", "one_ignored", "mean",
                  "randn"),
    "v_off_128": (64, 32, 1000, "float32", "one_ignored", "mean",
                  "randn"),
    "h_under_a_lane_tile": (64, 8, 200, "float32", "plain", "mean",
                            "randn"),
    "cotangent_differs_by_row": (96, 32, 256, "float32", "third_ignored",
                                 "rows", "randn"),
    "bf16_cotangent_differs_by_row": (100, 64, 1000, "bfloat16",
                                      "third_ignored", "rows", "randn"),
}


@pytest.mark.parametrize("case", sorted(_RULE_CASES))
def test_differentiated_rule_matches_the_composition(case):
    """The hand-written rule of a differentiated call (the softmax's sum
    taken out of the dx matmul, `_exp_fwd` / `_exp_bwd`) against
    `_reference` differentiated by jax.vjp: loss, dx and dW. Under bf16
    inputs the oracle runs in f32 THROUGH the same bf16 operand values
    (ADVICE r5: the logits' gradient stays f32 through both matmuls,
    only the results narrow), so the rule is held to one final rounding."""
    import jax
    import jax.numpy as jnp
    t, h, v, dtype, labels, cotangent, inputs = _RULE_CASES[case]
    rs = np.random.RandomState(sorted(_RULE_CASES).index(case))
    dtype = jnp.dtype(dtype)
    if inputs == "spread":
        x, w = _spread_case(rs, t, h, v, dtype)
    else:
        x = jnp.asarray(rs.randn(t, h).astype(np.float32) * 0.3, dtype)
        w = jnp.asarray(rs.randn(v, h).astype(np.float32) * 0.3, dtype)
    lab_np = rs.randint(0, v, (t,))
    if labels == "one_ignored":
        lab_np[3] = -100
    elif labels == "third_ignored":
        lab_np[::3] = -100
    elif labels == "all_ignored":
        lab_np[:] = -100
    elif labels == "last_row":
        lab_np[::2] = v - 1
    elif labels == "past_end":
        lab_np[::4] = v + 5
    lab = jnp.asarray(lab_np.astype(np.int32))
    g = (jnp.full((t,), 1.0 / t, jnp.float32) if cotangent == "mean"
         else jnp.asarray(rs.rand(t).astype(np.float32)))

    loss, vjp = jax.vjp(
        lambda x_, w_: fused_ce._fused_core(x_, w_, lab, -100), x, w)
    dx, dw = vjp(g)
    assert dx.dtype == dtype and dw.dtype == dtype
    loss_r, vjp_r = jax.vjp(
        lambda x_, w_: fused_ce._reference(x_, w_, lab, -100),
        x.astype(jnp.float32), w.astype(jnp.float32))
    dx_r, dw_r = vjp_r(g)

    assert np.isfinite(np.asarray(loss)).all()
    if inputs == "spread":   # s == 1: the loss is max - logits[label]
        logits = np.asarray(x, np.float32) @ np.asarray(w, np.float32).T
        want = logits.max(-1) - logits[np.arange(t), np.clip(lab_np, 0, v - 1)]
        want[lab_np == -100] = 0.0
        np.testing.assert_array_equal(np.asarray(loss), want)
    if labels == "all_ignored":
        assert not np.asarray(loss).any()
        assert not np.asarray(dx).any() and not np.asarray(dw).any()
    rtol, atol = (2e-4, 2e-6) if dtype == jnp.float32 else (2e-2, 1e-5)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(loss_r),
                               rtol=2e-5 if dtype == jnp.float32 else 2e-3,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(dx, np.float32),
                               np.asarray(dx_r), rtol=rtol, atol=atol)
    np.testing.assert_allclose(np.asarray(dw, np.float32),
                               np.asarray(dw_r), rtol=rtol, atol=atol)


def test_only_a_differentiated_call_pays_the_second_matmul():
    """A forward-only call (evaluation) holds the logits' matmul alone;
    a differentiated one holds three (logits, `e @ [W | 1]` = dx and the
    softmax's sum, dW) and no pass of its own for the sum; the call the
    tape records for a backward is spelled like the rule's forward, so
    that XLA merges the two in the compiled step."""
    import jax
    import jax.numpy as jnp
    x = jnp.ones((16, 8), jnp.float32)
    w = jnp.ones((40, 8), jnp.float32)
    lab = jnp.zeros((16,), jnp.int32)

    def dots(fn):
        return str(jax.make_jaxpr(fn)(x, w)).count("dot_general")
    assert dots(lambda x_, w_: fused_ce._fused_core(
        x_, w_, lab, -100)) == 1
    assert dots(lambda x_, w_: fused_ce._fused_core(
        x_, w_, lab, -100, True)) == 2
    grad = jax.grad(lambda x_, w_: fused_ce._fused_core(
        x_, w_, lab, -100).sum(), argnums=(0, 1))
    text = str(jax.make_jaxpr(grad)(x, w))
    assert text.count("dot_general") == 3
    # no reduction over the vocabulary but the row maximum
    assert "reduce_max[axes=(1,)" in text
    assert "reduce_sum[axes=(1,)" not in text


def test_wrapper_tells_the_op_whether_the_tape_records(monkeypatch):
    """No option chooses the spelling: the wrapper reads whether the
    call will be differentiated from the grad mode and its operands."""
    seen = []
    orig = fused_ce._fused_op
    monkeypatch.setattr(
        fused_ce, "_fused_op",
        lambda *a, **k: seen.append(k["taped"]) or orig(*a, **k))
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(8, 16).astype(np.float32))
    w = paddle.to_tensor(rs.randn(32, 16).astype(np.float32))
    lab = paddle.to_tensor(rs.randint(0, 32, (8,)).astype(np.int64))
    a = fused_ce.fused_linear_cross_entropy(x, w, lab)
    w.stop_gradient = False
    b = fused_ce.fused_linear_cross_entropy(x, w, lab)
    with paddle.no_grad():
        fused_ce.fused_linear_cross_entropy(x, w, lab)
    assert seen == [False, True, False]
    np.testing.assert_allclose(a.numpy(), b.numpy(), rtol=1e-6)
    b.sum().backward()
    assert np.isfinite(w.grad.numpy()).all()


def test_fused_head_hardware_optin_policy(monkeypatch):
    """Policy pin (2026-08-02 perf finding): on a real accelerator the
    Pallas head is OPT-IN (PADDLE_FUSED_CE=1) — the XLA composition is
    the measured-fast default — and PADDLE_FUSED_CE_DISABLE=1 always
    wins. Interpret-forced tests are unaffected by the policy."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jnp.zeros((256, 128), jnp.float32)
    w = jnp.zeros((1024, 128), jnp.float32)
    monkeypatch.delenv("PADDLE_FUSED_CE", raising=False)
    monkeypatch.delenv("PADDLE_FUSED_CE_DISABLE", raising=False)
    assert not fused_ce._use_pallas(x, w)
    monkeypatch.setenv("PADDLE_FUSED_CE", "1")
    assert fused_ce._use_pallas(x, w)
    monkeypatch.setenv("PADDLE_FUSED_CE_DISABLE", "1")
    assert not fused_ce._use_pallas(x, w)


def test_gpt_head_uses_fused_and_trains():
    """GPT with a tied head routes through the fused op and the loss
    matches the unfused composition; one train step decreases it."""
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    paddle.seed(0)
    cfg = TransformerLMConfig(vocab_size=96, hidden_size=32,
                              num_layers=2, num_heads=2, max_seq_len=16,
                              dropout=0.0)
    model = GPTForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 96, (2, 16)).astype(np.int64))
    labels = paddle.to_tensor(rs.randint(0, 96,
                                         (2, 16)).astype(np.int64))
    loss_fused = model(ids, labels=labels)

    # unfused comparison: logits path + cross_entropy
    from paddle_tpu.ops import manipulation, nn_ops
    h = model.gpt(ids)
    logits = model._head_loss(h)  # labels=None -> logits
    loss_ref = nn_ops.cross_entropy(
        manipulation.reshape(logits, (-1, 96)),
        manipulation.reshape(labels, (-1,)))
    np.testing.assert_allclose(float(loss_fused.numpy()),
                               float(loss_ref.numpy()), rtol=1e-5)

    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
    l0 = None
    for _ in range(4):
        loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        l0 = l0 or float(loss.numpy())
    assert float(loss.numpy()) < l0


def test_gpt_head_ignore_index_mean_over_valid():
    """Review finding: the fused head must mean over NON-IGNORED tokens
    (cross_entropy reduction='mean' semantics), not over all tokens —
    a plain mean scales loss by the valid fraction on padded batches."""
    from paddle_tpu.ops import manipulation, nn_ops
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    paddle.seed(0)
    cfg = TransformerLMConfig(vocab_size=96, hidden_size=32,
                              num_layers=1, num_heads=2, max_seq_len=8,
                              dropout=0.0)
    model = GPTForCausalLM(cfg)
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(rs.randint(0, 96, (2, 8)).astype(np.int64))
    lab_np = rs.randint(0, 96, (2, 8))
    lab_np[:, 4:] = -100  # half the positions padded out
    labels = paddle.to_tensor(lab_np.astype(np.int64))

    loss_fused = model(ids, labels=labels)
    h = model.gpt(ids)
    logits = model._head_loss(h)
    loss_ref = nn_ops.cross_entropy(
        manipulation.reshape(logits, (-1, 96)),
        manipulation.reshape(labels, (-1,)))
    np.testing.assert_allclose(float(loss_fused.numpy()),
                               float(loss_ref.numpy()), rtol=1e-5)


def test_pallas_kernel_real_backend_parity(monkeypatch):
    """On a real accelerator backend this compiles the ACTUAL Mosaic
    kernels (the interpret tests above can't see Mosaic lowering
    issues); on CPU the gate routes to the reference path and the test
    still checks the public wrapper end to end. PADDLE_FUSED_CE=1
    because the kernels are opt-in on hardware since the 2026-08-02
    perf finding (see _use_pallas) — this test exists precisely to keep
    compiling them."""
    import jax
    monkeypatch.setenv("PADDLE_FUSED_CE", "1")
    rs = np.random.RandomState(3)
    t, h, v = 256, 128, 1024
    x = rs.randn(t, h).astype(np.float32) * 0.3
    w = rs.randn(v, h).astype(np.float32) * 0.3
    lab = rs.randint(0, v, (t,))
    xt = paddle.to_tensor(x)
    xt.stop_gradient = False
    wt = paddle.to_tensor(w)
    wt.stop_gradient = False
    out = fused_ce.fused_linear_cross_entropy(
        xt, wt, paddle.to_tensor(lab.astype(np.int64)))
    np.testing.assert_allclose(np.asarray(out.numpy()),
                               _reference_loss_np(x, w, lab),
                               rtol=3e-5, atol=3e-5)
    out.mean().backward()
    assert xt.grad is not None and wt.grad is not None
    assert np.isfinite(np.asarray(xt.grad.numpy())).all()


def _tp_mesh(dp, mp):
    import jax
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:dp * mp]).reshape(dp, mp)
    return Mesh(devs, ("dp", "mp"))


def test_tp_fused_loss_and_grads_match_unfused():
    """Vocab-sharded fused CE (shard_map over 'mp' with pmax/psum
    combine — the c_softmax_with_cross_entropy scheme) matches the
    single-device unfused composition: per-token loss and BOTH grads,
    ignore_index included, on the dp2 x mp4 mesh."""
    import jax
    import jax.numpy as jnp
    mesh = _tp_mesh(2, 4)
    rs = np.random.RandomState(4)
    t, h, v = 32, 16, 64
    x = jnp.asarray(rs.randn(t, h).astype(np.float32) * 0.3)
    w = jnp.asarray(rs.randn(v, h).astype(np.float32) * 0.3)
    lab_np = rs.randint(0, v, (t,))
    lab_np[7] = -100
    lab = jnp.asarray(lab_np.astype(np.int64))

    mesh_key = fused_ce._register_mesh(mesh)
    loss_tp = fused_ce._fused_tp_core(x, w, lab, mesh_key, -100)
    np.testing.assert_allclose(np.asarray(loss_tp),
                               _reference_loss_np(np.asarray(x),
                                                  np.asarray(w), lab_np),
                               rtol=2e-5, atol=2e-5)

    lab32 = lab.astype(jnp.int32)
    gx_f, gw_f = jax.grad(
        lambda x_, w_: fused_ce._fused_tp_core(
            x_, w_, lab, mesh_key, -100).mean(),
        argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(
        lambda x_, w_: fused_ce._reference(x_, w_, lab32, -100).mean(),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                               rtol=2e-4, atol=2e-6)


def test_tp_fused_pallas_interpret_parity(interpret_kernels):
    """The TP path with the PALLAS kernels forced (interpret mode)
    inside each shard: per-shard streaming tiles + cross-shard combine
    still match the unfused composition, loss and both grads."""
    import jax
    import jax.numpy as jnp
    mesh = _tp_mesh(2, 4)
    rs = np.random.RandomState(5)
    t, h, v = 256, 128, 4096          # local: [128, 128] x [1024, 128]
    x = jnp.asarray(rs.randn(t, h).astype(np.float32) * 0.3)
    w = jnp.asarray(rs.randn(v, h).astype(np.float32) * 0.3)
    lab_np = rs.randint(0, v, (t,))
    lab_np[11] = -100
    lab = jnp.asarray(lab_np.astype(np.int64))
    # the per-shard shapes must clear the pallas gate or this test
    # exercises nothing
    assert fused_ce._use_pallas(jnp.zeros((t // 2, h), jnp.float32),
                                jnp.zeros((v // 4, h), jnp.float32))

    mesh_key = fused_ce._register_mesh(mesh)
    loss_tp = fused_ce._fused_tp_core(x, w, lab, mesh_key, -100)
    np.testing.assert_allclose(np.asarray(loss_tp),
                               _reference_loss_np(np.asarray(x),
                                                  np.asarray(w), lab_np),
                               rtol=3e-5, atol=3e-5)

    lab32 = lab.astype(jnp.int32)
    gx_f, gw_f = jax.grad(
        lambda x_, w_: fused_ce._fused_tp_core(
            x_, w_, lab, mesh_key, -100).mean(),
        argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(
        lambda x_, w_: fused_ce._reference(x_, w_, lab32, -100).mean(),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                               rtol=3e-4, atol=3e-6)
    np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                               rtol=3e-4, atol=3e-6)


def test_gpt_mp_head_takes_fused_tp_path(monkeypatch):
    """GPT with mp>1 routes through the vocab-sharded fused head (the
    large-vocab configs that need TP keep the fused win) INSIDE the
    compiled step only: eager phases stay on one device (a shard_map
    there would make the whole lazily-fused eager step a multi-device
    program, which Mosaic kernels refuse on the chip). Loss parity vs
    the unfused composition, and it trains through it."""
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.ops import manipulation
    from paddle_tpu.text.models import (GPTForCausalLM,
                                        TransformerLMConfig)

    topology._HYBRID = None
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(0)
        cfg = TransformerLMConfig(vocab_size=128, hidden_size=32,
                                  num_layers=2, num_heads=2,
                                  max_seq_len=16, dropout=0.0,
                                  use_mp=True)
        inner = GPTForCausalLM(cfg)
        model = fleet.distributed_model(inner)
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(rs.randint(0, 128, (4, 16))
                               .astype(np.int64))
        labels = paddle.to_tensor(rs.randint(0, 128, (4, 16))
                                  .astype(np.int64))

        calls = []
        orig = fused_ce.fused_linear_cross_entropy_tp
        monkeypatch.setattr(
            fused_ce, "fused_linear_cross_entropy_tp",
            lambda *a, **k: calls.append(1) or orig(*a, **k))

        loss_eager = model(ids, labels=labels)
        assert not calls, "the eager head ran the mesh-wide shard_map"
        assert len(loss_eager.value.devices()) == 1
        # the fused TP head computes the same loss as that composition
        mesh = fleet.get_hybrid_communicate_group().mesh
        flat = manipulation.reshape(labels, (-1,))
        per_tok = orig(manipulation.reshape(inner.gpt(ids), (-1, 32)),
                       inner.gpt.word_embeddings.weight, flat, mesh)
        np.testing.assert_allclose(float(per_tok.mean().numpy()),
                                   float(loss_eager.numpy()), rtol=1e-5)

        opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
            1e-2, parameters=model.parameters()))

        @paddle.jit.to_static
        def train_step(ids, labels):
            loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        losses = []
        for i in range(4):
            losses.append(float(train_step(ids, labels).numpy()))
            assert bool(calls) == (i >= 2), \
                "fused TP head must be taken by the compiled step only"
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
    finally:
        topology._HYBRID = None


def test_gpt_recompute_matches_baseline():
    """cfg.recompute=True (per-block activation recompute) must produce
    the same training losses as the baseline up to XLA fusion
    reassociation — it only changes WHEN activations are computed."""
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig

    def run(recompute):
        paddle.seed(7)
        cfg = TransformerLMConfig(vocab_size=64, hidden_size=32,
                                  num_layers=2, num_heads=2,
                                  max_seq_len=16, dropout=0.0,
                                  recompute=recompute)
        m = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        rs = np.random.RandomState(0)
        ids = paddle.to_tensor(rs.randint(0, 64, (2, 16)).astype(np.int64))
        lab = paddle.to_tensor(rs.randint(0, 64, (2, 16)).astype(np.int64))
        losses = []
        for _ in range(3):
            loss = m(ids, labels=lab)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


def test_tp_fused_fuzz_shapes_and_labels():
    """Differential fuzz of the vocab-sharded combine math: random
    (t, h, v, mp, logits scale, ignore fraction) configs, labels forced
    onto shard boundaries (first/last row of a shard's tile) where
    off-by-one bugs in the local-index remap would hide. Loss and both
    grads vs the single-device composition every time."""
    import jax
    import jax.numpy as jnp
    rs = np.random.RandomState(99)
    for trial in range(8):
        mp = int(rs.choice([2, 4, 8]))
        dp = 8 // mp
        t = int(rs.choice([8, 16, 32]))
        h = int(rs.choice([8, 16]))
        v = mp * int(rs.choice([8, 16, 32]))
        scale = float(rs.choice([0.1, 3.0, 30.0]))  # 30: lse stability
        mesh = _tp_mesh(dp, mp)
        x = jnp.asarray(rs.randn(t, h).astype(np.float32) * scale)
        w = jnp.asarray(rs.randn(v, h).astype(np.float32) * scale)
        lab_np = rs.randint(0, v, (t,))
        vs = v // mp
        lab_np[0] = 0                   # first row, first shard
        lab_np[1] = vs - 1              # last row of shard 0
        lab_np[2] = vs                  # first row of shard 1
        lab_np[3] = v - 1               # last row, last shard
        if rs.rand() < 0.5:
            lab_np[4] = -100            # ignore_index
        lab = jnp.asarray(lab_np.astype(np.int64))
        mesh_key = fused_ce._register_mesh(mesh)

        loss_tp = fused_ce._fused_tp_core(x, w, lab, mesh_key, -100)
        ref = _reference_loss_np(np.asarray(x), np.asarray(w), lab_np)
        np.testing.assert_allclose(
            np.asarray(loss_tp), ref, rtol=2e-4, atol=2e-5,
            err_msg=f"trial {trial}: t={t} h={h} v={v} mp={mp} "
                    f"scale={scale}")

        lab32 = lab.astype(jnp.int32)
        gx_f, gw_f = jax.grad(
            lambda x_, w_: fused_ce._fused_tp_core(
                x_, w_, lab, mesh_key, -100).mean(),
            argnums=(0, 1))(x, w)
        gx_r, gw_r = jax.grad(
            lambda x_, w_: fused_ce._reference(
                x_, w_, lab32, -100).mean(),
            argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(gx_f), np.asarray(gx_r),
                                   rtol=2e-3, atol=2e-5,
                                   err_msg=f"trial {trial} dx")
        np.testing.assert_allclose(np.asarray(gw_f), np.asarray(gw_r),
                                   rtol=2e-3, atol=2e-5,
                                   err_msg=f"trial {trial} dw")


def test_tp_pallas_gate_defaults_on(monkeypatch):
    """ADVICE r5 (medium): on real hardware the vocab-sharded TP path
    keeps its own Pallas gate that defaults ON — the single-chip
    PADDLE_FUSED_CE=1 opt-in must NOT silently disable the TP kernel
    (whose win is the per-shard [T, V/mp] logits never materializing).
    PADDLE_FUSED_CE_TP=0 opts out; the global DISABLE kill still wins."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jnp.zeros((256, 128), jnp.float32)
    w = jnp.zeros((1024, 128), jnp.float32)
    for var in ("PADDLE_FUSED_CE", "PADDLE_FUSED_CE_TP",
                "PADDLE_FUSED_CE_DISABLE"):
        monkeypatch.delenv(var, raising=False)
    assert not fused_ce._use_pallas(x, w)        # single-chip: opt-in
    assert fused_ce._use_pallas(x, w, tp=True)   # TP shard: default ON
    monkeypatch.setenv("PADDLE_FUSED_CE_TP", "0")
    assert not fused_ce._use_pallas(x, w, tp=True)
    monkeypatch.delenv("PADDLE_FUSED_CE_TP")
    monkeypatch.setenv("PADDLE_FUSED_CE_DISABLE", "1")
    assert not fused_ce._use_pallas(x, w, tp=True)
