"""The program times its own waits and names its own device work.

Host side: the gateway's lock wait (``serving/submit_wait``,
``Request.t_received``), the drive loop around a step
(``serving/drive``, ``serving/drive_lock_wait``), the observers' tick
(``serving/health_tick``), the callers' callbacks
(``serving/on_token``), the prefill stamps on ``Request``, the
DataLoader (``io/next``) and ``to_static`` (``jit/*``); all through
``profiler.host_scope``, the span without the ``named_scope`` push.
Device side: every Pallas kernel under its own name and the model's
layers under ``jax.named_scope`` (HLO metadata only).
"""
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.observability import default_recorder
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.router.transport import EngineGateway
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig


def _model(seed=7, max_seq_len=64):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32, num_layers=2,
                              num_heads=4, max_seq_len=max_seq_len,
                              dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompt(rs, n):
    return rs.randint(0, 97, (n,)).astype(np.int64)


def _ring_since(t0, name=None, tid=None):
    return [s for s in default_recorder().spans()
            if s.t0 >= t0 and (name is None or s.name == name)
            and (tid is None or s.tid == tid)]


# ------------------------------------------------------------ host_scope
def test_host_scope_feeds_ring_registry_and_sink_without_named_scope():
    got = []
    t0 = time.perf_counter()

    def f(x):
        with profiler.host_scope("wait_test/host", sink=lambda n, dt:
                                 got.append((n, dt))):
            return x * 2.0
    text = jax.jit(f).lower(jnp.ones((4,))).as_text(debug_info=True)
    assert "wait_test/host" not in text
    (span,) = _ring_since(t0, "wait_test/host")
    assert got == [("wait_test/host", span.dur)]
    reg = paddle.observability.default_registry()
    calls = reg.get("host_span_calls_total").labels("wait_test/host")
    assert calls.value == 1


def test_record_scope_still_names_the_ops_staged_under_it():
    def f(x):
        with profiler.record_scope("wait_test/staged"):
            return x * 2.0
    text = jax.jit(f).lower(jnp.ones((4,))).as_text(debug_info=True)
    assert "wait_test/staged" in text


def test_host_scope_keeps_its_span_for_late_args():
    t0 = time.perf_counter()
    with profiler.host_scope("wait_test/late") as scope:
        pass
    scope.span.args = {"rid": 5}
    (span,) = _ring_since(t0, "wait_test/late")
    assert span is scope.span and span.args == {"rid": 5}


def _calls(name):
    reg = paddle.observability.default_registry()
    return reg.get("host_span_calls_total").labels(name).value


def test_a_dropped_host_scope_leaves_no_record():
    got = []
    t0 = time.perf_counter()
    with profiler.host_scope("wait_test/dropped",
                             sink=lambda n, dt: got.append(n)) as scope:
        scope.drop()
    assert scope.span is None and not got
    assert not _ring_since(t0, "wait_test/dropped")
    assert _calls("wait_test/dropped") == 0


def test_a_backdated_host_scope_counts_from_the_callers_stamp():
    t0 = time.perf_counter()
    time.sleep(0.02)
    with profiler.host_scope("wait_test/backdated", t0=t0) as scope:
        pass
    assert scope.span.t0 == t0 and scope.span.dur >= 0.02


def test_record_span_feeds_ring_registry_and_sink_from_stamps():
    got = []
    t0 = time.perf_counter()
    span = profiler.record_span("wait_test/by_hand", t0, 0.25,
                                sink=lambda n, dt: got.append((n, dt)))
    assert (span.name, span.t0, span.dur) == ("wait_test/by_hand", t0,
                                              0.25)
    assert _ring_since(t0, "wait_test/by_hand") == [span]
    assert got == [("wait_test/by_hand", 0.25)]
    assert _calls("wait_test/by_hand") == 1


# --------------------------------------------------------------- gateway
@pytest.fixture
def gateway():
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8,
                        block_size=8)
    gw = EngineGateway(eng)
    yield gw
    gw.close()


def _held_submit(gw, hold_s, prompt, max_new):
    """submit() from a thread of its own while this thread holds the
    gateway's lock for ``hold_s``; returns (request, submitter's tid)."""
    out = {}

    def offer():
        out["tid"] = threading.get_ident()
        out["req"] = gw.submit(prompt, max_new)

    with gw._lock:
        t = threading.Thread(target=offer)
        t.start()
        time.sleep(hold_s)
    t.join(10.0)
    assert gw.wait(out["req"], timeout=60.0)
    return out["req"], out["tid"]


def test_submit_wait_is_a_span_with_the_rid_and_part_of_ttft(gateway):
    rs = np.random.RandomState(0)
    t0 = time.perf_counter()
    req, tid = _held_submit(gateway, 0.05, _prompt(rs, 5), 3)
    assert req.t_received < req.t_arrival
    assert req.t_arrival - req.t_received >= 0.045
    (wait,) = _ring_since(t0, "serving/submit_wait", tid)
    assert wait.args == {"rid": req.rid}
    assert wait.dur == pytest.approx(req.t_arrival - req.t_received,
                                     abs=0.005)
    M = gateway.engine.metrics
    assert M.span_s["serving/submit_wait"] == pytest.approx(wait.dur)
    # the program's first-token time and latency count from received
    ttft = req.t_first_token - req.t_received
    assert M._h_ttft.count == 1
    assert M._h_ttft.sum == pytest.approx(ttft)
    assert M._h_ttft.sum >= 0.045 + (req.t_first_token - req.t_arrival)
    assert M._h_latency.sum == pytest.approx(req.t_done - req.t_received)
    # the queue alone still counts from arrival
    assert M._h_queue_wait.sum == pytest.approx(
        req.t_admitted - req.t_arrival)
    assert M._h_submit_wait.count == 1
    assert M._h_submit_wait.sum == pytest.approx(wait.dur)
    assert "serving_submit_wait_seconds" in M.prometheus_text()


def test_a_direct_add_request_is_received_as_it_arrives():
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    req = eng.add_request(_prompt(np.random.RandomState(1), 5), 2)
    assert req.t_received == req.t_arrival
    late = eng.add_request(_prompt(np.random.RandomState(1), 5), 2,
                           t_received=req.t_arrival - 1.0)
    assert late.t_arrival - late.t_received > 1.0
    # deadlines keep counting from arrival
    assert not late.past_deadline() and late.deadline_ms is None
    eng.close()


def test_prefill_hop_stamps_received_and_waits_under_the_same_span(
        gateway):
    t0 = time.perf_counter()
    out = gateway.prefill(_prompt(np.random.RandomState(2), 6))
    spans = _ring_since(t0, "serving/submit_wait")
    assert [s.args for s in spans] == [{"rid": out["rid"]}]


def test_drive_covers_step_and_health_tick_and_idles_silently(gateway):
    rs = np.random.RandomState(3)
    M = gateway.engine.metrics
    reqs = [gateway.submit(_prompt(rs, n), 6) for n in (5, 9, 7)]
    for r in reqs:
        assert gateway.wait(r, timeout=60.0)
    spans = M.span_s
    assert spans["serving/drive"] >= spans["serving/step"] \
        + spans["serving/health_tick"]
    assert spans["serving/drive_lock_wait"] <= spans["serving/drive"]
    assert spans["serving/health_tick"] > 0
    # an idle gateway writes nothing: no span of the drive loop starts
    # once the work is done
    time.sleep(0.05)
    t_idle = time.perf_counter()
    time.sleep(0.1)
    assert not [s for s in _ring_since(t_idle)
                if s.name.startswith("serving/")]
    # every iteration that stepped: one drive, one lock wait before it
    assert _calls("serving/drive") <= _calls("serving/step")
    assert _calls("serving/drive_lock_wait") == _calls("serving/drive")


def test_the_drivers_lock_wait_is_inside_its_drive_span(gateway):
    """The driver asks for the lock while this thread holds it: the
    wait is ``serving/drive_lock_wait`` and ``serving/drive`` counts
    from before it."""
    rs = np.random.RandomState(8)
    with gateway._lock:
        t0 = time.perf_counter()
        req = gateway.engine.add_request(_prompt(rs, 5), 2)
        gateway._wake.set()
        time.sleep(0.05)
    assert gateway.wait(req, timeout=60.0)
    wait = min(_ring_since(t0, "serving/drive_lock_wait"),
               key=lambda s: s.t0)
    drive = min(_ring_since(t0, "serving/drive"), key=lambda s: s.t0)
    assert wait.dur >= 0.04
    assert drive.t0 == wait.t0 and drive.t1 >= wait.t1
    step = min(_ring_since(t0, "serving/step"), key=lambda s: s.t0)
    assert wait.t1 <= step.t0 and step.t1 <= drive.t1


def test_one_on_token_span_per_harvest_that_delivered(decode_attention):
    """The callbacks of one harvest are charged to ONE span that lasts
    as long as all of them together; a request without a callback adds
    none; a callback runs where it always did, after its token was
    accounted and before the next token's."""
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8,
                        block_size=8)
    assert eng.decode_layout == decode_attention
    rs = np.random.RandomState(4)
    seen, done_at_call, n_generated, slept = [], [], [], []

    def on_token(req, tok):
        seen.append((req.rid, tok))
        done_at_call.append(req.done)
        n_generated.append(len(req.generated))
        t = time.perf_counter()
        time.sleep(0.002)
        slept.append(time.perf_counter() - t)   # a loaded host oversleeps

    t0 = time.perf_counter()
    a = eng.add_request(_prompt(rs, 5), 4, on_token=on_token)
    b = eng.add_request(_prompt(rs, 6), 4, on_token=on_token)
    eng.add_request(_prompt(rs, 7), 4)           # no callback
    eng.run()
    for r in (a, b):
        assert [t for rid, t in seen if rid == r.rid] == r.generated
    assert not any(done_at_call)
    assert sorted(n_generated) == [1, 1, 2, 2, 3, 3, 4, 4]
    spans = _ring_since(t0, "serving/on_token")
    harvests = _ring_since(t0, "serving/harvest")
    assert 3 <= len(spans) <= len(harvests)
    for s in spans:     # one in a harvest, and inside it
        inside = [h for h in harvests
                  if h.t0 <= s.t0 and s.t1 <= h.t1 + 1e-9]
        assert len(inside) == 1
    assert len({id(h) for s in spans for h in harvests
                if h.t0 <= s.t0 <= h.t1}) == len(spans)
    # 8 callbacks of 2 ms each (as long as each really slept), and
    # nothing but callbacks
    total = sum(s.dur for s in spans)
    assert len(slept) == 8 and sum(slept) >= 0.016
    assert sum(slept) <= total < sum(slept) + 0.008
    assert eng.metrics.span_s["serving/on_token"] == pytest.approx(total)
    t1 = time.perf_counter()
    eng.add_request(_prompt(rs, 5), 3)
    eng.run()
    assert not _ring_since(t1, "serving/on_token")
    eng.close()


def test_a_request_retires_before_the_next_ones_callback_runs():
    """The harvest's order is account, callback, retire, token by
    token: a later callback of the same dispatch sees the earlier
    request already done."""
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    rs = np.random.RandomState(9)
    others_done = []

    def on_token(req, tok):
        others_done.append((req.rid, [r.rid for r in reqs if r.done]))

    reqs = [eng.add_request(_prompt(rs, 5), 2, on_token=on_token)
            for _ in range(2)]
    eng.run()
    first, second = reqs
    assert (second.rid, [first.rid]) in others_done
    assert first.t_done < second.t_done
    eng.close()


# ----------------------------------------------------- prefill stamps
@pytest.mark.parametrize("block_size,chunk", [(8, None), (16, None),
                                              (8, 12), (16, 12)])
def test_prefill_stamps_equal_the_buckets_dispatched(block_size, chunk):
    kw = {"prefill_chunk": chunk} if chunk else {}
    eng = ServingEngine(_model(), num_slots=4, bucket_min=8,
                        block_size=block_size, **kw)
    rs = np.random.RandomState(5)
    lengths = (5, 9, 17, 30)
    t0 = time.perf_counter()
    reqs = [eng.add_request(_prompt(rs, n), 2) for n in lengths]
    for r in reqs:
        assert r.t_prefill_dispatched is None
        assert r.prefill_tokens_dispatched == 0
    eng.run()
    t1 = time.perf_counter()
    buckets = eng.scheduler.buckets
    for r, n in zip(reqs, lengths):
        if chunk and n > chunk:     # ceil(n / chunk) chunks of `chunk`
            want = -(-n // chunk) * chunk
        else:
            want = next(b for b in buckets if b >= n)
        assert r.prefill_tokens_dispatched == want, (n, buckets)
        assert t0 < r.t_admitted <= r.t_prefill_dispatched \
            < r.t_first_token < t1
    eng.close()


def test_a_rolled_back_admission_loses_its_prefill_stamps():
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8)
    req = eng.add_request(_prompt(np.random.RandomState(6), 5), 2)
    req.t_prefill_dispatched, req.prefill_tokens_dispatched = 1.0, 8
    eng.scheduler.queue.clear()
    eng.scheduler.rollback_admission([req], eng.pool)
    assert req.t_prefill_dispatched is None
    assert req.prefill_tokens_dispatched == 0
    eng.close()


# ----------------------------------------------------------- DataLoader
class _Rows(paddle.io.Dataset):
    def __len__(self):
        return 12

    def __getitem__(self, i):
        return np.full((3,), i, np.float32)


@pytest.mark.parametrize("workers", [0, 2])
def test_dataloader_leaves_one_io_next_span_per_batch(workers):
    loader = paddle.io.DataLoader(_Rows(), batch_size=4,
                                  num_workers=workers)
    tid = threading.get_ident()
    t0 = time.perf_counter()
    batches = list(loader)
    assert len(batches) == 3
    # the probe that found the epoch over left nothing
    assert [s.name for s in _ring_since(t0, tid=tid)
            if s.name.startswith("io/")] == ["io/next"] * 3


def test_an_abandoned_dataloader_iterator_closes_its_source():
    it = iter(paddle.io.DataLoader(_Rows(), batch_size=4))
    t0 = time.perf_counter()
    next(it)
    it.close()
    assert len(_ring_since(t0, "io/next")) == 1


# ------------------------------------------------------------ to_static
def test_to_static_phases_and_one_enqueue_inside_every_call():
    paddle.seed(0)
    lin = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(0.1, parameters=lin.parameters())

    @paddle.jit.to_static
    def step(x):
        loss = lin(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    tid = threading.get_ident()
    t0 = time.perf_counter()
    for _ in range(5):
        step(x)
    names = [s.name for s in sorted(_ring_since(t0, tid=tid),
                                    key=lambda s: s.t0)
             if s.name.startswith("jit/")]
    assert names == ["jit/eager", "jit/record"] \
        + ["jit/call", "jit/enqueue"] * 3
    calls = _ring_since(t0, "jit/call", tid)
    enqueues = _ring_since(t0, "jit/enqueue", tid)
    for c, e in zip(calls, enqueues):
        assert c.t0 <= e.t0 and e.t1 <= c.t1


# ---------------------------------------------------------- device names
def _interpreted(module):
    module._FORCE_INTERPRET[0] = True
    try:
        yield
    finally:
        module._FORCE_INTERPRET[0] = False


@pytest.fixture
def flash_interpreted():
    from paddle_tpu.ops import attention
    yield from _interpreted(attention)


@pytest.fixture
def ce_interpreted():
    from paddle_tpu.ops import fused_ce
    yield from _interpreted(fused_ce)


@pytest.fixture
def paged_interpreted():
    from paddle_tpu.ops import paged_attention
    yield from _interpreted(paged_attention)


# one tile holds a sequence of 128: one backward kernel; 384 is three
# tiles of 128: the backward's two kernels
@pytest.mark.parametrize("seq,name", [(128, "flash_fwd"),
                                      (128, "flash_bwd_dqkv"),
                                      (384, "flash_bwd_dq"),
                                      (384, "flash_bwd_dkv")])
def test_flash_kernels_carry_their_names(flash_interpreted, seq, name):
    from paddle_tpu.ops import attention
    q = jnp.ones((1, 2, seq, 64), jnp.float32)

    def loss(q, k, v):
        return attention._flash_attention_core(q, k, v, 0.125, True).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    assert re.search(rf"\b{name}\b", text), text[:2000]


@pytest.mark.parametrize("name", ["fused_ce_fwd", "fused_ce_bwd_dx",
                                  "fused_ce_bwd_dw"])
def test_fused_ce_kernels_carry_their_names(ce_interpreted, name):
    from paddle_tpu.ops import fused_ce
    x = jnp.ones((128, 128), jnp.float32)
    w = jnp.ones((1024, 128), jnp.float32)
    lab = jnp.zeros((128,), jnp.int32)

    def loss(x, w):
        return fused_ce._fused_core(x, w, lab, -100).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, w))
    assert name in text, text[:2000]


def test_paged_decode_kernel_carries_its_name(paged_interpreted):
    from paddle_tpu.ops import paged_attention
    q = jnp.ones((2, 4, 8), jnp.float32)
    cache = jnp.ones((5, 4, 8, 8), jnp.float32)
    tables = jnp.zeros((2, 2), jnp.int32)
    lengths = jnp.ones((2,), jnp.int32)
    text = str(jax.make_jaxpr(paged_attention.paged_decode_attention)(
        q, cache, cache, tables, lengths))
    assert "paged_decode_attn" in text


def _has_scope(lowered_text, scope):
    """``scope`` as whole components of some op's name stack (the ops
    of a scan body start theirs at the body)."""
    return re.search(r'["/]' + re.escape(scope) + r'["/]',
                     lowered_text) is not None


@pytest.fixture(scope="module")
def gpt_step_text():
    """The lowered text of a compiled to_static step (forward + loss +
    backward): eager ops are replayed lazily, outside any scope, so the
    names show where the step is staged, as in training."""
    from paddle_tpu.jit.to_static import captured_arrays
    m = _model()
    m.train()

    @paddle.jit.to_static
    def step(ids, labels):
        loss = m(ids, labels=labels)
        loss.backward()
        return loss

    ids = paddle.to_tensor(np.zeros((2, 16), np.int64))
    for _ in range(3):
        step(ids, ids)
    (entry,) = step.entries.values()
    c = entry["compiled"]
    return c["jitted"].lower([ids.value, ids.value],
                             *captured_arrays(c)).as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["embed", "block/attn", "block/mlp",
                                   "lm_head", "loss"])
def test_gpt_step_names_its_layers_in_the_lowered_program(gpt_step_text,
                                                          scope):
    assert _has_scope(gpt_step_text, scope)


@pytest.mark.parametrize("program,scope", [
    ("decode", "embed"), ("decode", "attn/kv_write"),
    ("decode", "attn/kv_gather"), ("decode", "mlp"),
    ("decode", "lm_head"), ("decode", "sample"),
    ("prefill", "kv_gather"), ("prefill", "attn/kv_write"),
    ("prefill", "mlp"), ("prefill", "lm_head"), ("prefill", "sample")])
def test_paged_programs_name_their_stages(program, scope):
    eng = ServingEngine(_model(), num_slots=2, bucket_min=8,
                        block_size=8)
    try:
        eng.add_request(_prompt(np.random.RandomState(8), 5), 2)
        seen = {}
        real = eng._compiled

        def spy(key, fn, args, donate=()):
            seen.setdefault(key[0], (fn, args))
            return real(key, fn, args, donate=donate)
        eng._compiled = spy
        eng.run()
    finally:
        eng.close()
    fn, args = seen["paged_prefill" if program == "prefill"
                    else "decode"]
    assert _has_scope(jax.jit(fn).lower(*args).as_text(debug_info=True),
                      scope)
