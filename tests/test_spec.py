"""Self-drafting speculative decoding (paddle_tpu.serving.spec): the
n-gram drafter as a pure unit (determinism, bounded memory under
adversarial streams, fixed-shape padding), the acceptance property
(drafts that agree with the model's greedy choice are totally
accepted), and the engine contract — greedy streams with speculation
ON bit-exact with generate() and with speculation OFF, on BOTH pools,
sync and pipelined, under a raise-mode compile watchdog (zero steady-
state compiles with two interchangeable decode programs)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import NGramDrafter, ServingEngine
from paddle_tpu.serving.spec import SpecDecoder
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig


def _model(seed=7, max_seq_len=96, num_layers=2):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=num_layers, num_heads=4,
                              max_seq_len=max_seq_len, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _ref(m, prompt, n_new):
    out = m.generate(paddle.to_tensor(prompt[None]),
                     max_new_tokens=n_new, temperature=0.0)
    return np.asarray(out.numpy())[0]


def _prompts(rs, lengths):
    return [rs.randint(0, 97, (n,)).astype(np.int64) for n in lengths]


# --------------------------------------------------------- drafter unit

def test_drafter_rejects_bad_width():
    with pytest.raises(ValueError):
        NGramDrafter(0)
    with pytest.raises(ValueError):
        NGramDrafter(4, ngram_max=1, ngram_min=2)


def test_drafter_deterministic_proposals():
    """Identical token streams yield identical proposals — the chaos
    sweep's bit-exact replay depends on this."""
    rs = np.random.RandomState(3)
    stream = list(rs.randint(0, 12, (200,)))
    props = []
    for _ in range(2):
        d = NGramDrafter(4)
        got = []
        for i in range(8, len(stream)):
            d.sync(0, "r1", stream[:i])
            got.append(tuple(d.propose(0)))
        props.append(got)
    assert props[0] == props[1]
    assert any(p for p in props[0])   # a 12-symbol stream repeats


def test_drafter_proposes_continuation_of_prior_occurrence():
    """Prompt-lookup semantics: the proposal is the k tokens that
    followed the most recent PRIOR occurrence of the context's suffix
    n-gram."""
    d = NGramDrafter(3)
    d.sync(0, "r1", [1, 2, 3, 9, 8, 7, 1, 2, 3])
    assert d.propose(0) == [9, 8, 7]
    # width cap: a finishing request drafts fewer
    assert d.propose(0, width=2) == [9, 8]
    assert d.propose(0, width=0) == []


def test_drafter_bounded_memory_adversarial():
    """An adversarial all-unique stream (no n-gram ever repeats) can
    not grow the per-slot index past max_entries, and churning many
    distinct prompts cannot grow the shared index past its cap."""
    d = NGramDrafter(4, max_entries=64, shared_entries=128)
    # unique-ish ngrams: strictly increasing values
    d.sync(0, "r1", list(range(10_000)))
    sizes = d.index_sizes()
    assert sizes[0] <= 64
    assert d.propose(0) == []          # nothing repeats, nothing drafts
    # prompt churn: every new rid re-binds the slot and feeds the
    # shared index; both the LRU and the fingerprint set stay capped
    for i in range(300):
        prompt = [(i * 31 + j) % 9973 for j in range(24)]
        d.sync(0, f"r{i}", prompt)
    sizes = d.index_sizes()
    assert sizes["shared"] <= 128
    assert sizes["seen_prompts"] <= 128
    assert len(d._slots) == 1          # rebinding never leaks slots


def test_drafter_shared_prompt_index_radix_sharing():
    """Radix-style sharing: a SECOND request with the same prompt
    drafts from the first's statistics immediately — before it has
    generated anything of its own."""
    d = NGramDrafter(4)
    prompt = [5, 6, 7, 8, 5, 6, 7, 8, 5, 6]
    d.sync(0, "r1", prompt)
    # a different slot, different rid, same (shared) prompt: its own
    # index only has the prompt too, but the lookup that matters for a
    # fresh request — the prompt suffix — hits the shared entries
    d.sync(1, "r2", prompt)
    assert d.propose(1) == [7, 8, 5, 6]
    # exact-repeat prompts skip re-indexing (fingerprint dedupe)
    assert d.index_sizes()["seen_prompts"] == 1


def test_spec_decoder_fixed_shapes_and_padding():
    """propose() always returns the fixed [S, k] / [S] arrays the AOT
    verify program needs, zero-padded past each slot's real draft."""

    class _R:
        def __init__(self, rid, ids, gen, max_new):
            self.rid, self.prefill_ids = rid, ids
            self.generated = gen
            self.max_new_tokens = max_new
            self.inflight = 0

    sd = SpecDecoder(4, 4, 0.3)
    rep = [1, 2, 3, 1, 2, 3, 1, 2]
    reqs = {0: _R("a", rep + [3], [3], 16),
            2: _R("b", [9, 8, 7], [7], 16),     # nothing to look up
            3: _R("c", rep + [3], [3], 3)}      # width-capped to 1
    drafts, dlen, drafted = sd.propose(reqs)
    assert drafts.shape == (4, 4) and drafts.dtype == np.int32
    assert dlen.shape == (4,) and dlen.dtype == np.int32
    assert dlen[1] == 0 and dlen[2] == 0       # empty slot / no match
    assert dlen[0] == drafted[0] > 0
    assert (drafts[0, dlen[0]:] == 0).all()    # zero padding
    assert dlen[3] <= 1                        # remaining-1 width cap
    # a slot with an in-flight token never drafts (misalignment guard)
    reqs[0].inflight = 1
    _, dlen2, drafted2 = sd.propose(reqs)
    assert dlen2[0] == 0 and 0 not in drafted2


def test_spec_decoder_ewma_gate_and_bound():
    sd = SpecDecoder(4, 4, min_accept=0.5, ewma_alpha=0.5)
    assert sd.acceptance_ewma("r") == 1.0      # optimistic seed
    sd.observe("r", 4, 0)                      # 1.0 -> 0.5
    sd.observe("r", 4, 0)                      # 0.5 -> 0.25
    assert sd.acceptance_ewma("r") < 0.5
    # bounded LRU: churning rids cannot grow the table unboundedly
    for i in range(5000):
        sd.observe(f"x{i}", 4, 2)
    assert len(sd._ewma) <= 4096


# ------------------------------------------- engine config validation

def test_config_rejects_bad_spec_knobs():
    m = _model()
    with pytest.raises(ValueError):
        ServingEngine(m, num_slots=2, speculative=True, spec_k=0)
    with pytest.raises(ValueError):
        ServingEngine(m, num_slots=2, speculative=True,
                      spec_min_accept=1.5)
    with pytest.raises(ValueError):
        ServingEngine(m, num_slots=2, speculative=True, sampling=True)


def test_spec_env_gate(monkeypatch):
    m = _model()
    monkeypatch.setenv("PADDLE_SPEC_DECODE", "1")
    eng = ServingEngine(m, num_slots=2)
    assert eng.speculative is True
    monkeypatch.setenv("PADDLE_SPEC_DECODE", "0")
    eng = ServingEngine(m, num_slots=2)
    assert eng.speculative is False
    assert eng.metrics.snapshot()["perf"]["spec"]["enabled"] is False


# ----------------------------------------------------- engine parity

@pytest.mark.parametrize("async_depth", [0, 1])
def test_spec_parity_with_generate(decode_attention, async_depth):
    """THE contract: greedy streams with speculation ON are bit-exact
    with per-request generate() (and hence with speculation OFF) on
    both schedules, whichever attention the plain decode program (the
    step where nobody drafts) runs — with watchdog_mode="raise", so a
    single steady-state compile in the two-program schedule fails
    loudly, and a SECOND post-warmup wave proves it stays warm."""
    m = _model()
    rs = np.random.RandomState(0)
    prompts = _prompts(rs, (5, 9, 13, 7, 21, 6))
    n_new = 24
    refs = [_ref(m, p, n_new) for p in prompts]
    eng = ServingEngine(m, num_slots=4, bucket_min=8,
                        async_depth=async_depth, speculative=True,
                        spec_k=4, watchdog_mode="raise")
    assert eng.decode_layout == decode_attention
    reqs = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    eng.run()
    for r, ref in zip(reqs, refs):
        assert np.array_equal(np.asarray(r.output_ids), ref)
    eng.declare_warmup()
    reqs = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    eng.run()
    for r, ref in zip(reqs, refs):
        assert np.array_equal(np.asarray(r.output_ids), ref)
    spec = eng.metrics.snapshot()["perf"]["spec"]
    assert spec["enabled"] is True and spec["k"] == 4
    assert spec["verify_steps"] > 0
    assert spec["drafted_tokens"] == \
        spec["accepted_tokens"] + spec["rejected_tokens"]
    assert spec["effective_tokens_per_dispatch"] >= 1.0


class _OracleDrafter:
    """Proposes the model's TRUE greedy continuation (precomputed):
    every draft agrees with the verify argmax by construction."""

    def __init__(self, k, refs):
        self.k = k
        self.max_entries = 0
        self.shared_entries = 0
        self._refs = [list(int(t) for t in r) for r in refs]
        self._ctx = {}

    def sync(self, slot, rid, tokens):
        self._ctx[slot] = [int(t) for t in tokens]

    def propose(self, slot, width=None):
        toks = self._ctx[slot]
        w = self.k if width is None else min(self.k, int(width))
        for ref in self._refs:
            if len(ref) > len(toks) and ref[:len(toks)] == toks:
                return ref[len(toks):len(toks) + w]
        return []


def test_greedy_agreeing_drafts_totally_accepted(decode_attention):
    """Acceptance property: when every drafted token equals the
    model's greedy choice, the verify program accepts ALL of them —
    zero rejections, and each verify leg yields its full draft + the
    bonus token."""
    m = _model()
    rs = np.random.RandomState(1)
    prompts = _prompts(rs, (5, 9, 12))
    n_new = 12
    refs = [_ref(m, p, n_new) for p in prompts]
    eng = ServingEngine(m, num_slots=4, bucket_min=8,
                        speculative=True, spec_k=4,
                        watchdog_mode="raise")
    assert eng.decode_layout == decode_attention
    eng._spec.drafter = _OracleDrafter(4, refs)
    reqs = [eng.add_request(p, max_new_tokens=n_new) for p in prompts]
    eng.run()
    for r, ref in zip(reqs, refs):
        assert np.array_equal(np.asarray(r.output_ids), ref)
    spec = eng.metrics.snapshot()["perf"]["spec"]
    assert spec["drafted_tokens"] > 0
    assert spec["rejected_tokens"] == 0
    assert spec["acceptance_rate"] == 1.0
    # full acceptance: each drafting leg emits k+1 (width caps only
    # near max_new), so amortization approaches k+1 per slot-leg
    assert spec["effective_tokens_per_dispatch"] >= 3.0


def test_spec_off_engine_unchanged():
    """A default engine carries no spec machinery and the same greedy
    streams as ever (the OFF arm of the A/B)."""
    m = _model()
    rs = np.random.RandomState(2)
    prompts = _prompts(rs, (5, 9))
    refs = [_ref(m, p, 10) for p in prompts]
    eng = ServingEngine(m, num_slots=2, bucket_min=8)
    assert eng.speculative is False and eng._spec is None
    reqs = [eng.add_request(p, max_new_tokens=10) for p in prompts]
    eng.run()
    for r, ref in zip(reqs, refs):
        assert np.array_equal(np.asarray(r.output_ids), ref)
    spec = eng.metrics.snapshot()["perf"]["spec"]
    assert spec["enabled"] is False and spec["verify_steps"] == 0


def test_spec_flight_recorder_events():
    """Verify outcomes land in the request's flight trace as
    draft_accepted / draft_rejected events."""
    m = _model()
    rs = np.random.RandomState(0)
    prompts = _prompts(rs, (5, 9, 13))
    refs = [_ref(m, p, 16) for p in prompts]
    eng = ServingEngine(m, num_slots=4, bucket_min=8, speculative=True,
                        spec_k=4)
    eng._spec.drafter = _OracleDrafter(4, refs)
    reqs = [eng.add_request(p, max_new_tokens=16) for p in prompts]
    eng.run()
    trace = eng.request_trace(reqs[0].rid)
    events = [e["event"] for e in trace.as_dict()["events"]]
    assert "draft_accepted" in events


def test_spec_k_must_fit_cache():
    m = _model(max_seq_len=8)
    with pytest.raises(ValueError):
        ServingEngine(m, num_slots=2, bucket_min=8, speculative=True,
                      spec_k=8)
