"""The ``ouro`` family (a LOOPED language model: one stack of layers run
``total_ut_steps`` times over the same weights, a cache entry for every
pass of every layer, an exit gate after each pass) on the served path,
at small sizes on the CPU: the eager model, ``generate()`` and prefill +
decode through ``ServingEngine`` (one bucket, chunked prefill across a
block edge, a second request that shares a cached prefix through the
radix index) against the plain reference
(``benchmarks/reference/ouro.py``) on seeded weights, at 1, 2 and 4
passes; what the cache spec and the pool count; the exit rule; every
refusal by name; the Pallas arm in interpret mode against the ``jnp``
arm; the decode programs of the models whose access objects this one
shares, shown unchanged.

Tolerances. Everything here is float32 on both sides, so what differs is
the order of additions (blocked attention): logits of magnitude ~1 agree
to a few 1e-6; ``TOL`` = 2e-4 leaves room for other BLAS builds.
"""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import weights_ouro as W  # noqa: E402
from benchmarks.reference import ouro as ref  # noqa: E402
from paddle_tpu.ops import paged_attention as pa  # noqa: E402
from paddle_tpu.serving import ServingEngine  # noqa: E402
from paddle_tpu.text import ouro as oo  # noqa: E402

TOL = 2e-4
HF = dict(head_dim=16, hidden_act="silu", hidden_size=64,
          intermediate_size=96, layer_types=["full_attention"] * 3,
          max_position_embeddings=512, max_window_layers=3,
          model_type="ouro", num_attention_heads=4, num_hidden_layers=3,
          num_key_value_heads=4, rms_norm_eps=1e-6, rope_scaling=None,
          rope_theta=1000000, sliding_window=None,
          tie_word_embeddings=False, total_ut_steps=4,
          early_exit_threshold=1, use_sliding_window=False,
          vocab_size=128)
PASSES = [1, 2, 4]


def _weights(hf, seed=3):
    """The benchmark's seeded weights with what it draws as 1 and 0
    PERTURBED: every norm's gain, the gate's bias, and a gate wide
    enough that the passes' masses differ: a path that drops or swaps a
    gain, or leaves the bias out, cannot agree with the reference."""
    w = W.make(seed, hf, "float32")
    rng = np.random.default_rng(seed + 100)

    def shaken(a):
        return a * jnp.asarray(1 + 0.1 * rng.standard_normal(a.shape),
                               a.dtype)
    for n in ("n1", "n2", "n3", "n4"):
        w["layers"][n] = shaken(w["layers"][n])
    w["norm_f"] = shaken(w["norm_f"])
    w["w_gate"] = w["w_gate"] * 20
    w["b_gate"] = jnp.asarray([0.3], jnp.float32)
    return w


def _model(passes=4, seed=3, **over):
    hf = dict(HF, total_ut_steps=passes, **over)
    if "num_hidden_layers" in over:
        hf["layer_types"] = ["full_attention"] * over["num_hidden_layers"]
    w = _weights(hf, seed)
    cfg = oo.OuroConfig.from_hf(hf, dtype="float32")
    return oo.OuroForCausalLM(cfg, weights=w), w, hf


def _ref_logits(w, ids, hf):
    return np.asarray(ref.logits(w, jnp.asarray(ids, jnp.int32), hf)[0])


@pytest.fixture(scope="module", params=PASSES,
                ids=[f"passes{r}" for r in PASSES])
def model_w(request):
    return _model(request.param)


# ------------------------------------------------------ the whole model
def test_eager_logits_match_reference(model_w):
    m, w, hf = model_w
    ids = np.random.default_rng(0).integers(0, 128, size=(2, 40))
    got = np.asarray(m.forward(ids).value)
    assert got.shape == (2, 40, 128) and got.dtype == np.float32
    for b in range(2):
        assert np.abs(got[b] - _ref_logits(w, ids[b], hf)).max() < TOL


def test_generate_matches_reference(model_w):
    """Prefill + decode over the contiguous cache of ``passes x layers``
    entries: every generated token is the reference's best at its
    position, by the logit gap."""
    m, w, hf = model_w
    ids = np.random.default_rng(1).integers(0, 128, size=(2, 20))
    out = np.asarray(m.generate(ids, max_new_tokens=10).value)
    assert out.shape == (2, 30) and (out[:, :20] == ids).all()
    for b in range(2):
        lg = _ref_logits(w, out[b, :-1], hf)
        at = lg[np.arange(19, 29), out[b, 20:]]
        assert (lg[19:].max(-1) - at).max() < TOL


# ----------------------------------------------------- through the engine
def _drive(engine, prompts, new):
    reqs = [engine.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    engine.run()
    return reqs


def _served_gap(w, prompt, req, hf):
    served = np.asarray(req.generated)
    seq = np.concatenate([prompt, served])
    lg = _ref_logits(w, seq[:-1], hf)
    at = lg[np.arange(len(prompt) - 1, len(seq) - 1), served]
    return (lg[len(prompt) - 1:].max(-1) - at).max()


@pytest.mark.parametrize("chunk", [None, 16], ids=["whole", "chunk16"])
def test_paged_prefill_and_decode_match_reference(model_w, chunk):
    """Through ``ServingEngine`` over the paged pool of ``passes x
    layers`` entries a position: three slots, six requests of uneven
    lengths, so slots are released and taken again and released slots
    keep stepping meanwhile; with ``prefill_chunk`` the long prompts
    prefill chunk by chunk ACROSS BLOCK EDGES (blocks of 8, chunks of
    16, a prompt of 45 ends mid-chunk and mid-block), every pass of a
    chunk run before the next chunk, their slots parked through the
    decode steps in between; the LAST request repeats the first one's
    first 24 tokens and is served its three blocks from the radix index
    (every pass's entries of them). Every served token is the
    reference's best at its position, by the logit gap that ``correct``
    reads on the chip."""
    m, w, hf = model_w
    R = hf["total_ut_steps"]
    eng = ServingEngine(m, num_slots=3, block_size=8, max_len=96,
                        buckets=[16, 32] if chunk is None else [16],
                        prefill_chunk=chunk)
    rng = np.random.default_rng(1)
    lens = (30, 17, 9, 5, 12 if chunk is None else 45)
    new = (26, 29, 24, 32, 20, 9)
    prompts = [rng.integers(0, 128, size=n) for n in lens]
    prompts.append(np.concatenate([prompts[0][:24],
                                   rng.integers(0, 128, size=7)]))
    reqs = _drive(eng, prompts, new)
    assert eng.pool.reuse_count >= 2          # released slots came back
    for p, r, k in zip(prompts, reqs, new):
        assert len(r.generated) == k
        assert _served_gap(w, p, r, hf) < TOL
    snap = eng.metrics.snapshot()
    assert snap["prefix_cache"]["hits"] >= 1
    assert snap["prefix_cache"]["cached_tokens"] == 24
    # the loop's counters: every decoded token ran every pass and was
    # read from the last; the exit distribution's mass adds up to them
    loop = snap["loop"]
    tokens = sum(loop["exit_pass"])
    assert loop["passes"] == loop["cache_passes"] == R
    assert loop["exit_pass"][:-1] == [0] * (R - 1) and tokens > 0
    assert loop["passes_run"] == R * tokens
    assert abs(sum(loop["gate_mass"]) - tokens) < 1e-3 * tokens
    if R > 1:
        assert 0 < loop["gate_mass"][0] < tokens
    text = eng.metrics.prometheus_text()
    per_token = R * 3 * 2 * 4 * 16 * 4   # passes x layers x (k, v) f32
    assert f"serving_kv_bytes_per_token {per_token}" in text
    assert f"serving_cache_passes {R}" in text
    assert f"serving_loop_passes_total {R * tokens}" in text
    assert f'serving_loop_exit_pass{{pass="{R - 1}"}} {tokens}' in text


def test_deep_pipeline(model_w):
    """``async_depth`` steps of results unread (the benchmark cell keeps
    4 in flight): a slot is released and prefilled again while older
    steps that still name it are queued on the device."""
    m, w, hf = model_w
    eng = ServingEngine(m, num_slots=2, block_size=8, max_len=96,
                        buckets=[16, 32], async_depth=4)
    rng = np.random.default_rng(5)
    lens, new = (5, 17, 9, 30, 12), (16, 19, 14, 22, 17)
    prompts = [rng.integers(0, 128, size=n) for n in lens]
    reqs = [eng.add_request(p, max_new_tokens=k)
            for p, k in zip(prompts, new)]
    deepest = 0
    while eng.step():
        deepest = max(deepest, len(eng._pending_steps))
    assert deepest == 4 and not eng._pending
    for p, r in zip(prompts, reqs):
        assert _served_gap(w, p, r, hf) < TOL


def test_sampling_program_runs_and_repeats():
    m, _, _ = _model(2)
    outs = []
    for _ in range(2):
        eng = ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                            buckets=[16], sampling=True)
        r = eng.add_request(np.arange(9) % 128, max_new_tokens=8,
                            temperature=0.9, top_k=20, seed=7)
        eng.run()
        outs.append(list(r.generated))
    assert outs[0] == outs[1] and len(outs[0]) == 8


# ------------------------------------------------- what the spec counts
def test_cache_layers_are_passes_times_weight_layers(model_w):
    """The pool has ``total_ut_steps x num_hidden_layers`` cache layers
    over ``num_hidden_layers`` weight layers, a token costs that many
    (k, v) pairs, and ONE block table a slot reaches them all."""
    from paddle_tpu.serving.paged import PagedKVPool
    m, _, hf = model_w
    R, L = hf["total_ut_steps"], hf["num_hidden_layers"]
    spec = m.cache_spec()
    assert [a.name for a in spec.arrays] == ["k", "v"]
    assert [a.layers for a in spec.arrays] == [R * L] * 2
    assert spec.num_layers == R * L and m.cfg.num_layers == L
    assert spec.shareable and spec.window is None and spec.ring is None
    assert spec.bytes_per_token == R * L * 2 * 4 * 16 * 4
    assert spec.bytes_per_slot == 0
    assert [(n, s) for n, s, _ in spec.state] == [
        ("loop_counts", (R + 1,)), ("loop_gate_mass", (R,))]
    pool = PagedKVPool(3, max_len=64, block_size=8, spec=spec)
    assert [a.shape for a in pool.arrays] == [(R * L, 25, 4, 8, 16)] * 2
    assert pool.device_tables().shape == (3, 8)
    assert m.export_decode_params()["layers"]["wqkv"].shape == (L, 64, 192)
    eng = ServingEngine(m, num_slots=3, block_size=8, max_len=64,
                        buckets=[16])
    # not the GPT's pair: no KV wire, no analytic decode model of it
    assert not eng._kv_pair
    with pytest.raises(NotImplementedError, match="KV wire"):
        eng.add_request(np.arange(5), max_new_tokens=2, hold_kv=True)


# ------------------------------------------------------------ the exit
def test_exit_distribution_sums_to_one_and_threshold_one_reads_the_last():
    g = jnp.asarray(np.random.default_rng(0).uniform(0.05, 0.95, (4, 7)),
                    jnp.float32)
    p = oo.exit_distribution(g)
    want = np.asarray(ref.exit_distribution(g))
    assert np.abs(np.asarray(p) - want).max() < 1e-6
    assert np.abs(np.asarray(p).sum(0) - 1).max() < 1e-6
    assert (np.asarray(oo.exit_pass(p, 1.0)) == 3).all()
    # under 1 a token leaves at the first pass whose cumulative mass
    # reaches the threshold
    cum = np.cumsum(np.asarray(p), 0)
    got = np.asarray(oo.exit_pass(p, 0.6))
    assert (got == np.minimum((cum < 0.6).sum(0), 3)).all()
    assert 0 < got.min() + 1 and got.max() <= 3 and len(set(got)) > 1
    one = oo.exit_distribution(g[:1])
    assert (np.asarray(one) == 1).all()
    assert (np.asarray(oo.exit_pass(one, 1.0)) == 0).all()


def test_eager_forward_reads_each_token_from_its_exit_pass():
    """``early_exit_threshold`` 0.5: the eager forward (no cache, so no
    entry is owed to anybody) reads a token from the first pass whose
    cumulative mass reaches it, as the reference does; the model's
    ``exit_distribution`` is the reference's."""
    m, w, hf = _model(4, early_exit_threshold=0.5)
    ids = np.random.default_rng(2).integers(0, 128, size=(1, 32))
    want, p, at = ref.logits(w, jnp.asarray(ids[0], jnp.int32), hf)
    assert len(set(np.asarray(at).tolist())) > 1      # passes DO differ
    assert np.abs(np.asarray(m.forward(ids).value)[0]
                  - np.asarray(want)).max() < TOL
    assert np.abs(np.asarray(m.exit_distribution(ids))[:, 0]
                  - np.asarray(p)).max() < 1e-5


# ------------------------------------------------------------- refusals
def test_a_threshold_under_one_is_refused_by_the_cached_paths():
    m, _, _ = _model(2, early_exit_threshold=0.5)
    with pytest.raises(NotImplementedError, match="early exit"):
        ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                      buckets=[16])
    with pytest.raises(NotImplementedError, match="early_exit_threshold"):
        m.build_paged_serving_fns(2, 8, 17, 8)
    with pytest.raises(NotImplementedError, match="early exit"):
        m.generate(np.arange(5)[None], max_new_tokens=2)


@pytest.mark.parametrize("option", [
    dict(speculative=True), dict(role="prefill"), dict(role="decode")],
    ids=["speculative", "prefill_role", "decode_role"])
def test_engine_refuses_an_option_without_a_program(option):
    m, _, _ = _model(2)
    with pytest.raises(ValueError, match="no program for"):
        ServingEngine(m, num_slots=2, block_size=8, max_len=64,
                      buckets=[16], **option)
    with pytest.raises(NotImplementedError, match="speculative verify"):
        m.build_paged_spec_verify_fn(2, 8, 17, 8, 2)


@pytest.mark.parametrize("key,value,name", [
    ("rope_scaling", {"type": "yarn", "factor": 4}, "rope_scaling"),
    ("sliding_window", 4096, "sliding_window"),
    ("use_sliding_window", True, "sliding_window"),
    ("layer_types", ["full_attention", "sliding_attention",
                     "full_attention"], "sliding_attention"),
    ("hidden_act", "gelu", "hidden_act"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("model_type", "llama", "model_type")])
def test_config_refuses_what_it_has_no_equations_for(key, value, name):
    with pytest.raises(NotImplementedError, match=name):
        oo.OuroConfig.from_hf(dict(HF, **{key: value}))
    if key != "model_type":
        with pytest.raises(NotImplementedError, match=key):
            ref.logits(_weights(HF), jnp.zeros((8,), jnp.int32),
                       dict(HF, **{key: value}))


def test_config_reads_every_key_of_the_row_and_refuses_one_it_lacks():
    """The published ``config.json`` of the catalog row: every key is a
    parameter of ``OuroConfig``, ``total_ut_steps`` and
    ``early_exit_threshold`` among them; a key it does not know, a pass
    count under 1 and a list that disagrees with the depth are errors."""
    import json
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ouro_2p6b.json")) as f:
        doc = json.load(f)
    from benchmarks.planes import serve_arch
    cfg = oo.OuroConfig.from_hf(serve_arch.model_of(doc), dtype="bfloat16")
    assert (cfg.num_layers, cfg.num_passes, cfg.cache_layers) \
        == (48, 4, 192)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) \
        == (2048, 16, 16, 128, 5632, 49152)
    assert cfg.exit_threshold == 1.0 and cfg.rope_theta == 1e6
    assert oo.looped_cache_spec(cfg).bytes_per_token == 1572864
    with pytest.raises(TypeError, match="qk_norm"):
        oo.OuroConfig.from_hf(dict(HF, qk_norm=True))
    with pytest.raises(ValueError, match="total_ut_steps"):
        oo.OuroConfig.from_hf(dict(HF, total_ut_steps=0))
    with pytest.raises(ValueError, match="layer_types"):
        oo.OuroConfig.from_hf(dict(HF, num_hidden_layers=4))


# ----------------------------------------------------------- the kernel
@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", [True])


def test_engine_kernel_arm_equals_the_jnp_arm(monkeypatch):
    """The decode program with the paged kernel in it (interpret mode,
    heads of 128, the kernel placing the step's new entry in each of the
    ``passes x layers`` entries) against the ``jnp`` arm with the block
    write: four slots, seven requests, so slots are released and taken
    again; a prompt of 45 prefilled in chunks of 16. The same tokens on
    both, each the reference's best."""
    m, w, hf = _model(2, seed=1, head_dim=128, num_attention_heads=2,
                      num_key_value_heads=2, num_hidden_layers=2)
    rng = np.random.default_rng(43)
    lens, new = (5, 45, 9, 17, 12, 3, 7), (12, 7, 10, 14, 6, 11, 9)
    prompts = [rng.integers(0, 128, size=n) for n in lens]
    served = {}
    for kernel in (True, False):
        monkeypatch.setattr(pa, "_FORCE_INTERPRET", [kernel])
        eng = ServingEngine(m, num_slots=4, block_size=8, max_len=96,
                            buckets=[16], prefill_chunk=16)
        reqs = _drive(eng, prompts, new)
        assert eng.pool.reuse_count >= 2
        served[kernel] = [np.asarray(r.output_ids) for r in reqs]
        for p, r in zip(prompts, reqs):
            assert _served_gap(w, p, r, hf) < TOL
    for a, b in zip(served[True], served[False]):
        np.testing.assert_array_equal(a, b)


def test_a_shape_the_kernel_cannot_take_is_refused_by_name(monkeypatch):
    from paddle_tpu.serving.paged import looped_programs as lp
    m, _, _ = _model(2)
    assert lp.decode_kernel(m.cfg, 8) is False           # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="paged_decode_attn cannot take"):
        lp.decode_kernel(m.cfg, 8)                       # heads of 16


# ------------------------------- the programs whose accesses are shared
def _digest(fn, *args):
    return hashlib.sha256(
        str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()[:16]


def test_the_shared_access_objects_left_their_models_programs_alone():
    """This model reaches its cache through ``hybrid_programs
    .PagedAccess`` and ``nemotron_h``'s ``SeqAccess`` / ``ContigAccess``
    as they stand: the Nemotron and MiMo decode programs' jaxprs are
    those of the parent commit (648fcc3; the GPT's and the latent
    model's are pinned in ``tests/test_nemotron_h.py``)."""
    from tests.test_mimo_v2 import _model as mimo_model
    from tests.test_nemotron_h import _model as nemotron_model
    for build, want in ((nemotron_model, HYBRID_DECODE_DIGEST),
                        (mimo_model, MIXED_DECODE_DIGEST)):
        eng = ServingEngine(build()[0], num_slots=3, block_size=8,
                            max_len=64, buckets=[16])
        args, _ = eng._decode_dispatch_args(eng.pool)
        assert _digest(eng._decode_fn, *args) == want


# digests of str(jax.make_jaxpr(decode program)) at the sizes above,
# taken on the parent commit (648fcc3) with this same test code
HYBRID_DECODE_DIGEST = "a18b63eca0d7b956"
MIXED_DECODE_DIGEST = "8a49eeea53aeaf53"
