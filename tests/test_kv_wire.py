"""KV wire format + engine-level handoff (PR 17 disaggregation).

Satellite proofs for the prefill/decode handoff unit: the serialized
block frames round-trip byte-exact across every cache dtype (including
a partial last block and refcount>1 shared-prefix blocks), a corrupted
digest is refused with the typed :class:`KVWireError` BEFORE any pool
mutation, and a full prefill->export->import->decode handoff between
two engines reproduces the monolithic stream bit-exact with zero
leaked blocks on either tier.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_wire import (KVWireError, blocks_for_prompt,
                                        deserialize_handoff,
                                        payload_wire_bytes,
                                        serialize_handoff)
from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig


# -------------------------------------------------- pure wire round-trip

def _tiles(dtype, layers=2, n_blocks=3, heads=4, bs=8, hd=16, seed=0):
    rs = np.random.RandomState(seed)
    shape = (layers, n_blocks, heads, bs, hd)
    k = rs.randn(*shape)
    v = rs.randn(*shape)
    if str(dtype) == "bfloat16":
        import ml_dtypes
        return (k.astype(ml_dtypes.bfloat16),
                v.astype(ml_dtypes.bfloat16))
    return k.astype(dtype), v.astype(dtype)


@pytest.mark.parametrize("dtype", ["float32", "float16", "bfloat16"])
def test_round_trip_byte_exact_all_dtypes(dtype):
    k, v = _tiles(dtype)
    bs = k.shape[3]
    prompt = list(range(2 * bs + 3))       # partial last block
    payload = serialize_handoff(k, v, prompt, first_token=42)
    # JSON-safe by construction: the HTTP transport ships it verbatim
    payload = json.loads(json.dumps(payload))
    assert payload_wire_bytes(payload) == k.nbytes + v.nbytes
    h = deserialize_handoff(payload)
    assert h.prompt == prompt and h.first_token == 42
    assert h.n_blocks == blocks_for_prompt(len(prompt), bs) == 3
    assert h.k.dtype == k.dtype and h.v.dtype == v.dtype
    assert h.k.tobytes() == k.tobytes()    # byte-exact, not allclose
    assert h.v.tobytes() == v.tobytes()
    assert h.wire_bytes == k.nbytes + v.nbytes


def test_partial_last_block_counts_whole():
    assert blocks_for_prompt(1, 16) == 1
    assert blocks_for_prompt(16, 16) == 1
    assert blocks_for_prompt(17, 16) == 2
    with pytest.raises(ValueError):
        blocks_for_prompt(0, 16)
    k, v = _tiles("float32", n_blocks=2, bs=8)
    with pytest.raises(ValueError):        # 9 tokens need 2 blocks of 8
        serialize_handoff(k[:, :1], v[:, :1], list(range(9)), 0)


def test_corrupted_digest_raises_typed_error():
    k, v = _tiles("float32", n_blocks=2, bs=8)
    payload = serialize_handoff(k, v, list(range(16)), 7)
    bad = json.loads(json.dumps(payload))
    bad["frames"][1]["digest"] ^= 0x1
    with pytest.raises(KVWireError, match="digest mismatch"):
        deserialize_handoff(bad)
    # structural damage is the same typed error
    for mutate in (
            lambda p: p.__setitem__("version", 99),
            lambda p: p.__setitem__("prompt", []),
            lambda p: p["frames"].pop(),
            lambda p: p["frames"][0].__setitem__("k", "!!notb64"),
    ):
        mangled = json.loads(json.dumps(payload))
        mutate(mangled)
        with pytest.raises(KVWireError):
            deserialize_handoff(mangled)


# ---------------------------------------------- engine-level handoff

def _model(seed=11):
    paddle.seed(seed)
    cfg = TransformerLMConfig(vocab_size=97, hidden_size=32,
                              num_layers=2, num_heads=4,
                              max_seq_len=64, dropout=0.0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _engine(role="monolithic", **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("bucket_min", 8)
    return ServingEngine(_model(), role=role, **kw)


def _pool_empty(eng):
    pool = eng.pool
    pool.check_conservation()
    return pool.live_blocks == 0


def test_engine_handoff_parity_and_zero_leak():
    """prefill->export->import->decode across two engines == one
    monolithic engine, bit-exact, with both pools empty after."""
    prompt = list(range(1, 20))            # partial last block (19/16)
    ref_eng = _engine()
    r = ref_eng.add_request(np.asarray(prompt, np.int64), 6)
    ref_eng.run()
    ref = [int(t) for t in r.generated]
    ref_eng.close()

    pe, de = _engine("prefill"), _engine("decode")
    try:
        req = pe.add_request(np.asarray(prompt, np.int64), 1,
                             hold_kv=True)
        pe.run()
        payload = pe.export_kv(req.rid)
        assert payload_wire_bytes(payload) > 0
        assert _pool_empty(pe)             # export releases the slot
        got = []
        dreq = de.import_kv(payload, 6,
                            on_token=lambda _r, t: got.append(int(t)))
        de.run()
        assert [int(t) for t in dreq.generated] == ref
        # on_token sees only post-first tokens (hop 1 journaled the
        # first token already)
        assert got == ref[1:]
        assert _pool_empty(de)
    finally:
        pe.close()
        de.close()


def test_export_shared_prefix_blocks_byte_exact():
    """Blocks shared with the radix prefix index (refcount > 1) ship
    byte-exact: export reads the pool, never copies-on-write."""
    eng = _engine("prefill")
    try:
        prompt = list(range(1, 33))        # two full blocks: indexable
        r1 = eng.add_request(np.asarray(prompt, np.int64), 1,
                             hold_kv=True)
        eng.run()
        # a second request over the same prefix shares the indexed
        # blocks while r1's export is still parked
        r2 = eng.add_request(np.asarray(prompt, np.int64), 1,
                             hold_kv=True)
        eng.run()
        pool = eng.pool
        shared = [b for b, c in pool._ref.items() if c > 1]
        assert shared, "prefix blocks should be refcount>1"
        blocks = pool._slot_blocks[r1.slot][:2]
        want_k = np.asarray(pool.kc)[:, blocks]
        want_v = np.asarray(pool.vc)[:, blocks]
        h = deserialize_handoff(eng.export_kv(r1.rid))
        assert h.k[:, :2].tobytes() == want_k.tobytes()
        assert h.v[:, :2].tobytes() == want_v.tobytes()
        eng.export_kv(r2.rid)              # release the second hold
        assert _pool_empty(eng)
    finally:
        eng.close()


def test_corrupt_import_never_poisons_pool():
    """A corrupted frame is refused by the typed error with the
    importing pool bit-identical to before: same free count, same
    conservation, and a subsequent clean import still works."""
    pe, de = _engine("prefill"), _engine("decode")
    try:
        prompt = list(range(1, 18))
        req = pe.add_request(np.asarray(prompt, np.int64), 1,
                             hold_kv=True)
        pe.run()
        payload = pe.export_kv(req.rid)
        bad = json.loads(json.dumps(payload))
        bad["frames"][0]["digest"] ^= 0x2
        free_before = de.pool.free_blocks
        kc_before = np.asarray(de.pool.kc).tobytes()
        with pytest.raises(KVWireError):
            de.import_kv(bad, 4)
        assert de.pool.free_blocks == free_before
        assert np.asarray(de.pool.kc).tobytes() == kc_before
        de.pool.check_conservation()
        dreq = de.import_kv(payload, 4)    # clean retry: pool fine
        de.run()
        assert len(dreq.generated) == 4
        assert _pool_empty(de)
    finally:
        pe.close()
        de.close()


def test_import_rejects_pool_mismatch():
    """Shape/dtype drift between exporter and importer is a typed
    refusal, not a crash or a silent mis-bind."""
    pe = _engine("prefill")
    de = _engine("decode", block_size=8)   # wrong block size
    try:
        req = pe.add_request(np.asarray(range(1, 10), np.int64), 1,
                             hold_kv=True)
        pe.run()
        payload = pe.export_kv(req.rid)
        with pytest.raises(KVWireError, match="block"):
            de.import_kv(payload, 4)
        de.pool.check_conservation()
    finally:
        pe.close()
        de.close()
