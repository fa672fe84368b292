"""Pallas ring decode kernel (ops/slot_ring_decode.py, a window layer's
decode step over per-slot rings): interpret-mode parity against the
``jnp`` formulation it replaces on a chip
(``mixed_programs.PagedAccess.win_decode``, the parity oracle), the
guard that chooses it, and what it may and may not write."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import slot_ring_decode as rd
from paddle_tpu.serving.paged.mixed_programs import PagedAccess

BS, MB = 8, 48
C = BS * MB           # a slot's capacity: position C - 1 is the park


@pytest.fixture
def interpret_kernel(monkeypatch):
    monkeypatch.setattr(rd, "_FORCE_INTERPRET", [True])


# scenario -> (S, nq, nkv, hd, dv, W, Lw, positions): what the ISSUE
# names, each against both dtypes, with and without the sink
SCENARIOS = {
    # the sequence has not reached most entries: they hold garbage
    "short_of_the_window": (5, 8, 4, 24, 16, 8, 3, [0, 1, 3, 6, 7]),
    # around W and 2 W: the ring wraps, the entry is pos % W
    "wrapping": (6, 8, 4, 24, 16, 8, 3, [7, 8, 9, 15, 16, 17]),
    # a slot parked between its prefill's chunks, one released and
    # counted past the capacity, among live ones
    "parked_and_released": (5, 8, 2, 24, 16, 8, 2,
                            [4, C - 1, 11, C + 5, C - 2]),
    # the cell's widths, four slots a grid step, three steps
    "four_slots_a_step": (12, 16, 2, 192, 128, 32, 2,
                          [3, 31, 32, 70, 0, 33, 64, 95, 96, 12, 1, 40]),
    # more lanes than one tile of 128: the key's tile AROUND the entry
    "two_lane_tiles": (2, 4, 2, 16, 8, 256, 2, [130, 300]),
    # query groups that are no whole tile of 8 rows, odd slot count
    "group_of_three": (7, 6, 2, 16, 8, 8, 2, [2, 8, 13, 40, 0, 7, 9]),
}


def _case(seed, S, nq, nkv, hd, dv, W, Lw, dtype, sink):
    rs = np.random.RandomState(seed)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rs.randn(*shape) * scale, dtype)
    # the rings are GARBAGE of a size that would swamp any softmax it
    # reached (a released slot's last owner, an uncleared pool)
    return (arr(S, nq, hd), arr(S, nkv, hd), arr(S, nkv, dv),
            (arr(Lw, S, nkv, hd, W, scale=30.0),
             arr(Lw, S, nkv, W, dv, scale=30.0)),
            jnp.asarray(rs.randn(nq), jnp.float32) if sink else None)


def _step(kernel, W, S, wi, pos, q, k, v, rings, sink):
    access = PagedAccess(types.SimpleNamespace(window=W), S, S * MB + 1,
                         BS, MB, kernel=kernel)

    def run(wi, pos, q, k, v, rings, sink):
        (_, _, _, kring, vring), o = access.win_decode(
            (None, None, None) + rings, wi, pos, q, k, v, sink)
        return o, (kring, vring)
    # wi is TRACED, as the layer loop's is
    return jax.jit(run)(jnp.int32(wi), jnp.asarray(pos, jnp.int32), q, k,
                        v, rings, sink)


@pytest.mark.parametrize("sink", [True, False], ids=["sink", "no_sink"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 2e-5)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_ring_kernel_matches_the_jnp_win_decode(interpret_kernel, scenario,
                                                dtype, tol, sink):
    S, nq, nkv, hd, dv, W, Lw, pos = SCENARIOS[scenario]
    q, k, v, rings, sk = _case(len(scenario), S, nq, nkv, hd, dv, W, Lw,
                               jnp.dtype(dtype), sink)
    wi = Lw - 1
    want = _step(False, W, S, wi, pos, q, k, v, rings, sk)
    got = _step(True, W, S, wi, pos, q, k, v, rings, sk)
    pos = np.asarray(pos)
    live = pos < C - 1
    o, o_ref = np.asarray(got[0]), np.asarray(want[0])
    assert o.shape == (S, nq, dv) and o.dtype == np.float32
    # the same products of the same 16-bit values, summed in f32 in
    # another order: a part in 50,000 of the largest output
    np.testing.assert_allclose(o[live], o_ref[live], rtol=tol,
                               atol=tol * np.abs(o_ref[live]).max())
    assert np.isfinite(o).all()
    # what the sequence has not reached weighs nothing, whatever the
    # ring holds there: the same rows from rings that hold zeros
    unseen = np.arange(W)[None, :] > pos[:, None]              # [S, W]
    if unseen.any():
        clean = _step(True, W, S, wi, pos, q, k, v, (
            jnp.where(unseen[None, :, None, None, :], 0, rings[0]),
            jnp.where(unseen[None, :, None, :, None], 0, rings[1])), sk)
        np.testing.assert_array_equal(np.asarray(clean[0])[live], o[live])
    # the key ring with an entry a row, as the value ring has it
    for ring, ref, old, new in zip(
            got[1], want[1], rings, (k, v)):
        ring, ref, old = (np.asarray(a, np.float32)
                          for a in (ring, ref, old))
        if new is k:
            ring, ref, old = (np.swapaxes(a, -1, -2)
                              for a in (ring, ref, old))
        # the oracle's ring, bit for bit
        np.testing.assert_array_equal(ring, ref)
        # the other layers' rings, and a parked or released slot's
        np.testing.assert_array_equal(np.delete(ring, wi, 0),
                                      np.delete(old, wi, 0))
        np.testing.assert_array_equal(ring[wi][~live], old[wi][~live])
        for s in np.flatnonzero(live):
            # the new entry exactly at pos % W, every other row kept
            np.testing.assert_array_equal(
                ring[wi, s, :, pos[s] % W], np.asarray(new[s], np.float32))
            np.testing.assert_array_equal(
                np.delete(ring[wi, s], pos[s] % W, 1),
                np.delete(old[wi, s], pos[s] % W, 1))


def test_kernel_viable_asks_the_shapes_and_the_backend(monkeypatch):
    cell = (8, 192, 128, 128, "bfloat16")
    assert not rd.kernel_viable(*cell)              # the CPU: jnp
    monkeypatch.setattr(rd.jax, "default_backend", lambda: "tpu")
    assert rd.kernel_viable(*cell)
    assert rd.kernel_viable(8, 192, 128, 128, "float32")
    assert rd.kernel_viable(8, 192, 128, 256, "bfloat16")
    assert not rd.kernel_viable(8, 192, 128, 64, "bfloat16")     # W
    assert not rd.kernel_viable(8, 192, 64, 128, "bfloat16")     # dv
    assert not rd.kernel_viable(8, 200, 128, 128, "bfloat16")    # hd
    assert rd.kernel_viable(8, 200, 128, 128, "float32")
    assert not rd.kernel_viable(8, 192, 128, 128, "float64")
    # two halves of a slot's rings have to fit the buffers' budget,
    # counted once for the guard and the call
    assert rd.slot_ring_bytes(*cell) == 655360
    assert not rd.kernel_viable(64, 192, 128, 1024, "float32")
    assert rd.slots_per_step(48, 655360) == 4
    assert rd.slots_per_step(6, 655360) == 2
    assert rd.slots_per_step(7, 655360) == 1
    monkeypatch.setattr(rd, "_FORCE_INTERPRET", [True])
    assert rd.kernel_viable(2, 24, 16, 8, "float32")


def test_queries_come_in_the_rings_dtype(interpret_kernel):
    """The model casts q to the cache's dtype; the kernel multiplies in
    it and says so rather than cast a wider q down."""
    q, k, v, (kring, vring), _ = _case(
        0, 4, 8, 4, 24, 16, 8, 2, jnp.dtype("bfloat16"), False)
    with pytest.raises(ValueError, match="queries in the rings' dtype"):
        rd.ring_decode_attention(
            q.astype(jnp.float32), k, v, kring, vring, 0,
            jnp.zeros((4, 8), jnp.int32), jnp.zeros((4,), jnp.int32))


def test_decode_kernels_refuses_a_ring_by_name(monkeypatch):
    import json
    import os
    from paddle_tpu.serving.paged import mixed_programs as mp
    from paddle_tpu.text import mimo_v2 as mm
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "mimo_v2_flash_pp8ep16.json")) as f:
        config = json.load(f)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = mm.MimoV2Config.from_hf(config, dtype="bfloat16")
    assert mp.decode_kernels(cfg, 48, 256)           # the cell's own
    cfg = mm.MimoV2Config.from_hf(config, dtype="bfloat16",
                                  sliding_window=72, sliding_window_size=72)
    with pytest.raises(ValueError, match="ring_decode_attn cannot take"):
        mp.decode_kernels(cfg, 48, 256)
