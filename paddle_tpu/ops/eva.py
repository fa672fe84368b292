"""The parts of EVA attention (Zheng et al., ICLR 2023, arXiv:2302.04542,
as ``text.evabyte`` serves it) that are not plain softmax attention over
a slot's cache entries: rotary positions by half-split pairs, the
COMPACTION of a finished window (every chunk of ``C`` positions pooled
into one key and one value), and a run of queries over its own window
and the summaries of the windows before it in one softmax.

A query at position ``t`` in window ``w = t // W`` attends, in ONE
softmax, to the positions ``W w .. t`` exactly and to one pooled pair
``(kbar_c, vbar_c)`` for each chunk ``c < (W / C) w``:

  ``kbar_c = sum_j softmax_j(<k_j, mu> / sqrt(d)) k_j``
  ``vbar_c = sum_j softmax_j(<k_j, phi> / sqrt(d)) v_j``

over the chunk's ``C`` positions ``j`` (``k_j`` rotated), ``mu`` and
``phi`` a learned vector a head. Decode is therefore ordinary attention
over a slot's entries (``ops.paged_attention`` with ``lengths`` =
entries); only prefill and the compaction are here. All in ``jnp``: the
compaction is two weighted sums over 16 rows a chunk on the vector unit,
once a window of 2,048 steps.
"""
import jax
import jax.numpy as jnp

from ..profiler import device_scope

_NEG = -1e30


def rope_half(x, pos, theta):
    """Rotary positions over the whole head by HALF-SPLIT pairs: lanes
    ``(i, i + d/2)`` are one pair, turned by ``pos * theta**(-2i/d)``
    (the ``rotate_half`` form). x ``[..., d]``, pos broadcastable to
    ``x.shape[:-1]``."""
    d = x.shape[-1]
    inv = jnp.float32(theta) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / jnp.float32(d))
    ang = pos.astype(jnp.float32)[..., None] * inv       # [..., d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def window_compact(k, v, mu, phi, chunk):
    """A run's chunks pooled: k, v ``[..., H, n, d]`` (``n`` a multiple
    of ``chunk``), mu, phi ``[H, d]`` -> (kbar, vbar) ``[..., H,
    n / chunk, d]`` in the inputs' dtype. Scores, softmax and both sums
    in float32 on the vector unit (no matmul rounds a weight)."""
    with device_scope("eva/compact"):
        *lead, H, n, d = k.shape
        shape = tuple(lead) + (H, n // chunk, chunk, d)
        kc = k.astype(jnp.float32).reshape(shape)
        vc = v.astype(jnp.float32).reshape(shape)
        scale = jnp.float32(d) ** -0.5

        def weights(vec):
            s = jnp.sum(kc * vec.astype(jnp.float32)[:, None, None, :],
                        axis=-1) * scale                # [..., H, n/C, C]
            return jax.nn.softmax(s, axis=-1)[..., None]

        kbar = jnp.sum(weights(mu) * kc, axis=-2)
        vbar = jnp.sum(weights(phi) * vc, axis=-2)
        return kbar.astype(k.dtype), vbar.astype(v.dtype)


def window_attention(q, k, v, ks, vs, live, block=512):
    """One sequence's run of ``B`` queries that starts at a window's
    start: q, k, v ``[H, B, d]`` (rotated; query ``i`` sees keys ``0 ..
    i`` of the run), ks, vs ``[H, R, d]`` the summaries of the windows
    before it, those marked in ``live [R]`` visible. One softmax over
    both, in float32; ``block`` query rows at a time. -> ``[H, B, d]``
    f32."""
    H, B, d = q.shape
    R = ks.shape[1]
    qb = block if B % block == 0 else B
    scale = jnp.float32(d) ** -0.5
    kpos = jnp.arange(B, dtype=jnp.int32)

    def rows(args):
        qi, i0 = args                                   # [H, qb, d]
        s = jnp.concatenate([
            jnp.where(live[None, None, :], jnp.einsum(
                "hqd,hrd->hqr", qi, ks,
                preferred_element_type=jnp.float32), _NEG),
            jnp.where(kpos[None, None, :] <= (
                i0 + jnp.arange(qb, dtype=jnp.int32))[None, :, None],
                jnp.einsum("hqd,hkd->hqk", qi, k,
                           preferred_element_type=jnp.float32), _NEG),
        ], axis=-1) * scale
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqr,hrd->hqd", p[..., :R].astype(vs.dtype),
                          vs, preferred_element_type=jnp.float32) \
            + jnp.einsum("hqk,hkd->hqd", p[..., R:].astype(v.dtype), v,
                         preferred_element_type=jnp.float32)

    o = jax.lax.map(rows, (
        q.reshape(H, B // qb, qb, d).transpose(1, 0, 2, 3),
        jnp.arange(B // qb, dtype=jnp.int32) * qb))
    return o.transpose(1, 0, 2, 3).reshape(H, B, d)


def entry_attention(q, k, v, lengths):
    """One query a sequence over its first ``lengths`` cache entries:
    q ``[b, H, d]``, k, v ``[b, E, H, d]`` -> ``[b, H, d]`` f32 (the
    contiguous cache of ``generate()``; the paged pool has
    ``ops.paged_attention``)."""
    d = q.shape[-1]
    s = jnp.einsum("bhd,behd->bhe", q, k,
                   preferred_element_type=jnp.float32) \
        * jnp.float32(d) ** -0.5
    mask = jnp.arange(k.shape[1], dtype=jnp.int32)[None, None, :] \
        < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, _NEG), axis=-1)
    return jnp.einsum("bhe,behd->bhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
