"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

Greenfield (SURVEY §5: the reference has NO sequence-parallel support —
`ring_attention|ulysses|context_parallel` absent from its tree). Design:

- ring_attention: shard_map over the 'sp' mesh axis. Each device holds
  q/k/v chunks [B, H, S/sp, D]. K/V blocks rotate around the ring with
  lax.ppermute while each device accumulates online-softmax partial
  attention of its local Q against every block — compute overlaps the
  ICI transfer (the Ring Attention construction, Liu et al. 2023).
  HBM footprint per chip stays O(S/sp), enabling sequences sp x longer.
- ulysses_attention: all_to_all re-shard seq->heads, full-sequence
  attention per head subset, all_to_all back (DeepSpeed Ulysses).
  Cheaper comms for moderate S, needs num_heads % sp == 0.

Both are differentiable (built from jax primitives; autodiff of ppermute /
all_to_all yields the reversed collectives).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _online_block(q, k, v, acc, m_prev, l_prev, mask=None):
    """One online-softmax accumulation step. q:[B,H,Sq,D] k/v:[B,H,Sk,D],
    acc:[B,H,Sq,D] accumulates unnormalized output; m,l:[B,H,Sq]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return acc_new, m_new, l_new


def _ring_attention_sharded(q, k, v, *, axis_name, sp, scale, causal):
    """Per-device body under shard_map. q/k/v: local [B, H, S/sp, D]."""
    my = jax.lax.axis_index(axis_name)
    q = q.astype(jnp.float32) * scale
    b, h, sq, d = q.shape
    acc0 = jnp.zeros((b, h, sq, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def body(step, carry):
        acc, m, l, kb, vb = carry
        # block currently held came from device (my - step) mod sp
        src = (my - step) % sp
        if causal:
            # query position i (global: my*sq + i) attends key j
            # (global: src*sq + j) iff qpos >= kpos
            qpos = my * sq + jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 0)
            kpos = src * sq + jax.lax.broadcasted_iota(jnp.int32, (sq, sq), 1)
            mask = (qpos >= kpos)[None, None]
        else:
            mask = None
        acc, m, l = _online_block(q, kb, vb, acc, m, l, mask)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return acc, m, l, kb, vb

    acc, m, l, _, _ = jax.lax.fori_loop(0, sp, body, (acc0, m0, l0, k, v))
    return (acc / l[..., None]).astype(v.dtype)


def _bh_specs(mesh, q, axis_name, heads_groups=1):
    """Batch/head placements for the sp shard_map: keep the batch on
    'dp' and the heads on 'mp' when the mesh has those axes (Megatron-SP
    composition — attention is head- and batch-independent, so each
    dp x mp shard runs its own ring on its slice; an unmentioned axis
    would force an all-gather instead). heads_groups: extra divisibility
    the body needs on the per-mp-shard head count (Ulysses sp groups).
    axis_name (the ring/a2a axis) must not repeat in the spec, so a ring
    run over 'dp' or 'mp' itself keeps that dim replicated as before."""
    b, h = q.shape[0], q.shape[1]
    bspec = "dp" if ("dp" in mesh.axis_names and axis_name != "dp"
                     and b % int(mesh.shape["dp"]) == 0) else None
    mp = int(mesh.shape.get("mp", 1))
    hspec = "mp" if (mp > 1 and axis_name != "mp" and h % mp == 0
                     and (h // mp) % heads_groups == 0) else None
    return bspec, hspec


def ring_attention(q, k, v, mesh, axis_name="sp", causal=True, scale=None):
    """q/k/v: GLOBAL [B, H, S, D] arrays (sharded or not) — runs the ring
    over mesh[axis_name], sequence dimension sharded sp-ways; batch and
    heads stay dp-/mp-sharded when those axes exist."""
    sp = int(mesh.shape[axis_name])
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if sp == 1:
        from .attention import _flash_attention_core
        return _flash_attention_core(q, k, v, sc, causal)
    body = functools.partial(_ring_attention_sharded, axis_name=axis_name,
                             sp=sp, scale=sc, causal=causal)
    bspec, hspec = _bh_specs(mesh, q, axis_name)
    spec = P(bspec, hspec, axis_name, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def _ulysses_sharded(q, k, v, *, axis_name, sp, scale, causal):
    """Per-device: [B, H, S/sp, D] -> all_to_all -> [B, H/sp, S, D] ->
    attention -> all_to_all back."""
    def seq_to_heads(x):
        # split heads into sp groups, exchange so each device gets full seq
        # for its head group: all_to_all over the head axis
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    # full-sequence attention per head group through the flash core: at
    # long S the dense [S, S] score matrix this used to build is exactly
    # what Ulysses + flash avoids (the core self-falls-back to the dense
    # composition for small shapes / CPU)
    from .attention import _flash_attention_core
    out = _flash_attention_core(qh, kh, vh, scale, causal)
    return heads_to_seq(out)


def ulysses_attention(q, k, v, mesh, axis_name="sp", causal=True, scale=None):
    sp = int(mesh.shape[axis_name])
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if sp == 1:
        from .attention import _flash_attention_core
        return _flash_attention_core(q, k, v, sc, causal)
    assert q.shape[1] % sp == 0, "num_heads must divide sp for Ulysses"
    body = functools.partial(_ulysses_sharded, axis_name=axis_name, sp=sp,
                             scale=sc, causal=causal)
    bspec, hspec = _bh_specs(mesh, q, axis_name, heads_groups=sp)
    spec = P(bspec, hspec, axis_name, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
