"""Dropless mixture-of-experts arithmetic for the served path: the
sigmoid router with bias-corrected choice (DeepSeek-V3 ``noaux_tc``), a
grouped expert MLP over the experts a DECODE step's tokens hit (Pallas
TPU kernel + its ``jnp`` formulation), and the sorted grouped matmul for
PREFILL. No capacity, no dropped token, no ``[T, E, C]`` mask.

An expert has one of two FORMS, told by the matrices handed in: gated
SwiGLU, ``(silu(x Wg) * x Wu) Wd`` with three matrices ``[h, f]``,
``[h, f]``, ``[f, h]``; or non-gated relu-squared, ``relu(x Wu^T)^2
Wd`` with two, BOTH stored ``[f, h]`` (``Wu`` transposed), so that a
width ``f`` that is no multiple of the 128 lanes (1856 = 4 x 464) rides
the sublanes, where a tile need only be a multiple of 16, and ``h``
fills the lanes.

A layer may hold only a contiguous share of the experts, ``held =
(first, count)``: the router always scores all of them, and the share
returns the part of the sum that its own experts give. The experts'
matrices of ALL layers that have them are stacked flat on the leading
axis (``[layers * count, h, f]``; layer ``m``'s expert ``e`` is row ``m
* count + e``), so a layer loop hands the kernel the whole array and a
row offset instead of slicing 1.2 GB out of it every step.
"""
import functools

import jax
import jax.numpy as jnp

from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the kernel in interpret mode on CPU
_FORCE_INTERPRET = [False]


# --------------------------------------------------------------- router
def route_sigmoid(x, w_router, bias, top_k, norm_topk=True, scale=1.0,
                  dtype=jnp.float32):
    """x ``[T, h]`` (normed), w_router ``[h, E]``, bias ``[E]``.
    ``s = sigmoid(x W)`` in ``dtype`` (float32: a served model never
    sets another; the tests do, to prove that they would notice); the
    ``top_k`` largest of ``s + bias`` are chosen, their weights are
    ``s`` WITHOUT the bias, normalised to sum 1 (``norm_topk``) and
    scaled. Returns (idx ``[T, k]`` int32, w ``[T, k]`` float32)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(dtype), w_router.astype(dtype),
                               preferred_element_type=dtype))
    _, idx = jax.lax.top_k(s + bias.astype(dtype)[None, :], int(top_k))
    w = jnp.take_along_axis(s, idx, axis=1).astype(jnp.float32)
    if norm_topk:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + jnp.float32(1e-20))
    return idx.astype(jnp.int32), w * jnp.float32(scale)


def combine_matrix(idx, w, first, count):
    """Dense combine weights of the held experts: ``[T, count]`` f32,
    zero where a token did not choose the expert."""
    local = idx - jnp.int32(first)
    hot = local[:, :, None] == jnp.arange(count, dtype=jnp.int32)
    return jnp.sum(jnp.where(hot, w[:, :, None], jnp.float32(0)), axis=1)


def expert_counts(idx, first, count):
    """Tokens routed to each held expert: ``[count]`` int32."""
    local = idx.reshape(-1) - jnp.int32(first)
    hot = local[:, None] == jnp.arange(count, dtype=jnp.int32)
    return jnp.sum(hot, axis=0, dtype=jnp.int32)


def _silu(g):
    return g * jax.nn.sigmoid(g)


def swiglu(x, w_gate, w_up, w_down):
    """One SwiGLU: ``(silu(x Wg) * x Wu) Wd``; the product in f32."""
    g = jnp.dot(x, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(x, w_up, preferred_element_type=jnp.float32)
    return jnp.dot((_silu(g) * u).astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


def relu2_mlp(x, w_up_t, w_down):
    """One non-gated expert: ``relu(x Wu^T)^2 Wd``, both matrices
    ``[f, h]``; the square in f32."""
    u = jax.lax.dot_general(x, w_up_t, (((x.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    r = jnp.maximum(u, jnp.float32(0))
    return jnp.dot((r * r).astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


# ----------------------------------------------------- decode: jnp form
def moe_experts_swiglu_jnp(x, w_gate, w_up, w_down, cw, base):
    """``sum_e (silu(x Wg_e) * x Wu_e * cw[:, e]) Wd_e`` over the
    ``count = cw.shape[1]`` experts stacked from row ``base``: every
    expert computed for every token (the formulation beside the kernel;
    the CPU path at test sizes). Returns ``[T, h]`` f32."""
    count = cw.shape[1]
    wg = jax.lax.dynamic_slice_in_dim(w_gate, base, count)
    wu = jax.lax.dynamic_slice_in_dim(w_up, base, count)
    wd = jax.lax.dynamic_slice_in_dim(w_down, base, count)
    g = jnp.einsum("th,ehf->etf", x, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("th,ehf->etf", x, wu,
                   preferred_element_type=jnp.float32)
    mid = (_silu(g) * u * cw.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("etf,efh->th", mid, wd,
                      preferred_element_type=jnp.float32)


def moe_experts_relu2_jnp(x, w_up_t, w_down, cw, base):
    """``moe_experts_swiglu_jnp`` for relu-squared experts: ``sum_e
    (relu(x Wu_e^T)^2 * cw[:, e]) Wd_e``, matrices ``[., f, h]``."""
    count = cw.shape[1]
    wu = jax.lax.dynamic_slice_in_dim(w_up_t, base, count)
    wd = jax.lax.dynamic_slice_in_dim(w_down, base, count)
    u = jnp.einsum("th,efh->etf", x, wu,
                   preferred_element_type=jnp.float32)
    r = jnp.maximum(u, jnp.float32(0))
    mid = (r * r * cw.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("etf,efh->th", mid, wd,
                      preferred_element_type=jnp.float32)


# ----------------------------------------------------- decode: kernel
def _f_tile(f):
    """Largest multiple of 128 that divides ``f`` and keeps the three
    double-buffered weight tiles of a 2048-wide model inside the 16 MiB
    of scoped VMEM (384 columns: 1.5 MB a matrix); ``f`` itself when no
    such divisor exists."""
    for t in (384, 256, 128):
        if f % t == 0:
            return t
    return f


def _moe_decode_kernel(ids_ref, meta_ref, x_ref, cw_ref, *refs):
    """Grid (count, f tiles). Step ``(e, j)`` holds the j-th tile of the
    e-th HIT expert's matrices: column tiles of gate and up and the
    matching row tile of down (SwiGLU, three refs), or the row tiles of
    up^T and down (relu-squared, two); the output ``[T, h]`` stays
    resident and accumulates. Steps past the last hit expert do nothing
    (their index maps repeat the last tile, so nothing is fetched
    either)."""
    from jax.experimental import pallas as pl
    *w_refs, o_ref = refs
    e = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(e == 0, j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(e < meta_ref[0])
    def _compute():
        x = x_ref[...]
        if len(w_refs) == 3:
            wg_ref, wu_ref, wd_ref = w_refs
            g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
            act = _silu(g) * u
        else:
            wu_ref, wd_ref = w_refs
            u = jax.lax.dot_general(
                x, wu_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            r = jnp.maximum(u, jnp.float32(0))
            act = r * r
        mid = (act * cw_ref[...]).astype(x.dtype)
        o_ref[...] += jnp.dot(mid, wd_ref[...],
                              preferred_element_type=jnp.float32)


def _f_rows(f, h, itemsize):
    """Row tile of relu-squared matrices ``[f, h]``: the largest divisor
    of ``f`` in whole sublane tiles whose two double-buffered tiles stay
    under 12 MB (464 rows of 2688 in bf16: 2.5 MB a tile); ``f`` itself
    when none divides."""
    sub = 32 // itemsize
    for n in range(1, f // sub + 1):
        t = f // n
        if f % n == 0 and t % sub == 0 \
                and 4 * t * h * itemsize <= (12 << 20):
            return t
    return f


def _moe_decode_32(x, cw, base, *mats):
    """The hit-experts-once grid for either form: ``mats`` is (gate, up,
    down) for SwiGLU, (up^T, down) for relu-squared."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, h = x.shape
    gated = len(mats) == 3
    count = cw.shape[1]
    if gated:
        f = mats[0].shape[2]
        tf = _f_tile(f)
    else:
        f = mats[0].shape[1]
        tf = _f_rows(f, h, x.dtype.itemsize)
    nf = f // tf
    # hit experts first, in ascending order; the rest repeat the last
    # hit one so that their grid steps fetch nothing
    hit = jnp.any(cw != 0, axis=0)
    n_hit = jnp.sum(hit, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(hit), stable=True).astype(
        jnp.int32)
    slot = jnp.arange(count, dtype=jnp.int32)
    ids = jnp.where(slot < n_hit, order,
                    order[jnp.maximum(n_hit - 1, 0)])
    meta = jnp.stack([n_hit, jnp.asarray(base, jnp.int32)])
    cw_e = cw.T[:, :, None]                              # [count, T, 1]

    def tile(e, j, meta_ref):
        return jnp.where(e < meta_ref[0], j, nf - 1)

    def x_index(e, j, ids_ref, meta_ref):
        return (0, 0)

    def cw_index(e, j, ids_ref, meta_ref):
        return (ids_ref[e], 0, 0)

    def in_index(e, j, ids_ref, meta_ref):
        return (meta_ref[1] + ids_ref[e], 0, tile(e, j, meta_ref))

    def down_index(e, j, ids_ref, meta_ref):
        return (meta_ref[1] + ids_ref[e], tile(e, j, meta_ref), 0)

    if gated:
        name = "moe_experts_swiglu_decode"
        w_specs = [pl.BlockSpec((None, h, tf), in_index),
                   pl.BlockSpec((None, h, tf), in_index),
                   pl.BlockSpec((None, tf, h), down_index)]
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    else:
        name = "moe_experts_relu2_decode"
        w_specs = [pl.BlockSpec((None, tf, h), down_index),
                   pl.BlockSpec((None, tf, h), down_index)]
        params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count, nf),
        in_specs=[
            pl.BlockSpec((T, h), x_index),
            pl.BlockSpec((None, T, 1), cw_index),
        ] + w_specs,
        out_specs=pl.BlockSpec((T, h), x_index),
    )
    return pl.pallas_call(
        _moe_decode_kernel, name=name, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, h), jnp.float32),
        compiler_params=params,
        interpret=_FORCE_INTERPRET[0],
    )(ids, meta, x, cw_e, *mats)


def moe_experts_swiglu_decode(x, w_gate, w_up, w_down, cw, base):
    """Grouped SwiGLU over the held experts for a decode step's tokens:
    each HIT expert's three matrices are read once, experts no token
    chose are skipped. Same signature and numbers as
    ``moe_experts_swiglu_jnp``."""
    return _trace_32bit(_moe_decode_32)(x, cw, base, w_gate, w_up, w_down)


def moe_experts_relu2_decode(x, w_up_t, w_down, cw, base):
    """The same for relu-squared experts (kernel
    ``moe_experts_relu2_decode``): each hit expert's two ``[f, h]``
    matrices once. Same signature and numbers as
    ``moe_experts_relu2_jnp``."""
    return _trace_32bit(_moe_decode_32)(x, cw, base, w_up_t, w_down)


def kernel_viable(tokens, hidden, width, dtype, gated=True):
    """Static facts Mosaic needs: 2-byte or f32 tiles, the token axis a
    multiple of the sublane tile, lanes whole. The expert width rides
    the lanes of a gated expert's matrices and the sublanes of a
    relu-squared one's (``[f, h]``)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    sub = 8 if dtype == jnp.dtype(jnp.float32) else 16
    return tokens % sub == 0 and hidden % 128 == 0 \
        and width % (128 if gated else sub) == 0


# --------------------------------------------- prefill: sorted + grouped
def _experts_grouped(x, mats, idx, w, first, count, base, tile):
    """The held experts' part of the layer for MANY tokens: the (token,
    expert) pairs are sorted by expert, each expert's run is padded to
    a multiple of ``tile`` rows, and every tile is one matmul against
    ONE expert's matrices (``lax.map`` over tiles). ``mats`` is (gate,
    up, down) or (up^T, down): the expert's form. Work is proportional
    to the pairs, whatever the routing. Returns ``[T, h]`` f32."""
    T, h = x.shape
    k = idx.shape[1]
    P = T * k
    tile = max(8, min(int(tile), -(-P // 8) * 8))
    R = -(-(P + count * (tile - 1)) // tile) * tile      # static bound
    NT = R // tile
    eid = idx.reshape(-1) - jnp.int32(first)
    held = jnp.logical_and(eid >= 0, eid < count)
    eid = jnp.where(held, eid, count)        # not held: a last, unused run
    wflat = jnp.where(held, w.reshape(-1), jnp.float32(0))
    tid = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    cnt = jnp.zeros((count + 1,), jnp.int32).at[eid].add(1)
    padded = (cnt[:count] + (tile - 1)) // tile * tile
    ends = jnp.cumsum(padded)
    gstart = ends - padded
    cstart = jnp.cumsum(cnt) - cnt
    order = jnp.argsort(eid, stable=True)
    se = eid[order]
    rank = jnp.arange(P, dtype=jnp.int32) - cstart[se]
    dest = jnp.where(se < count,
                     gstart[jnp.minimum(se, count - 1)] + rank, R)
    rows = jnp.full((R,), T, jnp.int32).at[dest].set(tid[order],
                                                     mode="drop")
    roww = jnp.zeros((R,), jnp.float32).at[dest].set(wflat[order],
                                                     mode="drop")
    xp = jnp.concatenate([x, jnp.zeros((1, h), x.dtype)])
    xr = xp[rows].reshape(NT, tile, h)
    tile_e = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(NT, dtype=jnp.int32) * tile,
                         side="right"), count - 1).astype(jnp.int32)

    def one(args):
        xt, e = args
        row = jnp.asarray(base, jnp.int32) + e
        pick = functools.partial(jax.lax.dynamic_index_in_dim,
                                 index=row, keepdims=False)
        mlp = swiglu if len(mats) == 3 else relu2_mlp
        return mlp(xt, *(pick(m) for m in mats)).astype(x.dtype)

    y = jax.lax.map(one, (xr, tile_e)).reshape(R, h)
    out = jnp.zeros((T + 1, h), jnp.float32).at[rows].add(
        y.astype(jnp.float32) * roww[:, None])
    return out[:T]


def moe_experts_grouped(x, w_gate, w_up, w_down, idx, w, first, count,
                        base, tile=256):
    """``_experts_grouped`` over SwiGLU experts."""
    return _experts_grouped(x, (w_gate, w_up, w_down), idx, w, first,
                            count, base, tile)


def moe_experts_grouped_relu2(x, w_up_t, w_down, idx, w, first, count,
                              base, tile=256):
    """``_experts_grouped`` over relu-squared experts (``[., f, h]``)."""
    return _experts_grouped(x, (w_up_t, w_down), idx, w, first, count,
                            base, tile)
