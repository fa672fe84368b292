"""Mamba-2 state-space arithmetic for the served path: the causal
depthwise convolution in front of the scan, the chunked scan for PREFILL
(matmul form, ``jnp``), and the one-token state update for DECODE (a
Pallas TPU kernel named ``ssm_decode_step`` + its ``jnp`` formulation).

One head's recurrence over a state ``S [P, N]`` (``P`` channels of the
head, ``N`` state size), with ``a_t = dt_t * A`` (``A < 0``)::

    S_t = exp(a_t) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

``B_t``, ``C_t`` ``[N]`` are shared by the ``H / G`` heads of a group.
The ``D x_t`` skip, the gate and the norm are the caller's.

What a SLOT keeps between steps (``serving.paged.cache_spec``: per-slot
leaves) is the last ``K - 1`` inputs of the convolution, FLAT
(``[(K-1) * channels]``: the slots ride the sublanes, a shift is a move
by whole lane tiles) and the state in float32, PACKED as
``[H / q, N, q * P]`` (``pack_state``): ``q = 128 / P`` heads of one
group share a row of 128 lanes, the state size rides the sublanes. With
``(h, p)`` on the lanes the update's per-channel factors are lane
vectors, ``B`` and ``C`` are columns (``[N, 1]``, a lane broadcast), and
``y`` is a sum over SUBLANES that lands dense on the lanes: no
transpose, no lane reduction, nothing but float32 multiply-adds on a
tile that is read once and written once. In the reference layout
``[H, P, N]`` the channel factors would be columns and ``y`` a lane
reduction a row.

The decode kernel takes the state of EVERY state-space layer
(``[layers * slots, H / q, N, q * P]``) aliased in and out and a layer
index: it updates that layer's rows in place and touches nothing else,
so a layer loop carries one buffer and never slices 270 MB out of it.
A slot whose ``dt`` is zero keeps its state bit for bit (``exp(0) S +
0``): that is how parked slots are passed by.
"""
import functools

import jax
import jax.numpy as jnp

from .pallas_compat import trace_32bit as _trace_32bit

# tests flip this to run the kernel in interpret mode on CPU
_FORCE_INTERPRET = [False]
_HI = jax.lax.Precision.HIGHEST
# a grid step holds one slot's whole state, in and out, each
# double-buffered (2 MB at nemotron_h's 64 heads of 64 x 128, two heads
# a row; 4 MB at falcon_h1's 32 heads of 128 x 256, one head a row:
# exactly the limit, 16 MB of the 48 with the buffers): what the kernel
# may take of VMEM, and the largest state a slot may have for it
_VMEM_BYTES = 48 << 20
_SLOT_STATE_BYTES = 4 << 20


# ------------------------------------------------------ the packed state
def heads_per_row(num_heads, head_dim, num_groups):
    """``q``: heads sharing one 128-lane row of the packed state. They
    must share ``B`` and ``C``, so ``q`` divides the heads of a group."""
    q = max(1, 128 // int(head_dim))
    per_group = num_heads // num_groups
    while q > 1 and per_group % q:
        q //= 2
    return q


def packed_shape(num_heads, head_dim, state_size, num_groups):
    q = heads_per_row(num_heads, head_dim, num_groups)
    return (num_heads // q, state_size, q * head_dim)


def pack_state(s, num_groups):
    """``[..., H, P, N]`` -> ``[..., H / q, N, q * P]``."""
    *lead, H, P, N = s.shape
    q = heads_per_row(H, P, num_groups)
    n = len(lead)
    s = s.reshape(*lead, H // q, q, P, N)
    s = s.transpose(*range(n + 1), n + 3, n + 1, n + 2)
    return s.reshape(*lead, H // q, N, q * P)


def unpack_state(s, num_heads, num_groups):
    """Inverse of ``pack_state``."""
    *lead, rows, N, qp = s.shape
    q = num_heads // rows
    n = len(lead)
    s = s.reshape(*lead, rows, N, q, qp // q)
    s = s.transpose(*range(n + 1), n + 2, n + 3, n + 1)
    return s.reshape(*lead, num_heads, qp // q, N)


# ---------------------------------------------------------- convolution
def _silu(x):
    return x * jax.nn.sigmoid(x)


def conv_prefill(u, window, w, b, length):
    """Causal depthwise convolution of ONE sequence's run of inputs.
    u ``[T, ch]``; window ``[(K-1) * ch]`` the ``K - 1`` inputs before
    it (oldest first); w ``[K, ch]`` (tap ``K - 1`` meets the newest
    input); b ``[ch]``. Returns (``silu(conv + b)`` ``[T, ch]`` in u's
    dtype, the window after the first ``length`` inputs)."""
    T, ch = u.shape
    K = w.shape[0]
    full = jnp.concatenate([window.reshape(K - 1, ch).astype(u.dtype), u])
    acc = b.astype(jnp.float32)[None, :]
    for k in range(K):
        acc = acc + full[k:k + T].astype(jnp.float32) \
            * w[k].astype(jnp.float32)[None, :]
    new = jax.lax.dynamic_slice_in_dim(full, length, K - 1, axis=0)
    return _silu(acc).astype(u.dtype), new.reshape(-1).astype(window.dtype)


def conv_decode(window, u, w, b):
    """One new input a slot: window ``[S, (K-1) * ch]``, u ``[S, ch]``.
    Returns (``silu(conv + b)`` ``[S, ch]``, the shifted window)."""
    K, ch = w.shape
    acc = b.astype(jnp.float32)[None, :] \
        + u.astype(jnp.float32) * w[K - 1].astype(jnp.float32)[None, :]
    for k in range(K - 1):
        acc = acc + window[:, k * ch:(k + 1) * ch].astype(jnp.float32) \
            * w[k].astype(jnp.float32)[None, :]
    new = jnp.concatenate([window[:, ch:], u.astype(window.dtype)], axis=1)
    return _silu(acc).astype(u.dtype), new


# ------------------------------------------------- prefill: chunked scan
def _rows_to_heads(s, num_heads):
    """Packed ``[H / q, N, q * P]`` -> ``[H, N, P]``: the heads of a
    row apart, the state size still in front of the channels. (NOT
    ``[H, P, N]``: swapping the two minor axes of a slice is a layout
    XLA then gives the WHOLE carried state, copying 1.6 GB in and out of
    every prefill: AOT, PR 35.)"""
    rows, N, lanes = s.shape
    q = num_heads // rows
    return s.reshape(rows, N, q, lanes // q).transpose(0, 2, 1, 3) \
        .reshape(num_heads, N, lanes // q)


def _heads_to_rows(s, rows):
    """Inverse of ``_rows_to_heads``."""
    H, N, P = s.shape
    q = H // rows
    return s.reshape(rows, q, N, P).transpose(0, 2, 1, 3).reshape(
        rows, N, q * P)


def ssd_prefill(xs, dt, A, B, C, init, chunk=128):
    """The recurrence over ONE sequence in chunks of ``chunk`` steps
    (state-space duality: inside a chunk the outputs are a masked
    ``[L, L]`` matmul, between chunks the state is carried).

    xs ``[T, H, P]``, dt ``[T, H]`` (after softplus; a step with ``dt =
    0`` leaves the state as it was, which is how rows past a prompt's
    end are passed by), A ``[H]``, B, C ``[T, G, N]``, init the PACKED
    state ``[H / q, N, q * P]`` float32 (``pack_state``). ``T`` need
    not be a multiple of ``chunk``. Returns (y ``[T, H, P]`` float32,
    the packed state after step ``T``, float32). Float32 throughout,
    products at ``HIGHEST``: the scan's core is a few percent of a
    layer's operations."""
    T, H, P = xs.shape
    G, N = B.shape[1:]
    L = int(chunk)
    nc = -(-T // L)
    pad = nc * L - T
    f32 = jnp.float32

    def chunks(a):
        a = jnp.pad(a.astype(f32), ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((nc, L) + a.shape[1:])

    hg = H // G
    causal = jnp.tril(jnp.ones((L, L), bool))

    def step(S, inp):                                    # S [H, N, P]
        x, d, Bc, Cc = inp                  # [L,H,P] [L,H] [L,G,N] [L,G,N]
        cs = jnp.cumsum(d * A.astype(f32)[None, :], axis=0)      # [L, H]
        # exp(cs_t - cs_s) for s <= t, masked BEFORE the exp
        diff = cs[:, None, :] - cs[None, :, :]                # [t, s, H]
        decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
        cb = jnp.einsum("tgn,sgn->tsg", Cc, Bc, precision=_HI)
        wts = jnp.repeat(cb, hg, axis=2) * decay * d[None, :, :]
        y = jnp.einsum("tsh,shp->thp", wts, x, precision=_HI)
        Ch = jnp.repeat(Cc, hg, axis=1)                       # [L, H, N]
        Bh = jnp.repeat(Bc, hg, axis=1)
        y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
            "thn,hnp->thp", Ch, S, precision=_HI)
        tail = jnp.exp(cs[-1][None, :] - cs) * d              # [L, H]
        S = jnp.exp(cs[-1])[:, None, None] * S + jnp.einsum(
            "shn,shp->hnp", Bh, tail[:, :, None] * x, precision=_HI)
        return S, y

    S, y = jax.lax.scan(step, _rows_to_heads(init.astype(f32), H),
                        (chunks(xs), chunks(dt), chunks(B), chunks(C)))
    return y.reshape(nc * L, H, P)[:T], _heads_to_rows(S, init.shape[0])


# ------------------------------------------------------ decode: jnp form
def ssm_state_step_jnp(state, layer, xs, dt, A, B, C, num_slots):
    """One step of every slot's recurrence in layer ``layer``. state
    ``[layers * S, H / q, N, q * P]`` float32 (packed, all layers); xs
    ``[S, H, P]``, dt ``[S, H]`` float32, A ``[H]``, B, C ``[S, G, N]``.
    Returns (state with that layer's rows replaced, y ``[S, H, P]``
    float32)."""
    S, H, P = xs.shape
    G = B.shape[1]
    f32 = jnp.float32
    base = jnp.asarray(layer, jnp.int32) * jnp.int32(num_slots)
    cur = unpack_state(
        jax.lax.dynamic_slice_in_dim(state, base, num_slots), H, G)
    Bh = jnp.repeat(B.astype(f32), H // G, axis=1)            # [S, H, N]
    Ch = jnp.repeat(C.astype(f32), H // G, axis=1)
    dA = jnp.exp(dt * A.astype(f32)[None, :])
    new = cur * dA[:, :, None, None] \
        + (xs.astype(f32) * dt[:, :, None])[..., None] * Bh[:, :, None, :]
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1)
    state = jax.lax.dynamic_update_slice_in_dim(
        state, pack_state(new, G), base, axis=0)
    return state, y


# -------------------------------------------------------- decode: kernel
def _ssm_step_kernel(meta_ref, da_ref, xdt_ref, bt_ref, ct_ref, s_ref,
                     y_ref, o_ref, *, rows_per_group):
    """Grid (slots,). One step holds a slot's packed state ``[rows, N,
    q*P]``; row ``r``'s factors are lane vectors (``da``, ``xdt``
    ``[rows, q*P]``), its group's ``B`` and ``C`` columns of
    ``bt``/``ct`` ``[N, G]``."""
    del meta_ref
    rows = s_ref.shape[1]
    for g in range(rows // rows_per_group):
        bcol = bt_ref[0, :, g:g + 1]                            # [N, 1]
        ccol = ct_ref[0, :, g:g + 1]
        for r in range(g * rows_per_group, (g + 1) * rows_per_group):
            new = s_ref[0, r] * da_ref[0, r:r + 1, :] \
                + bcol * xdt_ref[0, r:r + 1, :]                 # [N, q*P]
            o_ref[0, r] = new
            y_ref[0, r:r + 1, :] = jnp.sum(new * ccol, axis=0,
                                           keepdims=True)


def _ssm_state_step_32(state, layer, da, xdt, bt, ct, num_slots,
                       rows_per_group):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S = num_slots
    _, rows, N, lanes = state.shape
    G = bt.shape[2]
    meta = jnp.asarray(layer, jnp.int32).reshape(1) * jnp.int32(S)

    def own(s, meta_ref):
        return (s, 0, 0)

    def tile(s, meta_ref):
        return (meta_ref[0] + s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, rows, lanes), own),
            pl.BlockSpec((1, rows, lanes), own),
            pl.BlockSpec((1, N, G), own),
            pl.BlockSpec((1, N, G), own),
            pl.BlockSpec((1, rows, N, lanes), tile),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, lanes), own),
            pl.BlockSpec((1, rows, N, lanes), tile),
        ],
    )
    kernel = functools.partial(_ssm_step_kernel,
                               rows_per_group=rows_per_group)
    y, state = pl.pallas_call(
        kernel, name="ssm_decode_step", grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state (argument 5, after the prefetched scalar) is updated
        # in place: rows of other layers are never touched
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=_FORCE_INTERPRET[0],
    )(meta, da, xdt, bt, ct, state)
    return state, y


def ssm_state_step(state, layer, xs, dt, A, B, C, num_slots):
    """``ssm_state_step_jnp`` as the Pallas kernel ``ssm_decode_step``:
    same signature, same numbers; a slot's state is read once and
    written once, in place."""
    S, H, P = xs.shape
    G, N = B.shape[1:]
    f32 = jnp.float32
    rows, _, lanes = packed_shape(H, P, N, G)
    q = H // rows
    # per-channel factors as lane vectors of the packed rows
    da = jnp.exp(dt * A.astype(f32)[None, :])                   # [S, H]
    da = jnp.broadcast_to(da[:, :, None], (S, H, P)).reshape(S, rows, lanes)
    xdt = (xs.astype(f32) * dt[:, :, None]).reshape(S, rows, lanes)
    bt = B.astype(f32).transpose(0, 2, 1)                    # [S, N, G]
    ct = C.astype(f32).transpose(0, 2, 1)
    state, y = _trace_32bit(_ssm_state_step_32)(
        state, layer, da, xdt, bt, ct, num_slots, (H // G) // q)
    return state, y.reshape(S, H, P)


def kernel_viable(num_heads, head_dim, state_size, num_groups):
    """Static facts Mosaic needs: the packed rows fill the lanes, the
    state size is whole sublane tiles, a slot's state fits a block."""
    rows, n, lanes = packed_shape(num_heads, head_dim, state_size,
                                  num_groups)
    return lanes % 128 == 0 and n % 8 == 0 \
        and rows * n * lanes * 4 <= _SLOT_STATE_BYTES


def ssm_decode_step(conv, state, layer, u, dt, A, split, conv_w, conv_b,
                    num_slots, active, kernel=False):
    """One token a slot through layer ``layer``'s convolution and
    recurrence. conv ``[layers, S, (K-1) * ch]`` and state ``[layers *
    S, H / q, N, q * P]`` are every state-space layer's, carried whole;
    u ``[S, ch]`` the new convolution inputs; dt ``[S, H]`` float32
    after softplus; ``split(act) -> (xs [S, H, P], B, C [S, G, N])``
    splits the activated channels; ``active [S]`` bool: a slot that is
    not keeps its window and its state. Returns (conv, state, xs, y
    ``[S, H, P]`` float32)."""
    window = jax.lax.dynamic_index_in_dim(conv, layer, keepdims=False)
    act, new = conv_decode(window, u, conv_w, conv_b)
    new = jnp.where(active[:, None], new, window)
    conv = jax.lax.dynamic_update_index_in_dim(conv, new, layer, axis=0)
    xs, B, C = split(act)
    dt = jnp.where(active[:, None], dt, jnp.float32(0))
    step = ssm_state_step if kernel else ssm_state_step_jnp
    state, y = step(state, layer, xs, dt, A, B, C, num_slots)
    return conv, state, xs, y
