"""Compiled pipeline parallelism over the 'pp' mesh axis.

TPU-native replacement for the reference 1F1B pipeline engine (reference:
paddle/fluid/framework/section_worker.cc:34 SectionWorker schedule_mode_==1,
fleet/meta_parallel/pp_utils/p2p_communication.py send/recv over NCCL p2p).

Design: instead of per-stage processes exchanging activations with p2p ops,
all pp devices run ONE compiled SPMD program (shard_map over 'pp'). Stage
parameters are stacked on a leading pp-sharded axis so device i holds stage
i's weights. The schedule is a lax.scan over M + P - 1 ticks; each tick
every device runs its stage on the microbatch in flight and the activation
ring advances with lax.ppermute (ICI neighbor transfer, overlapped by XLA's
latency-hiding scheduler). Backward is jax autodiff of the scan — the
reversed scan with reversed ppermute IS the pipeline backward pass, giving
1F1B-equivalent gradient accumulation without hand-written scheduling.
Memory: pass remat=True to checkpoint each tick (recompute in backward),
the analogue of the reference's per-microbatch scope recycling.
"""
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stack_stage_params(per_stage_params):
    """[stage][pytree] -> pytree with leading stage axis (to shard on pp)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def pipeline_apply(stage_fn, stacked_params, x_microbatches, mesh,
                   axis_name="pp", remat=True):
    """Run the pipelined stack.

    stage_fn(params_slice, x) -> y     homogeneous per-stage computation
    stacked_params: pytree, leading dim P (stage), sharded over axis_name
    x_microbatches: [M, ...mb shape...] microbatched inputs (replicated)

    Returns [M, ...] outputs of the final stage (replicated).
    """
    pp = int(mesh.shape[axis_name])
    m = x_microbatches.shape[0]
    if pp == 1:
        params0 = jax.tree.map(lambda a: a[0], stacked_params)
        return jax.vmap(lambda xb: stage_fn(params0, xb))(x_microbatches)

    def body(local_params, xs):
        # local_params: leading dim 1 (this device's stage); xs: [M, ...]
        params = jax.tree.map(lambda a: a[0], local_params)
        idx = jax.lax.axis_index(axis_name)
        ticks = m + pp - 1
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]

        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)      # activation arriving
        outs = jnp.zeros((m,) + mb_shape, xs.dtype)

        def tick(carry, t):
            state, outs = carry
            # stage 0 consumes fresh microbatch t (clamped), others consume
            # the activation that just arrived on the ring
            x_in = jnp.where(idx == 0,
                             xs[jnp.clip(t, 0, m - 1)], state)
            fn = jax.checkpoint(stage_fn) if remat else stage_fn
            y = fn(params, x_in)
            # last stage finished microbatch (t - pp + 1) at this tick
            done_idx = t - (pp - 1)
            is_last = idx == pp - 1
            valid = (done_idx >= 0) & (done_idx < m) & is_last
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(done_idx, 0, m - 1), 0),
                lambda o: o, outs)
            state = jax.lax.ppermute(y, axis_name, perm_fwd)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(ticks))
        # outs live on the last stage only; broadcast to every device so the
        # loss is computable SPMD (sum over the one non-zero contribution)
        outs = jax.lax.psum(
            jnp.where(idx == pp - 1, outs, jnp.zeros_like(outs)), axis_name)
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stacked_params),
                  P()),
        out_specs=P(), check_vma=False)
    return fn(stacked_params, x_microbatches)


def pipeline_blocks_apply(block_fn, stacked_params, valid, h, microbatches,
                          mesh, axis_name="pp", remat=True):
    """Heterogeneous-model middle pipeline (reference: SectionWorker 1F1B,
    section_worker.cc:34, but expressed as ONE compiled SPMD program).

    The model's edge stages (embedding / head / loss) run as plain GSPMD
    ops outside this call; only the repeated homogeneous blocks are
    pipelined — the idiomatic TPU split (praxis-style), since the edge
    stages hold almost no FLOPs and the shared/tied embedding then needs
    no cross-stage weight exchange at all.

    block_fn(params_one_block, h_mb) -> h_mb   one block, same signature
    stacked_params: pytree, leaves [pp, L, ...] — stage-major stacking of
        the blocks' params (L = max blocks per stage, padded); sharded on
        axis_name so device s holds only stage s's block weights.
    valid: bool [pp, L] — False marks padded slots (uneven segmentation).
    h: [B, ...] activations entering the first block; any non-pp sharding
        (dp/mp GSPMD) is preserved — shard_map is manual ONLY over
        axis_name, the rest of the mesh stays in auto (GSPMD) mode.
    microbatches: M; B must divide by M.

    Returns [B, ...] activations after the last block. The schedule is a
    lax.scan over M + pp - 1 ticks with lax.ppermute ring transfers;
    backward through it (jax autodiff) IS the reversed pipeline with
    1F1B-equivalent gradient accumulation.
    """
    pp = int(mesh.shape[axis_name])
    b = h.shape[0]
    m = int(microbatches)
    assert b % m == 0, f"batch {b} must divide microbatches {m}"

    def stage_fn(params, flags, x):
        # scan this stage's own blocks (uneven stages: padded slots are
        # computed-and-discarded via where, keeping shapes static)
        def one(carry, sl):
            p, flag = sl
            y = block_fn(p, carry)
            return jnp.where(flag, y, carry), None

        fn = jax.checkpoint(one) if remat else one
        x, _ = jax.lax.scan(fn, x, (params, flags))
        return x

    if pp == 1:
        params0 = jax.tree.map(lambda a: a[0], stacked_params)
        return stage_fn(params0, valid[0], h)

    xs = h.reshape((m, b // m) + h.shape[1:])

    def body(local_params, local_valid, xs):
        params = jax.tree.map(lambda a: a[0], local_params)
        flags = local_valid[0]
        idx = jax.lax.axis_index(axis_name)
        ticks = m + pp - 1
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outs = carry
            x_in = jnp.where(idx == 0, xs[jnp.clip(t, 0, m - 1)], state)
            y = stage_fn(params, flags, x_in)
            done_idx = t - (pp - 1)
            valid_t = (done_idx >= 0) & (done_idx < m) & (idx == pp - 1)
            outs = jax.lax.cond(
                valid_t,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(done_idx, 0, m - 1), 0),
                lambda o: o, outs)
            state = jax.lax.ppermute(y, axis_name, perm_fwd)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(ticks))
        # outputs live on the last stage; make them SPMD-visible
        outs = jax.lax.psum(
            jnp.where(idx == pp - 1, outs, jnp.zeros_like(outs)), axis_name)
        return outs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), stacked_params),
                  P(axis_name), P()),
        out_specs=P(), axis_names={axis_name}, check_vma=False)
    outs = fn(stacked_params, valid, xs)
    return outs.reshape((b,) + h.shape[1:])


def pipeline_loss_and_grad(stage_fn, loss_fn, stacked_params,
                           x_microbatches, y_microbatches, mesh,
                           axis_name="pp", remat=True):
    """Mean loss over microbatches + grads wrt stacked params — one compiled
    SPMD program; the backward pipeline emerges from autodiff."""

    def total_loss(params):
        outs = pipeline_apply(stage_fn, params, x_microbatches, mesh,
                              axis_name, remat)
        losses = jax.vmap(loss_fn)(outs, y_microbatches)
        return jnp.mean(losses)

    return jax.value_and_grad(total_loss)(stacked_params)
