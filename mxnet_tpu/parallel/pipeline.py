"""Pipeline parallelism: GPipe-style microbatching over a 'pp' mesh axis.

The reference's closest analog is manual layer placement with
`_CrossDeviceCopy` inserts (group2ctx model parallelism,
`src/executor/graph_executor.cc:411`) — activations hop devices but stages
run serially.  This module provides true pipelining as a first-class
capability: stage weights live sharded on the 'pp' axis (one stage per
mesh slice), activations advance stage-to-stage with `lax.ppermute`, and
microbatches fill the pipeline so all stages compute concurrently after
warm-up (bubble = (S-1)/(M+S-1)).

SPMD formulation (scaling-book recipe): ONE traced program for all
devices; `lax.axis_index('pp')` selects per-device behavior; XLA lowers
the ppermute to ICI neighbor exchanges.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _resolve(mesh, who: str) -> Mesh:
    """mesh=None -> ambient current_mesh(), typed error when neither is
    set (the island-unification rule shared across parallel/)."""
    from ..base import MXNetError
    from .mesh import resolve_mesh
    mesh = resolve_mesh(mesh)
    if mesh is None:
        raise MXNetError(
            f"{who} needs a mesh: pass mesh=, or install an ambient one "
            "(parallel.mesh.set_current_mesh / use_mesh / "
            "MXNET_MESH_BATCH / MXNET_MESH_MODEL)")
    return mesh


def gpipe(stage_fn: Callable, stage_params, x, n_microbatches: int,
          axis_name: str = "pp"):
    """Run a pipeline of `axis_size` identical-signature stages (call
    inside shard_map).

    stage_fn(params, h) -> h      one stage's computation
    stage_params                  THIS device's stage weights (pytree)
    x: (B, ...) local batch; B % n_microbatches == 0.  Activations keep
    shape (B/M, ...) across stages.

    Returns the last stage's outputs for the full batch, replicated to
    every pp rank (psum of the masked accumulation).
    """
    n = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    M = n_microbatches
    assert x.shape[0] % M == 0, (x.shape, M)
    micro = x.reshape((M, x.shape[0] // M) + x.shape[1:])
    perm = [(i, (i + 1) % n) for i in range(n)]
    outputs = jnp.zeros(micro.shape, x.dtype)
    state = jnp.zeros(micro.shape[1:], x.dtype)

    def body(t, carry):
        state, outputs = carry
        # stage 0 ingests microbatch t (clamped during drain); others take
        # the activation handed over by the previous stage
        inp = jnp.where(stage == 0, micro[jnp.minimum(t, M - 1)], state)
        out = stage_fn(stage_params, inp)
        # the last stage finishes microbatch t-(n-1); park invalid writes
        # out of bounds (mode="drop")
        mb = t - (n - 1)
        w_idx = jnp.where((stage == n - 1) & (mb >= 0), jnp.maximum(mb, 0), M)
        outputs = outputs.at[w_idx].set(out, mode="drop")
        state = lax.ppermute(out, axis_name, perm)
        return state, outputs

    state, outputs = lax.fori_loop(0, M + n - 1, body, (state, outputs),
                                   unroll=True)
    # only the last stage holds real outputs; replicate across the axis
    outputs = jnp.where(stage == n - 1, outputs, 0)
    outputs = lax.psum(outputs, axis_name)
    return outputs.reshape(x.shape)


def gpipe_sharded(stage_fn: Callable, stacked_params, x,
                  mesh: Optional[Mesh] = None,
                  n_microbatches: int = 4, axis_name: str = "pp"):
    """Convenience wrapper: `stacked_params` leaves have a leading axis of
    size mesh.shape[axis_name] (one slice per stage); x is replicated.
    ``mesh=None`` resolves the ambient current_mesh()."""
    mesh = _resolve(mesh, "gpipe_sharded")
    return _gpipe_fn(stage_fn, mesh, n_microbatches, axis_name,
                     jax.tree_util.tree_structure(stacked_params))(
        stacked_params, x)


def _stage_specs(treedef, axis_name):
    return jax.tree_util.tree_unflatten(
        treedef, [P(axis_name)] * treedef.num_leaves)


@functools.lru_cache(maxsize=32)
def _gpipe_fn(stage_fn, mesh, n_microbatches, axis_name, treedef):
    """The jitted shard_map'd forward, built once per (stage_fn, mesh,
    M, axis, parameter tree) -- the idiom of
    sequence_parallel._sharded_fn: a bare shard_map bound on concrete
    arrays compiles its body primitive by primitive at every call."""
    def per_device(params, xs):
        squeezed = jax.tree_util.tree_map(lambda a: a[0], params)
        return gpipe(stage_fn, squeezed, xs, n_microbatches, axis_name)

    return jax.jit(shard_map(  # graft-lint: disable=retrace-hazard
        per_device, mesh=mesh,
        in_specs=(_stage_specs(treedef, axis_name), P()),
        out_specs=P(), check_vma=False))


def pipeline_1f1b(stage_fn: Callable, stage_params, x, y, loss_fn: Callable,
                  n_microbatches: int, n_stages: int, axis_name: str = "pp"):
    """1F1B (PipeDream-flush) pipeline TRAINING step — call inside shard_map.

    Unlike `gpipe` + outer AD (which keeps all M microbatch activations
    live until the flush), 1F1B starts each microbatch's backward as soon
    as the last stage finishes its forward, so only O(pipeline_depth)
    activations are ever stashed — memory is bounded by 2S-1 microbatch
    inputs regardless of M.  The backward recomputes the stage forward
    from the stashed INPUT (rematerialization — the
    `MXNET_BACKWARD_DO_MIRROR` trade, graph_executor.cc:282-305, applied
    per stage), so the stash holds inputs only, not residuals.

    Schedule (tick t, stage s, S stages, M microbatches):
      forward  of microbatch m runs at t = m + s
      backward of microbatch m runs at t = m + 2(S-1) - s + 1
    so the activation cotangent computed by stage s+1 at tick T arrives at
    stage s (ppermute down) exactly at its backward tick T+1, and the last
    stage alternates F,B,F,B — the 1F1B steady state.  Total 2(M+S-1)
    ticks.

    stage_fn(params, h) -> h          one stage
    loss_fn(out, y_mb) -> scalar      per-microbatch loss (last stage)
    Returns (loss_sum_over_microbatches, param_grads) for THIS stage.
    """
    S = n_stages
    M = n_microbatches
    s = lax.axis_index(axis_name)
    assert x.shape[0] % M == 0, (x.shape, M)
    mb = x.shape[0] // M
    micro = x.reshape((M, mb) + x.shape[1:])
    ymicro = y.reshape((M, mb) + y.shape[1:])
    cap = 2 * S - 1
    up = [(i, (i + 1) % S) for i in range(S)]
    down = [((i + 1) % S, i) for i in range(S)]

    act_shape = (mb,) + x.shape[1:]
    act_in0 = jnp.zeros(act_shape, x.dtype)
    cot_in0 = jnp.zeros(act_shape, x.dtype)
    stash0 = jnp.zeros((cap,) + act_shape, x.dtype)
    grads0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), stage_params)

    def body(t, carry):
        act_in, cot_in, stash, grads, loss_acc = carry
        fwd_m = t - s
        do_fwd = (fwd_m >= 0) & (fwd_m < M)
        bwd_m = t - (2 * (S - 1) - s + 1)
        do_bwd = (bwd_m >= 0) & (bwd_m < M)
        fwd_idx = jnp.clip(fwd_m, 0, M - 1)
        bwd_idx = jnp.clip(bwd_m, 0, M - 1)

        # read the backward's stashed input BEFORE the forward overwrites
        # its ring slot: stage 0's in-flight window is exactly `cap` ticks,
        # so microbatch m+cap lands in m's slot on m's backward tick
        h_st = stash[bwd_idx % cap]

        # ---- forward tick: stage 0 ingests microbatch fwd_m, others take
        # the activation handed over by the previous stage
        h_in = jnp.where(s == 0, micro[fwd_idx], act_in)
        out = stage_fn(stage_params, h_in)
        stash = stash.at[jnp.where(do_fwd, fwd_m % cap, cap)].set(
            h_in, mode="drop")

        # ---- backward tick: recompute forward from the stashed input,
        # seed the cotangent (last stage: from the loss; others: from the
        # next stage's ppermute) and pull grads through the stage vjp
        o2, vjp = jax.vjp(stage_fn, stage_params, h_st)
        loss_m, loss_vjp = jax.vjp(lambda o: loss_fn(o, ymicro[bwd_idx]), o2)
        seed = loss_vjp(jnp.ones((), loss_m.dtype))[0]
        g_in = jnp.where(s == S - 1, seed.astype(cot_in.dtype), cot_in)
        dp, dh = vjp(g_in)
        # NaN-safe masking: a vjp evaluated on a zero-initialized stash may
        # be non-finite (sqrt/log at 0) and 0*inf would poison the sum
        grads = jax.tree_util.tree_map(
            lambda a, b: a + jnp.where(do_bwd, b.astype(jnp.float32), 0.0),
            grads, dp)
        loss_acc = loss_acc + jnp.where(
            do_bwd & (s == S - 1), loss_m.astype(jnp.float32), 0.0)

        act_in = lax.ppermute(out, axis_name, up)
        cot_in = lax.ppermute(dh, axis_name, down)
        return act_in, cot_in, stash, grads, loss_acc

    T = 2 * (M + S - 1)
    carry = (act_in0, cot_in0, stash0, grads0, jnp.zeros((), jnp.float32))
    _, _, _, grads, loss_acc = lax.fori_loop(0, T, body, carry)
    loss = lax.psum(loss_acc, axis_name)  # lives on the last stage only
    # grads accumulate in f32; hand back in param dtype so the two
    # schedules are drop-in interchangeable (gpipe returns param dtype)
    grads = jax.tree_util.tree_map(lambda g, p: g.astype(p.dtype),
                                   grads, stage_params)
    return loss, grads


def pipeline_train_step(stage_fn: Callable, stacked_params, x, y,
                        loss_fn: Callable, mesh: Optional[Mesh] = None,
                        n_microbatches: int = 4,
                        schedule: str = "1f1b", axis_name: str = "pp"):
    """One pipeline-parallel training step over the mesh's `axis_name`.

    schedule='gpipe': forward via the GPipe fill-drain loop, backward via
    outer AD (all microbatch activations live — reference-style mirror
    memory).  schedule='1f1b': bounded-memory 1F1B above.

    Both return (loss, grads) where loss = SUM over microbatches of
    loss_fn(out_mb, y_mb) and grads has the same stage-stacked layout as
    `stacked_params` (leading axis = n_stages, sharded on the pp axis).
    ``mesh=None`` resolves the ambient current_mesh().
    """
    mesh = _resolve(mesh, "pipeline_train_step")
    M = n_microbatches
    if schedule == "gpipe":
        def total_loss(params):
            out = gpipe_sharded(stage_fn, params, x, mesh, M, axis_name)
            outs = out.reshape((M, out.shape[0] // M) + out.shape[1:])
            ys = y.reshape((M, y.shape[0] // M) + y.shape[1:])
            losses = jax.vmap(loss_fn)(outs, ys)
            return jnp.sum(losses)

        return jax.value_and_grad(total_loss)(stacked_params)
    if schedule != "1f1b":
        raise ValueError(f"unknown pipeline schedule '{schedule}'")
    return _1f1b_fn(stage_fn, loss_fn, mesh, M, axis_name,
                    jax.tree_util.tree_structure(stacked_params))(
        stacked_params, x, y)


@functools.lru_cache(maxsize=32)
def _1f1b_fn(stage_fn, loss_fn, mesh, M, axis_name, treedef):
    """The jitted shard_map'd 1F1B step, built once per key (see
    _gpipe_fn)."""
    S = mesh.shape[axis_name]

    def per_device(params, xs, ys):
        squeezed = jax.tree_util.tree_map(lambda a: a[0], params)
        loss, grads = pipeline_1f1b(stage_fn, squeezed, xs, ys, loss_fn,
                                    M, S, axis_name)
        return loss, jax.tree_util.tree_map(lambda g: g[None], grads)

    specs = _stage_specs(treedef, axis_name)
    return jax.jit(shard_map(  # graft-lint: disable=retrace-hazard
        per_device, mesh=mesh, in_specs=(specs, P(), P()),
        out_specs=(P(), specs), check_vma=False))
