"""Expert parallelism: switch-style MoE with all_to_all token dispatch.

Absent from the reference (SURVEY.md §2.3: EP ❌); provided here as a
first-class capability.  One (or more) experts live on each slice of the
'ep' mesh axis; tokens are routed top-1 to experts, packed into fixed
capacity slots (static shapes — XLA-friendly), exchanged with
`lax.all_to_all` over ICI, transformed by the local expert, and combined
back weighted by the gate probability.  The load-balancing auxiliary loss
follows the Switch Transformer formulation.

How this differs from the expert layer the model zoo trains
(`ops/decoder.py` `moe_ffn`, `gluon.model_zoo.decoder.MoEFeedForward`):
here the router is a softmax with top-1/2 choices, tokens go into capacity
slots and those beyond a slot's capacity are DROPPED, there is one expert
a device, and the layer is a raw `shard_map` function that neither
`Trainer` nor `CachedOp` sees.  `moe_ffn` routes over all experts with
sigmoid scores and a selection bias, holds any number of experts, drops
nothing (grouped products over a ragged split) and runs inside the normal
step; it computes one device's share, and is what an expert-parallel step
wraps its exchange around (ROADMAP R5).
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


def topk_moe(x, gate_w, expert_fn: Callable, expert_params,
             axis_name: str = "ep", capacity_factor: float = 2.0,
             k: int = 1, normalize_gates: bool = True):
    """Top-k MoE layer (call inside shard_map).  k=1 is Switch routing;
    k=2 is the GShard formulation (gates renormalized over the selected
    experts, first choices take capacity priority over second choices).

    x: (T, D) local tokens; gate_w: (D, E) router weights (replicated),
    E == axis size; expert_params: THIS device's expert weights.
    Returns (y: (T, D), aux_loss: scalar load-balancing loss).
    """
    T, D = x.shape
    logits = x @ gate_w                       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    E = probs.shape[-1]
    gates, eidx = lax.top_k(probs, k)         # (T, k) each
    if normalize_gates and k > 1:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)

    C = max(1, int(capacity_factor * T / E))
    onehot = jax.nn.one_hot(eidx, E, dtype=x.dtype)          # (T, k, E)
    # queue position of every (token, choice) within its expert: count in
    # choice-major order so ALL first choices outrank any second choice
    flat = jnp.swapaxes(onehot, 0, 1).reshape(k * T, E)      # (k*T, E)
    fpos = (jnp.cumsum(flat, axis=0) - 1.0) * flat
    pos = jnp.swapaxes(fpos.reshape(k, T, E), 0, 1)          # (T, k, E)
    keep = (pos < C).astype(x.dtype) * onehot
    slot = jax.nn.one_hot(pos.sum(-1).astype(jnp.int32), C,
                          dtype=x.dtype)                     # (T, k, C)
    # (T, E, C): ≤1 slot per (token, choice); choices hit distinct experts
    dispatch = jnp.einsum("tke,tkc->tec", keep, slot)

    # pack: (E, C, D) — expert e's capacity slots filled with local tokens
    packed = jnp.einsum("td,tec->ecd", x, dispatch)
    # exchange: row e goes to device e; afterwards axis 0 indexes the
    # SOURCE device and every row holds tokens for MY expert
    recv = lax.all_to_all(packed, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                        # (E, C, D)
    out = expert_fn(expert_params, recv.reshape(-1, D)).reshape(recv.shape)
    # return each processed token to its owner
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                        # (E, C, D)
    combine = jnp.einsum("tke,tkc,tk->tec", keep, slot, gates)
    y = jnp.einsum("ecd,tec->td", back, combine)

    # Switch/GShard load-balance loss over FIRST choices:
    # E * Σ_e (fraction routed to e)(mean prob e)
    frac = jnp.mean(onehot[:, 0, :], axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return y, aux


def switch_moe(x, gate_w, expert_fn: Callable, expert_params,
               axis_name: str = "ep", capacity_factor: float = 2.0):
    """Top-1 (Switch) MoE — see `topk_moe`."""
    return topk_moe(x, gate_w, expert_fn, expert_params, axis_name,
                    capacity_factor, k=1)


def switch_moe_sharded(x, gate_w, expert_fn: Callable, stacked_expert_params,
                       mesh: Optional[Mesh] = None, axis_name: str = "ep",
                       capacity_factor: float = 2.0, k: int = 1):
    """Wrapper: tokens sharded on 'ep' (data-parallel over the same axis),
    expert weights stacked on a leading axis of size mesh.shape[axis_name].
    ``mesh=None`` resolves the ambient current_mesh()."""
    mesh = _resolve(mesh, "switch_moe_sharded")
    return _moe_fn(expert_fn, mesh, axis_name, float(capacity_factor), k,
                   jax.tree_util.tree_structure(stacked_expert_params))(
        x, gate_w, stacked_expert_params)


@functools.lru_cache(maxsize=32)
def _moe_fn(expert_fn, mesh, axis_name, capacity_factor, k, treedef):
    """The jitted shard_map'd layer, built once per (expert_fn, mesh,
    axis, capacity, k, parameter tree) -- the idiom of
    sequence_parallel._sharded_fn: a bare shard_map bound on concrete
    arrays compiles its body primitive by primitive at every call."""
    def per_device(xs, gw, params):
        squeezed = jax.tree_util.tree_map(lambda a: a[0], params)
        y, aux = topk_moe(xs, gw, expert_fn, squeezed, axis_name,
                          capacity_factor, k=k)
        return y, lax.pmean(aux, axis_name)

    return jax.jit(shard_map(  # graft-lint: disable=retrace-hazard
        per_device, mesh=mesh,
        in_specs=(P(axis_name), P(),
                  jax.tree_util.tree_unflatten(
                      treedef, [P(axis_name)] * treedef.num_leaves)),
        out_specs=(P(axis_name), P()), check_vma=False))
