"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO long-context parallelism (SURVEY.md §5 — 2017 codebase,
attention absent); this module provides it as the new first-class capability:
  - ring_attention: K/V blocks rotate around the mesh axis via
    `lax.ppermute` while each device keeps its Q shard; softmax is computed
    online (flash-style max/sum accumulators), so sequence length scales with
    the number of devices at O(block²) memory per device.
  - ulysses_attention: `lax.all_to_all` re-shards from sequence-parallel to
    head-parallel, runs dense local attention, and re-shards back.

Both are traceable and compose with jit/shard_map over a Mesh('sp') axis —
collectives ride ICI.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..base import MXNetError

NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """One attention block: returns (unnormalized_out, row_sum, row_max).
    q: (B,H,Tq,D) k/v: (B,H,Tk,D); mask broadcastable to (B,H,Tq,Tk)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o, l, m


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over sequence-sharded q/k/v (call inside shard_map).

    Shapes per device: (batch, heads, seq_local, head_dim).  The global
    sequence is the concatenation over the mesh axis in axis-index order.
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    Tq = q.shape[2]
    Tk = k.shape[2]
    B, H = q.shape[0], q.shape[1]
    acc_o = jnp.zeros(q.shape, jnp.float32)
    acc_l = jnp.zeros((B, H, Tq), jnp.float32)
    acc_m = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        acc_o, acc_l, acc_m, k_cur, v_cur = carry
        src = (my - i) % n  # shard index of k_cur/v_cur
        if causal:
            q_pos = my * Tq + jnp.arange(Tq)
            k_pos = src * Tk + jnp.arange(Tk)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = mask[None, None]
        else:
            mask = None
        o, l, m = _block_attn(q.astype(jnp.float32), k_cur.astype(jnp.float32),
                              v_cur.astype(jnp.float32), scale, mask)
        m_new = jnp.maximum(acc_m, m)
        corr_old = jnp.exp(acc_m - m_new)
        corr_new = jnp.exp(m - m_new)
        acc_o = acc_o * corr_old[..., None] + o * corr_new[..., None]
        acc_l = acc_l * corr_old + l * corr_new
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return acc_o, acc_l, m_new, k_nxt, v_nxt

    acc_o, acc_l, acc_m, _, _ = lax.fori_loop(
        0, n, body, (acc_o, acc_l, acc_m, k, v))
    out = acc_o / jnp.maximum(acc_l[..., None], 1e-30)
    return out.astype(q.dtype)


@functools.lru_cache(maxsize=64)
def _sharded_fn(kind, mesh: Mesh, axis_name: str, causal, scale):
    """Build (and CACHE) the jitted shard_map'd callable, one per
    (kind, mesh, axis, causal, scale).  Both halves matter: jax's
    dispatch cache is keyed on callable identity, so a fresh partial
    per call would retrace every step of a decode loop; and a bare
    shard_map called on concrete arrays runs its body primitive by
    primitive, compiling each one anew at EVERY call (a 12-position
    decode of a 2-layer model compiled 2,383 programs of 105
    signatures) -- under jax.jit the body is one program per input
    shape, loaded once."""
    spec = P(None, None, axis_name, None)
    rspec = P()
    if kind in ("ring", "ulysses"):
        body = ring_attention if kind == "ring" else ulysses_attention
        body = functools.partial(body, axis_name=axis_name,
                                 causal=causal, scale=scale)
        in_specs, out_specs = (spec, spec, spec), spec
    elif kind == "ulysses_decode":
        hspec = P(None, axis_name, None, None)    # head-sharded caches
        body = functools.partial(ulysses_decode_step,
                                 axis_name=axis_name, scale=scale)
        in_specs = (rspec, rspec, rspec, hspec, hspec, rspec)
        out_specs = (P(None, axis_name, None), hspec, hspec)
    else:
        body = functools.partial(ring_decode_step, axis_name=axis_name,
                                 scale=scale)
        in_specs = (rspec, rspec, rspec, spec, spec, rspec)
        out_specs = (rspec, spec, spec)
    # one jit per lru_cache entry, module-lifetime: built once, reused
    return jax.jit(shard_map(body, mesh=mesh, in_specs=in_specs,  # graft-lint: disable=retrace-hazard
                             out_specs=out_specs, check_vma=False))


def _resolve(mesh, who: str) -> Mesh:
    """mesh=None -> the ambient parallel.mesh.current_mesh(), raising a
    typed error when neither is set — the one island-unification rule
    (every parallel island resolves its mesh the same way)."""
    from .mesh import resolve_mesh
    mesh = resolve_mesh(mesh)
    if mesh is None:
        raise MXNetError(
            f"{who} needs a mesh: pass mesh=, or install an ambient one "
            "(parallel.mesh.set_current_mesh / use_mesh / "
            "MXNET_MESH_BATCH / MXNET_MESH_MODEL)")
    return mesh


def ring_attention_sharded(q, k, v, mesh: Optional[Mesh] = None,
                           axis_name: str = "sp",
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Convenience wrapper: shard (B,H,T,D) arrays on T and run the ring."""
    mesh = _resolve(mesh, "ring_attention_sharded")
    return _sharded_fn("ring", mesh, axis_name, bool(causal), scale)(q, k, v)


def single_device_of(a):
    """The one device an eager array is committed to, else None."""
    devs = list(a.devices()) if hasattr(a, "devices") else []
    return devs[0] if len(devs) == 1 else None


def place_on_mesh(mesh: Mesh, arrays, spec=None):
    """device_put each array onto `mesh` under PartitionSpec(*spec)
    (replicated when spec is None) — the one eager-placement
    implementation the sp ops share."""
    sh = NamedSharding(mesh, P(*spec) if spec else P())
    # transient mesh staging shared by the sp ops (see ops/registry)
    return tuple(jax.device_put(a, sh) if hasattr(a, "devices") else a  # graft-lint: disable=memory-hygiene
                 for a in arrays)


def ring_decode_step(q, k, v, kc, vc, pos, axis_name: str = "sp",
                     scale: Optional[float] = None):
    """One autoregressive decode step over SEQUENCE-SHARDED K/V caches
    (call inside shard_map) — the long-context decode counterpart of
    ring_attention: a context too large for one device's cache decodes
    without ever materializing it on one chip.

    Per device: q/k/v (B, H, dh) replicated — the current token's
    projections; kc/vc (B, H, T_local, dh) this device's cache columns
    (global sequence = concatenation over the axis in index order);
    pos (1,) the current position t.  The owner shard writes K/V at
    its local column; attention over all columns <= t runs as a
    distributed softmax — lax.pmax for the global row max, lax.psum
    for numerator/denominator — so ICI carries only the softmax stats
    (B, H) and the combined values (B, H, dh), never cache blocks.
    """
    my = lax.axis_index(axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    Tl = kc.shape[2]
    t = pos.astype(jnp.int32).reshape(())
    loc = t - my * Tl
    in_range = jnp.logical_and(loc >= 0, loc < Tl)
    locc = jnp.clip(loc, 0, Tl - 1)
    zero = jnp.zeros((), jnp.int32)
    upd_k = lax.dynamic_update_slice(
        kc, k[:, :, None, :].astype(kc.dtype), (zero, zero, locc, zero))
    upd_v = lax.dynamic_update_slice(
        vc, v[:, :, None, :].astype(vc.dtype), (zero, zero, locc, zero))
    kc = jnp.where(in_range, upd_k, kc)
    vc = jnp.where(in_range, upd_v, vc)
    col = my * Tl + jnp.arange(Tl)
    s = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32) * scale,
                   kc.astype(jnp.float32))
    s = jnp.where(col[None, None, :] <= t, s, NEG_INF)
    m = lax.pmax(jnp.max(s, axis=-1), axis_name)          # (B, H)
    p = jnp.exp(s - m[..., None])
    denom = lax.psum(jnp.sum(p, axis=-1), axis_name)      # (B, H)
    num = lax.psum(jnp.einsum("bht,bhtd->bhd", p,
                              vc.astype(jnp.float32)), axis_name)
    out = num / jnp.maximum(denom[..., None], 1e-30)
    return out.astype(q.dtype), kc, vc


def ring_decode_step_sharded(q, k, v, kc, vc, pos, mesh: Mesh,
                             axis_name: str = "sp",
                             scale: Optional[float] = None):
    """Convenience wrapper: caches sharded on their T axis, q/k/v/pos
    replicated; returns (out (B,H,dh), new kc, new vc) with the caches
    still sharded."""
    return _sharded_fn("ring_decode", mesh, axis_name, False,
                       scale)(q, k, v, kc, vc, pos)


def ulysses_decode_step(q, k, v, kc, vc, pos, axis_name: str = "sp",
                        scale: Optional[float] = None):
    """One autoregressive decode step over HEAD-SHARDED K/V caches
    (call inside shard_map) — the Ulysses decode counterpart: each
    device owns H/n full-length head caches, so attention is entirely
    local per head (ordinary softmax, no distributed combine); the
    mesh reassembles the head axis in the outputs.

    Per device: q/k/v (B, H, dh) replicated; kc/vc (B, H/n, Tmax, dh)
    this device's head block (heads = concatenation over the axis in
    index order); pos (1,).
    """
    my = lax.axis_index(axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    Hl = kc.shape[1]
    t = pos.astype(jnp.int32).reshape(())
    zero = jnp.zeros((), jnp.int32)
    start = my * Hl
    qh = lax.dynamic_slice_in_dim(q, start, Hl, axis=1)   # (B, Hl, dh)
    kh = lax.dynamic_slice_in_dim(k, start, Hl, axis=1)
    vh = lax.dynamic_slice_in_dim(v, start, Hl, axis=1)
    kc = lax.dynamic_update_slice(
        kc, kh[:, :, None, :].astype(kc.dtype), (zero, zero, t, zero))
    vc = lax.dynamic_update_slice(
        vc, vh[:, :, None, :].astype(vc.dtype), (zero, zero, t, zero))
    s = jnp.einsum("bhd,bhtd->bht", qh.astype(jnp.float32) * scale,
                   kc.astype(jnp.float32))
    s = jnp.where(jnp.arange(kc.shape[2])[None, None, :] <= t, s,
                  NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bhtd->bhd", w, vc.astype(jnp.float32))
    return out.astype(q.dtype), kc, vc


def ulysses_decode_step_sharded(q, k, v, kc, vc, pos, mesh: Mesh,
                                axis_name: str = "sp",
                                scale: Optional[float] = None):
    """Caches sharded on their HEAD axis, q/k/v/pos replicated; the
    out_spec reassembles (B, H, dh) from the per-shard head blocks."""
    return _sharded_fn("ulysses_decode", mesh, axis_name, False,
                       scale)(q, k, v, kc, vc, pos)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      scale: Optional[float] = None):
    """Ulysses sequence parallelism (call inside shard_map).

    Input: (B, H, T_local, D) sequence-sharded.  all_to_all → (B, H/n,
    T_global, D) head-sharded, dense attention locally, all_to_all back.
    Requires heads % mesh_axis_size == 0.
    """
    n = lax.psum(1, axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    # (B,H,Tl,D) -> (B,H/n,Tg,D): split heads, concat sequence
    qg = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    Tg = qg.shape[2]
    mask = None
    if causal:
        pos = jnp.arange(Tg)
        mask = (pos[:, None] >= pos[None, :])[None, None]
    o, l, m = _block_attn(qg.astype(jnp.float32), kg.astype(jnp.float32),
                          vg.astype(jnp.float32), scale, mask)
    o = o / jnp.maximum(l[..., None], 1e-30)
    # back to sequence-sharded full heads
    out = lax.all_to_all(o.astype(q.dtype), axis_name, split_axis=2,
                         concat_axis=1, tiled=True)
    return out


def ulysses_attention_sharded(q, k, v, mesh: Optional[Mesh] = None,
                              axis_name: str = "sp",
                              causal: bool = False,
                              scale: Optional[float] = None):
    mesh = _resolve(mesh, "ulysses_attention_sharded")
    return _sharded_fn("ulysses", mesh, axis_name, bool(causal),
                       scale)(q, k, v)


# -- ambient sequence-parallel scope (user-facing product surface) ---------
# The gluon/symbol route into sequence parallelism: ops can't take a Mesh
# as an attribute, so the mesh is ambient — set it around model CALLS
# (trace time; CachedOp/executors capture it in the compiled program):
#
#     with parallel.sp_scope(mesh):
#         net = TransformerLM(..., attn_type="ring")
#         out = net(tokens)          # attention runs ring over 'sp'
#
import threading

_SP_TLS = threading.local()  # per-thread scope stack (concurrent traces
                             # must not observe each other's mesh)


def _sp_stack():
    if not hasattr(_SP_TLS, "stack"):
        _SP_TLS.stack = []
    return _SP_TLS.stack


class sp_scope:
    """Context manager declaring the mesh (and axis name) that
    impl='ring'/'ulysses' attention ops shard the sequence over."""

    def __init__(self, mesh: Optional[Mesh] = None, axis_name: str = "sp"):
        mesh = _resolve(mesh, "sp_scope")
        if axis_name not in mesh.axis_names:
            raise MXNetError(
                f"sp_scope: mesh has axes {mesh.axis_names}, no "
                f"'{axis_name}'")
        self._entry = (mesh, axis_name)

    def __enter__(self):
        _sp_stack().append(self._entry)
        return self._entry[0]

    def __exit__(self, *exc):
        _sp_stack().pop()
        return False


def current_sp_scope():
    """The innermost (mesh, axis_name), or a loud error — the op-level
    route (ops/flash_attention.py impl='ring'/'ulysses') calls this at
    trace time."""
    stack = _sp_stack()
    if not stack:
        raise MXNetError(
            "sequence-parallel attention (impl='ring'/'ulysses') needs "
            "an active parallel.sp_scope(mesh) around the model call "
            "that traces the graph")
    return stack[-1]
