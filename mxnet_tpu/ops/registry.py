"""Operator registry: single-definition ops that serve both `nd.*` and `sym.*`.

Reference parity: replaces the NNVM op registry + FCompute dispatch
(`include/mxnet/op_attr_types.h`, `src/operator/mxnet_op.h:355-372`) and the
per-op CUDA kernels.  Each op here is ONE pure-JAX forward function; gradients
come from `jax.vjp` (replacing hand-written Backward kernels and the NNVM
`Gradient` pass), and eager execution goes through a cached `jax.jit` per
(op, params) — XLA is the kernel author, fuser, and scheduler.

The registry drives mechanical codegen of `mx.nd.*` and `mx.sym.*` functions
(parity: python/mxnet/ndarray/register.py:31-47 autogen from
MXSymbolListAtomicSymbolCreators).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as _np

from ..base import Arg, MXNetError, ParamSchema

# An op whose output is dear to make and made where a caller's
# `jax.checkpoint` policy cannot see the primitive (a kernel call inside a
# `custom_vjp`) marks it `jax.ad_checkpoint.checkpoint_name(out,
# RESIDUAL_NAME)`: a recorded CachedOp call then keeps it for its backward
# program (gluon/block.py _RESIDUAL_POLICY) and does not run the op again.
RESIDUAL_NAME = "mx_residual"

# name -> Operator
OP_REGISTRY: Dict[str, "Operator"] = {}
# alias -> canonical name
OP_ALIASES: Dict[str, str] = {}


@dataclass
class Operator:
    """One operator definition.

    fn(params: dict, *inputs) -> jax array | tuple of jax arrays
      - params: normalized kwargs (plus '__is_train__' if takes_is_train)
      - inputs: jax arrays (plus a PRNG key appended last if needs_rng)
    """

    name: str
    fn: Callable
    input_names: List[str]
    schema: ParamSchema
    num_outputs: int = 1
    # indices of input_names that are auxiliary states (BatchNorm moving stats):
    # fn must return extra trailing outputs, one per aux input, holding the
    # updated aux value; eager invoke writes them back into the aux NDArrays.
    aux_inputs: List[int] = field(default_factory=list)
    variadic: bool = False          # takes *args (Concat, add_n, stack)
    needs_rng: bool = False         # appends a PRNG key input
    takes_is_train: bool = False    # receives '__is_train__' in params
    mutates_input: Optional[int] = None  # optimizer ops update this input in place
    differentiable: bool = True
    # input positions that stay float32 under reduced-precision training
    # (BN scale/stats — cuDNN contract the reference mirrors; class-id /
    # index inputs where bf16's 8-bit mantissa corrupts ids > 256).
    # infer_type consults this instead of a name-keyed side table.
    f32_inputs: Tuple[int, ...] = ()
    # optional custom vjp: bwd(params, primals, out_grads) -> input grads
    docstring: str = ""
    # `impl` values for which this op runs sequence-parallel shard_map
    # over the ambient sp mesh: eager dispatch and make_vjp must place
    # arrays on the mesh instead of the single-device jit wrapper.
    # Declared by the op itself (flash_attention.py), so a future op
    # whose unrelated 'impl' param happens to say "ring" is unaffected.
    sp_impls: Tuple[str, ...] = ()

    def normalize(self, kwargs) -> Tuple[Tuple[str, Any], ...]:
        return self.schema.normalize(kwargs)

    @property
    def total_outputs(self) -> int:
        return self.num_outputs + len(self.aux_inputs)


def register(name, input_names=("data",), args: Sequence[Arg] = (),
             num_outputs: int = 1, aliases: Sequence[str] = (), **flags):
    """Decorator registering a pure-jax forward as a framework operator."""

    def _reg(fn):
        op = Operator(
            name=name,
            fn=fn,
            input_names=list(input_names),
            schema=ParamSchema(list(args)),
            num_outputs=num_outputs,
            docstring=fn.__doc__ or "",
            **flags,
        )
        if name in OP_REGISTRY:
            raise MXNetError(f"op '{name}' registered twice")
        OP_REGISTRY[name] = op
        for a in aliases:
            OP_ALIASES[a] = name
        _attach_frontends(name, aliases)
        return fn

    return _reg


# Frontend attach hooks: the nd/sym register modules append a
# callback(op_name) here at import time; late registrations (a user op
# registered AFTER import — the docs/faq/new_op.md workflow; parity
# with the reference, where custom creators appear in the enumerated
# op list immediately) replay through them so mx.nd.*/mx.sym.* pick
# the new op up.  Empty during the initial import pass (populate()
# builds the full table then).
FRONTEND_ATTACH_HOOKS: List = []


def _attach_frontends(name, aliases):
    for hook in FRONTEND_ATTACH_HOOKS:
        for nm in (name, *aliases):
            hook(nm)


def get_op(name: str) -> Operator:
    cname = OP_ALIASES.get(name, name)
    if cname not in OP_REGISTRY:
        raise MXNetError(f"operator '{name}' not registered")
    return OP_REGISTRY[cname]


def list_ops() -> List[str]:
    return sorted(OP_REGISTRY) + sorted(OP_ALIASES)


# ---------------------------------------------------------------------------
# Eager execution: cached jit per (op, params)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _jitted(op_name: str, params: Tuple[Tuple[str, Any], ...],
            layout: str = "NCHW"):
    # `layout` is only a cache key: spatial ops trace
    # mxnet_tpu.layout.conv_layout() at trace time, so a flag flip must
    # miss the cache and re-trace
    op = OP_REGISTRY[op_name]
    pd = dict(params)

    def run(*inputs):
        out = op.fn(pd, *inputs)
        return out if isinstance(out, tuple) else (out,)

    return jax.jit(run)


def apply_op(op: Operator, params: Tuple[Tuple[str, Any], ...], inputs) -> Tuple:
    """Run the op on raw jax arrays; returns a tuple of all outputs (incl aux).

    Under an outer jax trace (symbolic executor inside jit) the op fn is
    inlined directly: a nested jit would be redundant for fusion and jax
    0.9 cannot linearize some primitives through a nested pjit (e.g.
    reduce_window_sum — avg-pool backward dies with 'Linearization
    failed to produce known values').

    Works both eagerly and under an outer jax trace (the symbolic executor
    calls this inside jit — XLA then fuses across ops, which is the TPU
    replacement for reference op-bulking, src/executor/graph_executor.cc:1350).
    """
    if any(isinstance(a, jax.core.Tracer) for a in inputs if a is not None):
        pd = dict(params)
        out = op.fn(pd, *inputs)
        return out if isinstance(out, tuple) else (out,)
    pd = dict(params)
    if op.sp_impls and pd.get("impl") in op.sp_impls:
        # sequence-parallel impls shard over the ambient sp mesh: run
        # the fn EAGERLY (shard_map places its own devices) — the
        # single-device _jitted wrapper would conflict with the mesh
        out = op.fn(pd, *inputs)
        return out if isinstance(out, tuple) else (out,)
    from .. import layout as _layout
    return _jitted(op.name, params, _layout.conv_layout())(*inputs)


@functools.lru_cache(maxsize=None)
def _sp_fwd_bwd(op_name: str, params: Tuple[Tuple[str, Any], ...],
                mesh, axis_name: str):
    """Cached jitted forward + vjp-backward for a sequence-parallel op
    under eager autograd (same idiom as _jitted).  The ambient scope's
    (mesh, axis) pair is captured at trace time inside op.fn, so BOTH
    are cache keys — the same mesh under a different sp axis must
    trace fresh.  jax.jit caches per input shape under each entry."""
    op = OP_REGISTRY[op_name]
    pd = dict(params)

    def run(*ins):
        out = op.fn(pd, *ins)
        return out if isinstance(out, tuple) else (out,)

    def bwd(ins, cts):
        _, vjp_fn = jax.vjp(run, *ins)
        return vjp_fn(tuple(cts))

    fwd_j, bwd_j = jax.jit(run), jax.jit(bwd)

    # jax.jit traces LAZILY (first call, and again per new input
    # shape) and op.fn reads the AMBIENT scope at trace time — so a
    # backward() issued after the user's `with sp_scope(...)` exited
    # (or under a different scope) would trace against the wrong/no
    # mesh and poison this cache entry.  Re-enter the KEYED scope
    # around every call: traces always see exactly the (mesh, axis)
    # this entry is keyed on; the push/pop is a list append when no
    # trace happens.
    from ..parallel.sequence_parallel import sp_scope

    def fwd_scoped(*ins):
        with sp_scope(mesh, axis_name):
            return fwd_j(*ins)

    def bwd_scoped(ins, cts):
        with sp_scope(mesh, axis_name):
            return bwd_j(ins, cts)

    return fwd_scoped, bwd_scoped


def make_vjp(op: Operator, params: Tuple[Tuple[str, Any], ...], inputs):
    """Forward + vjp closure for autograd (replaces hand-written Backwards)."""
    pd = dict(params)

    def run(*ins):
        out = op.fn(pd, *ins)
        return out if isinstance(out, tuple) else (out,)

    if op.sp_impls and pd.get("impl") in op.sp_impls:
        # Sequence-parallel op under eager autograd: jax.vjp traces
        # op.fn, so the fn's own concrete-input resharding never runs —
        # place primals on the ambient sp mesh (replicated: valid for
        # any op semantics; the inner shard_map re-shards to its specs)
        # BEFORE tracing, and round-trip outputs / cotangents / grads
        # so single-device eager neighbors compose.  The fwd and bwd
        # are CACHED jits keyed on (op, params, mesh): a fresh
        # jax.vjp per call re-traced the shard_map every training step
        # (~13s/step on the CPU mesh for the sp LM example); the bwd
        # recomputes the forward inside one compiled program — the
        # standard remat trade for cacheability.
        from ..parallel import sequence_parallel as _sp
        from jax.sharding import NamedSharding, PartitionSpec as _P
        mesh, _axis = _sp.current_sp_scope()
        repl = NamedSharding(mesh, _P())
        devs = [list(a.devices()) for a in inputs
                if hasattr(a, "devices")]
        orig = devs[0][0] if devs and len(devs[0]) == 1 else None

        def to_mesh(a):
            # transient mesh staging of caller-owned (already
            # attributed) arrays — freed when the sp op returns
            return jax.device_put(a, repl) if hasattr(a, "devices") else a  # graft-lint: disable=memory-hygiene

        fwd, bwd = _sp_fwd_bwd(op.name, params, mesh, _axis)
        mesh_ins = tuple(to_mesh(a) for a in inputs)
        outs = fwd(*mesh_ins)
        if orig is not None:
            outs = tuple(jax.device_put(o, orig) for o in outs)  # graft-lint: disable=memory-hygiene

            def vjp_back(cts):
                grads = bwd(mesh_ins, tuple(to_mesh(c) for c in cts))
                return tuple(jax.device_put(g, orig) for g in grads)  # graft-lint: disable=memory-hygiene

            return outs, vjp_back
        return outs, lambda cts: bwd(mesh_ins, tuple(cts))

    return jax.vjp(run, *inputs)


def zero_like_grad(g, primal):
    """Convert jax's float0 / None gradients into dense zeros."""
    if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
        import jax.numpy as jnp
        return jnp.zeros(_np.shape(primal), _np.result_type(primal))
    return g
