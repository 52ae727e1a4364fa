"""gluon Block / HybridBlock / SymbolBlock (parity: python/mxnet/gluon/block.py).

hybridize() parity with the TPU twist: `_build_cache` traces hybrid_forward
with Symbol placeholders into a graph (block.py:381-384 in the reference) and
compiles it whole through `jax.jit` (the CachedOp below) — XLA fuses the
entire block into one executable, the reason hybridize exists.  Eager mode
runs the same hybrid_forward with `F = mx.nd` and records on the autograd
tape.
"""
from __future__ import annotations

import copy
import threading
from collections import OrderedDict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError, getenv
from ..context import cpu
from ..observability import introspect as _introspect
from ..observability import metrics as _metrics
from ..observability.tracing import span
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import symbol as sym_mod
from ..symbol import Symbol
from ..symbol.graph import GraphPlan, infer_shapes_types
from ..ops.registry import RESIDUAL_NAME
from .. import autograd
from .parameter import Parameter, ParameterDict, DeferredInitializationError


class _BlockScope:
    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..name import NameManager
                prefix = NameManager.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = f"{hint}{count}_"
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        from ..name import NameManager, Prefix
        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


def _flatten(args, inout_str="input"):
    if isinstance(args, NDArray) or isinstance(args, Symbol):
        return [args], int(0)
    if args is None:
        return [None], None
    assert isinstance(args, (list, tuple)), \
        f"{inout_str} must be (nested) list of Symbol or NDArray, got {args}"
    flat = []
    fmts = []
    for i in args:
        arg, fmt = _flatten(i, inout_str)
        flat.extend(arg)
        fmts.append(fmt)
    return flat, fmts


def _regroup(args, fmt):
    if isinstance(fmt, int):
        if fmt == 0:
            return args[0], args[1:]
        return args[:fmt], args[fmt:]
    if fmt is None:
        return None, args[1:]
    assert isinstance(fmt, (list, tuple))
    ret = []
    for i in fmt:
        res, args = _regroup(args, i)
        ret.append(res)
    return ret, args


class Block:
    """Base building block (parity: gluon/block.py:121)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children: List["Block"] = []

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(f"  ({i}): {repr(b)}"
                           for i, b in enumerate(self._children))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self.register_child(value)
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self) -> ParameterDict:
        return self._params

    def collect_params(self, select=None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            import re
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children:
            ret.update(cld.collect_params(select=select))
        return ret

    def save_params(self, filename):
        self.collect_params().save(filename, strip_prefix=self.prefix)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        self.collect_params().load(filename, ctx, allow_missing, ignore_extra,
                                   self.prefix)

    def register_child(self, block):
        self._children.append(block)

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        from .. import initializer
        self.collect_params().initialize(init or initializer.Uniform(),
                                         ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children:
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children:
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def shard(self, mesh=None, spec_fn=None):
        """Annotate every parameter of this block (children included)
        with a ``NamedSharding`` on ``mesh`` (default: the ambient
        ``parallel.mesh.current_mesh()``).  ``spec_fn(name, param)``
        may return a ``PartitionSpec`` per parameter (None = keep the
        default rule: trainable >=2-D tensors shard their largest
        evenly-divisible dim along the model axis, everything else
        replicates).  Initialized params re-place immediately;
        uninitialized ones place at init — either way the whole-step
        compiler sees committed shardings and jit inserts the
        collectives.  Returns ``self`` for chaining."""
        from ..parallel import mesh as _pmesh
        mesh = _pmesh.resolve_mesh(mesh)
        if mesh is None:
            raise ValueError(
                "Block.shard() needs a mesh: pass one, or install an "
                "ambient mesh via parallel.mesh.set_current_mesh / "
                "use_mesh / MXNET_MESH_BATCH/MXNET_MESH_MODEL")
        for name, p in self.collect_params().items():
            spec = spec_fn(name, p) if spec_fn is not None else None
            if spec is None:
                shape = tuple(p.shape) if p.shape is not None else ()
                if not shape or any(d <= 0 for d in shape):
                    # deferred-init shape: leave the spec unset so the
                    # whole-step bind (or a re-shard after init)
                    # computes the default from the REAL shape
                    continue
                spec = _pmesh.default_param_spec(
                    mesh, shape, trainable=p.grad_req != "null")
            p.set_sharding(mesh, spec)
        return self

    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        raise NotImplementedError


# What a recorded call keeps for its backward program is decided by what
# made a value, nothing else: the outputs of matrix products, convolutions,
# grouped products and kernel calls stay on the device between the two
# programs; everything else (norms, activations, casts, masks, the softmax
# of a loss) is recomputed from them in the backward program, where it is
# bound by memory traffic.  jax hands the outermost `jax.checkpoint`'s
# policy down into an op's own (ops/decoder.py moe_ffn), so the grouped
# products inside it are kept by this rule too, and its gathers and
# permutations are not.  A kernel whose call the policy meets only as a
# `custom_vjp` marks its output itself (`ops/registry.py` RESIDUAL_NAME,
# as ops/flash_attention.py does).
_KEPT_PRIMITIVES = frozenset((
    "dot_general", "conv_general_dilated", "ragged_dot",
    "ragged_dot_general", "pallas_call"))
_RESIDUAL_POLICY = jax.checkpoint_policies.save_from_both_policies(
    lambda prim, *_, **__: prim.name in _KEPT_PRIMITIVES,
    jax.checkpoint_policies.save_only_these_names(RESIDUAL_NAME))


class _InputRef:
    """In the pullback a recording forward program returns, the place of a
    residual that is one of the program's own inputs (a parameter, the data,
    the key): the backward program is handed that input again, so the forward
    program writes no second copy of the weights.  A pytree node without
    leaves; `index` counts the leaves of `(args, aux, key)`."""
    __slots__ = ("index",)

    def __init__(self, index):
        self.index = index


jax.tree_util.register_pytree_node(
    _InputRef, lambda r: ((), r.index), lambda index, _: _InputRef(index))


def _is_input_ref(x):
    return isinstance(x, _InputRef)


def _committed(arrays: Dict[str, NDArray]) -> dict:
    """{name: buffer}, each buffer committed to the device it is on.  `jit`
    keys a call on committed-ness: parameters fresh from an initializer,
    `cast` or `set_data` are uncommitted and the update's outputs that
    replace them are committed, so a first step with the former lowered,
    compiled and cached every recorded program a second time at the second
    step (PERF.md Open questions 13).  The committed buffer replaces the
    NDArray's, so an array is placed once, at its first call."""
    out = {}
    for k, v in arrays.items():
        x = v._data
        if not getattr(x, "_committed", True):
            # device_put to the sharding it has reuses the buffer
            # (tests/test_moe_buffer.py); `_data` and not `_set_data`, as
            # the value is the same and `_version` keys the autograd tape
            x = v._data = jax.device_put(x, x.sharding)  # graft-lint: disable=memory-hygiene
        out[k] = x
    return out


class CachedOp:
    """Compiled graph closure (parity: Imperative::CachedOp,
    src/imperative/cached_op.cc).

    Outside `autograd.record()` a call is one XLA executable that returns
    the outputs and the new auxiliary states, nothing else.

    Under `autograd.record()` the forward program (`mx_cachedop_fwd`, the
    same name) differentiates as it runs: it also returns the pullback of
    the graph, a pytree whose leaves are the residuals `_RESIDUAL_POLICY`
    keeps.  The tape entry holds them, and `backward` launches a second
    executable (`mx_cachedop_bwd`) that applies the pullback to the
    cotangents: it recomputes the element-wise work from the residuals and
    runs none of the forward graph's products or kernels again.  Residuals
    that are the program's own inputs (the parameters above all) are not
    returned: the tape hands the backward program the same arrays.

    The kept residuals live from the forward launch until the tape is
    cleared (`backward(retain_graph=False)`, the default) and the backward
    program has run; with `retain_graph=True` they survive for a second
    `backward`.  A recorded call that no `backward` follows pins them,
    like its outputs, until the next `backward` clears the tape
    (`mxnet_cachedop_residual_bytes` says how many bytes that is).  They
    are not donated: jax gives a donated argument's memory to an output
    of the same size only, and no residual has a gradient's size.
    """

    def __init__(self, symbol: Symbol):
        self.symbol = symbol
        self.plan = plan = GraphPlan(symbol)

        def mx_cachedop_fwd(args, aux, key, is_train, recording):
            if not recording:
                return plan.run(args, aux, key, is_train)

            def run(args_):
                outs, new_aux = plan.run(args_, aux, key, is_train)
                return tuple(outs), new_aux

            (outs, new_aux), pullback = jax.vjp(
                jax.checkpoint(run, policy=_RESIDUAL_POLICY), args)
            inputs = {id(x): i for i, x in enumerate(
                jax.tree_util.tree_leaves((args, aux, key)))}
            leaves, tree = jax.tree_util.tree_flatten(pullback)
            nbytes = {"kept": 0, "primal": 0}
            for j, leaf in enumerate(leaves):
                ref = inputs.get(id(leaf))
                nbytes["kept" if ref is None else "primal"] += \
                    leaf.size * leaf.dtype.itemsize
                if ref is not None:
                    leaves[j] = _InputRef(ref)
            for kind, n in nbytes.items():
                _metrics.CACHEDOP_RESIDUAL_BYTES.set(n, kind=kind)
            return outs, new_aux, jax.tree_util.tree_unflatten(tree, leaves)

        def mx_cachedop_bwd(pullback, inputs, cots):
            inputs = jax.tree_util.tree_leaves(inputs)
            pullback = jax.tree_util.tree_map(
                lambda r: inputs[r.index] if _is_input_ref(r) else r,
                pullback, is_leaf=_is_input_ref)
            return pullback(cots)[0]

        self._fwd = jax.jit(mx_cachedop_fwd, static_argnums=(3, 4))
        self._bwd = jax.jit(mx_cachedop_bwd)
        self._fwd_donated = None  # built on first donated inference call
        self._noted = set()  # introspection captures done (fwd/bwd)

    def _get_fwd_donated(self):
        """Inference-mode forward that DONATES the non-parameter inputs
        (MXNET_DONATE_INFER): the data buffer's HBM block is released to
        the program instead of held live across the call — the serving
        path's donated-buffer dispatch, available to hybridized blocks.
        Params/aux ride a separate non-donated slot, so weights survive.
        Caveat (docs/inference.md): on backends with real donation the
        caller's input NDArray is consumed by the call."""
        if self._fwd_donated is None:
            plan = self.plan

            def mx_cachedop_fwd_donated(data_vals, param_vals, aux_vals,
                                        key, t):
                merged = dict(param_vals)
                merged.update(data_vals)
                return plan.run(merged, aux_vals, key, t)

            # one-time, narrowly-scoped filter install (NOT a per-call
            # warnings.catch_warnings, which mutates process-global
            # filter state non-thread-safely on every forward): backends
            # without usable donation warn at each retrace; the user
            # opted into best-effort donation, so that specific warning
            # is expected noise
            import warnings as _warnings
            _warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            self._fwd_donated = jax.jit(
                mx_cachedop_fwd_donated, static_argnums=(4,),
                donate_argnums=(0,))
        return self._fwd_donated

    def __call__(self, arg_arrays: Dict[str, NDArray],
                 aux_arrays: Dict[str, NDArray], ctx, input_names=None):
        with span("mx.cachedop.forward", cat="cachedop"):
            return self._call(arg_arrays, aux_arrays, ctx, input_names)

    def _call(self, arg_arrays, aux_arrays, ctx, input_names):
        from .. import random as _random
        is_train = autograd.is_training()
        arg_vals = _committed(arg_arrays)
        aux_vals = _committed(aux_arrays)
        key = _random.next_key()
        if _metrics.ENABLED:
            # the gluon analog of the executor's fwd/fwd_bwd accounting:
            # a hybridized step is visible in dispatch_counts() as one
            # xla:fwd plus (when recording) one xla:bwd at backward time
            _metrics.XLA_LAUNCHES.inc(kind="fwd")
        # the env read is short-circuited off the training path and is
        # one dict lookup per inference forward — kept per-call (not a
        # module snapshot) so the knob can be toggled at runtime
        if input_names and not is_train and not autograd.is_recording() \
                and getenv("MXNET_DONATE_INFER", False):
            data_vals = {k: arg_vals[k] for k in input_names
                         if k in arg_vals}
            param_vals = {k: v for k, v in arg_vals.items()
                          if k not in data_vals}
            outs, new_aux = self._get_fwd_donated()(
                data_vals, param_vals, aux_vals, key, is_train)
            out_nds = [NDArray(o, ctx) for o in outs]
            for k, v in new_aux.items():
                aux_arrays[k]._set_data(v)
            return out_nds
        recording = autograd.is_recording()
        outs, new_aux, *pullback = self._fwd(arg_vals, aux_vals, key,
                                             is_train, recording)
        if _introspect.ENABLED and "fwd" not in self._noted:
            # once per CachedOp: analytical cost of the compiled fwd —
            # the fused-path MFU numerator (a retrace, no XLA compile)
            self._noted.add("fwd")
            _introspect.note_jit("gluon:fwd", self._fwd, arg_vals,
                                 aux_vals, key, is_train, recording)
        out_nds = [NDArray(o, ctx) for o in outs]
        if recording:
            names = list(arg_vals)
            aux_names = sorted(new_aux)
            n_out = len(outs)
            # the backward program's three arguments: the residuals the
            # forward program kept, the inputs it left where they were
            # (aux_vals: the arrays this call read, not the new states)
            held = (pullback[0], (arg_vals, aux_vals, key))

            def vjp_fn(cots):
                cots = (tuple(cots[:n_out]),
                        dict(zip(aux_names, cots[n_out:])))
                if _metrics.ENABLED:
                    _metrics.XLA_LAUNCHES.inc(kind="bwd")
                    _metrics.CACHEDOP_BACKWARDS.inc()
                if _introspect.ENABLED and "bwd" not in self._noted:
                    self._noted.add("bwd")
                    _introspect.note_jit("gluon:bwd", self._bwd, *held, cots)
                with span("mx.cachedop.backward", cat="cachedop"):
                    grads = self._bwd(*held, cots)
                return tuple(grads[n] for n in names)

            autograd._record(
                None, [arg_arrays[n] for n in names], out_nds, vjp_fn,
                tuple(outs) + tuple(new_aux[k] for k in aux_names))
        for k, v in new_aux.items():
            aux_arrays[k]._set_data(v)
        return out_nds


class HybridBlock(Block):
    """Parity: gluon/block.py:321."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._reg_params: Dict[str, Parameter] = {}
        self._cached_graph = ()
        self._cached_op = None
        self._active = False
        self._flags = {}

    def __setattr__(self, name, value):
        if isinstance(value, Parameter):
            self._reg_params[name] = value
        if isinstance(value, HybridBlock):
            self._clear_cached_op()
        super().__setattr__(name, value)

    def register_child(self, block):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                "Children of HybridBlock must also be HybridBlock, but "
                f"{str(block)} has type {str(type(block))}.")
        super().register_child(block)
        self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs
        self._clear_cached_op()
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def _clear_cached_op(self):
        self._cached_graph = ()
        self._cached_op = None
        self._cached_by_fmt = {}

    @staticmethod
    def _fmt_key(fmt):
        """Hashable key for an input-structure format (call arity: an RNN
        layer called with vs without explicit states must not share a
        cached graph)."""
        return repr(fmt)

    def _get_graph(self, *args):
        flat_args, in_format = _flatten(args)
        key = self._fmt_key(in_format)
        if not hasattr(self, "_cached_by_fmt"):
            self._cached_by_fmt = {}
        entry = self._cached_by_fmt.get(key)
        if entry is None and getattr(self, "_graph_preset", False) \
                and self._cached_graph:
            # graph preset externally (SymbolBlock imports a ready-made
            # symbol) — adopt it for this call structure
            flat_out = self._cached_graph[1]
            entry = {"graph": self._cached_graph,
                     "out_format": getattr(self, "_out_format", None)
                     or [len(flat_out.list_outputs())]}
            self._cached_by_fmt[key] = entry
        if entry is None or not entry.get("graph"):
            inputs = [sym_mod.Variable(f"data{i}") if len(flat_args) > 1
                      else sym_mod.Variable("data")
                      for i in range(len(flat_args))]
            grouped, _ = _regroup(inputs, in_format)
            params = {name: p.var() for name, p in self._reg_params.items()}
            with self.name_scope():
                out = self.hybrid_forward(sym_mod, grouped, **params) \
                    if not isinstance(grouped, list) else \
                    self.hybrid_forward(sym_mod, *grouped, **params)
            flat_out, out_format = _flatten(out, "output")
            entry = {"graph": (inputs, sym_mod.Group(flat_out)),
                     "out_format": out_format}
            self._cached_by_fmt[key] = entry
        self._in_format = in_format
        self._out_format = entry["out_format"]
        self._cached_graph = entry["graph"]
        return self._cached_graph

    def infer_shape(self, *args):
        self._infer_attrs("shape", *args)

    def _infer_attrs(self, attr, *args):
        inputs, out = self._get_graph(*args)
        flat_args, _ = _flatten(args)
        shapes = {i.name: a.shape for i, a in zip(inputs, flat_args)}
        plan, info, _ = infer_shapes_types(out, shapes, {}, partial=False)
        all_params = {p.name: p for p in self._all_params()}
        for name, struct in info.items():
            if name in all_params and struct is not None:
                all_params[name].shape = tuple(struct.shape)

    def _all_params(self):
        out = list(self.collect_params().values())
        return out

    def _build_cache(self, *args):
        inputs, out = self._get_graph(*args)
        self._cached_op = CachedOp(out)
        # map graph input names → (is_param, source)
        params = {p.name: p for p in self._all_params()}
        self._cached_input_names = [i.name for i in inputs]
        self._cached_params = {
            n: params[n] for n in out.list_inputs() if n in params}
        self._cached_aux = set(out.list_auxiliary_states())
        entry = self._cached_by_fmt[self._fmt_key(self._in_format)]
        entry["op"] = (self._cached_op, self._cached_input_names,
                       self._cached_params, self._cached_aux)

    def _call_cached_op(self, *args):
        flat_args, in_format = _flatten(args)
        entry = getattr(self, "_cached_by_fmt", {}).get(
            self._fmt_key(in_format))
        if entry is not None and "op" in entry:
            # the cached-op analog of the executor's _jit_cache
            # accounting: a hybridized forward that reuses its compiled
            # op is a hit, a (re)trace is a miss — snapshot()["jit_cache"]
            # now covers the gluon path too
            if _metrics.ENABLED:
                _metrics.JIT_CACHE_HITS.inc()
            (self._cached_op, self._cached_input_names,
             self._cached_params, self._cached_aux) = entry["op"]
            self._in_format = in_format
            self._out_format = entry["out_format"]
        else:
            if _metrics.ENABLED:
                _metrics.JIT_CACHE_MISSES.inc()
            self._build_cache(*args)
        arg_dict = {}
        aux_dict = {}
        for name, arr in zip(self._cached_input_names, flat_args):
            arg_dict[name] = arr
        for name, p in self._cached_params.items():
            if name in self._cached_aux:
                aux_dict[name] = p.data()
            else:
                arg_dict[name] = p.data()
        ctx = flat_args[0].context if flat_args else cpu()
        out = self._cached_op(arg_dict, aux_dict, ctx,
                              input_names=self._cached_input_names)
        ret, _ = _regroup(out, self._out_format)
        return ret

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            if self._active:
                try:
                    return self._call_cached_op(x, *args)
                except DeferredInitializationError:
                    self._deferred_infer_shape(x, *args)
                    for p in self.collect_params().values():
                        p._finish_deferred_init()
                    return self._call_cached_op(x, *args)
            try:
                params = {name: p.data() for name, p in self._reg_params.items()}
            except DeferredInitializationError:
                self._deferred_infer_shape(x, *args)
                for _, p in self._reg_params.items():
                    p._finish_deferred_init()
                params = {name: p.data() for name, p in self._reg_params.items()}
            return self.hybrid_forward(nd, x, *args, **params)
        assert isinstance(x, Symbol), \
            f"HybridBlock requires the first argument to forward be either " \
            f"Symbol or NDArray, but got {type(x)}"
        params = {name: p.var() for name, p in self._reg_params.items()}
        with self.name_scope():
            return self.hybrid_forward(sym_mod, x, *args, **params)

    def _deferred_infer_shape(self, *args):
        try:
            self.infer_shape(*args)
        except Exception as e:
            raise ValueError(
                f"Deferred initialization failed because shape cannot be "
                f"inferred: {e}")

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


class SymbolBlock(HybridBlock):
    """Wrap a Symbol as a Block (parity: gluon/block.py:542)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=params)
        self._prefix = ""
        self._params = ParameterDict("", params)
        if isinstance(inputs, Symbol) and len(inputs) == 1:
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(outputs)
        syms = inputs
        input_names = {i.name for i in syms}
        for name in outputs.list_arguments():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            if name not in input_names:
                self.params.get(name, grad_req="null",
                                allow_deferred_init=True)
        self._cached_graph = (syms, outputs)
        self._graph_preset = True  # imported symbol, not traced
        self._reg_params = {}

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            try:
                return self._call_cached_op(x, *args)
            except DeferredInitializationError:
                self._deferred_infer_shape(x, *args)
                for p in self.collect_params().values():
                    p._finish_deferred_init()
                return self._call_cached_op(x, *args)
        assert isinstance(x, Symbol)
        ret = copy.copy(self._cached_graph[1])
        ret._compose(**{self._cached_graph[0][0].name: x})
        return ret

    def _build_cache(self, *args):
        inputs, out = self._cached_graph
        flat_args, self._in_format = _flatten(args)
        self._out_format = int(0) if len(out) == 1 else [int(0)] * len(out)
        self._cached_op = CachedOp(out)
        params = {p.name: p for p in self.params.values()}
        self._cached_input_names = [i.name for i in inputs]
        self._cached_params = {
            n: params[n] for n in out.list_inputs() if n in params}
        self._cached_aux = set(out.list_auxiliary_states())
        # register in the arity-keyed cache so _call_cached_op reuses the
        # compiled op instead of re-tracing every forward
        if not hasattr(self, "_cached_by_fmt"):
            self._cached_by_fmt = {}
        self._cached_by_fmt[self._fmt_key(self._in_format)] = {
            "graph": self._cached_graph, "out_format": self._out_format,
            "op": (self._cached_op, self._cached_input_names,
                   self._cached_params, self._cached_aux)}

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError
