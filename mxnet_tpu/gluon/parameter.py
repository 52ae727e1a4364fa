"""gluon.Parameter / ParameterDict (parity: python/mxnet/gluon/parameter.py:43,462).

Deferred initialization, grad_req plumbing, save/load.  TPU note: a
Parameter owns ONE buffer; multi-device replication/sharding is a placement
property handled by the Trainer/mesh (SPMD), not N copies as in the
reference's per-GPU lists — list_data() returns the single (possibly
mesh-sharded) array for API parity.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as _np

from ..base import MXNetError, np_dtype
from ..context import Context, cpu, current_context
from ..observability import memory as _memory
from ..observability.memory import memory_scope as _memory_scope
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import initializer
from ..initializer import InitDesc


class DeferredInitializationError(MXNetError):
    pass


_zero_all_fn = None


def _zero_all(arrs):
    """One compiled program producing zeros for every buffer (jax caches
    per shape/dtype signature)."""
    global _zero_all_fn
    if _zero_all_fn is None:
        import jax
        import jax.numpy as jnp
        _zero_all_fn = jax.jit(lambda xs: [jnp.zeros_like(x) for x in xs])
    return _zero_all_fn(arrs)


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype=_np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self._var = None
        self._data = None
        self._grad = None
        self._ctx = None
        self._deferred_init = ()
        # GSPMD placement (ISSUE 18): the NamedSharding this parameter's
        # buffer is committed to, plus the (mesh, PartitionSpec) pair it
        # was derived from.  A placement PROPERTY, not data: _init_impl
        # re-applies it on every load path (checkpoint restore,
        # supervisor snapshot restore, deferred init), so a sharded
        # param stays sharded through every restore the last 8 PRs built
        self._sharding = None
        self._sharding_spec = None
        self._sharding_mesh = None
        self.name = name
        self._grad_req = None
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.grad_req = grad_req if differentiable else "null"
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        # row_sparse grad_stype: the grad buffer IS a RowSparseNDArray
        # (rows-only); autograd deposits token rows into it and the
        # optimizer/kvstore stay on the O(nnz) lazy path
        # (parity: gluon sparse embeddings, optimizer_op.cc rsp kernels)
        self._grad_stype = grad_stype

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        if not getattr(self, "_differentiable", True):
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null" and self._data is not None:
            self._grad = None
            self._data._grad = None
        elif self._data is not None:
            self._init_grad()

    def _check_and_get(self, arr, ctx):
        if arr is not None:
            return arr
        if self._deferred_init:
            raise DeferredInitializationError(
                f"Parameter {self.name} has not been initialized yet because "
                "initialization was deferred. Actual initialization happens "
                "during the first forward pass.")
        raise RuntimeError(
            f"Parameter {self.name} has not been initialized. You should "
            "initialize parameters with Block.collect_params().initialize()")

    def _load_init(self, data, ctx):
        if self.shape and _np.prod(self.shape) > 0:
            for self_dim, data_dim in zip(self.shape, data.shape):
                if self_dim not in (0, data_dim):
                    raise AssertionError(
                        f"Failed loading Parameter {self.name}: shape mismatch "
                        f"{self.shape} vs {data.shape}")
        self.shape = tuple(data.shape)
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._deferred_init = ()
        self._init_impl(data, ctx)

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        if self.shape is None or _np.prod(self.shape) <= 0:
            raise MXNetError(
                f"Cannot initialize Parameter {self.name} because it has "
                "invalid shape: {self.shape}.")
        if data is None:
            # HBM ledger: the parameter buffer is born here — tag it.
            # ``_memory_tag`` (default "param") lets a subsystem claim
            # its own ledger row: ShardedEmbedding stamps "embed_shards"
            # so ensure_headroom / the registry cost model see table
            # bytes as their own class (docs/memory.md tag classes)
            with _memory_scope(getattr(self, "_memory_tag", "param")):
                data = nd.zeros(self.shape, dtype=self.dtype, ctx=ctx[0])
                initializer.create(default_init)(
                    InitDesc(self.name, {"__init__": init}), data)
        self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        self._ctx = list(ctx_list)
        with _memory_scope(getattr(self, "_memory_tag", "param")):
            if not isinstance(data, NDArray):
                data = nd.array(data, dtype=self.dtype)
            self._data = data.as_in_context(self._ctx[0]) if \
                data.context != self._ctx[0] else data
            if self._sharding is not None:
                # re-commit to the mesh placement: this is THE point
                # every load path funnels through (_load_init from
                # checkpoint restore, the supervisor's donation-safe
                # snapshot restore, deferred init), so a restored host
                # copy lands back as the same sharded device array a
                # failed donated dispatch consumed
                self._apply_sharding_locked()
            if _memory.ENABLED:
                # load-path wrappers (ParameterDict.load / _load_init)
                # arrive already registered under their creation tag
                # (nd.load -> _untagged); re-registering retags the
                # same live wrapper to param instead of double counting
                _memory.register_nd(self._data)
        self._init_grad()

    # -- GSPMD sharding (ISSUE 18) ------------------------------------------
    def _apply_sharding_locked(self):
        """device_put the live buffer onto its NamedSharding (committed
        placement — jax.jit then treats the spec as an in_sharding and
        inserts the collectives).  Caller holds the param-tag scope."""
        import jax
        # mesh placement of the param's own buffer — a retag of the same
        # logical allocation, not a new one
        self._data._set_data(
            jax.device_put(self._data._data, self._sharding))  # graft-lint: disable=memory-hygiene

    def __getstate__(self):
        """The live NamedSharding/Mesh hold Device handles that cannot
        cross a pickle boundary (Updater.get_states packs the optimizer
        whose param_dict points back here).  Drop them — the spec
        string survives, and the next whole-step bind re-resolves the
        mesh and re-commits the placement in the new process."""
        state = self.__dict__.copy()
        state["_sharding"] = None
        state["_sharding_mesh"] = None
        return state

    @property
    def sharding_spec(self):
        """The PartitionSpec this parameter is annotated with (None =
        replicated / never sharded)."""
        return self._sharding_spec

    @property
    def sharding(self):
        """The committed NamedSharding, or None."""
        return self._sharding

    def set_sharding(self, mesh, spec) -> None:
        """Annotate this parameter with a GSPMD placement: ``spec`` is a
        ``jax.sharding.PartitionSpec`` (or axis-name tuple) over
        ``mesh``.  Applies immediately when the buffer exists and
        re-applies on every restore path (``_init_impl``).  ``mesh=None``
        clears the annotation (the buffer keeps its current placement
        until the next restore)."""
        if mesh is None:
            self._sharding = None
            self._sharding_spec = None
            self._sharding_mesh = None
            return
        from jax.sharding import NamedSharding, PartitionSpec
        if not isinstance(spec, PartitionSpec):
            spec = PartitionSpec(*spec) if spec else PartitionSpec()
        self._sharding_mesh = mesh
        self._sharding_spec = spec
        self._sharding = NamedSharding(mesh, spec)
        if self._data is not None:
            with _memory_scope(getattr(self, "_memory_tag", "param")):
                self._apply_sharding_locked()
            from ..ndarray.sparse import RowSparseNDArray
            if self._grad is not None and \
                    not isinstance(self._grad, RowSparseNDArray):
                # keep the grad buffer's placement consistent with the
                # data it shadows (the eager fallback path deposits into
                # it; mismatched placements would force XLA reshards)
                import jax
                self._grad._set_data(
                    jax.device_put(self._grad._data, self._sharding))  # graft-lint: disable=memory-hygiene

    def _init_grad(self):
        if self.grad_req == "null":
            self._grad = None
            return
        if self._grad_stype == "row_sparse":
            # rows-only gradient buffer: autograd deposits (ids, rows)
            # directly — O(vocab) dense grads are never allocated
            # (parity: rsp embedding grads, optimizer_op.cc rsp kernels)
            from ..ndarray import sparse as _sp
            with _memory_scope("grad"):
                self._grad = _sp.zeros_sparse(
                    "row_sparse", self._data.shape,
                    ctx=self._data.context, dtype=self._data.dtype)
        else:
            with _memory_scope("grad"):
                self._grad = nd.zeros(self._data.shape,
                                      dtype=self._data.dtype,
                                      ctx=self._data.context)
        from .. import autograd
        autograd.mark_variables([self._data], [self._grad], self.grad_req)

    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        if default_init is None:
            default_init = initializer.Uniform()
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if self.shape is None or _np.prod([d for d in self.shape]) <= 0 \
                or any(d == 0 for d in self.shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise MXNetError(f"Cannot initialize Parameter {self.name} "
                             "because it has invalid shape.")
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            self._data = self._data.as_in_context(ctx[0])
            self._ctx = ctx
            self._init_grad()
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx, default_init, data)
        else:
            raise ValueError(f"Cannot reset context for Parameter {self.name} "
                             "because it has not been initialized.")

    def set_data(self, data):
        if self._data is None:
            assert self._deferred_init, \
                f"Parameter {self.name} has not been initialized"
            init, ctx, default_init, _ = self._deferred_init
            self.shape = tuple(data.shape)
            self._deferred_init = (init, ctx, default_init, data)
            self._finish_deferred_init()
            return
        self._data._set_data(
            (data._data if isinstance(data, NDArray) else nd.array(data)._data
             ).astype(self._data.dtype))

    def data(self, ctx=None) -> NDArray:
        arr = self._check_and_get(self._data, ctx)
        return arr

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None) -> NDArray:
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                f"Cannot get gradient array for Parameter {self.name} "
                "because grad_req='null'")
        return self._check_and_get(self._grad, ctx)

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return self._deferred_init[1]
            raise RuntimeError(f"Parameter {self.name} has not been initialized")
        return self._ctx

    def zero_grad(self):
        if self._grad is None:
            return
        from ..ndarray.sparse import RowSparseNDArray
        if isinstance(self._grad, RowSparseNDArray):
            self._grad._clear_rows()
        else:
            self._grad[:] = 0

    @property
    def fresh_grad(self):
        """True when backward has deposited into this parameter's grad on
        any device copy since the last Trainer step (the stale-grad
        guard's source of truth; parity: NDArray::fresh_out_grad)."""
        return self._data is not None and \
            any(getattr(d, "_fresh_grad", False) for d in self.list_data())

    def var(self):
        from .. import symbol
        if self._var is None:
            self._var = symbol.Variable(self.name, shape=self.shape,
                                        dtype=self.dtype, lr_mult=self.lr_mult,
                                        wd_mult=self.wd_mult,
                                        init=self.init)
        return self._var

    def cast(self, dtype):
        self.dtype = np_dtype(dtype)
        if self._data is None:
            return
        self._data = self._data.astype(self.dtype)
        if self._grad is not None:
            self._grad = self._grad.astype(self.dtype)
            from .. import autograd
            autograd.mark_variables([self._data], [self._grad], self.grad_req)


class Constant(Parameter):
    """Constant parameter (grad_req null, init from value)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        self.value = value

        class _Init(initializer.Initializer):
            def _init_weight(self, _, arr):
                value.copyto(arr)

            _init_default = _init_weight

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_Init())


class ParameterDict:
    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._shared = shared

    def __repr__(self):
        s = "\n".join(repr(v) for v in self.values())
        return f"ParameterDict '{self._prefix}' (\n{s}\n)"

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __len__(self):
        return len(self._params)

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def get(self, name, **kwargs) -> Parameter:
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            for k, v in kwargs.items():
                if getattr(param, k, None) is not None and v is not None:
                    existing = getattr(param, k)
                    if k == "shape" and len(v) == len(existing):
                        inferred = tuple(vi if vi != 0 else ei
                                         for vi, ei in zip(v, existing))
                        param.shape = inferred
                        continue
                else:
                    setattr(param, k, v)
        return param

    def get_constant(self, name, value=None) -> Constant:
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError(f"No constant named '{name}'.")
            param = Constant(name, value)
            self._params[name] = param
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    f"Cannot update because duplicate Parameter '{k}'"
            else:
                self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            init = initializer.Uniform()
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        """Zero every dense grad buffer in ONE jitted dispatch (the
        per-parameter loop issued O(#params) device ops); row-sparse
        grads clear their rows host-side as before."""
        from ..ndarray.sparse import RowSparseNDArray
        dense = []
        for p in self.values():
            g = p._grad
            if g is None:
                continue
            if isinstance(g, RowSparseNDArray):
                g._clear_rows()
            else:
                dense.append(g)
        if not dense:
            return
        for g, z in zip(dense, _zero_all([g._data for g in dense])):
            g._set_data(z)

    def reset_ctx(self, ctx):
        for i in self.values():
            i.reset_ctx(ctx)

    def setattr(self, name, value):
        for i in self.values():
            setattr(i, name, value)

    def save(self, filename, strip_prefix=""):
        arg_dict = {}
        for param in self.values():
            weight = param.data()
            if not param.name.startswith(strip_prefix):
                raise ValueError(f"Prefix '{strip_prefix}' is to be stripped "
                                 f"but Parameter's name '{param.name}' does "
                                 "not start with it")
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        arg_dict = nd.load(filename)
        if restore_prefix:
            arg_dict = {restore_prefix + k: v for k, v in arg_dict.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    f"Parameter {name} is missing in file {filename}"
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    f"Parameter {name} loaded from {filename} is not present " \
                    "in ParameterDict"
                continue
            self[name]._load_init(arg_dict[name], ctx or [cpu()])
