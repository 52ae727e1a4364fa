"""NDArray: the user-facing async tensor, backed by a jax.Array.

Reference parity: `include/mxnet/ndarray.h:79` + `src/ndarray/ndarray.cc` +
`python/mxnet/ndarray/ndarray.py:169`.  Design mapping:
  - ref-counted Chunk + engine var  →  an immutable jax.Array buffer; PJRT
    async dispatch gives the "returns immediately, syncs on read" semantics
    (WaitToRead == block_until_ready).
  - in-place mutation (a += b, a[:] = x, optimizer updates)  →  functional
    update producing a new buffer swapped into the wrapper (`_set_data`),
    with a version counter so the autograd tape sees writes.
  - CopyFromTo cross-device copy  →  jax.device_put.
  - save/load  →  same API (`mx.nd.save/load`), container format is a
    single-file archive of npy payloads (the reference's dmlc binary format
    is CUDA-era; docstring notes divergence).
"""
from __future__ import annotations

import builtins
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as _np

from ..analysis import sanitizer as _sanitizer
from ..base import MXNetError, np_dtype
from ..context import Context, current_context
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from ..observability.tracing import span
from .. import engine as _engine


class NDArray:
    """Multi-dimensional array on a device, with async execution semantics."""

    __slots__ = ("_data", "_ctx", "_version", "_grad", "_grad_req", "_writable",
                 "_base", "_fresh_grad", "__weakref__")
    # make numpy defer to our __r*__ ops
    __array_priority__ = 100.0

    def __init__(self, data, ctx: Optional[Context] = None, writable: bool = True):
        self._data = data
        self._ctx = ctx or current_context()
        self._version = 0
        self._grad: Optional["NDArray"] = None
        self._grad_req: str = "null"
        self._writable = writable
        self._base = None
        # set True by autograd.backward when it deposits into this array's
        # grad buffer; Trainer.step clears it after consuming the gradient
        # (parity: NDArray::fresh_out_grad, the stale-grad guard)
        self._fresh_grad = False
        # HBM ledger: track the wrapper (it survives _set_data swaps)
        # under the current memory_scope tag — one boolean test when
        # MXNET_MEMORY_LEDGER=0 (docs/memory.md)
        if _memory.ENABLED:
            _memory.register_nd(self)
        _engine.maybe_sync([data])

    # -- core accessors -----------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self) -> int:
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def context(self) -> Context:
        return self._ctx

    ctx = context

    @property
    def stype(self) -> str:
        return "default"

    @property
    def handle(self):
        """The underlying jax.Array (the TPU analog of the C NDArrayHandle)."""
        return self._data

    @property
    def grad(self) -> Optional["NDArray"]:
        return self._grad

    def attach_grad(self, grad_req: str = "write", stype=None) -> None:
        """Allocate gradient buffer for autograd (parity: ndarray.py attach_grad)."""
        self._grad = zeros(self.shape, ctx=self._ctx, dtype=self.dtype)
        self._grad_req = grad_req
        from .. import autograd
        autograd._mark_variable(self)

    # -- mutation -----------------------------------------------------------
    def _set_data(self, new_data) -> None:
        if not self._writable:
            raise MXNetError("cannot write to a read-only NDArray")
        self._data = new_data
        self._version += 1
        _engine.maybe_sync([new_data])

    # -- sync / export ------------------------------------------------------
    def wait_to_read(self) -> None:
        """Parity: NDArray::WaitToRead — block until the buffer is
        computed (via the engine, so the stall is metered)."""
        from .. import engine as _engine
        if _metrics.ENABLED:
            _metrics.HOST_SYNC_READS.inc()
        with span("mx.sync.read", cat="sync"):
            _engine.wait_for_var(self._data)

    wait_to_write = wait_to_read

    def asnumpy(self) -> _np.ndarray:
        """Copy to host numpy (the synchronization point, as in the
        reference).  Always a WRITABLE copy — jax device buffers surface as
        read-only views, but the reference contract (NDArray::SyncCopyToCPU)
        hands the caller an owned buffer (custom-op backwards mutate it)."""
        # sanitizer chokepoint: inside an analysis.no_sync() region this
        # raises (MXNET_SANITIZE=1); one flag test otherwise
        _sanitizer.check_sync("NDArray.asnumpy")
        if _metrics.ENABLED:
            _metrics.HOST_SYNC_READS.inc()
        # the host blocked on the device: the copy waits for the buffer
        with span("mx.sync.read", cat="sync"):
            out = _np.asarray(self._data)
        if not out.flags.writeable:
            out = out.copy()
        return out

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("asscalar requires size-1 array")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    # -- conversion / copies ------------------------------------------------
    def astype(self, dtype, copy=True) -> "NDArray":
        dt = np_dtype(dtype)
        if not copy and dt == self.dtype:
            return self
        return NDArray(self._data.astype(dt), self._ctx)

    def copy(self) -> "NDArray":
        return NDArray(self._data + 0 if False else jnp.asarray(self._data), self._ctx)

    def copyto(self, other: Union["NDArray", Context]) -> "NDArray":
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device()), other)
        # preserve the target's sharding (mesh-replicated params stay
        # replicated through kvstore pulls / set_params)
        tgt_sharding = getattr(other._data, "sharding", None)
        data = self._data.astype(other.dtype)
        if tgt_sharding is not None and \
                getattr(data, "sharding", None) == tgt_sharding:
            # already typed and placed: no transfer (keeps the training
            # hot path at 0 device_puts/step, tests/test_dispatch_count)
            other._set_data(data)
        else:
            placement = tgt_sharding if tgt_sharding is not None else \
                other._ctx.jax_device()
            other._set_data(jax.device_put(data, placement))
        return other

    def as_in_context(self, ctx: Context) -> "NDArray":
        if ctx == self._ctx:
            return self
        return self.copyto(ctx)

    def detach(self) -> "NDArray":
        out = NDArray(self._data, self._ctx)
        return out

    def tostype(self, stype: str) -> "NDArray":
        if stype == "default":
            return self
        from . import sparse
        return sparse.cast_storage(self, stype)

    # -- shape views ---------------------------------------------------------
    # under autograd.record() these dispatch through the registered ops so
    # the tape sees them (reference parity: every view is an NNVM node);
    # outside recording they stay raw jnp views (no registry overhead)
    def _recording(self) -> bool:
        from .. import autograd
        return autograd.is_recording()

    def reshape(self, *shape, **kwargs) -> "NDArray":
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        if self._recording():
            from . import _gen
            return _gen.Reshape(self, shape=tuple(shape))
        from ..ops.matrix import infer_reshape
        return NDArray(jnp.reshape(self._data, infer_reshape(shape, self.shape)), self._ctx)

    def reshape_like(self, other) -> "NDArray":
        # other.shape is literal here — MXNet special codes (0 = copy dim)
        # apply only to user-passed reshape specs
        if self._recording() and all(d > 0 for d in other.shape):
            from . import _gen
            return _gen.Reshape(self, shape=tuple(other.shape))
        return NDArray(jnp.reshape(self._data, other.shape), self._ctx)

    def expand_dims(self, axis) -> "NDArray":
        if self._recording():
            from . import _gen
            return _gen.expand_dims(self, axis=axis)
        return NDArray(jnp.expand_dims(self._data, axis), self._ctx)

    def flatten(self) -> "NDArray":
        if self._recording():
            from . import _gen
            return _gen.Flatten(self)
        return NDArray(jnp.reshape(self._data, (self.shape[0], -1)), self._ctx)

    def squeeze(self, axis=None) -> "NDArray":
        if self._recording():
            from . import _gen
            return _gen.squeeze(self, axis=axis)
        return NDArray(jnp.squeeze(self._data, axis), self._ctx)

    def transpose(self, axes=None) -> "NDArray":
        if self._recording():
            from . import _gen
            return _gen.transpose(self, axes=axes)
        return NDArray(jnp.transpose(self._data, axes), self._ctx)

    @property
    def T(self) -> "NDArray":
        return self.transpose()

    def broadcast_to(self, shape) -> "NDArray":
        if self._recording():
            from . import _gen
            return _gen.broadcast_to(self, shape=tuple(shape))
        return NDArray(jnp.broadcast_to(self._data, shape), self._ctx)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        from . import _gen
        return _gen.split(self, num_outputs=num_outputs, axis=axis,
                          squeeze_axis=squeeze_axis)

    # -- indexing ------------------------------------------------------------
    def __getitem__(self, key):
        if self._recording():
            routed = self._getitem_recorded(key)
            if routed is not None:
                return routed
        if isinstance(key, NDArray):
            key = key._data.astype(jnp.int32)
        elif isinstance(key, tuple):
            key = tuple(k._data.astype(jnp.int32) if isinstance(k, NDArray) else k
                        for k in key)
        return NDArray(self._data[key], self._ctx)

    def _getitem_recorded(self, key):
        """Route tape-visible indexing through registered ops (int / slice /
        tuple-of-slices / integer-array); returns None for exotic keys
        (boolean masks etc.), which stay raw views."""
        from . import _gen
        if isinstance(key, NDArray):
            # wrap mode keeps numpy negative-index semantics (clip, the op
            # default, would clamp -1 to 0)
            return _gen.take(self, key, axis=0, mode="wrap")
        if isinstance(key, int):
            end = key + 1 if key != -1 else None
            return _gen.slice_axis(self, axis=0, begin=key,
                                   end=end).squeeze(axis=0)
        if isinstance(key, slice):
            if key.step in (None, 1):
                b, e, _ = key.indices(self.shape[0])
                return _gen.slice_axis(self, axis=0, begin=b, end=e)
            return None
        if isinstance(key, tuple) and len(key) <= self.ndim and all(
                isinstance(k, int) or
                (isinstance(k, slice) and k.step in (None, 1))
                for k in key):
            # an int is the slice [k, k+1) with its axis dropped after:
            # x[0, a] under record must reach the tape like x[0][a] does
            # (as a raw view its gradient was silently zero and the
            # parameters behind it looked stale to Trainer.step)
            begin, end, keep = [], [], []
            for k, d in zip(key, self.shape):
                if isinstance(k, int):
                    if not -d <= k < d:
                        raise IndexError(f"index {k} is out of bounds for "
                                         f"axis with size {d}")
                    b, e = k % d, k % d + 1
                else:
                    b, e, _ = k.indices(d)
                    keep.append(max(e - b, 0))
                begin.append(b)
                end.append(e)
            out = _gen.slice(self, begin=tuple(begin), end=tuple(end))
            if len(keep) < len(key):
                out = out.reshape(tuple(keep) + out.shape[len(key):])
            return out
        return None

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, (int, float, bool)):
            v = value
        else:
            v = jnp.asarray(_np.asarray(value), dtype=self.dtype)
        if isinstance(key, NDArray):
            key = key._data.astype(jnp.int32)
        elif isinstance(key, tuple):
            key = tuple(k._data.astype(jnp.int32) if isinstance(k, NDArray) else k
                        for k in key)
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            if not _np.isscalar(v):
                v = jnp.broadcast_to(v, self.shape).astype(self.dtype)
                self._set_data(jnp.asarray(v))
                return
        self._set_data(self._data.at[key].set(v))

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    def __len__(self) -> int:
        return self.shape[0]

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("ambiguous truth value of multi-element NDArray")

    # -- arithmetic (dispatch through registered ops so autograd records) ----
    def _binary(self, other, op, scalar_op, rop=False):
        from . import _gen
        if isinstance(other, NDArray):
            a, b = (other, self) if rop else (self, other)
            return getattr(_gen, op)(a, b)
        if rop and not op.startswith("broadcast_"):
            return getattr(_gen, scalar_op)(self, scalar=float(other))
        return getattr(_gen, scalar_op)(self, scalar=float(other))

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "broadcast_sub", "_rminus_scalar", rop=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binary(o, "broadcast_div", "_rdiv_scalar", rop=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binary(o, "broadcast_mod", "_rmod_scalar", rop=True)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binary(o, "broadcast_power", "_rpower_scalar", rop=True)

    def __neg__(self):
        from . import _gen
        return _gen.negative(self)

    def __abs__(self):
        from . import _gen
        return _gen.abs(self)

    def __eq__(self, o):
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    # in-place ops mutate the buffer (parity: engine write-dependency ops)
    def __iadd__(self, o):
        res = self.__add__(o)
        self._set_data(res._data.astype(self.dtype))
        return self

    def __isub__(self, o):
        res = self.__sub__(o)
        self._set_data(res._data.astype(self.dtype))
        return self

    def __imul__(self, o):
        res = self.__mul__(o)
        self._set_data(res._data.astype(self.dtype))
        return self

    def __itruediv__(self, o):
        res = self.__truediv__(o)
        self._set_data(res._data.astype(self.dtype))
        return self

    __idiv__ = __itruediv__

    # -- reductions as methods ----------------------------------------------
    def sum(self, axis=None, keepdims=False, **kw):
        from . import _gen
        return _gen.sum(self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False, **kw):
        from . import _gen
        return _gen.mean(self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        from . import _gen
        return _gen.max(self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        from . import _gen
        return _gen.min(self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, **kw):
        from . import _gen
        return _gen.argmax(self, axis=axis)

    def argmin(self, axis=None, **kw):
        from . import _gen
        return _gen.argmin(self, axis=axis)

    def norm(self, **kw):
        from . import _gen
        return _gen.norm(self, **kw)

    def abs(self, **kw):
        from . import _gen
        return _gen.abs(self)

    def clip(self, a_min, a_max):
        from . import _gen
        return _gen.clip(self, a_min=a_min, a_max=a_max)

    def sqrt(self):
        from . import _gen
        return _gen.sqrt(self)

    def square(self):
        from . import _gen
        return _gen.square(self)

    def dot(self, other, **kw):
        from . import _gen
        return _gen.dot(self, other, **kw)

    def sigmoid(self):
        from . import _gen
        return _gen.sigmoid(self)

    def tanh(self):
        from . import _gen
        return _gen.tanh(self)

    def relu(self):
        from . import _gen
        return _gen.relu(self)

    def softmax(self, axis=-1):
        from . import _gen
        return _gen.softmax(self, axis=axis)

    def log_softmax(self, axis=-1):
        from . import _gen
        return _gen.log_softmax(self, axis=axis)

    def slice_axis(self, axis, begin, end):
        from . import _gen
        return _gen.slice_axis(self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0, mode="clip"):
        from . import _gen
        return _gen.take(self, indices, axis=axis, mode=mode)

    def one_hot(self, depth, **kw):
        from . import _gen
        return _gen.one_hot(self, depth=depth, **kw)

    def swapaxes(self, dim1, dim2):
        from . import _gen
        return _gen.swapaxes(self, dim1=dim1, dim2=dim2)

    def flip(self, axis):
        from . import _gen
        return _gen.flip(self, axis=axis)

    def tile(self, reps):
        from . import _gen
        return _gen.tile(self, reps=reps)

    def repeat(self, repeats, axis=None):
        from . import _gen
        return _gen.repeat(self, repeats=repeats, axis=axis)

    def pad(self, mode, pad_width, constant_value=0.0):
        from . import _gen
        return _gen.pad(self, mode=mode, pad_width=pad_width,
                        constant_value=constant_value)

    def topk(self, **kw):
        from . import _gen
        return _gen.topk(self, **kw)

    def sort(self, **kw):
        from . import _gen
        return _gen.sort(self, **kw)

    def argsort(self, **kw):
        from . import _gen
        return _gen.argsort(self, **kw)

    def round(self):
        from . import _gen
        return _gen.round(self)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        """Run autograd from this head (parity: ndarray.py backward)."""
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    def __repr__(self):
        return f"\n{self.asnumpy()}\n<NDArray {'x'.join(map(str, self.shape))} @{self._ctx}>"

    # pickling (used by Updater.get_states / multiprocessing DataLoader)
    def __reduce__(self):
        return (_rebuild_ndarray, (self.asnumpy(), self._ctx.device_type,
                                   self._ctx.device_id))


def _rebuild_ndarray(np_data, dev_type, dev_id):
    return array(np_data, ctx=Context(dev_type, dev_id), dtype=np_data.dtype)


# ---------------------------------------------------------------------------
# creation helpers (parity: python/mxnet/ndarray/utils.py + ndarray.py)
# ---------------------------------------------------------------------------
def _place(jarr, ctx: Optional[Context]) -> NDArray:
    ctx = ctx or current_context()
    return NDArray(jax.device_put(jarr, ctx.jax_device()), ctx)


def array(source_array, ctx=None, dtype=None, **kw) -> NDArray:
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
    else:
        src = _np.asarray(source_array)
    if dtype is None:
        # parity: mx.nd.array keeps numpy/NDArray dtype, defaults python
        # lists/scalars to float32 (python/mxnet/ndarray/utils.py)
        dtype = src.dtype if isinstance(source_array, (_np.ndarray, NDArray)) \
            else _np.float32
    return _place(jnp.asarray(src.astype(np_dtype(dtype))), ctx)


def zeros(shape, ctx=None, dtype=None, stype=None, **kw) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return _place(jnp.zeros(shape, np_dtype(dtype)), ctx)


def ones(shape, ctx=None, dtype=None, **kw) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return _place(jnp.ones(shape, np_dtype(dtype)), ctx)


def full(shape, val, ctx=None, dtype=None, **kw) -> NDArray:
    if isinstance(shape, int):
        shape = (shape,)
    return _place(jnp.full(shape, val, np_dtype(dtype)), ctx)


def empty(shape, ctx=None, dtype=None) -> NDArray:
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None,
           **kw) -> NDArray:
    out = jnp.arange(start, stop, step, np_dtype(dtype or "float32"))
    if repeat > 1:
        out = jnp.repeat(out, repeat)
    return _place(out, ctx)


def eye(N, M=0, k=0, ctx=None, dtype=None) -> NDArray:
    return _place(jnp.eye(N, M or N, k=k, dtype=np_dtype(dtype)), ctx)


def from_numpy(a, zero_copy=False) -> NDArray:
    return array(a)


def from_dlpack(cap) -> NDArray:
    return NDArray(jnp.from_dlpack(cap))


def _mod_fn(dunder, mirror):
    """Module-level binary helper (parity: the ndarray.py free functions
    equal/greater/... that mirror the operator dunders).  A scalar lhs
    dispatches the MIRRORED comparison on the NDArray rhs
    (greater(2, x) == x < 2)."""
    def fn(lhs, rhs):
        if isinstance(lhs, NDArray):
            return getattr(lhs, dunder)(rhs)
        if isinstance(rhs, NDArray):
            return getattr(rhs, mirror)(lhs)
        raise TypeError("at least one operand must be an NDArray")
    return fn


equal = _mod_fn("__eq__", "__eq__")
not_equal = _mod_fn("__ne__", "__ne__")
greater = _mod_fn("__gt__", "__lt__")
greater_equal = _mod_fn("__ge__", "__le__")
lesser = _mod_fn("__lt__", "__gt__")
lesser_equal = _mod_fn("__le__", "__ge__")
modulo = _mod_fn("__mod__", "__rmod__")
true_divide = _mod_fn("__truediv__", "__rtruediv__")


def onehot_encode(indices, out):
    """Deprecated one-hot (parity: ndarray.onehot_encode — kept for v0
    compat; use `one_hot`)."""
    from . import _gen
    return _gen.one_hot(indices, depth=out.shape[1], out=out)


def moveaxis(a: NDArray, source, destination) -> NDArray:
    return NDArray(jnp.moveaxis(a._data, source, destination), a._ctx)


def concatenate(arrays: Sequence[NDArray], axis=0, always_copy=True) -> NDArray:
    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis),
                   arrays[0]._ctx)


def waitall() -> None:
    _engine.wait_for_all()


# ---------------------------------------------------------------------------
# save / load (parity API: mx.nd.save/load — src/c_api/c_api.cc:307,330)
# ---------------------------------------------------------------------------
def save(fname: str, data) -> None:
    """Save NDArray / list / dict of NDArrays to one file (.npz container)."""
    if isinstance(data, NDArray):
        payload = {"__mx_single__": data.asnumpy()}
    elif isinstance(data, dict):
        payload = {k: v.asnumpy() for k, v in data.items()}
    elif isinstance(data, (list, tuple)):
        payload = {f"__mx_list_{i:06d}": v.asnumpy() for i, v in enumerate(data)}
    else:
        raise MXNetError("save expects NDArray, list, or dict")
    import os
    # write to a temp file in the same directory, then one atomic
    # os.replace: a crash mid-save must never corrupt an existing file
    # at `fname` (model.save_checkpoint overwrites .params in place)
    tmp = f"{fname}.tmp-{os.getpid()}"
    _np.savez(tmp, **payload)  # numpy appends .npz when missing
    os.replace(tmp + ".npz", fname)


def _from_npz(z):
    keys = list(z.keys())
    if keys == ["__mx_single__"]:
        return array(z["__mx_single__"])
    if all(k.startswith("__mx_list_") for k in keys):
        return [array(z[k]) for k in sorted(keys)]
    return {k: array(z[k]) for k in keys}


def load(fname: str):
    # reference-era binary .params files (dmlc list container) load
    # transparently — load_checkpoint on a reference checkpoint works
    from ..legacy_format import is_reference_format, load_reference_format
    if is_reference_format(fname):
        return load_reference_format(fname)
    with _np.load(fname, allow_pickle=False) as z:
        return _from_npz(z)


def load_frombuffer(buf):
    """Deserialize an in-memory param/array blob — what `load` does for
    a file, without the file (parity: MXNDArrayLoadFromBuffer,
    c_api.cc; the C predict API hands the param blob over by pointer).
    Accepts both container formats `load` does: reference-era dmlc list
    files and the .npz container `save` writes."""
    import io as _io
    from ..legacy_format import (is_reference_buffer,
                                 load_reference_buffer)
    buf = bytes(buf)
    if is_reference_buffer(buf):
        return load_reference_buffer(buf)
    with _np.load(_io.BytesIO(buf), allow_pickle=False) as z:
        return _from_npz(z)
