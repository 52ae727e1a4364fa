"""`mx.nd` namespace: NDArray + one function per registered operator.

Parity: `python/mxnet/ndarray/__init__.py` — flat op functions plus
`random`, `linalg`, `sparse` sub-namespaces.
"""
from .ndarray import (NDArray, array, zeros, ones, full, empty, arange, eye,
                      concatenate, moveaxis, waitall, save, load,
                      load_frombuffer, from_numpy,
                      from_dlpack, equal, not_equal, greater, greater_equal,
                      lesser, lesser_equal, modulo, true_divide,
                      onehot_encode)
from ..legacy_format import save_reference_format, load_reference_format
from . import register
from .register import invoke, _gen

# hoist every generated op function into this namespace: mx.nd.<op>(...)
_g = globals()
for _name in dir(_gen):
    if not _name.startswith("__"):
        _g[_name] = getattr(_gen, _name)

from . import random
from . import linalg
from . import sparse
from . import contrib
from .sparse import CSRNDArray, RowSparseNDArray

# storage-class-aware forms shadow the value-level generated ops
cast_storage = sparse.cast_storage
sparse_retain = sparse.retain

imdecode = None  # provided by mxnet_tpu.image


def maximum(lhs, rhs, **kw):
    """Elementwise max of arrays/scalars (parity: nd.maximum)."""
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return _gen.broadcast_maximum(lhs, rhs)
    if isinstance(lhs, NDArray):
        return _gen._maximum_scalar(lhs, scalar=float(rhs))
    if isinstance(rhs, NDArray):
        return _gen._maximum_scalar(rhs, scalar=float(lhs))
    return lhs if lhs > rhs else rhs


def minimum(lhs, rhs, **kw):
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return _gen.broadcast_minimum(lhs, rhs)
    if isinstance(lhs, NDArray):
        return _gen._minimum_scalar(lhs, scalar=float(rhs))
    if isinstance(rhs, NDArray):
        return _gen._minimum_scalar(rhs, scalar=float(lhs))
    return lhs if lhs < rhs else rhs


def hypot(lhs, rhs):
    """sqrt(lhs² + rhs²) of arrays/scalars (parity: nd.hypot)."""
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return _gen.broadcast_hypot(lhs, rhs)
    if isinstance(lhs, NDArray):
        return _gen._hypot_scalar(lhs, scalar=float(rhs))
    if isinstance(rhs, NDArray):
        return _gen._hypot_scalar(rhs, scalar=float(lhs))
    return (lhs * lhs + rhs * rhs) ** 0.5


def add(l, r):
    return l + r


def subtract(l, r):
    return l - r


def multiply(l, r):
    return l * r


def divide(l, r):
    return l / r


def power(l, r):
    return l ** r


pow = power
