"""Imperative autograd: record/pause scopes, tape, backward.

Reference parity: `python/mxnet/autograd.py` + `src/imperative/imperative.cc`
(thread-local is_train/is_recording flags include/mxnet/imperative.h:153-172;
RecordOp tape :182; Backward :357).  TPU-native: each recorded op stores the
`jax.vjp` closure of its forward — backward is a reverse tape walk calling
those closures (no separate NNVM Gradient pass; XLA differentiates each op).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .base import MXNetError
from .observability.tracing import span
from .ops import registry as _reg


class _State(threading.local):
    def __init__(self):
        self.recording = False
        self.training = False
        self.tape: List = []


_state = _State()


def is_recording() -> bool:
    return _state.recording


def is_training() -> bool:
    return _state.training


def set_recording(is_record: bool) -> bool:
    old, _state.recording = _state.recording, is_record
    return old


def set_training(train_mode: bool) -> bool:
    old, _state.training = _state.training, train_mode
    return old


class _RecordingStateScope:
    def __init__(self, is_record: Optional[bool], train_mode: Optional[bool]):
        self._enter = (is_record, train_mode)
        self._prev = None

    def __enter__(self):
        rec, train = self._enter
        self._prev = (_state.recording, _state.training)
        if rec is not None:
            _state.recording = rec
        if train is not None:
            _state.training = train
        return self

    def __exit__(self, *exc):
        _state.recording, _state.training = self._prev


def record(train_mode: bool = True):
    """Scope in which executed ops are recorded (parity: autograd.py:122)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode: bool = False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# ---------------------------------------------------------------------------
# Tape
# ---------------------------------------------------------------------------
class _TapeEntry:
    # out_refs keeps the output NDArrays alive for the tape's lifetime:
    # keys are (id, version) and CPython recycles ids of collected
    # objects, so dropping the refs would let unrelated later arrays
    # alias a dead output's key (wrong-gradient corruption)
    __slots__ = ("in_keys", "in_refs", "out_keys", "out_refs", "vjp_fn",
                 "cot_zeros", "in_idx")

    def __init__(self, in_keys, in_refs, out_keys, out_refs, vjp_fn,
                 cot_zeros, in_idx=None):
        self.in_keys = in_keys
        self.in_refs = in_refs
        self.out_keys = out_keys
        self.out_refs = out_refs
        self.vjp_fn = vjp_fn       # cotangents tuple -> input grads tuple
        # (shape, dtype) spec per forward output; the zero cotangent is
        # materialized lazily in backward() and only for slots that did
        # not receive a gradient — recording must not allocate (a
        # row-sparse dot output would otherwise pin an O(vocab) dense
        # zeros buffer per recorded call)
        self.cot_zeros = cot_zeros
        # vjp-grad slot per tape input (optional tensor inputs may be None
        # in the op call — their slots exist in the vjp but not on the tape)
        self.in_idx = in_idx if in_idx is not None else list(range(len(in_keys)))


def _key(arr) -> Tuple[int, int]:
    return (id(arr), arr._version)


def _record(op, inputs, outputs, vjp_fn, raw_outs) -> None:
    """Called by ndarray.register.invoke when recording (RecordOp parity).

    `outputs` are the visible result NDArrays (their keys index the grad map);
    `raw_outs` is the full forward output tuple (visible + aux) whose
    shapes/dtypes define the cotangent structure for vjp_fn.
    """
    indexed = [(i, a) for i, a in enumerate(inputs) if hasattr(a, "_version")]
    _state.tape.append(_TapeEntry(
        [_key(a) for _, a in indexed],
        [a for _, a in indexed],
        [_key(o) for o in outputs],
        list(outputs),
        vjp_fn,
        tuple((tuple(o.shape), o.dtype) for o in raw_outs),
        in_idx=[i for i, _ in indexed]))


def _mark_variable(arr) -> None:
    pass


def mark_variables(variables, gradients, grad_reqs="write") -> None:
    """Parity: autograd.mark_variables."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._grad = g
        v._grad_req = req


def _acc(a, b):
    """Gradient accumulation that understands row-sparse cotangent
    markers (_RspCot): rsp+rsp stays rows-only; mixing with dense
    densifies (correct fallback, e.g. tied embeddings)."""
    from .ndarray.sparse import _RspCot
    if isinstance(a, _RspCot) or isinstance(b, _RspCot):
        return a + b if isinstance(a, _RspCot) else b + a
    return a + b


def _ones_cot(shape: Tuple[int, ...], dtype):
    """Default head cotangent — allocated FRESH each call, never cached:
    when a head is itself a leaf with attach_grad, this exact array is
    deposited as the user-visible ``.grad`` buffer, and several
    consumers donate gradient buffers into jitted programs (per-key
    ``Trainer.update``, module fit, serving).  A process-lifetime cache
    would hand out an array XLA may delete, poisoning every later
    default-seed backward of that (shape, dtype) with 'Array has been
    deleted'.  The fill is one cheap XLA op; the whole-step program
    never needs it at all — gluon/wholestep.py differentiates a summed
    loss instead."""
    return jnp.ones(shape, dtype)


def backward(heads, head_grads=None, retain_graph: bool = False,
             train_mode: bool = True) -> None:
    """Reverse walk of the tape from `heads` (parity: Imperative::Backward)."""
    with span("mx.autograd.backward", cat="autograd"):
        _backward(heads, head_grads, retain_graph)


def _backward(heads, head_grads, retain_graph) -> None:
    from .ndarray.sparse import _RspCot, RowSparseNDArray
    tape = _state.tape
    grad_map: Dict[Tuple[int, int], jax.Array] = {}
    for i, h in enumerate(heads):
        hg = None if head_grads is None else head_grads[i]
        g = _ones_cot(tuple(h.shape), h.dtype) if hg is None else (
            hg._data if hasattr(hg, "_data") else jnp.asarray(hg))
        k = _key(h)
        grad_map[k] = _acc(grad_map[k], g) if k in grad_map else g

    for entry in reversed(tape):
        if not any(k in grad_map for k in entry.out_keys):
            continue
        cots = [None] * len(entry.cot_zeros)
        for j, k in enumerate(entry.out_keys):
            if k in grad_map:
                g = grad_map[k]
                if isinstance(g, _RspCot):
                    g = g.to_dense()  # upstream op needs a dense cotangent
                cots[j] = g.astype(entry.cot_zeros[j][1])
        cots = [jnp.zeros(*entry.cot_zeros[j]) if c is None else c
                for j, c in enumerate(cots)]
        in_grads = entry.vjp_fn(tuple(cots))
        for idx, k in enumerate(entry.in_keys):
            g = in_grads[entry.in_idx[idx]]
            if not isinstance(g, _RspCot):
                g = _reg.zero_like_grad(g, entry.in_refs[idx]._data)
            grad_map[k] = _acc(grad_map[k], g) if k in grad_map else g

    # write accumulated grads into attached .grad buffers
    seen = set()

    def _deposit(ref, k):
        if id(ref) in seen or ref._grad is None or ref._grad_req == "null":
            return
        if k in grad_map:
            seen.add(id(ref))
            ref._fresh_grad = True
            g = grad_map[k]
            if isinstance(ref._grad, RowSparseNDArray):
                if not isinstance(g, _RspCot):
                    # dense grad into an rsp buffer: keep only nonzero
                    # rows (correct, though the dense detour already paid)
                    from .ndarray.sparse import row_sparse_array
                    rs = row_sparse_array(g)
                    ids, vals = rs._indices, rs._values
                else:
                    ids, vals = g.ids, g.vals
                vals = vals.astype(ref._grad.dtype)
                if ref._grad_req == "add":
                    ref._grad._add_rows(ids, vals)
                else:
                    ref._grad._assign_rows(ids, vals)
                return
            if isinstance(g, _RspCot):
                g = g.to_dense()
            g = g.astype(ref._grad.dtype)
            if ref._grad_req == "add":
                ref._grad._set_data(ref._grad._data + g)
            else:
                ref._grad._set_data(g)

    for entry in tape:
        for ref, k in zip(entry.in_refs, entry.in_keys):
            _deposit(ref, k)
    for h in heads:
        _deposit(h, _key(h))

    if not retain_graph:
        _state.tape = []


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Grads of heads wrt variables (convenience; later-mxnet API)."""
    if create_graph:
        raise MXNetError("create_graph=True not supported yet")
    heads = heads if isinstance(heads, (list, tuple)) else [heads]
    for v in variables:
        if v._grad is None:
            v.attach_grad()
    backward(list(heads), head_grads, retain_graph=bool(retain_graph),
             train_mode=train_mode)
    return [v._grad for v in variables]


def get_symbol(x):
    raise MXNetError("autograd.get_symbol is not supported in mxnet_tpu; "
                     "use gluon HybridBlock tracing instead")


# ---------------------------------------------------------------------------
# Custom differentiable Function (parity: autograd.Function, autograd.py:495,
# backed by c_api_function.cc in the reference)
# ---------------------------------------------------------------------------
class Function:
    """User-defined op with explicit forward/backward over NDArrays."""

    def __init__(self):
        self._saved = None

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError

    def __call__(self, *inputs):
        from .ndarray import NDArray
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)
        if is_recording():
            func = self
            ctx = inputs[0]._ctx if inputs else None

            def vjp_fn(cots):
                with pause():
                    gin = func.backward(*[NDArray(c, ctx) for c in cots])
                gin = [gin] if not isinstance(gin, (list, tuple)) else list(gin)
                return tuple(g._data for g in gin)

            _state.tape.append(_TapeEntry(
                [_key(a) for a in inputs], list(inputs),
                [_key(o) for o in outs], list(outs), vjp_fn,
                tuple((tuple(o.shape), o.dtype) for o in outs)))
        return outputs
