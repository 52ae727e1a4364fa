"""Optimizers + Updater (parity: python/mxnet/optimizer.py, 1210 LoC).

SGD/Adam/RMSProp/Ftrl dispatch to the fused update operators
(`mxnet_tpu.ops.optimizer_ops`, parity src/operator/optimizer_op.cc) so each
step is one XLA kernel; the rest are composed NDArray math.  Updater state
pickling matches the reference API (set_states/get_states) for
checkpoint/resume and kvstore server-side optimizers.
"""
from __future__ import annotations

import logging
import math
import pickle
from typing import Any, Dict, Optional

import numpy as _np
import jax
import jax.numpy as jnp

from .analysis import hot_path
from .analysis import sanitizer as _san_mod
from .base import MXNetError, Registry, getenv
from . import ndarray as nd
from .ndarray import NDArray
from .faultinject import fire as _fi_fire
from .observability import introspect as _introspect
from .observability import memory as _memory
from .observability import metrics as _metrics
from .observability.tracing import span

_REG = Registry("optimizer")
_logger = logging.getLogger("mxnet_tpu.optimizer")


def cast_like(new, old):
    """Keep weights/states in their own dtype after a compiled step
    (traced lr/wd are strong f32; the per-key path's weak python floats
    did this implicitly).  Tolerant of None and nested tuple states.
    Shared by FusedUpdater.update_all and the gluon whole-step compiler
    — their bitwise-parity contract depends on identical casting."""
    if new is None or old is None:
        return new
    if isinstance(old, (tuple, list)):
        return type(old)(cast_like(n, o) for n, o in zip(new, old))
    return new.astype(old.dtype) if hasattr(old, "dtype") else new


def _rows_of(arr, rows):
    """Gather arr[rows] without densifying rsp storage (shared gather in
    ndarray.sparse — same semantics as KVStore.row_sparse_pull)."""
    from .ndarray.sparse import gather_rows
    return gather_rows(arr, rows)


def _write_rows(arr, rows, new_rows) -> None:
    """arr[rows] = new_rows, rows-only for rsp storage (an rsp weight is
    never materialized dense on the optimizer hot path)."""
    from .ndarray.sparse import RowSparseNDArray
    if isinstance(arr, RowSparseNDArray):
        arr._upsert_rows(rows, new_rows)
    else:
        arr._set_data(arr._data.at[jnp.asarray(rows)].set(new_rows))


def _is_low_prec(dtype) -> bool:
    """float16/bfloat16 weights get fp32 master copies under multi_precision
    (parity: optimizer_op.cc mp_sgd_* — bf16 is the TPU-native low precision)."""
    return _np.dtype(dtype).name in ("float16", "bfloat16")


class Optimizer:
    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult: Dict[Any, float] = {}
        self.wd_mult: Dict[Any, float] = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count: Dict[Any, int] = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.idx2name = dict(param_idx2name or {})
        self.sym_info = (sym.attr_dict(), sym.list_arguments()) if sym is not None \
            else ({}, [])
        self.param_dict = param_dict or {}
        self.set_lr_mult({})
        self.set_wd_mult({})

    # -- registry -----------------------------------------------------------
    @staticmethod
    def register(klass):
        _REG.register(klass)
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        return _REG.get(name)(**kwargs)

    # -- state --------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        if self.multi_precision and _is_low_prec(weight.dtype):
            from .ndarray.sparse import RowSparseNDArray
            if isinstance(weight, RowSparseNDArray):
                # rows-only fp32 master: rows present now, new rows
                # upserted by the rsp update path — never the dense
                # O(vocab) copy (parity: mp SGDUpdateRspRspImpl)
                w32 = RowSparseNDArray(
                    weight._indices, weight._values.astype(jnp.float32),
                    weight.shape, weight.context, _dedup=False)
            else:
                w32 = weight.astype(_np.float32)
            return (self.create_state(index, w32), w32)
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and _is_low_prec(weight.dtype):
            inner, w32 = state
            g32 = grad.astype(_np.float32)
            self.update(index, w32, g32, inner)
            w32.copyto(weight)
        else:
            self.update(index, weight, grad, state)

    # -- fused multi-tensor path ---------------------------------------------
    # The TPU analog of the reference's engine op-bulking
    # (src/executor/graph_executor.cc:1350): FusedUpdater traces fused_step
    # for EVERY parameter into ONE jitted XLA program per training step, so
    # Module.update / Trainer.step issue O(1) dispatches instead of O(#params).
    fused = False  # subclasses with a pure fused_step set True
    # True when fused_step itself implements the fp32-master path (SGD's
    # mp_sgd_* kernels); otherwise _fused_step_mp wraps any fused_step with
    # the generic master-weight recipe (parity: update_multi_precision).
    fused_handles_mp = False

    def fused_hyper_key(self):
        """Static hyperparameters baked into the fused trace (cache key)."""
        return (self.rescale_grad, self.clip_gradient)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        """Pure single-param step on jax values: returns (new_weight,
        new_state).  `lr`/`wd` are traced f32 scalars, `t` the traced update
        count (for bias correction); everything else is baked static."""
        raise NotImplementedError

    def _fused_step_mp(self, index, weight, grad, state, lr, wd, t):
        """fused_step with generic multi-precision handling: low-precision
        weights step their fp32 master copy and cast back (parity:
        update_multi_precision)."""
        if self.multi_precision and _is_low_prec(weight.dtype) \
                and not self.fused_handles_mp:
            inner, w32 = state
            nw32, ninner = self.fused_step(index, w32,
                                           grad.astype(jnp.float32), inner,
                                           lr, wd, t)
            return nw32.astype(weight.dtype), (ninner, nw32)
        return self.fused_step(index, weight, grad, state, lr, wd, t)

    def _clip(self, g):
        if self.clip_gradient is not None:
            return jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def _fused_common(self, lr, wd, **extra):
        p = {"lr": lr, "wd": wd, "rescale_grad": self.rescale_grad,
             "clip_gradient": self.clip_gradient
             if self.clip_gradient is not None else -1.0}
        p.update(extra)
        return p

    # -- lr/wd plumbing ------------------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("lr_scheduler is set; cannot set lr directly")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        attr, arg_names = self.sym_info
        for name in arg_names:
            if name in attr and "__lr_mult__" in attr[name]:
                self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        attr, arg_names = self.sym_info
        for name in arg_names:
            if name in attr and "__wd_mult__" in attr[name]:
                self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index) -> float:
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index) -> float:
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    def _common_kwargs(self):
        kw = dict(rescale_grad=self.rescale_grad)
        if self.clip_gradient is not None:
            kw["clip_gradient"] = self.clip_gradient
        return kw


register = Optimizer.register
create = Optimizer.create_optimizer


@register
class SGD(Optimizer):
    """SGD with momentum and multi-precision (parity: optimizer.py:435)."""

    fused = True
    fused_handles_mp = True

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.multi_precision and _is_low_prec(weight.dtype):
            return self.create_state_multi_precision(index, weight)
        if self.momentum == 0.0:
            return None
        if getattr(weight, "stype", "default") == "row_sparse":
            # rsp weight gets an rsp momentum (parity: optimizer.py SGD
            # create_state uses stype=weight.stype) — O(nnz), not O(vocab)
            from .ndarray.sparse import zeros_sparse
            return zeros_sparse("row_sparse", weight.shape,
                                ctx=weight.context, dtype=weight.dtype)
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.momentum,
                self.multi_precision)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        from .ops.registry import OP_REGISTRY as _K
        p = self._fused_common(lr, wd, momentum=self.momentum)
        if self.multi_precision and _is_low_prec(weight.dtype):
            mom, w32 = state
            if self.momentum != 0.0:
                nw, nmom, nw32 = _K["mp_sgd_mom_update"].fn(
                    p, weight, grad, mom, w32)
                return nw, (nmom, nw32)
            nw, nw32 = _K["mp_sgd_update"].fn(p, weight, grad, w32)
            return nw, (None, nw32)
        if self.momentum != 0.0:
            nw, nmom = _K["sgd_mom_update"].fn(p, weight, grad, state)
            return nw, nmom
        return _K["sgd_update"].fn(p, weight, grad), None

    def _update_impl(self, index, weight, grad, state, multi_precision):
        """One count bump + one fused kernel (parity: optimizer.py SGD
        _update_impl — update/update_multi_precision share it so num_update
        advances exactly once per step)."""
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        from .ndarray.sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray):
            # row-sparse lazy update: ONLY rows present in the gradient
            # step (incl. their wd term) — parity: optimizer_op.cc
            # SGDUpdateRspRspImpl / SGDMomUpdateRspRspImpl (+ mp variants:
            # the fp32 master rows step and cast back).  Rows-only on BOTH
            # sides: an rsp-stored weight/state is gathered and written
            # back through its stored rows, never materialized dense.
            rows = _np.asarray(grad._indices)
            g = grad._values.astype(jnp.float32) * self.rescale_grad
            if self.clip_gradient is not None:
                g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
            if multi_precision:
                mom_state, w32 = state
            else:
                mom_state, w32 = state, weight
            wr = _rows_of(w32, rows).astype(jnp.float32)
            if self.momentum != 0.0 and mom_state is not None:
                mr = _rows_of(mom_state, rows).astype(jnp.float32)
                new_m = self.momentum * mr - lr * (g + wd * wr)
                _write_rows(mom_state, rows, new_m.astype(mom_state.dtype))
                delta = new_m
            else:
                delta = -lr * (g + wd * wr)
            new_rows = wr + delta
            _write_rows(w32, rows, new_rows.astype(w32.dtype))
            if multi_precision:
                _write_rows(weight, rows, new_rows.astype(weight.dtype))
            return
        kw = self._common_kwargs()
        if multi_precision:
            inner, w32 = state
            if self.momentum != 0.0:
                nd.mp_sgd_mom_update(weight, grad, inner, w32, lr=lr, wd=wd,
                                     momentum=self.momentum, **kw)
            else:
                nd.mp_sgd_update(weight, grad, w32, lr=lr, wd=wd, **kw)
        elif state is not None:
            nd.sgd_mom_update(weight, grad, state, lr=lr, wd=wd,
                              momentum=self.momentum, **kw)
        else:
            nd.sgd_update(weight, grad, lr=lr, wd=wd, **kw)

    def update(self, index, weight, grad, state):
        self._update_impl(index, weight, grad, state, False)

    def update_multi_precision(self, index, weight, grad, state):
        use_mp = self.multi_precision and _is_low_prec(weight.dtype)
        self._update_impl(index, weight, grad, state, use_mp)


@register
class ccSGD(SGD):
    """Deprecated alias of SGD (parity: optimizer.py ccSGD — the old
    C++-side SGD; identical math here)."""

@register
class NAG(Optimizer):
    """Nesterov accelerated SGD (parity: optimizer.py NAG — the lookahead
    form: w -= lr*(grad + momentum*mom) after mom = momentum*mom + grad)."""

    fused = True

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.momentum)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        g = self._clip(grad.astype(jnp.float32) * self.rescale_grad) \
            + wd * weight.astype(jnp.float32)
        if self.momentum == 0.0:
            return (weight.astype(jnp.float32) - lr * g).astype(weight.dtype), None
        mom = state.astype(jnp.float32) * self.momentum + g
        neww = weight.astype(jnp.float32) - lr * (g + self.momentum * mom)
        return neww.astype(weight.dtype), mom.astype(state.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        from .ndarray.sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray):
            # lazy row-sparse update: only rows present in the gradient
            # step (same invariant as SGD/Adam — untouched rows never
            # decay and their momentum does not advance)
            rows = grad._indices
            g = self._clip(grad._values.astype(jnp.float32)
                           * self.rescale_grad)
            wr = jnp.take(weight._data, rows, axis=0).astype(jnp.float32)
            g = g + wd * wr
            if self.momentum != 0.0 and state is not None:
                mr = jnp.take(state._data, rows, axis=0).astype(jnp.float32)
                new_m = self.momentum * mr + g
                state._set_data(state._data.at[rows].set(
                    new_m.astype(state.dtype)))
                step = lr * (g + self.momentum * new_m)
            else:
                step = lr * g
            weight._set_data(weight._data.at[rows].add(
                (-step).astype(weight.dtype)))
            return
        nw, nmom = self.fused_step(index, weight._data, grad._data,
                                   None if state is None else state._data,
                                   lr, wd, self._index_update_count[index])
        weight._set_data(nw)
        if state is not None:
            state._set_data(nmom)

    def update_multi_precision(self, index, weight, grad, state):
        from .ndarray.sparse import RowSparseNDArray
        if self.multi_precision and _is_low_prec(weight.dtype) \
                and isinstance(grad, RowSparseNDArray):
            # the generic path's grad.astype would densify — recast only
            # the stored values so the lazy row invariant holds under mp
            inner, w32 = state
            g32 = RowSparseNDArray(grad._indices,
                                   grad._values.astype(jnp.float32),
                                   grad.shape, weight.context,
                                   _dedup=False)
            self.update(index, w32, g32, inner)
            w32.copyto(weight)
            return
        super().update_multi_precision(index, weight, grad, state)


@register
class Adam(Optimizer):
    fused = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.beta1, self.beta2,
                self.epsilon)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        from .ops.registry import OP_REGISTRY as _K
        tf = t.astype(jnp.float32)
        coef = jnp.sqrt(1.0 - self.beta2 ** tf) / (1.0 - self.beta1 ** tf)
        p = self._fused_common(lr * coef, wd, beta1=self.beta1,
                               beta2=self.beta2, epsilon=self.epsilon)
        mean, var = state
        nw, nm, nv = _K["adam_update"].fn(p, weight, grad, mean, var)
        return nw, (nm, nv)

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),
                nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) * math.sqrt(1.0 - self.beta2 ** t) / \
            (1.0 - self.beta1 ** t)
        wd = self._get_wd(index)
        mean, var = state
        from .ndarray.sparse import RowSparseNDArray
        if isinstance(grad, RowSparseNDArray):
            # lazy row-sparse Adam: only gradient rows step and only their
            # mean/var slots advance (parity: optimizer_op.cc
            # AdamUpdateRspRspRspImpl)
            rows = grad._indices
            g = grad._values.astype(jnp.float32) * self.rescale_grad
            if self.clip_gradient is not None:
                g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
            wr = jnp.take(weight._data, rows, axis=0).astype(jnp.float32)
            g = g + wd * wr
            mr = jnp.take(mean._data, rows, axis=0).astype(jnp.float32)
            vr = jnp.take(var._data, rows, axis=0).astype(jnp.float32)
            nm = self.beta1 * mr + (1 - self.beta1) * g
            nv = self.beta2 * vr + (1 - self.beta2) * jnp.square(g)
            step = lr * nm / (jnp.sqrt(nv) + self.epsilon)
            mean._set_data(mean._data.at[rows].set(nm.astype(mean.dtype)))
            var._set_data(var._data.at[rows].set(nv.astype(var.dtype)))
            weight._set_data(weight._data.at[rows].add(
                (-step).astype(weight.dtype)))
            return
        nd.adam_update(weight, grad, mean, var, lr=lr, wd=wd,
                       beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
                       **self._common_kwargs())


@register
class RMSProp(Optimizer):
    fused = True

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.gamma1,
                self.gamma2, self.epsilon, self.centered, self.clip_weights)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        from .ops.registry import OP_REGISTRY as _K
        p = self._fused_common(
            lr, wd, gamma1=self.gamma1, epsilon=self.epsilon,
            clip_weights=self.clip_weights if self.clip_weights else -1.0)
        if self.centered:
            p["gamma2"] = self.gamma2
            n, g, delta = state
            nw, nn, ng, nd_ = _K["rmspropalex_update"].fn(
                p, weight, grad, n, g, delta)
            return nw, (nn, ng, nd_)
        (n,) = state
        nw, nn = _K["rmsprop_update"].fn(p, weight, grad, n)
        return nw, (nn,)

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2 = gamma1, gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        z = lambda: nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)
        if self.centered:
            return (z(), z(), z())
        return (z(),)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        kw = self._common_kwargs()
        if self.clip_weights:
            kw["clip_weights"] = self.clip_weights
        if self.centered:
            n, g, delta = state
            nd.rmspropalex_update(weight, grad, n, g, delta, lr=lr, wd=wd,
                                  gamma1=self.gamma1, gamma2=self.gamma2,
                                  epsilon=self.epsilon, **kw)
        else:
            (n,) = state
            nd.rmsprop_update(weight, grad, n, lr=lr, wd=wd, gamma1=self.gamma1,
                              epsilon=self.epsilon, **kw)


@register
class AdaGrad(Optimizer):
    fused = True

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.float_stable_eps)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        g = self._clip(grad * self.rescale_grad)
        hist = state + g * g
        nw = weight - lr * (g / jnp.sqrt(hist + self.float_stable_eps)
                            + wd * weight)
        return nw, hist

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        history = state
        history += grad * grad
        weight += -lr * (grad / (history + self.float_stable_eps).sqrt() + wd * weight)


@register
class AdaDelta(Optimizer):
    fused = True

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.rho, self.epsilon)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        g = self._clip(grad * self.rescale_grad)
        acc_g, acc_delta = state
        nacc_g = self.rho * acc_g + (1.0 - self.rho) * g * g
        cd = jnp.sqrt(acc_delta + self.epsilon) / \
            jnp.sqrt(nacc_g + self.epsilon) * g
        nacc_d = self.rho * acc_delta + (1.0 - self.rho) * cd * cd
        return weight - cd - wd * weight, (nacc_g, nacc_d)

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context),
                nd.zeros(weight.shape, ctx=weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        acc_g, acc_delta = state
        acc_g[:] = self.rho * acc_g + (1.0 - self.rho) * grad * grad
        current_delta = ((acc_delta + self.epsilon).sqrt() /
                         (acc_g + self.epsilon).sqrt()) * grad
        acc_delta[:] = self.rho * acc_delta + (1.0 - self.rho) * \
            current_delta * current_delta
        weight[:] = weight - current_delta - wd * weight


@register
class Ftrl(Optimizer):
    fused = True

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.lamda1, self.beta)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        from .ops.registry import OP_REGISTRY as _K
        p = self._fused_common(lr, wd, lamda1=self.lamda1, beta=self.beta)
        z, n = state
        nw, nz, nn = _K["ftrl_update"].fn(p, weight, grad, z, n)
        return nw, (nz, nn)

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context),
                nd.zeros(weight.shape, ctx=weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        z, n = state
        nd.ftrl_update(weight, grad, z, n, lr=self._get_lr(index),
                       wd=self._get_wd(index), lamda1=self.lamda1,
                       beta=self.beta, **self._common_kwargs())


@register
class Adamax(Optimizer):
    fused = True

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def fused_hyper_key(self):
        return (self.rescale_grad, self.clip_gradient, self.beta1, self.beta2)

    def fused_step(self, index, weight, grad, state, lr, wd, t):
        g = self._clip(grad * self.rescale_grad + wd * weight)
        m_t, u_t = state
        nm = self.beta1 * m_t + (1.0 - self.beta1) * g
        nu = jnp.maximum(self.beta2 * u_t, jnp.abs(g))
        lr_t = lr / (1.0 - self.beta1 ** t.astype(jnp.float32))
        return weight - lr_t * nm / nu, (nm, nu)

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context),
                nd.zeros(weight.shape, ctx=weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr = self._get_lr(index) / (1.0 - self.beta1 ** t)
        wd = self._get_wd(index)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        m_t, u_t = state
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * grad
        u_t[:] = nd.maximum(self.beta2 * u_t, grad.abs())
        weight[:] = weight - lr * m_t / u_t


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (nd.zeros(weight.shape, ctx=weight.context),
                nd.zeros(weight.shape, ctx=weight.context))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        lr, wd = self._get_lr(index), self._get_wd(index)
        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        momentum_t = self.beta1 * (1.0 - 0.5 * 0.96 ** (t * self.schedule_decay))
        momentum_t_1 = self.beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule *= momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1
        m_t, v_t = state
        m_t[:] = self.beta1 * m_t + (1.0 - self.beta1) * grad
        v_t[:] = self.beta2 * v_t + (1.0 - self.beta2) * grad * grad
        grad_prime = grad / (1.0 - self.m_schedule)
        m_t_prime = m_t / (1.0 - m_schedule_next)
        v_t_prime = v_t / (1.0 - self.beta2 ** t)
        m_t_bar = (1.0 - momentum_t) * grad_prime + momentum_t_1 * m_t_prime
        weight[:] = weight - lr * m_t_bar / (v_t_prime.sqrt() + self.epsilon)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (parity: optimizer.py SGLD)."""

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        weight[:] = weight - lr / 2 * (grad + wd * weight) + \
            nd.random.normal(0, math.sqrt(lr), weight.shape,
                             dtype=weight.dtype, ctx=weight.context)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (parity: optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous: Dict[Any, NDArray] = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (nd.zeros(weight.shape, ctx=weight.context), weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = grad.clip(-self.clip_gradient, self.clip_gradient)
        mom, previous_weight = state
        comp = grad + wd * weight + self.lamda * grad * grad * \
            (weight - previous_weight)
        if mom is not None:
            mom[:] = self.momentum * mom - lr * comp
            weight[:] = weight + mom
        else:
            weight[:] = weight - lr * comp
        previous_weight[:] = weight


@register
class Test(Optimizer):
    """Simple test optimizer (parity: optimizer.py:1127 — used by kvstore
    server tests)."""

    def create_state(self, index, weight):
        return nd.zeros(weight.shape, ctx=weight.context)

    def update(self, index, weight, grad, state):
        weight[:] = weight + grad * self.rescale_grad
        state[:] = weight


ccSGD = SGD  # deprecated alias kept by the reference


def _conform_state_sharding(state, weight):
    """Place freshly-created optimizer state on the weight's sharding.

    Under a multi-device Module the weights are mesh-replicated
    (NamedSharding); states created by nd.zeros land on one device and
    would make the fused update's jit see mixed placements.  Same-shape
    leaves (momentum, fp32 masters) take the weight's own sharding;
    other array leaves replicate over the weight's mesh."""
    from .ndarray.sparse import BaseSparseNDArray
    if isinstance(weight, BaseSparseNDArray):
        # rows-only storage is host-orchestrated; no mesh sharding to
        # conform to (and ._data would materialize the dense O(vocab) view)
        return state
    wdata = weight._data if isinstance(weight, NDArray) else weight
    sharding = getattr(wdata, "sharding", None)
    if sharding is None or not hasattr(sharding, "mesh") or \
            len(getattr(wdata, "devices", lambda: [0])()) <= 1:
        return state

    from jax.sharding import NamedSharding, PartitionSpec
    repl = NamedSharding(sharding.mesh, PartitionSpec())

    def place(s):
        if s is None:
            return None
        if isinstance(s, NDArray):
            tgt = sharding if s.shape == wdata.shape else repl
            s._set_data(jax.device_put(s._data, tgt))
            return s
        if isinstance(s, (tuple, list)):
            return type(s)(place(x) for x in s)
        return s

    return place(state)


def _register_state(state) -> None:
    """Ledger-register raw jax arrays inside an optimizer state tree
    (NDArray states already self-registered at creation under the
    enclosing memory_scope)."""
    if state is None or isinstance(state, NDArray):
        return
    if isinstance(state, (tuple, list)):
        for s in state:
            _register_state(s)
        return
    if hasattr(state, "shape") and hasattr(state, "dtype"):
        _memory.register(state, tag="optimizer_state")


class Updater:
    """Applies an optimizer with per-index states (parity: optimizer.get_updater)."""

    def __init__(self, optimizer: Optimizer):
        self.optimizer = optimizer
        self.states: Dict[Any, Any] = {}
        self.states_synced: Dict[Any, bool] = {}

    def _ensure_state(self, index, weight):
        if index not in self.states:
            # HBM ledger: optimizer state (momentum/adam moments, fp32
            # masters) is born here — NDArray states self-register under
            # the scope tag, raw jax states register explicitly
            with _memory.memory_scope("optimizer_state"):
                state = self.optimizer.create_state_multi_precision(
                    index, weight)
                state = _conform_state_sharding(state, weight)
                if _memory.ENABLED:
                    _register_state(state)
            self.states[index] = state
            self.states_synced[index] = True
        elif not self.states_synced[index]:
            self.states[index] = self.sync_state_context(self.states[index],
                                                         weight.context)
            self.states_synced[index] = True

    def __call__(self, index, grad, weight):
        self._ensure_state(index, weight)
        if _metrics.ENABLED:
            _metrics.OPTIMIZER_STEPS.inc()
            # a per-key update launches at least one device program; the
            # legacy (non-fused) trainer path is O(params) of these, and
            # TRAINER_STEP_DISPATCHES must show that against the fused
            # path's single update_all launch
            _metrics.XLA_LAUNCHES.inc(kind="optimizer")
        self.optimizer.update_multi_precision(index, weight, grad,
                                              self.states[index])

    def sync_state_context(self, state, context):
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, (tuple, list)):
            return type(state)(self.sync_state_context(i, context) for i in state)
        return state

    def set_states(self, states):
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)

    def get_states(self, dump_optimizer=False):
        if dump_optimizer:
            return pickle.dumps((
                {k: _to_np_state(v) for k, v in self.states.items()},
                self.optimizer))
        return pickle.dumps({k: _to_np_state(v) for k, v in self.states.items()})


def _to_np_state(state):
    # states pickle as numpy; rehydrated lazily on first use
    if isinstance(state, NDArray):
        return state
    return state


class HyperDeviceCache:
    """Device-cached (lr, wd) vectors + a device-resident step counter
    per key tuple — the ONE implementation behind
    ``FusedUpdater.hyper_arrays`` and ``WholeStepCompiler``'s hyper
    plumbing (formerly two ~30-line mirrors; the fused/whole-step
    bitwise-parity tests pin that sharing it changes nothing).

    Every fresh host->device transfer is a launch on the hot path, so lr/wd re-upload only when a
    schedule actually changes them (last-VALUE cache — a per-step
    schedule must not grow a dict by one device array per step), and
    the step counter lives ON DEVICE, incremented by the compiled
    update itself; call ``commit(...)`` after the step lands.  When the
    python-side schedule counts diverge from the committed device
    counter (a per-key update interleaved, ``load_states``), the
    counter re-seeds from them — or, via ``pending_ts``, from a
    checkpointed APPLIED-step vector (fp16 skip-steps make Adam's
    bias-correction t lag the schedule counts; docs/perf_tuning.md)."""

    def __init__(self):
        self._hc: Dict[str, Any] = {}
        self._ts: Dict[tuple, tuple] = {}  # idx -> (device ts, counts)

    def arrays(self, opt_, indices, pending_ts=None):
        """Return ``(lrs, wds, ts, counts_t)`` for ``indices``.
        ``pending_ts``: zero-arg callable yielding an int tuple to seed
        the device counter from (consumed only when a (re)seed actually
        happens), or None."""
        idx = tuple(indices)
        hc = self._hc
        lr_t = tuple(opt_._get_lr(i) for i in idx)
        wd_t = tuple(opt_._get_wd(i) for i in idx)
        # np.array over PYTHON scalars (lr/wd schedules) builds a host
        # constant to ship device-ward — no device value is read, so
        # these are not the syncs the host-sync rule hunts:
        if hc.get("lr_key") != lr_t:
            hc["lr_key"] = lr_t
            hc["lr"] = jnp.asarray(_np.array(lr_t, _np.float32))  # graft-lint: disable=host-sync
        if hc.get("wd_key") != wd_t:
            hc["wd_key"] = wd_t
            hc["wd"] = jnp.asarray(_np.array(wd_t, _np.float32))  # graft-lint: disable=host-sync
        counts_t = tuple(opt_._index_update_count[i] for i in idx)
        ent = self._ts.get(idx)
        if ent is not None and ent[1] == counts_t:
            ts = ent[0]
        else:
            seed = pending_ts() if pending_ts is not None else None
            # python ints -> device constant (see lr/wd note above)
            ts = jnp.asarray(_np.array(
                counts_t if seed is None else seed, _np.int32))  # graft-lint: disable=host-sync
        return hc["lr"], hc["wd"], ts, counts_t

    def commit(self, indices, new_ts, counts_t) -> None:
        """Adopt the stepped device counter for ``indices`` — valid
        while the python schedule counts advance exactly once."""
        self._ts[tuple(indices)] = (new_ts,
                                    tuple(c + 1 for c in counts_t))


class FusedUpdater(Updater):
    """Multi-tensor updater: ONE jitted XLA program updates every parameter.

    The TPU redesign of the reference's per-parameter engine pushes
    (python/mxnet/model.py:126 `_update_params_on_kvstore` loops keys; the
    engine bulks op segments, graph_executor.cc:1350).  Here the whole
    grads→optimizer→params pass for all keys traces into a single compiled
    call per step: Module.update / Trainer.step / KVStore.pushpull issue O(1)
    dispatches regardless of parameter count.  Per-key `__call__` (inherited)
    stays available and bit-identical for optimizers without a fused_step.
    """

    #: compiled-step program cache bound (LRU).  Generous: a training
    #: process legitimately holds a handful of live programs (per step
    #: mode x dtype policy x param-group signature); what must NOT
    #: accumulate are dead entries from recreated whole-step compilers
    FN_CACHE_MAX = 64

    def __init__(self, optimizer: Optimizer):
        super().__init__(optimizer)
        self._fn_cache: Dict[Any, Any] = {}
        # introspection captures done, one per compiled-step cache key
        self._noted_keys: set = set()
        # dtype policy the compiled step programs were traced under
        # ("f32" | "bf16" | "fp16"; set from MXNET_AMP by the trainer /
        # whole-step compiler).  It is position 1 of every program cache
        # key, so a policy flip can never silently reuse a program traced
        # for another precision — see lookup_program.
        self.dtype_policy = "f32"

    def lookup_program(self, key, build):
        """Compiled-step program cache shared by update_all and the gluon
        whole-step compiler (`gluon/wholestep.py`).

        ``key`` = (step_mode, dtype_policy, *rest): step_mode names the
        program shape ("update_all" / "whole_step"), dtype_policy the
        MXNET_AMP precision it was traced under.  A miss whose ``rest``
        matches a cached entry under a DIFFERENT dtype policy recompiles
        LOUDLY — warning + FUSED_DTYPE_RECOMPILES counter — because the
        silent failure mode here is real: reusing an f32-traced program
        for bf16/fp16 gradients would train in the wrong precision
        without ever erroring."""
        fn = self._fn_cache.get(key)
        if fn is not None:
            self._fn_cache[key] = self._fn_cache.pop(key)  # LRU refresh
            return fn
        for k2 in self._fn_cache:
            if isinstance(k2, tuple) and len(k2) >= 2 and \
                    k2[0] == key[0] and k2[1] != key[1] and \
                    k2[2:] == key[2:]:
                _logger.warning(
                    "dtype-policy change (%s -> %s): recompiling the %s "
                    "fused program — the %s-traced program is NOT reused",
                    k2[1], key[1], key[0], k2[1])
                if _metrics.ENABLED:
                    # key[0] comes from the two call sites' literals
                    # ("update_all" / "whole_step") — bounded label set
                    _metrics.FUSED_DTYPE_RECOMPILES.inc(mode=key[0])
                break
        fn = build()
        self._fn_cache[key] = fn
        # bounded LRU: superseded programs (dead per-compiler uids,
        # abandoned dtype policies) must not pin their jitted
        # executables + traced-graph closures for the trainer's
        # lifetime; evicting a LIVE entry only costs a retrace
        while len(self._fn_cache) > self.FN_CACHE_MAX:
            evicted = next(iter(self._fn_cache))
            del self._fn_cache[evicted]
            _logger.info("fused program cache full (%d): evicted LRU "
                         "entry %s/%s", self.FN_CACHE_MAX,
                         evicted[0], evicted[1])
        return fn

    @staticmethod
    def _state_data(state):
        if state is None:
            return None
        if isinstance(state, NDArray):
            return state._data
        if isinstance(state, (tuple, list)):
            return tuple(FusedUpdater._state_data(s) for s in state)
        return state

    def _state_writeback(self, old, new):
        if old is None:
            return None
        if isinstance(old, NDArray):
            old._set_data(new)
            return old
        if isinstance(old, (tuple, list)):
            return type(old)(self._state_writeback(o, n)
                             for o, n in zip(old, new))
        # raw jax state: the registered old array dies here — the
        # replacement must re-register or optimizer_state attribution
        # drifts to zero after the first fused step (same per-step
        # re-registration the compression residuals do)
        if _memory.ENABLED:
            _memory.register(new, tag="optimizer_state")
        return new

    def hyper_arrays(self, indices):
        """Device-cached (lrs, wds, ts, commit_ts) for a key tuple —
        ``HyperDeviceCache`` does the work (one implementation shared
        with ``WholeStepCompiler``, so fused/whole-step optimizer state
        stays interchangeable by construction).  Shared by update_all
        and the module-level fused train step."""
        # lazy but allocation-free once built: setdefault would
        # construct (and discard) a fresh cache object every step
        cache = self.__dict__.get("_hyper_dev")
        if cache is None:
            cache = self.__dict__["_hyper_dev"] = HyperDeviceCache()
        idx = tuple(indices)
        lrs, wds, ts, counts_t = cache.arrays(self.optimizer, idx)

        def commit_ts(nts):
            cache.commit(idx, nts, counts_t)

        return lrs, wds, ts, commit_ts

    @staticmethod
    def _materialize_views(grads, grad_views):
        """Slice per-key gradients out of flat bucket arrays eagerly (the
        rare non-fused-optimizer fallback; the fused path slices inside
        its compiled program instead)."""
        out = []
        for b, off, shape in grad_views:
            f = grads[b]._data if isinstance(grads[b], NDArray) else grads[b]
            size = int(_np.prod(shape)) if shape else 1
            out.append(f[off:off + size].reshape(shape))
        return out

    @hot_path
    def update_all(self, indices, grads, weights, grad_views=None,
                   donate_weights=None) -> None:
        """Apply the optimizer to all (grad, weight) pairs in one dispatch.

        grads: NDArray or raw jax arrays; weights: NDArrays (updated
        in place via _set_data).  Falls back to the per-key path for
        optimizers without fused_step.

        grad_views: when set, `grads` holds the FLAT BUCKET arrays of a
        bucketed allreduce (kvstore.GradBucketer) and grad_views[k] =
        (bucket, offset, shape) locates parameter k's gradient inside
        them; the slice+reshape traces into the same fused program, so
        un-flattening costs no extra dispatch or copy.  (The bucket
        buffers are NOT donated — no output shares their shape — they
        stay live until the trainer drops its reference after the call.)

        2-bit-compressed buckets arrive here already dequantized in the
        gradient dtype (the error-feedback residual treedef lives with
        the Trainer/kvstore, never in this program), so the cache key
        below is compression-agnostic by construction: toggling
        compression_params cannot grow the compiled-step cache.

        donate_weights (default MXNET_DONATE_WEIGHTS, off): donate the
        weight buffers too — each new weight aliases its old buffer, so
        the optimizer step updates parameters truly IN PLACE (no second
        copy of the model live during the update).  Off by default
        because executor snapshots / user-held NDArray views may still
        alias the old buffers; enable when the trainer owns the weights
        outright (docs/perf_tuning.md).
        """
        opt_ = self.optimizer
        if donate_weights is None:
            donate_weights = getenv("MXNET_DONATE_WEIGHTS", False)
        if not getattr(opt_, "fused", False):
            if grad_views is not None:
                grads = self._materialize_views(grads, grad_views)
            for i, g, w in zip(indices, grads, weights):
                g = g if isinstance(g, NDArray) else NDArray(g, w.context)
                self(i, g, w)
            return
        from .ndarray.sparse import RowSparseNDArray
        if grad_views is None and \
                any(isinstance(g, RowSparseNDArray) for g in grads):
            # rsp grads take the FUSED sparse leg (ISSUE 20): rows-only
            # gather/step/scatter in one compiled program (reading ._data
            # here would densify the O(vocab) gradient the executor just
            # kept rows-only); dense keys stay in the multi-tensor trace
            sparse = [(i, g, w) for i, g, w in zip(indices, grads, weights)
                      if isinstance(g, RowSparseNDArray)]
            dense = [(i, g, w) for i, g, w in zip(indices, grads, weights)
                     if not isinstance(g, RowSparseNDArray)]
            si, sg, sw = zip(*sparse)
            self.update_sparse(list(si), list(sg), list(sw),
                               donate_weights=donate_weights)
            if dense:
                di, dg, dw = zip(*dense)
                self.update_all(list(di), list(dg), list(dw),
                                donate_weights=donate_weights)
            return
        indices = list(indices)
        for i, w in zip(indices, weights):
            self._ensure_state(i, w)
        for i in indices:
            opt_._update_count(i)
        lrs, wds, ts, commit_ts = self.hyper_arrays(indices)
        wvals = [w._data for w in weights]
        gvals = [g._data if isinstance(g, NDArray) else g for g in grads]
        svals = [self._state_data(self.states[i]) for i in indices]
        views = tuple(grad_views) if grad_views is not None else None

        # dispatch-stability key: identity of the compiled step is pinned
        # on (step mode, dtype policy, optimizer, hypers, key tuple,
        # dtypes, shardings, state treedef, bucket views) — any drift
        # re-selects a cached program instead of silently retracing under
        # the same entry, and a dtype-policy flip recompiles loudly
        # (lookup_program)
        key = ("update_all", self.dtype_policy,
               type(opt_).__name__, opt_.fused_hyper_key(), tuple(indices),
               tuple(str(w.dtype) for w in wvals),
               tuple(str(g.dtype) for g in gvals),
               tuple(str(getattr(w, "sharding", None)) for w in wvals),
               jax.tree_util.tree_structure(svals), views,
               bool(donate_weights))

        def _build():
            idx = list(indices)

            def mx_fused_update(wv, gv, sv, lrs, wds, ts):
                # the fused optimizer math traces under one literal
                # named scope, so per_layer() attributes its HLO
                # instructions to "optimizer" (ISSUE 13)
                with _introspect.layer_scope("optimizer"):
                    nws, nss = [], []
                    for k in range(len(wv)):
                        if views is not None:
                            b, off, shape = views[k]
                            size = int(_np.prod(shape)) if shape else 1
                            g_k = gv[b][off:off + size].reshape(shape)
                        else:
                            g_k = gv[k]
                        nw, ns = opt_._fused_step_mp(idx[k], wv[k], g_k,
                                                     sv[k], lrs[k], wds[k],
                                                     ts[k])
                        nws.append(cast_like(nw, wv[k]))
                        nss.append(cast_like(ns, sv[k]))
                    return nws, nss, ts + 1

            # donate states (owned exclusively by this updater, aliased to
            # the new-state outputs); weights join the donation set only
            # under the donate_weights knob — executor snapshots may
            # still alias their buffers in the general case.  Flat grad
            # buckets are NOT donated: no output shares their shape, so
            # donation could never alias and would only warn.
            return jax.jit(mx_fused_update,
                           donate_argnums=(0, 2) if donate_weights else (2,))

        fn = self.lookup_program(key, _build)
        if _introspect.ENABLED and key not in self._noted_keys:
            # once per compiled-step cache key, BEFORE the call (the
            # donated state buffers are still live): analytical cost of
            # the fused update — a retrace, no XLA compile, no dispatch.
            # The signature hashes the dispatch-stability key (optimizer
            # class, hypers, param set, dtypes, shardings, state
            # treedef), so perf baselines stay per-(model, optimizer,
            # platform) — two different models must never share one
            # baseline file
            self._noted_keys.add(key)
            import hashlib
            sig = hashlib.sha1(repr(key).encode()).hexdigest()[:16]
            # auditable program contract (analysis.audit_programs,
            # ISSUE 15): donated state (and weight, under
            # donate_weights) leaves must alias outputs; the fused
            # update is pure optimizer math — no host callbacks, no
            # collectives (the bucketed allreduce runs in its own
            # program on this path)
            donated = (0, 2) if donate_weights else (2,)
            leaves = len(jax.tree_util.tree_leaves(svals)) + \
                (len(jax.tree_util.tree_leaves(wvals)) if donate_weights
                 else 0)
            _introspect.note_jit("fused_update", fn, wvals, gvals, svals,
                                 lrs, wds, ts, signature=sig,
                                 contracts={"donate_argnums": donated,
                                            "donated_leaves": leaves,
                                            "host_callbacks": 0,
                                            "collectives": 0})
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="optimizer")
            _metrics.OPTIMIZER_STEPS.inc()
        # OOM post-mortem chokepoint: the fused multi-tensor update is
        # the other program that holds a whole model (+states) live;
        # the memory.oom chaos site injects a synthetic one here
        with span("mx.optimizer.update_all", cat="optimizer", mem=True), \
                _memory.oom_guard("optimizer.update_all"):
            _fi_fire("memory.oom", at="optimizer")
            # transient-device chaos site at the fused-update dispatch
            # boundary (the fused-path twin of the whole-step site):
            # fires before fn(), so weights/states are still pre-step
            _fi_fire("device.unavailable", at="optimizer")
            try:
                nws, nss, nts = fn(wvals, gvals, svals, lrs, wds, ts)
            except BaseException:
                # MXNET_SANITIZE twin (ISSUE 15): the failed donated
                # dispatch may have consumed the state (and, under
                # donate_weights, weight) buffers — poison the
                # wrappers so later touches raise typed
                # DonatedBufferError; set_states_bytes / _set_data on
                # restore clears the poison
                if _san_mod.ENABLED:
                    _san_mod.poison_donated(
                        "fused_update",
                        *[self.states[i] for i in indices],
                        *(list(weights) if donate_weights else []))
                raise
            commit_ts(nts)
            for k, i in enumerate(indices):
                weights[k]._set_data(nws[k])
                self.states[i] = self._state_writeback(self.states[i],
                                                       nss[k])

    def _rowable_state(self, state, vocab) -> bool:
        """True when every state leaf is a DENSE per-row slab (leading dim
        == vocab) the sparse leg can gather/scatter by row — rsp-stored
        or scalar/oddly-shaped state exiles that key to the per-key lazy
        path instead of silently densifying."""
        if state is None:
            return True
        if isinstance(state, (tuple, list)):
            return all(self._rowable_state(s, vocab) for s in state)
        if getattr(state, "stype", "default") != "default":
            return False
        shp = getattr(state, "shape", None)
        return bool(shp) and shp[0] == vocab

    @hot_path
    def update_sparse(self, indices, grads, weights,
                      donate_weights=None) -> None:
        """Fused ROW-SPARSE optimizer leg (ISSUE 20): one compiled
        program steps every row-sparse (grad, weight) pair — gather the
        touched weight/state rows, run the optimizer's ``fused_step`` on
        the O(nnz) row slabs, scatter back with ``.at[ids].set(...,
        mode="drop")``.  Replaces the per-key exile that cost one python
        round-trip + several dispatches PER EMBEDDING per step.

        Semantics match the eager lazy-update paths bit-for-bit in
        structure: only gradient rows step (their wd term included),
        only their optimizer-state slots advance, per-key t (not
        per-row) feeds Adam's bias correction.

        grads: RowSparseNDArrays (sorted-unique ids by construction;
        ``MXNET_EMBED_DEDUP_IDS=0`` wire duplicates are legal — the
        program always runs its own unique + segment-sum, a bitwise
        identity on already-unique input).  nnz is padded OUTSIDE the
        jit to the next power of two with a POSITIVELY out-of-range
        sentinel id (vocab — never -1, which ``.at[]`` would wrap onto
        the last real row), so steady-state traffic reuses log-many
        compiled programs instead of one per nnz.

        Optimizers without ``fused_step``, rsp-STORED weights, and
        non-row-gatherable state (rsp momentum, scalar accumulators)
        exile per-key exactly as before — rows-only either way."""
        opt_ = self.optimizer
        if donate_weights is None:
            donate_weights = getenv("MXNET_DONATE_WEIGHTS", False)
        from .ndarray.sparse import RowSparseNDArray
        for g in grads:
            if not isinstance(g, RowSparseNDArray):
                raise TypeError("update_sparse expects row_sparse grads, "
                                f"got {type(g).__name__}")
        for i, w in zip(indices, weights):
            self._ensure_state(i, w)
        fused, exiled = [], []
        for i, g, w in zip(indices, grads, weights):
            ok = getattr(opt_, "fused", False) and \
                getattr(w, "stype", "default") == "default" and \
                self._rowable_state(self.states[i], w.shape[0])
            (fused if ok else exiled).append((i, g, w))
        for i, g, w in exiled:
            self(i, g, w)
        if not fused:
            return
        indices = [i for i, _, _ in fused]
        grads = [g for _, g, _ in fused]
        weights = [w for _, _, w in fused]
        for i in indices:
            opt_._update_count(i)
        lrs, wds, ts, commit_ts = self.hyper_arrays(indices)
        wvals = [w._data for w in weights]
        svals = [self._state_data(self.states[i]) for i in indices]
        # pad ids/rows OUTSIDE the jit to the pow2 nnz bucket; sentinel
        # = vocab is dropped by every mode="drop" scatter below (and the
        # matching mode="clip" gathers read a real row whose update is
        # then dropped — garbage-in, dropped-out)
        ivals, gvals, buckets = [], [], []
        for g, w in zip(grads, weights):
            nnz = int(g._indices.shape[0])
            bucket = max(8, 1 << max(0, nnz - 1).bit_length())
            sent = w.shape[0]
            ids = jnp.full((bucket,), sent, g._indices.dtype) \
                .at[:nnz].set(g._indices)
            rows = jnp.zeros((bucket,) + g._values.shape[1:],
                             g._values.dtype).at[:nnz].set(g._values)
            ivals.append(ids)
            gvals.append(rows)
            buckets.append(bucket)

        key = ("sparse_update", self.dtype_policy,
               type(opt_).__name__, opt_.fused_hyper_key(), tuple(indices),
               tuple(str(w.dtype) for w in wvals),
               tuple(str(g.dtype) for g in gvals), tuple(buckets),
               tuple(str(getattr(w, "sharding", None)) for w in wvals),
               jax.tree_util.tree_structure(svals), bool(donate_weights))

        def _build():
            idx = list(indices)

            def mx_sparse_update(wv, iv, gv, sv, lrs, wds, ts):
                with _introspect.layer_scope("optimizer"):
                    nws, nss = [], []
                    for k in range(len(wv)):
                        vocab = wv[k].shape[0]
                        # in-program dedup: segment-sum duplicate ids
                        # exactly once (identity on the default
                        # already-unique wire); sentinel slots collapse
                        # onto the fill entry and scatter-drop
                        uids, inv = jnp.unique(
                            iv[k], size=iv[k].shape[0], fill_value=vocab,
                            return_inverse=True)
                        g_k = jnp.zeros(gv[k].shape, gv[k].dtype) \
                            .at[jnp.ravel(inv)].add(gv[k])
                        wr = jnp.take(wv[k], uids, axis=0, mode="clip")
                        sr = jax.tree_util.tree_map(
                            lambda s: jnp.take(s, uids, axis=0,
                                               mode="clip"), sv[k])
                        nwr, nsr = opt_._fused_step_mp(
                            idx[k], wr, g_k, sr, lrs[k], wds[k], ts[k])
                        nws.append(wv[k].at[uids].set(
                            cast_like(nwr, wr), mode="drop"))
                        nss.append(jax.tree_util.tree_map(
                            lambda s, r: s.at[uids].set(cast_like(r, s),
                                                        mode="drop"),
                            sv[k], nsr))
                    return nws, nss, ts + 1

            # states are owned by this updater — donated, and the
            # row-scatter output is table-shaped so donation really
            # aliases; weights join only under donate_weights (same
            # caveat as update_all: user-held views may alias them).
            # The padded id/row slabs are NOT donated (wrong shapes).
            return jax.jit(mx_sparse_update,
                           donate_argnums=(0, 3) if donate_weights else (3,))

        fn = self.lookup_program(key, _build)
        if _introspect.ENABLED and key not in self._noted_keys:
            self._noted_keys.add(key)
            import hashlib
            sig = hashlib.sha1(repr(key).encode()).hexdigest()[:16]
            donated = (0, 3) if donate_weights else (3,)
            leaves = len(jax.tree_util.tree_leaves(svals)) + \
                (len(jax.tree_util.tree_leaves(wvals)) if donate_weights
                 else 0)
            _introspect.note_jit("sparse_update", fn, wvals, ivals, gvals,
                                 svals, lrs, wds, ts, signature=sig,
                                 contracts={"donate_argnums": donated,
                                            "donated_leaves": leaves,
                                            "host_callbacks": 0,
                                            "collectives": 0})
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="optimizer")
            _metrics.OPTIMIZER_STEPS.inc()
        with span("mx.optimizer.update_all", cat="optimizer", mem=True), \
                _memory.oom_guard("optimizer.update_sparse"):
            _fi_fire("memory.oom", at="optimizer")
            _fi_fire("device.unavailable", at="optimizer")
            try:
                nws, nss, nts = fn(wvals, ivals, gvals, svals, lrs, wds, ts)
            except BaseException:
                if _san_mod.ENABLED:
                    _san_mod.poison_donated(
                        "sparse_update",
                        *[self.states[i] for i in indices],
                        *(list(weights) if donate_weights else []))
                raise
            commit_ts(nts)
            for k, i in enumerate(indices):
                weights[k]._set_data(nws[k])
                self.states[i] = self._state_writeback(self.states[i],
                                                       nss[k])


def get_updater(optimizer: Optimizer) -> Updater:
    return FusedUpdater(optimizer)


# NDArray needs nd.maximum for Adamax — ensure generated fn exists
