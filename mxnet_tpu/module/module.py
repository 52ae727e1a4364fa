"""Module: intermediate-level training API over one compiled executor.

Reference parity: `python/mxnet/module/module.py:39` (bind/init_params/
init_optimizer/forward/backward/update + kvstore wiring, model.py:97-138).

TPU redesign of the multi-device path: where the reference's
DataParallelExecutorGroup (`executor_group.py:128`) sliced each batch across
per-GPU executors and pushed gradients through KVStore reduce, a Module bound
with several contexts builds ONE executor over a `jax.sharding.Mesh` of those
devices — batch sharded on 'dp', parameters replicated, gradient all-reduce
inserted by XLA over ICI.  KVStore('tpu_sync') then applies the optimizer to
the replicated gradients (update_on_kvstore semantics preserved).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

from ..base import MXNetError
from ..context import Context, cpu
from ..initializer import InitDesc, Uniform
from .. import ndarray as nd
from ..io import DataDesc
from ..observability import introspect as _introspect
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from ..observability.tracing import span
from .. import optimizer as opt
from ..model import _create_kvstore, load_checkpoint, save_checkpoint
from .base_module import BaseModule, _check_input_names


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=cpu(), work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        if isinstance(context, Context):
            context = [context]
        self._context = context
        self._work_load_list = work_load_list
        self._group2ctxs = group2ctxs
        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        state_names = list(state_names) if state_names is not None else []
        fixed_param_names = list(fixed_param_names) if fixed_param_names \
            is not None else []
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, state_names, "state", True)
        _check_input_names(symbol, fixed_param_names, "fixed_param", True)
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names + state_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = fixed_param_names
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = state_names
        self._output_names = symbol.list_outputs()
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._compression_params = compression_params
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._grad_req = "write"
        # "computed" after a training forward-backward on the standard
        # path, "applied" once update() has applied it: prepare() launches
        # ahead only behind an applied step
        self._last_step = None

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        reference_format=False):
        self._sync_params_from_devices()
        save_checkpoint(prefix, epoch, self.symbol, *self.get_params(),
                        reference_format=reference_format)
        if save_optimizer_states:
            self.save_optimizer_states(f"{prefix}-{epoch:04d}.states")

    # -- shapes ---------------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, tuple(o.shape)) for n, o in
                zip(self._output_names, self._exec.outputs)] \
            if self._exec._outputs_cache is not None else \
            list(zip(self._output_names, self._infer_output_shapes()))

    def _infer_output_shapes(self):
        shapes = {d.name: d.shape for d in self._data_shapes}
        if self._label_shapes:
            shapes.update({d.name: d.shape for d in self._label_shapes})
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return out_shapes

    # -- params ---------------------------------------------------------------
    def get_params(self):
        assert self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return (self._arg_params, self._aux_params)

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing the parameters"
        if self._arg_params is None:
            with _memory.memory_scope("param"):
                self._arg_params = {
                    name: nd.zeros(arr.shape, dtype=arr.dtype)
                    for name, arr in self._exec.arg_dict.items()
                    if name in self._param_names}
        if self._aux_params is None:
            with _memory.memory_scope("param"):
                self._aux_params = {
                    name: nd.zeros(arr.shape, dtype=arr.dtype)
                    for name, arr in self._exec.aux_dict.items()}
        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache_arr = cache[name]
                if cache_arr is not arr:
                    cache_arr.copyto(arr)
            elif not allow_missing and cache is not None:
                raise RuntimeError(f"{name} is not presented")
            elif initializer is not None:
                initializer(InitDesc(name, attrs.get(name)), arr)

        for name in self._param_names:
            arr = self._arg_params[name]
            _impl(name, arr, arg_params)
        for name, arr in sorted(self._aux_params.items()):
            _impl(name, arr, aux_params)
        self.params_initialized = True
        self._params_dirty = False
        self._exec.copy_params_from(self._arg_params, self._aux_params,
                                    allow_extra_params=True)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=allow_missing,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            return
        self._exec.copy_params_from(arg_params, aux_params,
                                    allow_extra_params=True)
        self.params_initialized = True
        self._params_dirty = False

    def _sync_params_from_devices(self):
        """Refresh the host-side param mirror by POINTER HANDOFF, not
        copy: jax arrays are immutable (the executor swaps whole buffers
        on update, never mutates), so aliasing is safe — and the per-
        param device_put the old copyto loop paid was O(params)
        transfers per epoch (fit() syncs every epoch for the epoch-end
        callback; 2x193 per epoch on ResNet-50)."""
        fused_active = self.__dict__.get("_fstep") is not None

        def _handoff(src_nd, tgt_nd):
            data = src_nd._data
            if fused_active:
                # the fused train step DONATES param buffers each step;
                # a handed-off alias held by the user (get_params,
                # epoch-end callback) would be invalidated on the next
                # step — give them their own buffer instead
                import jax.numpy as jnp
                data = jnp.array(data)
            if data.dtype != tgt_nd.dtype:
                data = data.astype(tgt_nd.dtype)
            tgt_nd._set_data(data)

        for name in self._param_names:
            _handoff(self._exec.arg_dict[name], self._arg_params[name])
        for name, arr in self._exec.aux_dict.items():
            _handoff(arr, self._aux_params[name])
        self._params_dirty = False

    # -- bind -----------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if force_rebind:
            self._drop_prepared()
            self._exec = None
            self.binded = False
        if self.binded:
            self._adopt_existing_bind(data_shapes, label_shapes,
                                      for_training, inputs_need_grad,
                                      grad_req)
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req
        assert not for_training or label_shapes is not None or \
            not self._label_names

        self._data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                             for d in data_shapes]
        self._label_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                              for d in label_shapes] if label_shapes else []

        shapes = {d.name: tuple(d.shape) for d in self._data_shapes}
        shapes.update({d.name: tuple(d.shape) for d in self._label_shapes})
        types = {d.name: d.dtype for d in
                 self._data_shapes + self._label_shapes}

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shapes)
        arg_types, _, aux_types = self._symbol.infer_type(**types)
        arg_names = self._symbol.list_arguments()
        ctx0 = self._context[0]

        mesh = None
        data_shard_args = ()
        if len(self._context) > 1:
            from ..parallel.mesh import make_mesh
            devs = [c.jax_device() for c in self._context]
            mesh = make_mesh(dp=len(devs), devices=devs)
            data_shard_args = tuple(self._data_names) + tuple(self._label_names)

        args, grads, reqs = {}, {}, {}
        shared_args = shared_module._exec.arg_dict if shared_module else {}
        shared_aux = shared_module._exec.aux_dict if shared_module else {}
        # HBM ledger: bind-time buffers are the symbolic path's params/
        # grads — tag them like the gluon owners so Module.fit training
        # attributes the same way a gluon Trainer run does (the inner
        # "grad" scope overrides for gradient buffers; innermost wins)
        with _memory.memory_scope("param"):
            for name, shp, dt in zip(arg_names, arg_shapes, arg_types):
                if name in shared_args and \
                        tuple(shared_args[name].shape) == tuple(shp):
                    args[name] = shared_args[name]
                else:
                    args[name] = nd.zeros(shp, ctx=ctx0, dtype=dt)
                is_input = name in self._data_names \
                    or name in self._label_names \
                    or name in self._state_names
                if not for_training:
                    reqs[name] = "null"
                elif is_input:
                    if name in self._data_names and inputs_need_grad:
                        reqs[name] = "write"
                    else:
                        reqs[name] = "null"
                elif name in self._fixed_param_names:
                    reqs[name] = "null"
                else:
                    reqs[name] = grad_req if isinstance(grad_req, str) else \
                        grad_req.get(name, "write")
                if reqs[name] != "null":
                    with _memory.memory_scope("grad"):
                        grads[name] = nd.zeros(shp, ctx=ctx0, dtype=dt)
            aux = {}
            for name, shp, dt in zip(self._aux_names, aux_shapes, aux_types):
                if name in shared_aux and \
                        tuple(shared_aux[name].shape) == tuple(shp):
                    aux[name] = shared_aux[name]
                else:
                    aux[name] = nd.zeros(shp, ctx=ctx0, dtype=dt)

        if mesh is not None:
            # keep params/grads/aux replicated over the mesh so optimizer
            # updates and kvstore pulls stay SPMD-consistent
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            repl = NamedSharding(mesh, P())
            from ..ndarray.sparse import BaseSparseNDArray
            for d in (args, grads, aux):
                for k, v in d.items():
                    if k not in data_shard_args and \
                            not isinstance(v, BaseSparseNDArray):
                        v._set_data(jax.device_put(v._data, repl))

        from ..executor import Executor
        group2ctx = None
        if self._group2ctxs:
            group2ctx = self._group2ctxs if isinstance(self._group2ctxs, dict) \
                else self._group2ctxs[0]
        self._exec = Executor(self._symbol, ctx0, args, grads, reqs, aux,
                              group2ctx=group2ctx,
                              shared_exec=shared_module._exec if shared_module
                              else None,
                              mesh=mesh, data_shard_args=data_shard_args)
        # Embedding(sparse_grad=True) weights get ROW-SPARSE grad buffers
        # (parity: infer-storage marking the weight grad rsp,
        # indexing_op.h) — the EXECUTOR owns eligibility (it disables the
        # rewrite under remat/group2ctx), so the storage swap follows its
        # decision rather than duplicating the predicate here
        from ..ndarray.sparse import zeros_sparse
        for name in self._exec._rsp_grad_args:
            tgt = self._exec.grad_dict.get(name)
            if tgt is not None:
                self._exec.grad_dict[name] = zeros_sparse(
                    "row_sparse", tgt.shape, ctx=ctx0, dtype=tgt.dtype)
        self.binded = True
        if shared_module is not None and shared_module.params_initialized:
            self.set_params(*shared_module.get_params())

    # -- optimizer ------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            # pre-initialized optimizer state is adopted silently — the
            # pre-bind + pre-init + fit() pattern is first-class (bench,
            # resume-from-checkpoint); force_init=True replaces it
            self.logger.debug("optimizer already initialized, adopting")
            return
        if self._params_dirty:
            self._sync_params_from_devices()
        kvstore, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), dict(zip(self._param_names,
                                                  [self._exec.arg_dict[n]
                                                   for n in self._param_names])))
        batch_size = self._data_shapes[0].shape[0]
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        idx2name = {i: n for i, n in enumerate(self._param_names)}
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but rescale_grad "
                    f"is not normalized to 1.0/batch_size/num_workers ({rescale_grad} "
                    f"vs. {optimizer.rescale_grad}). Is this intended?")
            if not optimizer.idx2name:
                optimizer.idx2name = idx2name.copy()

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            for i, name in enumerate(self._param_names):
                # init from the executor's (possibly mesh-replicated) buffers
                # so kvstore-side updates stay SPMD-consistent
                kvstore.init(name, self._exec.arg_dict[name])
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        if not update_on_kvstore:
            self._updater = opt.get_updater(optimizer)
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- compute --------------------------------------------------------------
    @staticmethod
    def _load_arg(arr, tgt):
        """Batch data typed AND placed like the executor's buffer (the
        reference copies batches to executor contexts in _load_data,
        executor_group.py:28-71 — a CPU-built mx.nd.array fed to a
        TPU-bound module must hop devices here, and a mesh-sharded
        target keeps its sharding so re-jit never triggers).  The
        dtype-cast + sharding-preserving placement rule lives in ONE
        place: NDArray.copyto."""
        if isinstance(arr, nd.NDArray):
            arr.copyto(tgt)
        else:
            # host (numpy) batch: one transfer, straight to the
            # executor's placement — no default-device stopover
            import jax
            import numpy as _np
            want = getattr(tgt._data, "sharding", None) \
                or tgt.context.jax_device()
            val = _np.asarray(arr, dtype=tgt.dtype)
            if _metrics.ENABLED:
                _metrics.DEVICE_PUTS.inc()
                _metrics.TRANSFER_BYTES.inc(val.nbytes)
            tgt._set_data(jax.device_put(val, want))

    def _set_batch(self, data_batch, is_train):
        for name, arr in zip(self._data_names, data_batch.data):
            tgt = self._exec.arg_dict[name]
            if tuple(tgt.shape) != tuple(arr.shape):
                # shape change (e.g. last partial batch): XLA re-specializes;
                # placement decided by the buffer, same rule as copyto
                src = arr if isinstance(arr, nd.NDArray) \
                    else nd.array(arr, ctx=tgt.context)
                self._exec.arg_dict[name] = \
                    src.astype(tgt.dtype).copyto(tgt.context)
            else:
                self._load_arg(arr, tgt)
        if is_train and data_batch.label:
            for name, arr in zip(self._label_names, data_batch.label):
                if name not in self._exec.arg_dict:
                    continue
                self._load_arg(arr, self._exec.arg_dict[name])

    def prepare(self, data_batch):
        """Launch `data_batch`'s forward-backward now and HOLD its results.

        `fit` calls this with the next batch before it reads this step's
        metric, so the device starts the next step while the host reads,
        runs callbacks and comes back.  Until `forward_backward(data_batch)`
        (or `forward(data_batch, is_train=True)`) takes the held launch,
        everything a caller can read is the last step's: outputs,
        parameters, auxiliary states, gradients.  It is taken only for the
        very batch object prepared and only while every array the program
        read is still the one bound; otherwise it is dropped and the step
        runs as if prepare() had not been called, with the same key.

        It launches only behind a training step of the standard path whose
        update() was applied, with no monitor installed and no group2ctx
        placement: a caller who scores between steps, or uses prepare()
        for something else, never pays for a program nobody takes."""
        ex = self._exec
        if self._last_step != "applied" or ex._monitor is not None \
                or ex.group2ctx or not ex._grad_names:
            return
        self._set_batch(data_batch, True)
        ex.launch_ahead(data_batch)

    def _drop_prepared(self):
        self._last_step = None
        if self._exec is not None:
            self._exec.drop_held()

    def _load_batch(self, data_batch, is_train):
        """The batch into the executor's arguments, unless a launch held
        for this very batch has read them already."""
        self._last_step = None
        if not (is_train and self._exec.holds_launch(data_batch)):
            self._exec.drop_held()
            self._set_batch(data_batch, is_train or bool(data_batch.label))

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        self._load_batch(data_batch, is_train)
        self._exec.forward(is_train=is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)
        self._last_step = "computed"

    def forward_backward(self, data_batch):
        """Fused single-compiled-call training step (TPU hot path)."""
        assert self.binded and self.params_initialized
        # a stale flag from a fused step whose update() was skipped must
        # not swallow the NEXT standard-path update
        self.__dict__.pop("_fused_stepped", None)
        with span("mx.module.forward_backward", cat="executor"):
            if self._maybe_fused_train_step(data_batch):
                self._drop_prepared()  # the one-program step prepares none
                return
            self._load_batch(data_batch, True)
            self._exec.forward_backward()
            self._last_step = "computed"

    # -- single-program train step (MXNET_FUSED_STEP=1) ---------------------
    def _fused_step_updater(self):
        if self._update_on_kvstore and self._kvstore is not None:
            return getattr(self._kvstore, "_updater", None)
        return self._updater

    def _fused_step_eligible(self):
        """ONE donated XLA program per step (fwd+bwd+optimizer) — the
        full engine-bulking limit.  Opt-in (MXNET_FUSED_STEP=1) because
        it changes two observable contracts: grad_dict is not
        materialized per step, and params/optimizer states are donated
        (updated in place device-side)."""
        from ..base import getenv
        from ..optimizer import FusedUpdater
        if not getenv("MXNET_FUSED_STEP", 0):
            return False
        if not self.optimizer_initialized:
            return False
        ex = self._exec
        upd = self._fused_step_updater()
        ok = (isinstance(upd, FusedUpdater)
              and getattr(upd.optimizer, "fused", False)
              and ex._mesh is None and not ex.group2ctx
              and not ex._rsp_grad_args
              and ex._monitor is None
              and not ex._remat  # mirror remat rides the standard path
              and not self.inputs_need_grad
              and not getattr(self._kvstore, "_gc", None)
              and (self._kvstore is None
                   or self._kvstore.num_workers == 1)
              and all(ex.grad_req.get(n, "null") in ("null", "write")
                      for n in ex.arg_dict))
        if not ok and not self.__dict__.get("_fstep_warned"):
            self.logger.warning(
                "MXNET_FUSED_STEP=1 requested but this module is not "
                "eligible (needs: fused optimizer, single device, dense "
                "write grads, no compression/monitor) — using the "
                "standard 2-program step")
            self._fstep_warned = True
        return ok

    def _maybe_fused_train_step(self, data_batch):
        if not self._fused_step_eligible():
            return False
        import jax
        import jax.numpy as jnp
        import numpy as _np
        from .. import random as _random

        ex = self._exec
        upd = self._fused_step_updater()
        opt_ = upd.optimizer
        self._set_batch(data_batch, True)
        arg_vals = {k: v._data for k, v in ex.arg_dict.items()}
        aux_vals = {k: v._data for k, v in ex.aux_dict.items()}
        feed = set(self._data_names) | set(self._label_names)
        grad_names = [n for n in ex._grad_names if n not in feed]
        pnames = [n for n in arg_vals if n not in feed]

        live = [(i, n) for i, n in enumerate(self._param_names)
                if n in ex.grad_dict]
        idx_of = {n: i for i, n in live}
        kv_key = bool(self._update_on_kvstore and self._kvstore is not None)
        from ..kvstore import _updater_key as _ukey
        for i, n in live:
            upd._ensure_state(_ukey(n) if kv_key else i,
                              ex.arg_dict[n])
            opt_._update_count(_ukey(n) if kv_key else i)
        ukeys = {n: (_ukey(n) if kv_key else idx_of[n]) for _, n in live}

        fs = self.__dict__.get("_fstep")
        fkey = (id(ex._plan), type(opt_).__name__,
                opt_.fused_hyper_key(), tuple(sorted(grad_names)),
                tuple(pnames))
        if fs is None or fs["key"] != fkey:
            plan = ex._plan
            gset = list(grad_names)

            def mx_module_fused_step(params, states, aux, xs, key, lrs,
                                     wds, ts):
                merged = dict(params)
                merged.update(xs)

                def fwd(p):
                    m = dict(merged)
                    m.update(p)
                    return plan.run(m, aux, key, True)

                (outs, new_aux), vjp = jax.vjp(
                    fwd, {n: params[n] for n in gset})
                cots = ([jnp.ones(o.shape, o.dtype) for o in outs],
                        jax.tree_util.tree_map(jnp.zeros_like, new_aux))
                (grads,) = vjp(cots)
                new_p, new_s = dict(params), dict(states)
                # the update's instructions land under "optimizer", as
                # FusedUpdater's own program's do: the one program holds
                # three passes and only the scopes tell them apart
                with _introspect.layer_scope("optimizer"):
                    for k, n in enumerate(sorted(gset)):
                        nw, ns = opt_._fused_step_mp(
                            ukeys[n], params[n], grads[n], states[n],
                            lrs[k], wds[k], ts[k])
                        new_p[n] = (nw if nw.dtype == params[n].dtype
                                    else nw.astype(params[n].dtype))
                        new_s[n] = jax.tree_util.tree_map(
                            lambda a, b: a if a.dtype == b.dtype
                            else a.astype(b.dtype), ns, states[n])
                    new_ts = ts + 1
                return outs, new_aux, new_p, new_s, new_ts

            # hold the plan ref: id() keys must not be recycled
            fs = {"key": fkey, "plan": plan,
                  "fn": jax.jit(mx_module_fused_step,
                                donate_argnums=(0, 1, 2))}
            self._fstep = fs

        snames = sorted(grad_names)
        # hyper/ts device caches shared with FusedUpdater.update_all
        lrs, wds, ts, commit_ts = upd.hyper_arrays(
            tuple(ukeys[n] for n in snames))

        params = {n: arg_vals[n] for n in pnames}
        states = {n: upd._state_data(upd.states[ukeys[n]])
                  for n in snames}
        xs = {n: arg_vals[n] for n in feed if n in arg_vals}
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="fused_step")
            _metrics.OPTIMIZER_STEPS.inc()
        key = _random.next_key()
        if _introspect.ENABLED and not fs.get("noted"):
            # once per compiled step, BEFORE the call (the donated
            # buffers are still live): a retrace, no XLA compile
            fs["noted"] = True
            _introspect.note_jit("module:fused_step", fs["fn"], params,
                                 states, aux_vals, xs, key, lrs, wds, ts)
        with span("mx.executor.launch", cat="executor"):
            outs, new_aux, new_p, new_s, nts = fs["fn"](
                params, states, aux_vals, xs, key, lrs, wds, ts)
        commit_ts(nts)

        with span("mx.executor.deposit", cat="executor"):
            kv_store = (self._kvstore._store
                        if kv_key and hasattr(self._kvstore, "_store")
                        else None)
            for n in pnames:
                ex.arg_dict[n]._set_data(new_p[n])
                if kv_store is not None and n in kv_store:
                    # keep the kvstore's weight copy current: a later
                    # pushpull/pull (eligibility flips mid-run) must not
                    # revert training to stale buffers
                    kv_store[n]._set_data(new_p[n])
            for n in snames:
                upd.states[ukeys[n]] = upd._state_writeback(
                    upd.states[ukeys[n]], new_s[n])
            ex._set_results(outs, new_aux)
        ex._snapshot = None
        ex._pending_grads = None
        self._params_dirty = True
        self._fused_stepped = True
        return True

    def update(self):
        """Parity: _update_params_on_kvstore / _update_params (model.py:97-138).

        TPU hot path: the whole multi-parameter update runs in O(1) XLA
        dispatches — KVStore.pushpull / FusedUpdater.update_all trace every
        key into one compiled program (the engine-bulking analog,
        graph_executor.cc:1350) instead of the reference's per-key engine
        pushes."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        if self.__dict__.pop("_fused_stepped", False):
            return  # the fused train step already applied the update
        self._params_dirty = True
        if self._last_step == "computed":
            self._last_step = "applied"
        with span("mx.module.update", cat="optimizer"):
            live = [(i, n) for i, n in enumerate(self._param_names)
                    if n in self._exec.grad_dict]
            names = [n for _, n in live]
            grads = [self._exec.grad_dict[n] for n in names]
            if self._kvstore is not None:
                if self._update_on_kvstore:
                    self._kvstore.pushpull(
                        names, [[g] for g in grads],
                        out=[[self._exec.arg_dict[n]] for n in names])
                else:
                    aggs = [nd.zeros(g.shape, dtype=g.dtype) for g in grads]
                    self._kvstore.pushpull(names, [[g] for g in grads],
                                           out=[[a] for a in aggs])
                    self._update_local([i for i, _ in live], aggs, names)
            else:
                self._update_local([i for i, _ in live], grads, names)

    def _update_local(self, indices, grads, names):
        from ..optimizer import FusedUpdater
        weights = [self._exec.arg_dict[n] for n in names]
        if isinstance(self._updater, FusedUpdater):
            self._updater.update_all(indices, grads, weights)
        else:
            for i, g, w in zip(indices, grads, weights):
                self._updater(i, g, w)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        eval_metric.update_dict(
            dict(zip(self._label_names, labels or [])),
            dict(zip(self._output_names, self.get_outputs())))

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    def get_optimizer_states_bytes(self) -> bytes:
        """Optimizer state as one bytes payload — the Module's durable
        checkpoint surface (mxnet_tpu.checkpoint / fit(checkpoint_dir))."""
        assert self.optimizer_initialized
        updater = self._kvstore._updater if self._update_on_kvstore \
            else self._updater
        if updater is None:
            raise MXNetError("no optimizer set")
        return updater.get_states()

    def set_optimizer_states_bytes(self, payload: bytes) -> None:
        assert self.optimizer_initialized
        updater = self._kvstore._updater if self._update_on_kvstore \
            else self._updater
        if updater is None:
            raise MXNetError("no optimizer set")
        updater.set_states(payload)

    def save_optimizer_states(self, fname):
        from ..base import atomic_write
        atomic_write(fname, self.get_optimizer_states_bytes())

    def load_optimizer_states(self, fname):
        with open(fname, "rb") as f:
            self.set_optimizer_states_bytes(f.read())

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                             for d in data_shapes]
        self._label_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                              for d in label_shapes] if label_shapes else []
        shapes = {d.name: tuple(d.shape) for d in
                  self._data_shapes + self._label_shapes}
        self._exec = self._exec.reshape(**shapes)

    def borrow_optimizer(self, shared_module):
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        self.optimizer_initialized = True
