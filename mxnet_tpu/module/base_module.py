"""BaseModule: the high-level train/predict interface.

Reference parity: `python/mxnet/module/base_module.py` — fit (:376-487),
score, predict, forward/backward contract, parameter get/set, checkpoints.
"""
from __future__ import annotations

import logging
import time
from typing import List, Optional

import numpy as _np

from ..base import MXNetError
from .. import metric as _metric
from .. import ndarray as nd
from ..io import DataDesc
from ..model import BatchEndParam
from ..observability import flight as _flight
from ..observability import metrics as _obs
from ..observability.tracing import span


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name not in args:
            msg = f"You created Module with Module(..., {typename}_names={names})" \
                  f" but input with name '{name}' is not found in symbol.list_arguments()."
            if throw:
                raise ValueError(msg)
            logging.warning(msg)


def _as_list(obj):
    if obj is None:
        return []
    return obj if isinstance(obj, (list, tuple)) else [obj]


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high-level ----------------------------------------------------------
    def forward_backward(self, data_batch) -> None:
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Parity: base_module.score."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Parity: base_module.predict."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise ValueError("Cannot merge batches: different number of outputs")
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint_dir=None, checkpoint_period=1,
            checkpoint_max_keep=None, supervise=False):
        """Train loop (parity: base_module.py:376-487).

        ``checkpoint_dir`` opts into the fault-tolerant checkpoint
        subsystem (docs/checkpointing.md): fit auto-resumes from the
        newest valid checkpoint there (params + optimizer state;
        ``begin_epoch`` advances to the saved epoch), saves one atomic
        async checkpoint every ``checkpoint_period`` epochs, keeps the
        newest ``checkpoint_max_keep`` (None = all), and barriers on
        outstanding writes before returning.

        ``supervise=True`` runs every fit step through a
        ``gluon.TrainingSupervisor`` (docs/training_resilience.md):
        transient step failures restore a rolling host snapshot of
        params + optimizer state and replay; divergence and stall
        watchdogs post-mortem and raise typed errors.  Inert under
        ``MXNET_SUPERVISE=0``."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform
        if initializer is None:
            initializer = Uniform(0.01)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        _ckpt = None
        if checkpoint_dir is not None:
            from .. import checkpoint as _ckpt_mod
            _ckpt = _ckpt_mod.CheckpointManager(
                checkpoint_dir, max_to_keep=checkpoint_max_keep)
            restored = _ckpt.restore()
            if restored is not None:
                ck_epoch, ck_state = restored
                ck_arg, ck_aux, ck_opt, _ = \
                    _ckpt_mod.unpack_module_state(ck_state)
                self.set_params(
                    {k: nd.array(v) for k, v in ck_arg.items()},
                    {k: nd.array(v) for k, v in ck_aux.items()})
                if ck_opt is not None:
                    if hasattr(self, "set_optimizer_states_bytes"):
                        self.set_optimizer_states_bytes(ck_opt)
                    else:
                        # BucketingModule/SequentialModule never had a
                        # durable optimizer-state surface (no
                        # save_optimizer_states either): params resume,
                        # optimizer restarts fresh — say so
                        self.logger.warning(
                            "checkpoint carries optimizer state but %s "
                            "cannot restore it; resuming params only",
                            type(self).__name__)
                begin_epoch = max(begin_epoch, int(ck_epoch))
                self.logger.info(
                    "fit: resumed from checkpoint epoch %d in %s",
                    ck_epoch, checkpoint_dir)
        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        _sup = None
        if supervise:
            from ..gluon.supervisor import TrainingSupervisor
            _sup = TrainingSupervisor.for_module(self)

        global_step = 0
        try:
            self._fit_epochs(
                train_data, eval_data, eval_metric, validation_metric,
                epoch_end_callback, batch_end_callback, eval_end_callback,
                eval_batch_end_callback, monitor, begin_epoch, num_epoch,
                global_step, _ckpt, checkpoint_period, _sup)
        finally:
            if _sup is not None:
                _sup.close()
            if _ckpt is not None:
                _ckpt.close()  # barrier: all queued writes committed

    def _fit_epochs(self, train_data, eval_data, eval_metric,
                    validation_metric, epoch_end_callback,
                    batch_end_callback, eval_end_callback,
                    eval_batch_end_callback, monitor, begin_epoch,
                    num_epoch, global_step, _ckpt, checkpoint_period,
                    _sup=None):
        for epoch in range(begin_epoch, num_epoch):
            tic = time.time()
            # decided ONCE per epoch: flipping the recorder on mid-epoch
            # must not fabricate a span with a t0 from before the flip
            ep_t0 = _flight.now_us() if _flight.ENABLED else None
            eval_metric.reset()
            nbatch = 0
            data_iter = iter(train_data)
            end_of_batch = False
            next_data_batch = self._fetch(data_iter, global_step)
            ahead = {}  # what prepare() dispatched for the step to come
            while not end_of_batch:
                data_batch = next_data_batch
                if monitor is not None:
                    monitor.tic()
                # dispatch accounting: the per-step delta of compiled
                # launches + device_puts over forward_backward+update is
                # the round-2 O(1) invariant, published as a gauge.
                # kind="data" launches are excluded: a PrefetchingIter
                # producer thread issues them DURING the step, which
                # would make the delta nondeterministic.
                obs_on = _obs.ENABLED
                deltas = None
                if obs_on:
                    c0, deltas = _obs.step_counts(), {}
                with span("mx.step", cat="step", step=global_step,
                          labels=deltas, watch=True):
                    if _sup is not None:
                        # supervised: fwd/bwd/update run as ONE step_fn
                        # under retry + divergence/stall watchdogs
                        _sup.step(data_batch)
                    else:
                        self.forward_backward(data_batch)
                        self.update()
                    if obs_on:
                        # the span's deltas ride its ring record
                        deltas.update(_obs.step_deltas(c0))
                        # what prepare() issued for this step is this step's
                        for k, v in ahead.items():
                            deltas[k] += v
                        _obs.FIT_STEP_DISPATCHES.set(
                            deltas["launches"] + deltas["device_puts"])
                step = global_step
                global_step += 1
                ahead = {}
                try:
                    next_data_batch = self._fetch(data_iter, step)
                    # prepare() works for the step to come: its span, and
                    # what it dispatches, carry that step's id
                    with span("mx.module.prepare", cat="io",
                              step=global_step):
                        before = _obs.step_counts()
                        self.prepare(next_data_batch)
                        ahead = _obs.step_deltas(before)
                except StopIteration:
                    end_of_batch = True
                with span("mx.module.update_metric", cat="metric",
                          step=step):
                    self.update_metric(eval_metric, data_batch.label)
                if monitor is not None:
                    monitor.toc_print()
                if batch_end_callback is not None:
                    with span("mx.fit.callbacks", cat="callback",
                              step=step):
                        batch_end_params = BatchEndParam(
                            epoch=epoch, nbatch=nbatch,
                            eval_metric=eval_metric, locals=locals())
                        for callback in _as_list(batch_end_callback):
                            callback(batch_end_params)
                nbatch += 1

            for name, val in eval_metric.get_name_value():
                self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            toc = time.time()
            self.logger.info("Epoch[%d] Time cost=%.3f", epoch, (toc - tic))

            with span("mx.fit.epoch_end", cat="train"):
                arg_params_, aux_params_ = self.get_params()
                self.set_params(arg_params_, aux_params_)
            if epoch_end_callback is not None:
                for callback in _as_list(epoch_end_callback):
                    callback(epoch, self.symbol, arg_params_, aux_params_)
            if _ckpt is not None and (
                    (epoch + 1) % max(1, checkpoint_period) == 0
                    or epoch == num_epoch - 1):  # final epoch always saved
                from .. import checkpoint as _ckpt_mod
                # async: the device->host snapshot happens here, the
                # serialize+write happens off the epoch loop.  Module
                # types without an optimizer-state surface checkpoint
                # params only (same coverage the legacy path had).
                opt_bytes = self.get_optimizer_states_bytes() \
                    if hasattr(self, "get_optimizer_states_bytes") else None
                _ckpt.save(epoch + 1, _ckpt_mod.pack_module_state(
                    self.symbol, arg_params_, aux_params_,
                    optimizer_states=opt_bytes))

            if eval_data:
                res = self.score(eval_data, validation_metric,
                                 score_end_callback=eval_end_callback,
                                 batch_end_callback=eval_batch_end_callback,
                                 epoch=epoch)
                for name, val in res:
                    self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)
            train_data.reset()
            if ep_t0 is not None:
                # non-lexical span (the epoch body is one loop pass):
                # recorded via the raw clock + record() pair
                _flight.record("mx.fit.epoch", "train", ep_t0,
                               _flight.now_us(), step=epoch)

    @staticmethod
    def _fetch(data_iter, step):
        """The next batch under ``mx.fit.data_fetch``; the span's clock
        feeds the data-wait histogram, unless the iterator times its own
        consumer-side stall (PrefetchingIter)."""
        with span("mx.fit.data_fetch", cat="io", step=step) as sp:
            batch = next(data_iter)
        if _obs.ENABLED and not getattr(
                data_iter, "_self_timed_data_wait", False):
            _obs.DATA_WAIT_SECONDS.observe(sp.seconds)
        return batch

    def _adopt_existing_bind(self, data_shapes, label_shapes, for_training,
                             inputs_need_grad=False, grad_req="write",
                             against=None):
        """Already-bound handshake shared by every Module subclass: a
        re-bind matching the current bind (data/label name+shape+dtype,
        training mode, inputs_need_grad, grad_req) is a silent no-op; a
        conflict raises instead of warn-and-ignore, which would silently
        keep stale executors.  `against` overrides the module whose bind
        state is compared (BucketingModule compares the default bucket,
        not whichever bucket is current)."""
        from ..io import DataDesc
        import numpy as _np
        ref = against if against is not None else self

        def norm(descs):
            out = []
            for d in (descs or []):
                d = d if isinstance(d, DataDesc) else DataDesc(*d)
                out.append((d.name, tuple(d.shape),
                            _np.dtype(d.dtype).name))
            return out

        req = (norm(data_shapes), norm(label_shapes), bool(for_training),
               bool(inputs_need_grad), grad_req)
        cur = (norm(ref.data_shapes), norm(ref.label_shapes),
               bool(ref.for_training), bool(ref.inputs_need_grad),
               getattr(ref, "_grad_req", grad_req))
        if req != cur:
            raise ValueError(
                "Module is already bound with (data, label, for_training, "
                "inputs_need_grad, grad_req)=%s; bind(%s) conflicts. "
                "Use force_rebind=True." % (cur, req))

    # -- interface to implement ----------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
        save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        save_dict = nd.load(fname)
        arg_params, aux_params = {}, {}
        for k, value in save_dict.items():
            arg_type, _, name = k.partition(":")
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError(f"Invalid param file {fname}")
        self.set_params(arg_params, aux_params)

    def get_states(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return []

    def set_states(self, states=None, value=None):
        assert self.binded and self.params_initialized

    def install_monitor(self, mon):
        raise NotImplementedError

    def prepare(self, data_batch):
        pass

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError
