"""gluon.Trainer (parity: python/mxnet/gluon/trainer.py:27,108-127,156).

Applies an Optimizer to a ParameterDict; kvstore-backed when requested so
`KVStore('tpu_sync')` data parallelism works unmodified from gluon code.

TPU fast path (MXNET_FUSED_TRAINER, default on): a steady-state `step` on a
dense model is O(1) XLA dispatches regardless of parameter count —
  1. bucketed allreduce: all dense grads flatten into size-capped buckets
     (MXNET_BUCKET_SIZE_MB, ~32MB) in ONE jitted program and reduce via
     one store-less `kvstore.allreduce` over the transient buckets;
  2. fused update: `FusedUpdater.update_all` slices each gradient straight
     out of the reduced flat buckets inside its single compiled optimizer
     program (grad_views), so un-flattening costs nothing.
`compression_params={'type': '2bit'}` composes with the fast path: the
buckets quantize against flat per-bucket error-feedback residuals (one
more fused program; the dist leg ships the packed 4-codes/byte payload,
~1/16 of the float32 bytes) while per-parameter residual semantics stay
identical to the reference's per-key quantizer — see
kvstore._compressed_allreduce_impl.
`MXNET_FUSED_TRAINER=0` pins the reference-shaped legacy path (per-key
push/pull loop + per-parameter updater calls) for A/B and bisection.
"""
from __future__ import annotations

import os as _os
import pickle

import jax.numpy as jnp
import numpy as _np

from ..analysis import hot_path
from ..base import MXNetError, getenv
from ..faultinject import fire as _fi_fire
from ..ndarray import NDArray
from ..observability import flight as _flight
from ..observability import journal as _journal
from ..observability import introspect as _introspect
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from ..observability.tracing import set_step, span
from .. import optimizer as opt
from ..model import _create_kvstore
from .parameter import ParameterDict, Parameter


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None,
                 mesh=None):
        if isinstance(params, (dict, ParameterDict)):
            params = list(params.values())
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        for param in params:
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            self._params.append(param)
        self._compression_params = compression_params
        # GSPMD mesh this trainer's params shard over (ISSUE 18): the
        # whole-step/superstep compilers resolve explicit arg > this >
        # the ambient parallel.mesh.current_mesh(); None = replicated
        self._mesh = mesh
        optimizer_params = optimizer_params or {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._init_optimizer(optimizer, optimizer_params)
        self._kv_initialized = False
        self._kvstore = kvstore
        self._update_on_kvstore_arg = update_on_kvstore
        self._fused = bool(getenv("MXNET_FUSED_TRAINER", True))
        self._bucketer = None
        self._bucket_sig = None
        # (flat bucket arrays, per-param views, index tuple) staged by a
        # for-step allreduce for the fused update to consume
        self._reduced = None
        # {param_idx: merged RowSparseNDArray} staged by a for-step
        # allreduce_rowsparse for the fused sparse update (ISSUE 20)
        self._reduced_rsp = None
        # (key, (live, rsp, rsp_idx, dense)) — see _live_split
        self._live_split_cache = None
        # 2-bit error-feedback state for the compressed bucketed
        # allreduce: one flat f32 residual per bucket, laid out by the
        # bucketer (each parameter's residual is its own slice, so
        # per-parameter error-feedback semantics survive bucketing);
        # rebuilt zero-initialized on bucket-signature change
        self._residuals = None
        # (bucket_sig, numpy arrays) from load_states, adopted — with a
        # signature check — when the bucketer is next built
        self._pending_residuals = None
        # dynamic loss-scaling state for MXNET_AMP=fp16 whole-step
        # training (gluon/wholestep.py): device scalars donated into the
        # compiled step each call; rides save_states/load_states so a
        # resumed run continues the same scale trajectory
        self._scaler = None
        # (idx, device applied-step vector) mirrored by the whole-step
        # compiler after each step; persisted with the scaler because
        # fp16 skip-steps make it lag the schedule counts — a resume
        # seeding Adam's bias-correction t from the counts would diverge
        self._applied_ts = None
        self._applied_ts_pending = None  # set by load_states, consumed once
        # monotonically increasing step id stamped on flight-recorder
        # phase records (joins allreduce/compress/update sub-phases to
        # their step in a timeline dump)
        self._step_id = 0
        self._counts = None  # metrics.step_counts() at the last step's end

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer " \
                "instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)]

    def _init_kvstore(self):
        arg_arrays = {param.name: param.data() for param in self._params}
        kvstore, update_on_kvstore = _create_kvstore(
            self._kvstore, 1, arg_arrays)
        if self._update_on_kvstore_arg is not None:
            # explicit user override (parity: later-1.x Trainer arg)
            update_on_kvstore = bool(self._update_on_kvstore_arg)
            if update_on_kvstore and kvstore is None:
                # parity: reference Trainer raises rather than silently
                # training with local updaters (save_states would then
                # write a different state format than the user asked for)
                raise ValueError(
                    "update_on_kvstore=True requires a kvstore, but "
                    f"kvstore={self._kvstore!r} resolved to none — set "
                    "update_on_kvstore=False or pass a kvstore")
        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            for i, param in enumerate(self._params):
                kvstore.init(i, param.data())
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        self._kv = kvstore
        self._update_on_kvstore = update_on_kvstore and kvstore is not None
        self._kv_initialized = True

    @property
    def learning_rate(self):
        return self._optimizer.lr_scheduler(self._optimizer.num_update) \
            if self._optimizer.lr_scheduler else self._optimizer.lr

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # -- stale-grad accounting ----------------------------------------------
    @staticmethod
    def _is_fresh(param):
        return param.fresh_grad

    def _mask_stale(self, live, ignore_stale_grad):
        """Parity: gluon/trainer.py:216 — a gradient that backward has not
        rewritten since the last step either raises (default) or masks its
        parameter out of the update (ignore_stale_grad=True)."""
        if ignore_stale_grad:
            return [(i, p) for i, p in live if self._is_fresh(p)]
        for i, p in live:
            for d in p.list_data():
                if not getattr(d, "_fresh_grad", False):
                    raise UserWarning(
                        f"Gradient of Parameter `{p.name}` on context "
                        f"{d.context} has not been updated by backward "
                        "since last `step`. This could mean a bug in your "
                        "model that made it only use a subset of the "
                        "Parameters (Blocks) for this iteration. If you "
                        "are intentionally only using a subset, call step "
                        "with ignore_stale_grad=True to suppress this "
                        "warning and skip updating of Parameters with "
                        "stale gradient")
        return live

    @staticmethod
    def _clear_fresh(entries):
        for _, p in entries:
            for d in p.list_data():
                d._fresh_grad = False

    def _live_split(self):
        """Cached dense/row-sparse split of the live params (ISSUE 20):
        ``(live, rsp, rsp_idx, dense)``.  The per-step linear
        ``getattr`` scans collapse to one build per param-set change —
        keyed on param identity + grad_req + grad_stype, the same
        identity discipline as the bucketer signature (PR 3)."""
        key = tuple((id(p), p.grad_req,
                     getattr(p, "_grad_stype", "default"))
                    for p in self._params)
        cached = self._live_split_cache
        if cached is not None and cached[0] == key:
            return cached[1]
        live = [(i, p) for i, p in enumerate(self._params)
                if p.grad_req != "null"]
        rsp = [(i, p) for i, p in live
               if getattr(p, "_grad_stype", "default") == "row_sparse"]
        rsp_idx = frozenset(i for i, _ in rsp)
        dense = [ip for ip in live if ip[0] not in rsp_idx]
        out = (live, rsp, rsp_idx, dense)
        self._live_split_cache = (key, out)
        return out

    @hot_path
    def step(self, batch_size, ignore_stale_grad=False):
        """Apply one optimization step with grads scaled by 1/batch_size.

        TPU hot path: all parameters update in O(1) XLA dispatches via
        bucketed KVStore.pushpull + FusedUpdater.update_all (replaces the
        reference's per-parameter kvstore push loop, gluon/trainer.py:191-226).
        The per-step dispatch delta is published as the
        mxnet_trainer_step_dispatches gauge."""
        on = _metrics.ENABLED
        deltas = None
        if on:
            c0, deltas = _metrics.step_counts(), {}
        with span("mx.trainer.step", cat="optimizer", step=self._step_id,
                  labels=deltas, watch=True, mem=True):
            self._step(batch_size, ignore_stale_grad)
            if on:
                # the record carries the whole Gluon step's counts: from
                # the last Trainer.step's return (forward, backward, the
                # loop's reads) to this one's
                now = _metrics.step_counts()
                deltas.update(_metrics.step_deltas(self._counts or c0, now))
                self._counts = now
                _metrics.TRAINER_STEP_DISPATCHES.set(
                    now[0] + now[1] - c0[0] - c0[1])
        self._step_id += 1
        # a Gluon step runs from one Trainer.step return to the next
        set_step(self._step_id)
        if _introspect.ENABLED:
            # perf-regression sentinel heartbeat for the fused path
            # (the whole-step path ticks its own phase in
            # WholeStepCompiler._dispatch): one counter bump per step
            _introspect.sentinel_tick("mx.trainer.step")
        if _journal.ENABLED:
            _journal.maybe_milestone(self._step_id, source="trainer")

    def _step(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        # chaos site (one global read when no plan): fires BEFORE any
        # param/optimizer mutation, so an injected raise models a step
        # that failed without consuming state — the TrainingSupervisor
        # classifies it transient and retries (whole-step mode fires the
        # same site in WholeStepCompiler._run; exactly one per step)
        _fi_fire("trainer.step", step=self._step_id)
        self._optimizer.rescale_grad = self._scale / batch_size
        live, rsp, rsp_idx, dense = self._live_split()
        if self._kv is not None and self._update_on_kvstore:
            # parity: the reference NEVER masks the kvstore push set —
            # only the no-kvstore updater loop honors ignore_stale_grad.
            # Masking here would also desynchronize collective
            # participation across hosts (worker A skips a stale param
            # worker B pushes → mismatched allreduce → pod hang), so
            # stale grads raise (default) or push as-is.
            if not ignore_stale_grad:
                self._mask_stale(live, False)
            # row-sparse grad_stype params go through the kvstore per-key
            # sparse path (class-preserving push → lazy rsp optimizer on
            # the store) so untouched rows never decay
            if rsp:
                from ..ndarray import sparse as _sp
                for i, p in rsp:
                    # grads are already RowSparseNDArrays (rows-only
                    # autograd deposit); cast is only a legacy fallback
                    self._kv.pushpull(
                        i, [g if isinstance(g, _sp.RowSparseNDArray)
                            else _sp.cast_storage(g, "row_sparse")
                            for g in p.list_grad()],
                        out=p.list_data())
            if dense:
                if self._fused:
                    self._kv.pushpull([i for i, _ in dense],
                                      [p.list_grad() for _, p in dense],
                                      out=[p.list_data() for _, p in dense])
                else:
                    # MXNET_FUSED_TRAINER=0: the reference-shaped per-key
                    # loop, for A/B runs and bisection
                    for i, p in dense:
                        self._kv.pushpull(i, p.list_grad(),
                                          out=p.list_data())
            self._clear_fresh(live)
            return
        self._allreduce_grads(for_step=True)
        self._update(ignore_stale_grad)

    def allreduce_grads(self):
        if not self._kv_initialized:
            self._init_kvstore()
        self._allreduce_grads()

    def _allreduce_grads(self, for_step=False):
        self._reduced = None
        self._reduced_rsp = None
        if self._kv is None:
            return
        live, rsp, rsp_idx, dense = self._live_split()
        if rsp:
            from ..ndarray import sparse as _sp
            fused_rsp = (for_step and self._fused
                         and not self._update_on_kvstore
                         and all(len(p.list_grad()) == 1 for _, p in rsp))
            if fused_rsp:
                # ONE row-sparse reduce over all sparse keys (ISSUE 20):
                # unique-concat + segment-sum, jit-inlinable — replaces
                # the per-key push/pull exile.  The merged grads are
                # staged for _update's fused sparse leg, consume-once.
                merged = self._kv.allreduce_rowsparse(
                    [[g if isinstance(g, _sp.RowSparseNDArray)
                       else _sp.cast_storage(g, "row_sparse")
                       for g in p.list_grad()] for _, p in rsp])
                self._reduced_rsp = {
                    i: m for (i, _), m in zip(rsp, merged)}
            else:
                for i, p in rsp:
                    # sparse keys keep the per-key class-preserving path
                    self._kv.push(i, p.list_grad())
                    if not self._update_on_kvstore:
                        self._kv.pull(i, p.list_grad())
        if not dense:
            return
        # 2-bit compression composes with bucketing: the quantizer is
        # purely elementwise, so flat per-bucket residuals (threaded
        # through _bucketed_pushpull) preserve per-parameter
        # error-feedback semantics exactly — fused-compressed matches
        # the legacy per-key-compressed path (tests/test_fused_step.py)
        fused_ok = (self._fused and not self._update_on_kvstore
                    and all(len(p.list_grad()) == 1 for _, p in dense))
        if not fused_ok:
            for i, param in dense:
                self._kv.push(i, param.list_grad())
                if not self._update_on_kvstore:
                    self._kv.pull(i, param.list_grad())
            return
        flats, views, idx = self._bucketed_pushpull(dense)
        if for_step:
            # the fused update slices grads straight out of the flat
            # buckets (grad_views); per-key grad buffers are rewritten
            # only for the public allreduce_grads() contract below
            self._reduced = (flats, views, idx)
        else:
            outs = self._bucketer.unflatten(flats)
            for (i, p), g in zip(dense, outs):
                p.list_grad()[0]._set_data(g)

    def _bucketed_pushpull(self, dense):
        """Flatten → one store-less fused allreduce over the buckets →
        reduced flat buckets.  Returns (flat arrays, per-param views,
        indices).  The buckets are TRANSIENT — they never enter the
        kvstore's backing store, so no gradient-sized copy is pinned and
        nothing is copied per step beyond the reduce itself."""
        grads = [p.list_grad()[0] for _, p in dense]
        sig = tuple((tuple(g.shape), str(g.dtype)) for g in grads)
        idx = tuple(i for i, _ in dense)
        bk = self._ensure_bucketer(sig, idx)
        gc = getattr(self._kv, "_gc", None)
        with span("mx.trainer.allreduce", cat="kvstore", mem=True), \
                _memory.memory_scope("grad_bucket"):
            flats = bk.flatten([g.handle for g in grads], whole=gc is None)
            ctx = grads[0].context
            # a bucket of one gradient is that gradient's own array: its
            # NDArray goes on, so the ledger does not count it twice
            own = {id(g.handle): g for g in grads}
            buckets = [own[id(f)] if id(f) in own else NDArray(f, ctx)
                       for f in flats]
            if gc is not None:
                if self._residuals is None:
                    self._residuals = self._init_residuals(bk)
                with _flight.phase_span("compress", cat="kvstore",
                                        step=self._step_id):
                    reduced, self._residuals = self._kv.allreduce(
                        buckets, compression=gc,
                        residuals=self._residuals)
                if _memory.ENABLED:
                    # the allreduce returns FRESH residual arrays each
                    # step (functional update) — re-register so the
                    # ledger keeps attributing the live ones
                    for r in self._residuals:
                        _memory.register(r, tag="compression_residual")
            else:
                reduced = self._kv.allreduce(buckets)
        return ([r.handle for r in reduced],
                [bk.views[j] for j in range(len(dense))], idx)

    def _ensure_bucketer(self, sig, idx):
        """Build (or reuse) the GradBucketer for this dense-gradient
        signature.  Shared by the fused allreduce AND the whole-step
        compiler so both lay residuals out identically — a checkpoint
        written under one path restores under the other."""
        from ..kvstore import GradBucketer
        if self._bucketer is None or self._bucket_sig != (sig, idx):
            mb = None
            if "MXNET_BUCKET_SIZE_MB" not in _os.environ:
                # env pin beats any persisted autotune decision; only an
                # UNSET env consults the tuner's measured pick for this
                # gradient signature (lazy import: autotune is optional
                # machinery, the trainer must not drag it in at import)
                from ..autotune import decisions as _decisions
                if _decisions.ENABLED:
                    mb = _decisions.knob(
                        _decisions.model_signature(sig),
                        "bucket_size_mb", None)
            cap = int(float(getenv("MXNET_BUCKET_SIZE_MB", 32.0)
                            if mb is None else mb) * 1024 * 1024)
            self._bucketer = GradBucketer(sig, cap)
            self._bucket_sig = (sig, idx)
            # the flat residual layout is a function of the bucket
            # layout — a signature change restarts error feedback
            self._residuals = None
        return self._bucketer

    def _ensure_scaler(self):
        """Dynamic loss-scaling state (MXNET_AMP=fp16): scale and
        consecutive-finite-step count as device scalars — the whole-step
        program reads, updates, and returns them functionally, so no
        per-step host sync ever inspects them.  Growth/backoff policy:
        x2 after MXNET_LOSS_SCALE_WINDOW consecutive finite steps, x0.5
        (floor 1.0) on any nonfinite gradient, that step skipped."""
        if self._scaler is None:
            self._scaler = self._make_scaler(
                getenv("MXNET_LOSS_SCALE_INIT", 65536.0), 0,
                getenv("MXNET_LOSS_SCALE_WINDOW", 200))
        return self._scaler

    @staticmethod
    def _make_scaler(scale, good, window):
        """The one place the scaler dict is constructed — fresh starts
        (_ensure_scaler) and checkpoint restores (load_states) must
        produce the identical structure."""
        return {
            "scale": _memory.register(
                jnp.asarray(float(scale), dtype=jnp.float32),
                tag="optimizer_state"),
            "good": _memory.register(
                jnp.asarray(int(good), dtype=jnp.int32),
                tag="optimizer_state"),
            "window": int(window),
        }

    @property
    def loss_scale(self) -> float:
        """Current dynamic loss scale (1.0 when fp16 scaling is off).
        Reading it syncs the device scalar — diagnostics/tests only,
        never the hot path."""
        if self._scaler is None:
            return 1.0
        return float(_np.asarray(self._scaler["scale"]))

    def _init_residuals(self, bk):
        """Fresh zero residuals sized to the bucket layout — unless
        load_states stashed checkpointed ones, which must match the
        current bucket signature exactly (a silent zero-reset would
        discard the checkpoint's error feedback)."""
        if self._pending_residuals is not None:
            saved_sig, arrays = self._pending_residuals
            # the param signature alone is not enough: a different
            # MXNET_BUCKET_SIZE_MB regroups the same params into
            # different flat buckets, so the residual ARRAY layout must
            # match too (else the jitted quantize dies on shapes)
            if saved_sig != self._bucket_sig or \
                    tuple(int(a.shape[0]) for a in arrays) != bk.sizes:
                raise MXNetError(
                    "Trainer.load_states: checkpointed compression "
                    "residuals were saved for a different parameter/"
                    f"bucket signature ({len(arrays)} buckets over "
                    f"{len(saved_sig[0])} dense params; current layout "
                    f"has {len(bk.sizes)} buckets over "
                    f"{len(self._bucket_sig[0])} dense params with "
                    "different shapes/dtypes/order). Resuming would "
                    "silently reset 2-bit error feedback — load states "
                    "saved from the same model and bucket layout "
                    "(MXNET_BUCKET_SIZE_MB included).")
            self._pending_residuals = None
            return [_memory.register(jnp.asarray(a),
                                     tag="compression_residual")
                    for a in arrays]
        return [_memory.register(jnp.zeros(n, dtype=jnp.float32),
                                 tag="compression_residual")
                for n in bk.sizes]

    def _update(self, ignore_stale_grad=False):
        from ..optimizer import FusedUpdater
        live, _, rsp_idx, _ = self._live_split()
        # pop the staged buckets BEFORE the stale check: if it raises,
        # a later update() must not consume the previous step's grads
        reduced, self._reduced = self._reduced, None
        reduced_rsp, self._reduced_rsp = self._reduced_rsp, None
        live = self._mask_stale(live, ignore_stale_grad)
        if self._update_on_kvstore and self._kv is not None:
            for i, param in live:
                self._kv.pull(i, out=param.list_data())
            self._clear_fresh(live)
            return
        upd = self._updaters[0]
        # one updater per device copy (parity: reference trainer keeps
        # len(contexts) updaters so every replica is updated)
        ncopies = max((len(p.list_data()) for _, p in live), default=1)
        while len(self._updaters) < ncopies:
            self._updaters.append(opt.get_updater(self._optimizer))
        done = list(live)
        fused_ok = self._fused and isinstance(upd, FusedUpdater)
        # update_all always runs f32 optimizer math — clear any sticky
        # whole-step AMP policy (a direct Trainer.step after AMP
        # whole-step training must not key, and loudly "recompile",
        # the update_all program under a precision it never traced)
        if fused_ok:
            for u in self._updaters:
                if u.dtype_policy != "f32":
                    u.dtype_policy = "f32"
        # row-sparse grad_stype params: one fused gather→step→scatter
        # dispatch over all sparse keys (ISSUE 20) when the updater is
        # fused and copies are single; MXNET_FUSED_TRAINER=0, multi-copy,
        # or non-fused optimizers keep the reference-shaped lazy per-key
        # loop for A/B runs
        rsp = [ip for ip in live if ip[0] in rsp_idx]
        if rsp:
            from ..ndarray import sparse as _sp

            def _as_rsp(g):
                return g if isinstance(g, _sp.RowSparseNDArray) \
                    else _sp.cast_storage(g, "row_sparse")
            if fused_ok and all(len(p.list_data()) == 1 for _, p in rsp):
                # _allreduce_grads(for_step=True) stages the merged
                # grads; a direct update() call consumes the raw per-key
                # grad buffers instead — same values single-worker
                sgrads = [_as_rsp(p.list_grad()[0])
                          if reduced_rsp is None or i not in reduced_rsp
                          else reduced_rsp[i] for i, p in rsp]
                upd.update_sparse([i for i, _ in rsp], sgrads,
                                  [p.list_data()[0] for _, p in rsp])
            else:
                for i, param in rsp:
                    for u, arr, grad in zip(self._updaters,
                                            param.list_data(),
                                            param.list_grad()):
                        u(i, _as_rsp(grad), arr)
            live = [ip for ip in live if ip[0] not in rsp_idx]
            if not live:
                self._clear_fresh(done)
                return
        if fused_ok and all(len(p.list_data()) == 1 for _, p in live):
            if reduced is not None:
                flats, views, idx = reduced
                pos = {i: j for j, i in enumerate(idx)}
                # _allreduce_grads staged every dense live param in the
                # buckets; a param outside `idx` would train on its raw
                # UN-REDUCED grad buffer (the for_step path deliberately
                # never rewrites per-key grads), so fail loudly — a real
                # raise, not an assert, so python -O cannot strip it
                missing = [i for i, _ in live if i not in pos]
                if missing:
                    raise MXNetError(
                        f"staged gradient buckets cover params {idx} but "
                        f"the update set includes {missing} — the "
                        "allreduce and update steps saw different live "
                        "parameter sets")
                if live:
                    upd.update_all(
                        [i for i, _ in live], flats,
                        [p.list_data()[0] for _, p in live],
                        grad_views=[views[pos[i]] for i, _ in live])
            else:
                upd.update_all([i for i, _ in live],
                               [p.list_grad()[0] for _, p in live],
                               [p.list_data()[0] for _, p in live])
            self._clear_fresh(done)
            return
        if fused_ok and ncopies > 1 and \
                all(len(p.list_data()) == ncopies for _, p in live):
            # uniform multi-device copies: one fused program per copy
            # slot — O(#copies) dispatches, still O(1) in param count
            for c in range(ncopies):
                self._updaters[c].update_all(
                    [i for i, _ in live],
                    [p.list_grad()[c] for _, p in live],
                    [p.list_data()[c] for _, p in live])
            self._clear_fresh(done)
            return
        # legacy per-parameter loop (MXNET_FUSED_TRAINER=0, ragged device
        # copies, or optimizers without a fused_step)
        for i, param in live:
            for u, arr, grad in zip(self._updaters, param.list_data(),
                                    param.list_grad()):
                u(i, grad, arr)
        self._clear_fresh(done)

    def update(self, batch_size, ignore_stale_grad=False):
        if not self._kv_initialized:
            self._init_kvstore()
        self._optimizer.rescale_grad = self._scale / batch_size
        self._update(ignore_stale_grad)

    def get_states_bytes(self) -> bytes:
        """The complete durable optimizer state as one bytes payload:
        updater state (+ optimizer) and, when gradient compression is
        active, the error-feedback residuals — exactly what
        ``save_states`` writes to disk.  This is the trainer's
        checkpoint surface (`mxnet_tpu.checkpoint.save_trainer`)."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._update_on_kvstore:
            if self._kv._updater is None:
                raise MXNetError("no optimizer set")
            states = self._kv._updater.get_states(dump_optimizer=True)
        else:
            states = self._updaters[0].get_states(dump_optimizer=True)
        return self._wrap_states(states)

    def save_states(self, fname):
        from ..base import atomic_write
        atomic_write(fname, self.get_states_bytes())

    def _wrap_states(self, states: bytes) -> bytes:
        """Without compression or loss scaling the file is the raw
        updater-state pickle (format unchanged).  With compression
        active, the 2-bit error-feedback residuals ride along in a
        sentinel-keyed wrapper so a resumed run continues the same
        quantization trajectory instead of silently restarting from
        zero error; with fp16 dynamic loss scaling active (whole-step
        AMP), the scaler's scale/good-step state rides the same wrapper
        so a resumed run continues the same scale trajectory."""
        bucket = None
        if self._residuals is not None:
            bucket = {"sig": self._bucket_sig,
                      "residuals": [_np.asarray(r) for r in self._residuals]}
        elif self._pending_residuals is not None:
            saved_sig, arrays = self._pending_residuals
            bucket = {"sig": saved_sig,
                      "residuals": [_np.asarray(a) for a in arrays]}
        kv_res = {}
        if self._kv is not None and getattr(self._kv, "_residuals", None):
            # per-key residuals (legacy per-key path and the
            # update_on_kvstore fused pushpull both key them in the kv)
            kv_res = {k: _np.asarray(v)
                      for k, v in self._kv._residuals.items()}
        scaler = None
        if self._scaler is not None:
            scaler = {"scale": float(_np.asarray(self._scaler["scale"])),
                      "good": int(_np.asarray(self._scaler["good"])),
                      "window": int(self._scaler["window"])}
            if self._applied_ts is not None:
                scaler["ts_idx"] = list(self._applied_ts[0])
                scaler["ts"] = [int(t) for t in
                                _np.asarray(self._applied_ts[1])]
        if bucket is None and not kv_res and scaler is None:
            return states
        return pickle.dumps({"__mxt_trainer_states__": 1,
                             "updater": states,
                             "bucket": bucket,
                             "kv_residuals": kv_res,
                             "scaler": scaler})

    @staticmethod
    def _unwrap_states(payload: bytes):
        """(updater-state bytes, residual extras or None).  Raw legacy
        files unpickle to the updater's own dict/tuple — never a dict
        with the sentinel key — so detection cannot misfire."""
        try:
            obj = pickle.loads(payload)
        except Exception:
            return payload, None
        if isinstance(obj, dict) and obj.get("__mxt_trainer_states__") == 1:
            return obj["updater"], obj
        return payload, None

    def load_states(self, fname):
        with open(fname, "rb") as f:
            payload = f.read()
        self.set_states_bytes(payload)

    def set_states_bytes(self, payload: bytes):
        """Inverse of ``get_states_bytes`` (both raw legacy pickles and
        the residual-carrying sentinel wrapper)."""
        if not self._kv_initialized:
            self._init_kvstore()
        states, extra = self._unwrap_states(payload)
        # loading REPLACES the trainer's auxiliary training state: a
        # checkpoint written without fp16 must not inherit this
        # process's previous scaler/applied-ts trajectory (the next
        # save would otherwise persist the stale scale into the new
        # run's checkpoints)
        self._scaler = None
        self._applied_ts = None
        self._applied_ts_pending = None
        if self._update_on_kvstore:
            if self._kv._updater is None:
                raise MXNetError("no optimizer set")
            self._kv._updater.set_states(states)
            self._optimizer = self._kv._updater.optimizer
        else:
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer
        if extra is None:
            return
        scaler = extra.get("scaler")
        if scaler is not None:
            self._scaler = self._make_scaler(
                scaler["scale"], scaler["good"], scaler["window"])
            if scaler.get("ts") is not None:
                self._applied_ts_pending = (
                    tuple(scaler["ts_idx"]),
                    [int(t) for t in scaler["ts"]])
        kv_res = extra.get("kv_residuals") or {}
        if kv_res and self._kv is not None:
            self._kv._residuals = {k: jnp.asarray(v)
                                   for k, v in kv_res.items()}
        bucket = extra.get("bucket")
        if bucket is None:
            return
        self._pending_residuals = (bucket["sig"], bucket["residuals"])
        self._residuals = None
        if self._bucket_sig is not None:
            # a bucketer already exists: adopt (or reject) immediately
            # instead of deferring the mismatch to the next step
            self._residuals = self._init_residuals(self._bucketer)
