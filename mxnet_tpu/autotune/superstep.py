"""Scan-compiled K-step supersteps: K training steps = ONE dispatch.

PR 10 compiled the whole training step into one donated XLA program;
the remaining per-step cost is pure host overhead — the dispatch
itself, the supervisor/flight/goodput hooks, the python
driver loop.  The Julia-to-TPU observation (arxiv 1810.09868) is that
once the step is one program, the *loop* compiles too:
``SuperStepCompiler`` wraps ``WholeStepCompiler``'s raw step function
(``_make_ftrain`` — the exact same tracer, shared so the bitwise-parity
contract is structural) in a ``jax.lax.scan`` over K host-prefetched
batches.  Params, optimizer state, 2-bit compression residuals, the
fp16 loss scaler, BN aux state, and the applied-step counter thread
through the scan CARRY (still donated); per-step losses come back
STACKED so per-step visibility survives; the fp16 skip-step select and
scale growth/backoff run per scan iteration exactly as they do per
sequential step.

Numerics: an f32 superstep is bitwise-identical to K sequential
whole-steps on the pinned nets (tests/test_superstep.py) — same op
sequence, same RNG key stream (K keys drawn from the same
``random.next_key`` sequence), same per-step lr/wd rows (stacked
host-side, so lr schedules that move mid-superstep stay exact).

Eligibility is whole-step eligibility; anything the whole-step tracer
rejects — and a refused HBM-headroom ask for staging K batches — warns
once and falls back to K=1 whole-step (which itself falls back to the
fused path when MXNET_WHOLE_STEP is off).  K resolves as
``MXNET_SUPERSTEP_K`` > constructor arg > persisted autotune decision
(``autotune/decisions.py``) > 4.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import getenv
from ..faultinject import fire as _fi_fire
from ..ndarray import NDArray
from ..analysis import hot_path
from ..analysis import sanitizer as _san
from ..gluon.wholestep import WholeStepCompiler, _AmpIneligible, \
    _Ineligible, _ShardIneligible, amp_policy
from ..observability import introspect as _introspect
from ..observability import journal as _journal
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from ..observability.tracing import span
from .. import autograd
from ..gluon.parameter import DeferredInitializationError
from . import decisions as _decisions

logger = logging.getLogger("mxnet_tpu.autotune.superstep")

#: default superstep length when neither env, constructor, nor a
#: persisted decision pins one
DEFAULT_K = 4


class _SuperIneligible(RuntimeError):
    """THIS call cannot run as a scanned superstep (e.g. the HBM ledger
    refused headroom for staging K batches) — demote to K=1 whole-step
    for the call without permanently demoting the compiler."""


class SuperStepCompiler(WholeStepCompiler):
    """K whole training steps as ONE scanned, donated XLA program.

    ::

        stepper = mx.autotune.SuperStepCompiler(net, loss_fn, trainer)
        K = stepper.k
        for datas, labels in staged_groups_of_K:
            losses = stepper.superstep(datas, labels)   # (K, ...) loss

    ``superstep`` accepts either a list/tuple of K per-step batches or
    pre-stacked arrays with a leading K axis (what a ``depth>=K``
    prefetcher stages); it returns the K per-step losses stacked on
    axis 0.  ``step`` (inherited) still runs single whole-steps — the
    two share program caches, hyper plumbing, and writeback, so modes
    can interleave freely.
    """

    def __init__(self, net, loss_fn, trainer, k=None):
        super().__init__(net, loss_fn, trainer)
        self._k_arg = k
        self._super_warned = False    # demotion to K=1, warn once
        self._super_ran = False       # a scan program has executed
        self._stack_cache = {}        # last-value cache: stacked lr/wd

    # -- K resolution --------------------------------------------------------
    @property
    def k(self) -> int:
        """The superstep length the training loop should stage for:
        ``MXNET_SUPERSTEP_K`` > constructor ``k`` > persisted autotune
        decision for this (model-signature, platform) > 4."""
        env_k = int(getenv("MXNET_SUPERSTEP_K", 0))
        if env_k > 0:
            return env_k
        if self._k_arg is not None:
            return max(1, int(self._k_arg))
        sig = self.decision_signature
        if sig is not None:
            dk = _decisions.knob(sig, "superstep_k", None)
            if dk is not None:
                return max(1, int(dk))
        return DEFAULT_K

    @property
    def decision_signature(self):
        """The autotune decision key for this model: a content hash of
        the trainable-parameter signature (None until the graph builds
        — resolving K before the first step falls through to the
        static default)."""
        if self._built is None:
            return None
        return _decisions.model_signature(self._built["sig"])

    @property
    def super_active(self) -> bool:
        """True once a scanned superstep program has executed."""
        return self._super_ran

    # -- public entry --------------------------------------------------------
    @hot_path
    def superstep(self, datas, labels, batch_size=None):
        """Run ``len(datas)`` training steps in one dispatch; returns
        the per-step losses stacked on axis 0 (an NDArray of shape
        ``(K, *loss_shape)`` — per-step visibility survives the fusion).

        ``datas``/``labels``: a list/tuple of K same-shaped NDArray
        batches, or ONE NDArray with a leading K axis (pre-staged)."""
        datas, labels, k, stacked = self._normalize(datas, labels)
        bs = batch_size if batch_size is not None else \
            int(datas[0].shape[0]) if not stacked else int(datas.shape[1])
        if k == 1 or self._fallback_reason is not None \
                or not getenv("MXNET_WHOLE_STEP", False):
            if k > 1:
                self._warn_demoted(
                    "MXNET_WHOLE_STEP is not enabled"
                    if self._fallback_reason is None
                    else self._fallback_reason)
            return self._sequential(datas, labels, bs, k, stacked)
        if autograd.is_recording():
            from ..base import MXNetError
            raise MXNetError(
                "SuperStepCompiler.superstep() must not be called inside "
                "autograd.record() — it manages forward/backward itself")
        policy = amp_policy()
        try:
            built = self._ensure_built()
            return self._run_super(built, datas, labels, bs, policy, k,
                                   stacked)
        except DeferredInitializationError:
            return self._sequential(datas, labels, bs, k, stacked)
        except _SuperIneligible as e:
            # per-call demotion (headroom refusal): the scan program
            # stays viable for the next call
            self._warn_demoted(str(e))
            return self._sequential(datas, labels, bs, k, stacked)
        except _AmpIneligible as e:
            self._warn_demoted(str(e))
            return self._sequential(datas, labels, bs, k, stacked)
        except _ShardIneligible as e:
            # per-call (ragged batch vs mesh data axis): K=1 whole-step
            # handles each batch, which itself falls back per step
            self._warn_demoted(str(e))
            return self._sequential(datas, labels, bs, k, stacked)
        except _Ineligible as e:
            self._warn_demoted(str(e))
            self._note_fallback(str(e))
            return self._sequential(datas, labels, bs, k, stacked)
        except Exception as e:  # noqa: BLE001 — tracing arbitrary graphs
            if self._ran or self._super_ran \
                    or self._is_execution_failure(e) \
                    or self._is_transient(e):
                # execution-typed failure: donated buffers were in play
                # — propagate for a supervisor restore+retry, exactly
                # like WholeStepCompiler.step (the superstep IS the
                # retry unit: a restore rewinds to the last superstep
                # boundary and the whole K-batch group replays)
                raise
            self._warn_demoted(f"{type(e).__name__}: {e}")
            self._note_fallback(f"{type(e).__name__}: {e}")
            return self._sequential(datas, labels, bs, k, stacked)

    # -- fallback ------------------------------------------------------------
    def _warn_demoted(self, reason: str) -> None:
        if not self._super_warned:
            logger.warning(
                "superstep demoted to K=1 whole-step (%s) — steps run "
                "one dispatch each instead of one dispatch per K",
                reason)
            self._super_warned = True

    def _slice(self, arrs, i, stacked):
        if not stacked:
            return arrs[i]
        return NDArray(arrs._data[i], arrs.context)

    def _sequential(self, datas, labels, bs, k, stacked):
        """K=1 fallback: run the batches through the inherited
        whole-step ``step`` (which itself falls back to the fused path
        when ineligible) and restack the losses."""
        losses = [self.step(self._slice(datas, i, stacked),
                            self._slice(labels, i, stacked),
                            batch_size=bs)
                  for i in range(k)]
        ctx = losses[0].context
        return NDArray(jnp.stack([l._data for l in losses]), ctx)

    @staticmethod
    def _normalize(datas, labels):
        if isinstance(datas, (list, tuple)):
            if not isinstance(labels, (list, tuple)) \
                    or len(labels) != len(datas) or not datas:
                from ..base import MXNetError
                raise MXNetError(
                    "superstep: datas and labels must be same-length "
                    "non-empty lists (or both pre-stacked NDArrays)")
            return list(datas), list(labels), len(datas), False
        # pre-stacked: leading axis is the superstep axis
        k = int(datas.shape[0])
        return datas, labels, k, True

    # -- the scanned program -------------------------------------------------
    def _build_super_fn(self, built, opt_, policy, thr, window, k):
        """``lax.scan`` the raw whole-step function over K batches.

        fsuper(gparams, states, residuals, scaler, aux, consts, datas,
               labels, keys, lrs, wds, ts)
          -> (losses[K], new_aux, new_params, new_states,
              new_residuals, new_scaler, new_ts)

        The carry is (params, opt states, residuals, scaler, aux, ts)
        — everything a sequential step would donate and write back; xs
        are the per-step (batch, label, RNG key, lr row, wd row).  The
        body is ``_make_ftrain`` VERBATIM, so one scan iteration is
        op-for-op one whole step (fp16 skip-step and residual feedback
        included)."""
        ftrain = self._make_ftrain(built, opt_, policy, thr, window)

        def fsuper(gparams, states, residuals, scaler, aux, consts,
                   datas, labels, keys, lrs, wds, ts):
            def body(carry, xs):
                gp, st, res, sc, ax, t = carry
                data, label, key, lr, wd = xs
                loss, nax, nparams, nstates, nres, nsc, nt = ftrain(
                    gp, st, res, sc, ax, consts, data, label, key,
                    lr, wd, t)
                return (nparams, nstates, nres, nsc, nax, nt), loss

            carry, losses = jax.lax.scan(
                body, (gparams, states, residuals, scaler, aux, ts),
                (datas, labels, keys, lrs, wds), length=k)
            ngp, nst, nres, nsc, nax, nts = carry
            return losses, nax, ngp, nst, nres, nsc, nts

        mesh = self.mesh
        if mesh is None or mesh.size <= 1:
            return jax.jit(fsuper, donate_argnums=(0, 1, 2, 3, 4))
        # same rule as WholeStepCompiler._build_fn: GSPMD may pick
        # different output shardings for the scan carry than its inputs,
        # and a donated buffer whose output layout differs cannot alias.
        # Pin every donated output to its input's committed
        # NamedSharding (same-shape state leaves shard like their
        # weight, everything else replicates).
        from jax.lax import with_sharding_constraint as _wsc
        from jax.sharding import NamedSharding, PartitionSpec
        params = built["params"]
        gnames = built["gnames"]
        psh = {n: params[n].sharding for n in gnames}
        repl = NamedSharding(mesh, PartitionSpec())

        def _pin_state(s, wsh, wshape):
            if s is None:
                return None
            if isinstance(s, (tuple, list)):
                return type(s)(_pin_state(x, wsh, wshape) for x in s)
            tgt = wsh if tuple(s.shape) == wshape and wsh is not None \
                else repl
            return _wsc(s, tgt)

        def fshard(gparams, states, residuals, scaler, aux, consts,
                   datas, labels, keys, lrs, wds, ts):
            (losses, nax, ngp, nst, nres, nsc,
             nts) = fsuper(gparams, states, residuals, scaler, aux,
                           consts, datas, labels, keys, lrs, wds, ts)
            ngp = {n: _wsc(v, psh[n] if psh[n] is not None else repl)
                   for n, v in ngp.items()}
            nst = [_pin_state(s, psh[gnames[j]],
                              tuple(gparams[gnames[j]].shape))
                   for j, s in enumerate(nst)]
            nax = {n: _wsc(v, repl) for n, v in nax.items()}
            nsc = {n: _wsc(v, repl) for n, v in nsc.items()} \
                if isinstance(nsc, dict) else nsc
            return losses, nax, ngp, nst, nres, nsc, nts

        return jax.jit(fshard, donate_argnums=(0, 1, 2, 3, 4))

    # -- per-superstep driver ------------------------------------------------
    def _run_super(self, built, datas, labels, bs, policy, k, stacked):
        tr = self.trainer
        # ONE chaos site per superstep, fired before the schedule
        # counters advance and before any donated buffer is touched: an
        # injected raise is a cleanly-retryable failed SUPERSTEP (the
        # supervisor's replay window holds whole K-batch groups)
        _fi_fire("trainer.step", step=tr._step_id)
        upd = tr._updaters[0]
        opt_ = upd.optimizer
        idx = built["idx"]
        if policy != "f32" and any(d != "float32"
                                   for _, d in built["sig"]):
            raise _AmpIneligible(
                f"MXNET_AMP={policy} needs float32 master weights")
        gc = getattr(tr._kv, "_gc", None) if tr._kv is not None else None
        thr = gc.threshold if gc is not None else None
        if thr is not None and self.mesh is not None \
                and self.mesh.size > 1:
            # same rule as WholeStepCompiler._run: GSPMD collectives
            # replace the bucketed allreduce on a real mesh (the scan
            # body is the shared tracer, so the two modes must agree)
            if not self._mesh_comp_warned:
                self._mesh_comp_warned = True
                from ..parallel.mesh import mesh_signature
                logger.warning(
                    "2-bit gradient compression is disabled inside the "
                    "superstep program on a multi-device mesh (%s) — "
                    "GSPMD collectives replace the bucketed allreduce",
                    mesh_signature(self.mesh))
            thr = None
        if built["bk"] is None:
            # every trainable param is a sparse embedding (ISSUE 20):
            # no dense buckets exist, so compression has nothing to act
            # on — the sparse leg's row grads never flatten
            thr = None
        residuals = []
        if thr is not None:
            if tr._residuals is None:
                tr._residuals = tr._init_residuals(built["bk"])
            residuals = tr._residuals
        scaler = {}
        window = 0
        if policy == "fp16":
            st = tr._ensure_scaler()
            window = st["window"]
            scaler = {"scale": st["scale"], "good": st["good"]}

        opt_.rescale_grad = tr._scale / bs
        # advance the schedule counters K times host-side, capturing
        # the per-step lr/wd rows EXACTLY as K sequential _run calls
        # would see them (stacked (K, n) xs — schedules that move
        # mid-superstep stay bitwise-exact); roll all K back if the
        # build/dispatch fails so the fallback's own counting starts
        # clean
        prev_nu = opt_.num_update
        prev_counts = {i: opt_._index_update_count.get(i) for i in idx}
        lr_rows, wd_rows = [], []
        ts = counts0 = None
        try:
            for s in range(k):
                for i in idx:
                    opt_._update_count(i)
                if s == 0:
                    # after the FIRST bump: the same seeding point one
                    # sequential step uses, so the device applied-step
                    # counter (and any checkpointed pending ts) carries
                    # over identically
                    _l, _w, ts, counts0 = self._hyper_arrays(opt_, idx)
                lr_rows.append(tuple(opt_._get_lr(i) for i in idx))
                wd_rows.append(tuple(opt_._get_wd(i) for i in idx))
            return self._dispatch_super(
                built, opt_, upd, policy, thr, window, scaler, residuals,
                datas, labels, bs, k, stacked, lr_rows, wd_rows, ts,
                counts0)
        except Exception:
            opt_.num_update = prev_nu
            for i, c in prev_counts.items():
                if c is None:
                    opt_._index_update_count.pop(i, None)
                else:
                    opt_._index_update_count[i] = c
            raise

    def _stage(self, datas, labels, k, stacked):
        """Device-stage the K batches as (K, ...) stacked arrays.  A
        list input asks the HBM ledger for headroom BEFORE staging (the
        arbitration point the multi-model registry also uses); refusal
        demotes this call to K=1."""
        if stacked:
            return datas._data, labels._data, datas.context
        need = sum(int(_np.prod(a.shape)) *
                   _np.dtype(str(a.dtype)).itemsize
                   for a in (datas[0], labels[0])) * k
        if _memory.ENABLED and not _memory.ensure_headroom(
                need, why=f"superstep staging (K={k} batches)"):
            raise _SuperIneligible(
                f"HBM ledger refused {need} bytes of headroom for "
                f"staging K={k} batches")
        return (jnp.stack([d._data for d in datas]),
                jnp.stack([l._data for l in labels]), datas[0].context)

    def _dispatch_super(self, built, opt_, upd, policy, thr, window,
                        scaler, residuals, datas, labels, bs, k, stacked,
                        lr_rows, wd_rows, ts, counts0):
        tr = self.trainer
        params = built["params"]
        gnames = built["gnames"]
        idx = built["idx"]
        mesh = self.mesh
        if mesh is not None:
            from ..parallel import mesh as _pmesh
            daxis = _pmesh.data_axis(mesh)
            dsize = int(mesh.shape[daxis])
            if bs % dsize != 0:
                raise _ShardIneligible(
                    f"batch of {bs} does not divide the mesh's "
                    f"{daxis} axis (size {dsize})")
        datas_j, labels_j, ctx = self._stage(datas, labels, k, stacked)
        if mesh is not None:
            # committed placement of the staged (K, batch, ...) stacks:
            # the scan axis replicates, the batch axis shards — jit
            # reads in_shardings off these and compiles the sharded
            # scan program (still 1 dispatch per K steps)
            from jax.sharding import NamedSharding, PartitionSpec
            ssh = NamedSharding(mesh, PartitionSpec(None, daxis))
            datas_j = jax.device_put(datas_j, ssh)  # graft-lint: disable=memory-hygiene
            labels_j = jax.device_put(labels_j, ssh)  # graft-lint: disable=memory-hygiene
        # stacked (K, n) lr/wd rows with a last-value cache — constant
        # schedules re-upload nothing after the first superstep
        lrk, wdk = tuple(lr_rows), tuple(wd_rows)
        sc = self._stack_cache
        if sc.get("lr_key") != lrk:
            sc["lr_key"] = lrk
            sc["lr"] = jnp.asarray(_np.array(lrk, _np.float32))  # graft-lint: disable=host-sync
        if sc.get("wd_key") != wdk:
            sc["wd_key"] = wdk
            sc["wd"] = jnp.asarray(_np.array(wdk, _np.float32))  # graft-lint: disable=host-sync
        lrs, wds = sc["lr"], sc["wd"]
        gparams = {n: params[n].list_data()[0]._data for n in gnames}
        consts = {n: params[n].list_data()[0]._data
                  for n in built["cnames"]}
        aux = {n: params[n].list_data()[0]._data
               for n in built["aux_names"]}
        if mesh is not None and mesh.size > 1:
            # same restore-path conformance as WholeStepCompiler._dispatch:
            # rehydrated states land on the default device; pull them
            # back onto their weights' committed NamedSharding
            from ..optimizer import _conform_state_sharding
            for j, n in enumerate(gnames):
                upd.states[idx[j]] = _conform_state_sharding(
                    upd.states[idx[j]], params[n].list_data()[0])
        svals = [upd._state_data(upd.states[i]) for i in idx]

        upd.dtype_policy = policy
        pol_key = policy if policy != "fp16" else f"fp16/w{window}"
        from ..parallel.mesh import mesh_signature as _mesh_sig
        msig = _mesh_sig(mesh)
        key = ("superstep", pol_key, type(opt_).__name__,
               opt_.fused_hyper_key(), idx,
               tuple(d for _, d in built["sig"]),
               built["uid"], thr,
               built["bk"].sizes if thr is not None else None,
               jax.tree_util.tree_structure(svals), k, msig)
        fn = upd.lookup_program(
            key, lambda: self._build_super_fn(built, opt_, policy, thr,
                                              window, k))
        note_key = (key, tuple(datas_j.shape), tuple(labels_j.shape))
        if _introspect.ENABLED and note_key not in self._noted_keys:
            self._noted_keys.add(note_key)
            import hashlib
            # K folds into the signature: the noted flops are the SCAN
            # program's (K x one step — XLA's cost model counts the
            # body per iteration), so the perf baseline and MFU
            # numerator track the superstep length honestly
            sig = hashlib.sha1(repr(
                (built["sig"], type(opt_).__name__, policy,
                 thr is not None, tuple(datas_j.shape),
                 tuple(labels_j.shape), k,
                 msig)).encode()).hexdigest()[:16]
            contracts = {
                "donate_argnums": (0, 1, 2, 3, 4),
                "donated_leaves": len(jax.tree_util.tree_leaves(
                    (gparams, svals, residuals, scaler, aux))),
                "amp": policy,
                "host_callbacks": 0,
                "buckets": len(built["bk"].sizes)
                if thr is not None else 0,
                "superstep_k": k,
            }
            if mesh is not None and mesh.size > 1:
                # same GSPMD plan the whole-step program declares: the
                # scan body carries the collectives, so each sized axis
                # shows at least one in the lowered HLO
                contracts["mesh_axes"] = {
                    a: int(mesh.shape[a]) for a in mesh.axis_names}
                contracts["collective_plan"] = {
                    a: 1 for a in mesh.axis_names
                    if int(mesh.shape[a]) > 1}
            else:
                contracts["collectives"] = 0
            _introspect.note_jit(
                "superstep", fn, gparams, svals, residuals, scaler, aux,
                consts, datas_j, labels_j,
                jnp.stack([jax.random.PRNGKey(i) for i in range(k)]),
                lrs, wds, ts, signature=sig, contracts=contracts)

        # chaos site for transient device loss at the dispatch boundary
        _fi_fire("device.unavailable", step=tr._step_id)
        from .. import random as _random
        # K keys drawn from the SAME next_key() sequence K sequential
        # steps would consume — the bitwise-parity contract includes
        # the RNG stream (dropout etc.)
        keys = jnp.stack([_random.next_key() for _ in range(k)])
        on = _metrics.ENABLED
        d0 = _metrics.step_dispatches() if on else 0.0
        if on:
            _metrics.XLA_LAUNCHES.inc(kind="superstep")
            _metrics.OPTIMIZER_STEPS.inc(float(k))
        try:
            with span("superstep", cat="trainer", step=tr._step_id,
                      watch=True, mem=True, labels={"k": k}), \
                    _memory.oom_guard("superstep.step"):
                losses, new_aux, new_p, new_s, new_res, new_scaler, \
                    nts = fn(gparams, svals, residuals, scaler, aux,
                             consts, datas_j, labels_j, keys, lrs, wds,
                             ts)
        except BaseException:
            if _san.ENABLED:
                _san.poison_donated(
                    "superstep",
                    *[params[n].list_data() for n in gnames],
                    *[params[n].list_data()
                      for n in built["aux_names"]],
                    *[upd.states[i] for i in idx])
            raise
        tr._step_id += k
        if on:
            delta = _metrics.step_dispatches() - d0
            # the demotion tripwire: 1 dispatch per SUPERSTEP when the
            # scan runs, K when silently demoted to per-step dispatches
            # — the perf sentinel's dispatch baseline reads this gauge
            # for the "superstep" phase
            _metrics.SUPERSTEP_DISPATCHES.set(delta)
            _metrics.TRAINER_STEP_DISPATCHES.set(delta / float(k))
        if _introspect.ENABLED:
            _introspect.sentinel_tick("superstep")
        if _journal.ENABLED:
            _journal.maybe_milestone(tr._step_id, source="superstep")

        # commit: counts advanced K times host-side, so the hyper
        # cache's next-step expectation is counts0 + K (commit adds 1)
        self._commit_outputs(built, upd, policy, thr, new_p, new_aux,
                             new_s, new_res, new_scaler, nts,
                             tuple(c + k - 1 for c in counts0))
        self._ran = True
        self._super_ran = True
        return NDArray(losses, ctx)
