"""TrainingSupervisor: fault-tolerant training steps (ISSUE 12).

PR 6 made *serving* survive chaos; the training loop — the thing a
production jax_graft system runs for days — still died on the first
transient device error, hung forever on a wedged chip, and (since PR 10
donates the whole step) could leave *poisoned buffers* behind a failed
dispatch: the params may already be consumed by XLA when the error
surfaces.  This module is the training-side twin of the serving
resilience tier (MXNet leans on the KVStore server as the recovery
consistency point for exactly this failure class, arxiv 1512.01274; the
TF paper treats checkpoint-mediated recovery from worker failure as a
first-class requirement, arxiv 1605.08695 §4.4):

  * **typed fault classification** — every step failure routes through
    ``resilience.classify``: *transient* (UNAVAILABLE device, RPC
    deadline, injected chaos) retries; *oom*
    (``DeviceMemoryError``, already post-mortemed by the PR 9 ledger)
    and *permanent* (trace/user errors) propagate immediately.
  * **donation-safe retry** — a bounded rolling host snapshot of
    params + optimizer state + compression residuals + loss scaler
    (every ``MXNET_SUPERVISE_SNAPSHOT_STEPS``, via the checkpoint
    layer's eager device→host ``snapshot_state``) plus the window of
    batch references since the snapshot.  On a transient failure the
    supervisor restores the snapshot, replays the window, and
    re-executes the failed step — donated buffers a failed whole-step
    dispatch consumed are rebuilt from host copies, and an f32 retry
    run is bitwise-identical to an uninterrupted one (deterministic
    steps; stochastic models re-draw RNG and match statistically).
  * **divergence watchdog** — ``MXNET_SUPERVISE_DIVERGE_PATIENCE``
    consecutive nonfinite losses triggers ONE rate-limited post-mortem
    (flight ring + HBM ledger report, the PR 8/9 surfaces) and then
    either a typed ``DivergenceError`` or a rewind to the last
    snapshot, per ``MXNET_SUPERVISE_ON_DIVERGE=raise|rewind``.
  * **stall watchdog** — steps execute on a dedicated worker thread
    while the caller waits with a deadline derived from the
    step-duration EWMA (the supervisor's own, seeded/maxed with the
    flight recorder's ``mx.trainer.step``/``whole_step`` watch EWMAs).  A
    step that blows ``MXNET_SUPERVISE_STALL_FACTOR`` × EWMA (floored at
    ``MXNET_SUPERVISE_STALL_MIN_S``) post-mortems and raises a typed
    ``TrainingStalledError`` instead of hanging forever; the supervisor
    is then poisoned (the wedged dispatch may still own the device).
  * **preemption** — ``install_preemption_hook`` upgrades the PR 5
    SIGTERM hook to fire *through* the supervisor: mid-step the
    emergency save uses the last consistent host snapshot instead of
    live (possibly half-updated, possibly donated) device buffers.

Overhead contract (the METRICS_ENABLED discipline):
``MXNET_SUPERVISE=0`` reduces ``step()`` to ONE module-global boolean
test and a direct call.  Enabled, a steady-state step costs one
worker-thread handoff, one EWMA update, and (every
``MXNET_SUPERVISE_CHECK_EVERY`` steps) one host read of the loss.

::

    sup = mx.gluon.TrainingSupervisor(stepper.step, trainer=trainer,
                                      params=net)
    uninstall = sup.install_preemption_hook(manager)
    for x, y in batches:
        loss = sup.step(x, y)     # retries transients, watches health
"""
from __future__ import annotations

import logging
import math
import queue as _queue
import threading
import time
from typing import Callable, Optional

import numpy as _np

from ..base import MXNetError, getenv
from ..checkpoint import layout as _layout
from ..observability import flight as _flight
from ..observability import goodput as _goodput
from ..observability import journal as _journal
from ..observability import metrics as _metrics
from .. import resilience as _res
from ..resilience import (DivergenceError, StepRetriesExhausted,
                          TrainingStalledError)

log = logging.getLogger(__name__)

__all__ = ["ENABLED", "enable", "disable", "enabled", "TrainingSupervisor"]

# -- the fast-path switch ----------------------------------------------------
# MXNET_SUPERVISE=0: every supervisor hook is one module-global boolean
# test; step() delegates straight to the wrapped step_fn.
ENABLED: bool = bool(getenv("MXNET_SUPERVISE", True))

_EWMA_ALPHA = 0.3   # same smoothing/warmup as the flight watchdog —
_EWMA_WARMUP = 5    # the two EWMAs must agree on what "normal" means

#: flight phases whose warmed EWMA seeds the stall deadline (whichever
#: step mode ran, its phase is warm)
_STEP_PHASES = ("mx.trainer.step", "whole_step")


def enabled() -> bool:
    return ENABLED


def enable() -> None:
    global ENABLED
    ENABLED = True


def disable() -> None:
    global ENABLED
    ENABLED = False


def _finite(value) -> bool:
    """Host-side finiteness of a step's returned loss.  NDArray / jax /
    numpy arrays read via ``np.asarray`` (on the CPU backend this is
    ~zero-copy; on TPU it transfers only the loss array) — no extra
    compiled dispatch.  Unrecognized types count as finite (the
    supervisor never fails a step it cannot interpret)."""
    if value is None:
        return True
    if isinstance(value, (float, int)):
        return math.isfinite(value)
    data = getattr(value, "_data", value)  # NDArray -> jax array
    try:
        return bool(_np.isfinite(_np.asarray(data)).all())
    except Exception:  # noqa: BLE001 — non-numeric step results
        return True


class TrainingSupervisor:
    """Supervise a training-step callable with typed-fault retry,
    divergence and stall watchdogs, and snapshot-consistent preemption.

    Parameters
    ----------
    step_fn : callable
        One training step: ``step_fn(*args, **kw) -> loss`` (the loss —
        NDArray / scalar — feeds the divergence watchdog; other return
        types are passed through unchecked).  Typical values:
        ``WholeStepCompiler(...).step``, or a closure doing
        record/backward/``Trainer.step``.
    trainer : gluon.Trainer, optional
        Snapshots ``get_states_bytes()`` (optimizer state, 2-bit
        residuals, fp16 scaler) and restores via ``set_states_bytes``.
    params : Block | ParameterDict | dict, optional
        The model parameters (aux states included) to snapshot/restore.
    snapshot_fn / restore_fn : callable, optional
        Override the state capture entirely: ``snapshot_fn() -> {name:
        value}`` (arrays/bytes, fed to ``layout.snapshot_state``) and
        ``restore_fn(state_dict)``.  Used by ``for_module``.
    steps_per_call : int, optional
        TRAINING steps one ``step_fn`` invocation advances — pass K
        when supervising ``SuperStepCompiler.superstep`` (the retry
        unit is then the whole superstep: snapshots land on superstep
        boundaries, the replay window holds K-batch groups, and a
        restore rewinds to the last superstep boundary).  The
        ``snapshot_steps`` budget keeps counting training steps: the
        snapshot cadence in CALLS is ``ceil(snapshot_steps /
        steps_per_call)``.  Default 1.
    snapshot_steps / retries / backoff_s / diverge_patience /
    on_diverge / check_every / stall_factor / stall_min_s : optional
        Override the corresponding ``MXNET_SUPERVISE_*`` env defaults
        (see docs/training_resilience.md for the tuning guide).
    """

    def __init__(self, step_fn: Callable, trainer=None, params=None,
                 snapshot_fn: Optional[Callable] = None,
                 restore_fn: Optional[Callable] = None,
                 snapshot_steps: Optional[int] = None,
                 retries: Optional[int] = None,
                 backoff_s: Optional[float] = None,
                 diverge_patience: Optional[int] = None,
                 on_diverge: Optional[str] = None,
                 check_every: Optional[int] = None,
                 stall_factor: Optional[float] = None,
                 stall_min_s: Optional[float] = None,
                 steps_per_call: Optional[int] = None):
        self._step_fn = step_fn
        self._trainer = trainer
        self._pd = None
        if params is not None:
            from ..checkpoint.manager import _as_param_dict
            self._pd = _as_param_dict(params)
        self._snapshot_fn = snapshot_fn
        self._restore_fn = restore_fn
        if (self._pd is None and trainer is None
                and (snapshot_fn is None) != (restore_fn is None)):
            raise MXNetError("snapshot_fn and restore_fn come as a pair")
        self.snapshot_steps = int(getenv("MXNET_SUPERVISE_SNAPSHOT_STEPS",
                                         50)) \
            if snapshot_steps is None else int(snapshot_steps)
        if self.snapshot_steps < 1:
            raise MXNetError("snapshot_steps must be >= 1")
        self.steps_per_call = 1 if steps_per_call is None \
            else int(steps_per_call)
        if self.steps_per_call < 1:
            raise MXNetError("steps_per_call must be >= 1")
        self.retries = int(getenv("MXNET_SUPERVISE_RETRIES", 2)) \
            if retries is None else int(retries)
        self.backoff_s = float(getenv("MXNET_SUPERVISE_RETRY_BACKOFF_S",
                                      0.05)) \
            if backoff_s is None else float(backoff_s)
        self.diverge_patience = int(getenv(
            "MXNET_SUPERVISE_DIVERGE_PATIENCE", 3)) \
            if diverge_patience is None else int(diverge_patience)
        od = str(getenv("MXNET_SUPERVISE_ON_DIVERGE", "raise")).lower() \
            if on_diverge is None else str(on_diverge).lower()
        if od not in ("raise", "rewind"):
            raise MXNetError(
                f"MXNET_SUPERVISE_ON_DIVERGE must be raise|rewind, got {od!r}")
        self.on_diverge = od
        self.check_every = int(getenv("MXNET_SUPERVISE_CHECK_EVERY", 1)) \
            if check_every is None else int(check_every)
        self.stall_factor = float(getenv("MXNET_SUPERVISE_STALL_FACTOR",
                                         60.0)) \
            if stall_factor is None else float(stall_factor)
        self.stall_min_s = float(getenv("MXNET_SUPERVISE_STALL_MIN_S",
                                        30.0)) \
            if stall_min_s is None else float(stall_min_s)

        # rolling snapshot: (step_count at capture, snapshot_state dict)
        self._snap: Optional[tuple] = None
        # batch windows since the snapshot: [(args, kwargs)], replayed
        # after a restore.  Bounded: cleared at every snapshot, so it
        # never holds more than snapshot_steps entries
        self._window: list = []
        self._step_count = 0
        self._nonfinite = 0
        self._retry_warned = False
        self._in_step = False
        self._stalled: Optional[str] = None  # poison reason after a stall
        # own step-duration EWMA (the flight recorder's may be disabled)
        self._ewma = 0.0
        self._ewma_n = 0
        # lazily-started step executor thread (the stall guard): jobs
        # and results are sequenced — at most one job in flight, and a
        # stall permanently poisons the supervisor, so a late result
        # from a wedged dispatch can never be matched to a new job
        self._work_q: Optional[_queue.SimpleQueue] = None
        self._result_q: Optional[_queue.SimpleQueue] = None
        self._worker: Optional[threading.Thread] = None

    # -- construction helpers ------------------------------------------------
    @classmethod
    def for_module(cls, module, **kw) -> "TrainingSupervisor":
        """Supervise a ``Module``'s fit step: ``step(batch)`` runs
        ``forward_backward`` + ``update`` with the same retry/stall
        machinery; snapshots pack ``get_params`` + optimizer-state
        bytes (what ``Module.fit(supervise=True)`` uses).

        The divergence watchdog defaults OFF here (``check_every=0``):
        the step has no loss to watch — module outputs are raw head
        activations, where ±inf can be legitimate (log-prob masks) and
        saturated-but-finite values can hide a diverged loss.  Pass
        ``check_every`` explicitly to watch the outputs anyway."""
        kw.setdefault("check_every", 0)
        from ..faultinject import fire as _fi_fire

        def step_fn(batch):
            # same chaos site as the gluon paths: one fire per step
            _fi_fire("trainer.step")
            module.forward_backward(batch)
            module.update()
            outs = module.get_outputs()
            return outs[0] if outs else None

        def snapshot_fn():
            from ..checkpoint.manager import pack_module_state
            arg_p, aux_p = module.get_params()
            opt_b = module.get_optimizer_states_bytes() \
                if hasattr(module, "get_optimizer_states_bytes") else None
            return pack_module_state(None, arg_p, aux_p,
                                     optimizer_states=opt_b)

        def restore_fn(state):
            from .. import ndarray as nd
            from ..checkpoint.manager import unpack_module_state
            arg_p, aux_p, opt_b, _ = unpack_module_state(state)
            module.set_params({k: nd.array(v) for k, v in arg_p.items()},
                              {k: nd.array(v) for k, v in aux_p.items()})
            if opt_b is not None and \
                    hasattr(module, "set_optimizer_states_bytes"):
                module.set_optimizer_states_bytes(opt_b)

        return cls(step_fn, snapshot_fn=snapshot_fn,
                   restore_fn=restore_fn, **kw)

    # -- public entry --------------------------------------------------------
    def step(self, *args, **kw):
        """Run one supervised training step.  With ``MXNET_SUPERVISE=0``
        this is exactly ``step_fn(*args, **kw)`` — one boolean test."""
        if not ENABLED:
            return self._step_fn(*args, **kw)
        if self._stalled is not None:
            raise TrainingStalledError(
                f"supervisor poisoned by an earlier stall ({self._stalled})"
                " — the wedged dispatch may still own the device; restart "
                "the process and resume from the last checkpoint",
                step=self._step_count)
        self._maybe_snapshot()
        if self._can_restore:
            # the replay window only exists to rebuild state after a
            # snapshot restore; without a snapshot surface it would
            # just grow one batch reference per step forever
            self._window.append((args, kw))
        try:
            out = self._attempt(args, kw)
        except BaseException:
            # the failed batch must not replay on a later retry of a
            # DIFFERENT step — the caller decides whether to resubmit
            if self._can_restore:
                self._window.pop()
            raise
        self._step_count += 1
        if _journal.ENABLED:
            # milestones count TRAINING steps, not calls — a K-superstep
            # step_fn advances K of them per call
            _journal.maybe_milestone(
                self._step_count * self.steps_per_call,
                source="supervisor")
        return self._check_divergence(out)

    __call__ = step

    # -- snapshot / restore --------------------------------------------------
    @property
    def _can_restore(self) -> bool:
        return (self._restore_fn is not None or self._pd is not None
                or self._trainer is not None)

    def _pack_live_state(self) -> dict:
        """The live training state in checkpoint-layer packing (the
        ``save_trainer`` key convention, so an emergency save of it is
        ``restore_trainer``-compatible)."""
        if self._snapshot_fn is not None:
            return self._snapshot_fn()
        from ..checkpoint.manager import PARAM_PREFIX, TRAINER_STATES_KEY
        state: dict = {}
        if self._pd is not None:
            state.update({f"{PARAM_PREFIX}{name}": p.data()
                          for name, p in self._pd.items()})
        if self._trainer is not None:
            state[TRAINER_STATES_KEY] = self._trainer.get_states_bytes()
        return state

    @property
    def _snapshot_calls(self) -> int:
        """Snapshot cadence in step_fn CALLS: ``snapshot_steps`` counts
        training steps, one call advances ``steps_per_call`` of them —
        under a K-superstep step_fn the boundary lands every
        ceil(snapshot_steps/K) calls, i.e. ON a superstep boundary."""
        return -(-self.snapshot_steps // self.steps_per_call)

    def _maybe_snapshot(self) -> None:
        due = self._snap is None \
            or self._step_count % self._snapshot_calls == 0
        if not due or not self._can_restore:
            return
        if self._snap is not None and self._snap[0] == self._step_count:
            return  # a retry re-entering the same boundary
        from .parameter import DeferredInitializationError
        try:
            snap = _layout.snapshot_state(self._pack_live_state())
        except DeferredInitializationError:
            # shapes materialize on the first forward; retry next step
            return
        self._snap = (self._step_count, snap)
        self._window.clear()
        if _metrics.ENABLED:
            _metrics.SUPERVISOR_SNAPSHOTS.inc()
            _metrics.SUPERVISOR_LAST_SNAPSHOT_STEP.set(self._step_count)

    def _restore_snapshot(self) -> None:
        assert self._snap is not None
        _, snap = self._snap
        state = {name: payload for name, (kind, payload) in snap.items()}
        if self._restore_fn is not None:
            self._restore_fn(state)
            return
        from ..checkpoint.manager import PARAM_PREFIX, TRAINER_STATES_KEY
        if self._pd is not None:
            for name, p in self._pd.items():
                arr = state.get(f"{PARAM_PREFIX}{name}")
                if arr is None:
                    raise MXNetError(
                        f"snapshot lacks parameter {name!r} — params "
                        "changed after the supervisor captured it")
                # same device-placement path restore_trainer uses: the
                # host copy becomes a FRESH device buffer, replacing
                # whatever a failed donated dispatch consumed.  A
                # sharded param re-commits to its NamedSharding here
                # too — _init_impl re-applies the recorded spec, so a
                # donation-safe retry restores the GSPMD placement, not
                # a single-device copy
                p._load_init(arr, p.list_ctx())
        if self._trainer is not None and TRAINER_STATES_KEY in state:
            self._trainer.set_states_bytes(state[TRAINER_STATES_KEY])

    # -- retry loop ----------------------------------------------------------
    def _attempt(self, args, kw):
        delay = self.backoff_s
        last: Optional[BaseException] = None
        for attempt in range(self.retries + 1):
            try:
                if attempt:
                    time.sleep(delay)
                    delay *= 2
                    self._rewind_for_retry()
                return self._execute(args, kw)
            except (DivergenceError, TrainingStalledError):
                raise
            except BaseException as e:  # noqa: BLE001 — classify decides
                kind = _res.classify(e)
                if kind is not _res.TRANSIENT:
                    raise
                if not self._can_restore:
                    if not self._retry_warned:
                        log.warning(
                            "supervisor has no snapshot surface (no "
                            "trainer/params/restore_fn) — transient step "
                            "failures propagate instead of retrying")
                        self._retry_warned = True
                    raise
                last = e
                if attempt == self.retries:
                    raise StepRetriesExhausted(
                        f"step {self._step_count} failed "
                        f"{self.retries + 1} times on transient errors "
                        f"(last: {type(e).__name__}: {e})",
                        step=self._step_count) from e
                if _metrics.ENABLED:
                    _metrics.SUPERVISOR_RETRIES.inc()
                if _journal.ENABLED:
                    _journal.emit("supervisor_retry",
                                  step=self._step_count,
                                  attempt=attempt + 1,
                                  error=f"{type(e).__name__}: {e}")
                log.warning(
                    "supervisor: transient failure at step %d "
                    "(%s: %s) — restoring snapshot from step %s and "
                    "retrying (%d/%d)", self._step_count,
                    type(e).__name__, e,
                    self._snap[0] if self._snap else None,
                    attempt + 1, self.retries)
        raise StepRetriesExhausted(  # pragma: no cover — loop invariant
            f"step {self._step_count}", step=self._step_count) from last

    def _rewind_for_retry(self) -> None:
        """Restore the last snapshot and replay the batch window up to
        (but not including) the failed step — rebuilding every donated
        buffer from host copies, on the exact op sequence the
        uninterrupted run executed.  Replayed steps go through
        ``_execute`` too, so an injected fault landing mid-replay
        surfaces to ``_attempt`` and simply costs another retry."""
        if self._snap is None:
            # a transient on the FIRST step: the boundary capture was
            # skipped because params were still deferred-initialized,
            # but the failed attempt's build/trace materialized them
            # BEFORE the fault fired — so the live state is the state
            # the step started from, and capturing it NOW yields the
            # missing restore point.  If the state is unreadable (a
            # donated first dispatch already consumed the buffers),
            # snapshot_state raises and the original transient
            # propagates from _attempt.
            cur = self._window[-1] if self._window else None
            log.warning(
                "supervisor: first-step transient with no snapshot — "
                "capturing the post-attempt live state as the restore "
                "point.  This assumes the failed attempt mutated "
                "nothing (true for the wired fault sites, which fire "
                "pre-mutation, and for whole-step dispatch, whose "
                "donated buffers become unreadable on partial "
                "execution); a fused-path transient landing MID-update "
                "sequence would bake the partial state into the "
                "baseline")
            try:
                self._maybe_snapshot()  # clears the window on capture
            except Exception as e:  # noqa: BLE001 — deleted donated buffers
                raise MXNetError(
                    "supervisor cannot retry the first step: the live "
                    f"state is unreadable after the failed attempt ({e})"
                ) from e
            if self._snap is None:
                raise MXNetError(
                    "supervisor retry without a snapshot — parameters "
                    "are still deferred-initialized after the failed "
                    "attempt")
            if cur is not None and not self._window:
                # the in-flight step's batch must stay in the replay
                # window: the fresh snapshot predates it
                self._window.append(cur)
            return
        if _metrics.ENABLED:
            _metrics.SUPERVISOR_REWINDS.inc(reason="retry")
        # the restore + window replay is re-done work, not progress:
        # its whole wall-clock books as retry_replay badput, and any
        # mx.trainer.step spans recorded inside are suppressed so replayed
        # steps don't double-count as goodput (docs/goodput.md)
        with _goodput.replay_scope("retry_replay"):
            self._restore_snapshot()
            for rargs, rkw in self._window[:-1]:
                self._execute(rargs, rkw)

    # -- stall-guarded execution ---------------------------------------------
    def _ensure_worker(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            return
        # SimpleQueue: C-implemented put/get — the per-step handoff is
        # the supervisor's main steady-state cost (the <=2% budget)
        self._work_q = _queue.SimpleQueue()
        self._result_q = _queue.SimpleQueue()
        self._worker = threading.Thread(
            target=self._worker_loop, name="mxt-supervisor-step",
            daemon=True)
        self._worker.start()

    def _worker_loop(self) -> None:
        while True:
            job = self._work_q.get()
            if job is None:
                return
            fn, args, kw = job
            try:
                self._result_q.put(("ok", fn(*args, **kw)))
            except BaseException as e:  # noqa: BLE001 — carried to caller
                self._result_q.put(("err", e))

    def _stall_timeout(self) -> Optional[float]:
        """The current step deadline: ``stall_factor`` × the warmed
        EWMA, floored at ``stall_min_s``.  None (wait forever) until
        the supervisor's OWN measurements warm — this supervisor's
        first steps include compilation, which has no baseline, and a
        long-lived process's flight EWMA (warmed on a DIFFERENT
        trainer's steps) must not arm a deadline against them.  Once
        armed, the flight recorder's ``mx.trainer.step``/``whole_step``
        watch EWMAs can only RAISE the deadline (they see the same
        steps plus whatever else shares the phase — the conservative
        direction)."""
        if self._ewma_n < _EWMA_WARMUP:
            return None
        ewma = self._ewma
        for phase in _STEP_PHASES:
            fe = _flight.watch_ewma(phase) if _flight.ENABLED else None
            if fe is not None and fe > ewma:
                ewma = fe
        return max(self.stall_min_s, self.stall_factor * ewma)

    def _execute(self, args, kw):
        t0 = time.perf_counter()
        self._in_step = True
        try:
            if self.stall_factor <= 0:
                # stall watchdog off: run inline — no worker thread, no
                # per-step context switches.  The hop costs a fixed
                # ~0.1-0.2 ms/step (two switches), invisible against
                # real accelerator steps but measurable against ms-scale
                # CPU ones; MXNET_SUPERVISE_STALL_FACTOR=0 is the
                # documented knob when that matters more than unhanging
                # a wedged device (retry + divergence still active)
                status, payload = "ok", self._step_fn(*args, **kw)
            else:
                self._ensure_worker()
                timeout = self._stall_timeout()
                self._work_q.put((self._step_fn, args, kw))
                try:
                    status, payload = self._result_q.get(timeout=timeout)
                except _queue.Empty:
                    self._on_stall(timeout)
        finally:
            self._in_step = False
        dur = time.perf_counter() - t0
        self._ewma = dur if self._ewma_n == 0 else \
            _EWMA_ALPHA * dur + (1.0 - _EWMA_ALPHA) * self._ewma
        self._ewma_n += 1
        if status == "err":
            raise payload
        return payload

    def _on_stall(self, timeout: float):
        self._stalled = (f"step {self._step_count} exceeded "
                         f"{timeout:.1f}s")
        if _metrics.ENABLED:
            _metrics.SUPERVISOR_WATCHDOG_TRIPS.inc(kind="stall")
        report = _res.post_mortem(
            "stall", step=self._step_count,
            detail={"timeout_s": round(timeout, 3),
                    "ewma_s": round(self._ewma, 6),
                    "stall_factor": self.stall_factor})
        if _goodput.ENABLED:
            # the wedged step never completes, so no span records it —
            # the watchdog's whole wait is the stall's badput
            _goodput.attribute("stall", timeout)
        if _journal.ENABLED:
            _journal.emit("supervisor_stall", step=self._step_count,
                          durable=True, timeout_s=round(timeout, 3),
                          report_path=(report or {}).get("report_path"),
                          flight_path=(report or {}).get("flight_path"))
        raise TrainingStalledError(
            f"training step {self._step_count} still running after "
            f"{timeout:.1f}s (EWMA {self._ewma * 1e3:.1f} ms x factor "
            f"{self.stall_factor:g}, floor {self.stall_min_s:g}s) — "
            "device presumed wedged; post-mortem "
            f"{(report or {}).get('report_path')}",
            step=self._step_count, timeout_s=timeout, report=report)

    # -- divergence watchdog -------------------------------------------------
    def _check_divergence(self, out):
        if self.check_every < 1 or \
                self._step_count % self.check_every != 0:
            return out
        if _finite(out):
            self._nonfinite = 0
            return out
        self._nonfinite += 1
        if self._nonfinite < self.diverge_patience:
            return out
        failing = self._step_count - 1  # the step just completed
        if _metrics.ENABLED:
            _metrics.SUPERVISOR_WATCHDOG_TRIPS.inc(kind="divergence")
        report = _res.post_mortem(
            "divergence", step=failing,
            detail={"consecutive_nonfinite": self._nonfinite,
                    "patience": self.diverge_patience})
        if _journal.ENABLED:
            _journal.emit("supervisor_divergence", step=failing,
                          durable=True, action=self.on_diverge,
                          report_path=(report or {}).get("report_path"),
                          flight_path=(report or {}).get("flight_path"))
        self._nonfinite = 0
        if self.on_diverge == "rewind" and self._snap is not None \
                and self._can_restore:
            if _metrics.ENABLED:
                _metrics.SUPERVISOR_REWINDS.inc(reason="divergence")
            log.warning(
                "supervisor: divergence at step %d — rewinding to the "
                "snapshot from step %d (MXNET_SUPERVISE_ON_DIVERGE="
                "rewind); post-mortem %s", failing, self._snap[0],
                (report or {}).get("report_path"))
            with _goodput.replay_scope("rewind"):
                self._restore_snapshot()
            # continuing FORWARD with fresh data from the snapshot
            # state: the window's batches produced the divergence, so
            # they are deliberately not replayed
            self._window.clear()
            return out
        raise DivergenceError(
            f"loss was nonfinite for {self.diverge_patience} consecutive "
            f"checked steps (last: step {failing}) — post-mortem "
            f"{(report or {}).get('report_path')}",
            step=failing, report=report)

    # -- preemption ----------------------------------------------------------
    def install_preemption_hook(self, manager, **kw) -> Callable[[], None]:
        """The PR 5 SIGTERM hook, fired through the supervisor: the
        emergency save uses the last rolling host snapshot when the
        signal lands MID-STEP (live device buffers may be half-updated
        or donated at that instant) and a fresh consistent pack
        otherwise.  State is saved in ``save_trainer`` key packing, so
        ``restore_trainer``/``restore_or_initialize`` resume it.  The
        hook also dumps the flight ring (``reason="preempt"``) — see
        checkpoint/hooks.py.  Returns the uninstaller."""
        from ..checkpoint.hooks import install_preemption_hook

        def state_fn():
            if self._in_step and self._snap is not None:
                step, snap = self._snap
                return step, {name: payload
                              for name, (kind, payload) in snap.items()}
            if self._in_step:
                log.warning("preemption landed mid-step with no snapshot "
                            "yet — saving live state (may be mid-update)")
            return self._step_count, self._pack_live_state()

        return install_preemption_hook(manager, state_fn, **kw)

    # -- lifecycle -----------------------------------------------------------
    @property
    def stalled(self) -> Optional[str]:
        """Poison reason after a stall (None = healthy)."""
        return self._stalled

    def stats(self) -> dict:
        return {
            "enabled": ENABLED,
            "steps": self._step_count,
            "snapshot_step": self._snap[0] if self._snap else None,
            "window": len(self._window),
            "nonfinite_streak": self._nonfinite,
            "stalled": self._stalled,
            "ewma_ms": round(self._ewma * 1e3, 3),
        }

    def close(self) -> None:
        """Stop the step executor thread (idempotent).  A poisoned
        (stalled) supervisor's worker is left behind on purpose — it is
        blocked inside the wedged dispatch."""
        w, q = self._worker, self._work_q
        self._worker = None
        if w is None or not w.is_alive():
            return
        if self._stalled is None and q is not None:
            q.put(None)
            w.join(timeout=5)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass
