"""Whole-step compilation + mixed precision for the Gluon hot loop.

PRs 2-3 left a dense hybridized model's training step at 3-4
steady-state XLA dispatches: fwd (CachedOp), bwd (vjp program),
bucketed allreduce, fused update.  Every remaining boundary is a
Python round trip to the device and a lost cross-stage fusion
opportunity — the TVM (arxiv 1802.04799) / TPU-MLIR (arxiv 2210.15016)
observation that the next hot-path win is compiling MORE of the step.

``WholeStepCompiler`` traces forward + loss + backward + bucketed
reduce (+ 2-bit quantize/dequantize against the Trainer's flat
error-feedback residuals) + the ``FusedUpdater`` optimizer math into
ONE ``jax.jit`` program with parameters, optimizer state, residuals,
aux state, and loss-scaler state DONATED: a steady-state training step
is **1 XLA dispatch** regardless of parameter count.  Opt-in via
``MXNET_WHOLE_STEP=1``; any unsupported construct — sparse params,
``update_on_kvstore``, multi-host kvstore, custom/non-differentiable
ops, non-``write`` grad_req, multi-device copies, a loss that cannot
compose symbolically — falls back to the PR 2 fused path (<= 4
dispatches) with a single warning.

Mixed precision rides the same program (``MXNET_AMP=bf16|fp16``):
matmul / conv / deconv compute autocasts to the low-precision dtype
inside the compiled step (per-op cast-in/cast-out over
``AMP_COMPUTE_OPS``; f32 master weights and optimizer state never
leave f32, so the backward's matmuls run low-precision too via the
cast vjp).  ``fp16`` adds dynamic loss scaling: scale/unscale,
nonfinite detection, skip-step, and scale growth/backoff
(``MXNET_LOSS_SCALE_INIT`` / ``MXNET_LOSS_SCALE_WINDOW``) all trace
into the same program; the scaler state is device-resident, donated,
and rides ``Trainer.save_states`` / ``load_states`` (and therefore
``mx.checkpoint.save_trainer``).

Numerics: the f32 whole-step program runs the exact op sequence of the
fused path (same GraphPlan, same bucket layout, same
quantize/dequantize math, same fused_step) — tests/test_wholestep.py
pins bitwise agreement over 5 steps on its nets.  (XLA may fuse the
single program differently than the fused path's separate programs, so
arbitrary models get f32 ulp-level agreement, not a bitwise
guarantee.)  Under fp16 skip-steps the
python-side ``num_update`` (lr schedules) still advances while the
device-side bias-correction counter ``t`` advances only on applied
steps — the numerically correct behavior for Adam-family optimizers.
"""
from __future__ import annotations

import itertools
import logging

import jax
import jax.numpy as jnp
import numpy as _np

from ..analysis import hot_path
from ..analysis import sanitizer as _san
from ..base import MXNetError, getenv
from ..faultinject import InjectedFault as _InjectedFault
from ..faultinject import fire as _fi_fire
from ..ndarray import NDArray
from ..resilience import DeviceUnavailableError as _DeviceUnavailableError
from ..observability import journal as _journal
from ..observability import introspect as _introspect
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from ..observability.tracing import span
from ..optimizer import HyperDeviceCache as _HyperDeviceCache
from ..optimizer import cast_like as _cast_like
from .. import symbol as sym_mod
from ..symbol.graph import GraphPlan
from .. import autograd
from .parameter import DeferredInitializationError

logger = logging.getLogger("mxnet_tpu.gluon.wholestep")

# internal graph-input names for the step's data/label feeds — namespaced
# so they can never collide with a parameter name
_DATA = "__wholestep_data__"
_LABEL = "__wholestep_label__"

# ops whose compute autocasts to the low-precision dtype under MXNET_AMP
# (the flops carriers; everything else — norms, softmax, loss, optimizer
# — stays f32).  Inputs flagged f32-forced by the op registry
# (Operator.f32_inputs) are never cast.
AMP_COMPUTE_OPS = frozenset({
    "FullyConnected", "Convolution", "Deconvolution", "dot", "batch_dot",
})

_LP_DTYPES = {"bf16": jnp.bfloat16, "fp16": jnp.float16}

# install the donation-noise filter ONCE per process, not per compiler:
# repeated unguarded filterwarnings() calls grow warnings.filters without
# bound (same expected-noise rationale as CachedOp's filter in block.py)
_donation_filter_installed = False


def _install_donation_filter():
    global _donation_filter_installed
    if not _donation_filter_installed:
        import warnings as _warnings
        _warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        _donation_filter_installed = True

# process-unique id per traced graph, used in the compiled-program cache
# key: the cache (FusedUpdater._fn_cache) outlives any one compiler, so
# keying on id(plan) could alias a NEW graph onto a dead one's recycled
# address and silently run the wrong program
_PLAN_UID = itertools.count(1)
_SCALE_GROWTH = 2.0
_SCALE_BACKOFF = 0.5
_SCALE_MAX = float(2 ** 24)


def amp_policy() -> str:
    """Resolve MXNET_AMP to a dtype policy ("f32" | "bf16" | "fp16")."""
    raw = str(getenv("MXNET_AMP", "")).strip().lower()
    if raw in ("", "0", "off", "none", "f32", "fp32", "float32"):
        return "f32"
    if raw in ("bf16", "bfloat16"):
        return "bf16"
    if raw in ("fp16", "f16", "float16"):
        return "fp16"
    raise MXNetError(
        f"MXNET_AMP={raw!r} not understood (use bf16, fp16, or off)")


def _amp_overrides(plan: GraphPlan, lp):
    """step_overrides for GraphPlan.run that autocast AMP_COMPUTE_OPS:
    f32 float inputs cast to ``lp`` for the op's compute, outputs cast
    back to f32 so the surrounding graph (activations, norms, loss) is
    unchanged.  jax.vjp of the cast pair makes the op's BACKWARD
    matmuls low-precision too, with f32 gradients delivered to the
    optimizer."""
    over = {}
    for si, step in enumerate(plan.steps):
        if step.op.name not in AMP_COMPUTE_OPS:
            continue
        keep32 = frozenset(step.op.f32_inputs)

        def _run(p, ins, _op=step.op, _keep=keep32):
            cast = [a.astype(lp)
                    if (i not in _keep and a is not None
                        and getattr(a, "dtype", None) == jnp.float32)
                    else a
                    for i, a in enumerate(ins)]
            out = _op.fn(p, *cast)
            out = out if isinstance(out, tuple) else (out,)
            return tuple(o.astype(jnp.float32)
                         if getattr(o, "dtype", None) == lp else o
                         for o in out)

        over[si] = _run
    return over


class _Ineligible(RuntimeError):
    """Construct the whole-step tracer cannot compile — fall back."""


class _AmpIneligible(_Ineligible):
    """MXNET_AMP cannot apply to this model — a CONFIG-dependent
    condition, so it falls back per-step (re-checked on every call)
    instead of permanently demoting a compiler whose f32 program may be
    built and working; unsetting MXNET_AMP resumes whole-step."""


class _ShardIneligible(_Ineligible):
    """THIS step cannot dispatch sharded (e.g. a ragged final batch
    that does not divide the mesh's data axis) — a per-batch condition,
    handled like _AmpIneligible: fall back for this call only, the next
    full batch runs the sharded program again."""


def _sel(finite, new, old):
    """Per-leaf where(finite, new, old) tolerant of None / nested
    tuple states (the fp16 skip-step select)."""
    if new is None or old is None:
        return new
    if isinstance(new, (tuple, list)):
        return type(new)(_sel(finite, a, b) for a, b in zip(new, old))
    return jnp.where(finite, new, old)


# the dtype-preservation rule is SHARED with FusedUpdater.update_all
# (optimizer.cast_like) — the whole-step/fused bitwise-parity contract
# depends on both paths casting identically


class WholeStepCompiler:
    """One donated XLA program per Gluon training step.

    ::

        stepper = mx.gluon.wholestep.WholeStepCompiler(net, loss_fn,
                                                       trainer)
        for x, y in batches:
            loss = stepper.step(x, y)          # per-sample loss NDArray

    ``step`` runs the single compiled whole-step program when
    ``MXNET_WHOLE_STEP=1`` and the model/trainer are eligible, and the
    classic record/backward/``Trainer.step`` fused path otherwise —
    the returned loss and the training trajectory are identical in f32
    either way.  ``net`` must be a ``HybridBlock`` (hybridized or not;
    the compiler traces its own graph) and ``loss_fn`` a HybridBlock
    loss taking ``(pred, label)``.
    """

    def __init__(self, net, loss_fn, trainer, mesh=None):
        self.net = net
        self.loss_fn = loss_fn
        self.trainer = trainer
        # GSPMD mesh: explicit arg > the trainer's mesh > the ambient
        # parallel.mesh.current_mesh() (which itself reads
        # MXNET_MESH_BATCH/MODEL).  Resolved once at build time so the
        # frozen program and its committed placements agree; None keeps
        # the replicated path bit-for-bit untouched.
        self._mesh_arg = mesh
        self.mesh = None
        self._built = None
        self._fallback_reason = None  # permanent-fallback explanation
        self._warned = False
        # lr/wd last-value cache + device-resident step counter: the
        # SAME implementation FusedUpdater.hyper_arrays uses (bitwise
        # parity between step modes depends on identical seeding)
        self._hyper_cache = _HyperDeviceCache()
        # once the program has executed successfully, runtime failures
        # (OOM included) must PROPAGATE, not silently fall back — the
        # failed call may already have invalidated donated buffers, so
        # re-running the step eagerly is not safe
        self._ran = False
        self._amp_warned = False       # AMP-ineligible model, warn once
        self._amp_env_checked = False  # AMP-without-whole-step, warn once
        self._shard_warned = False     # per-step shard fallback, once
        self._mesh_comp_warned = False  # compression off on mesh, once
        # introspection captures done, per (program cache key, data
        # shape) — a new shape re-notes so the recorded flops track the
        # running batch size
        self._noted_keys = set()
        # backends without real donation (CPU) warn per trace; the user
        # opted into best-effort donation, so this is expected noise
        _install_donation_filter()

    # -- public entry --------------------------------------------------------
    @hot_path
    def step(self, data, label, batch_size=None):
        """One full training step on (data, label); returns the loss
        NDArray (per-sample, exactly what ``loss_fn(net(data), label)``
        returns on the fallback path).  Steady state: 1 XLA dispatch
        when whole-step is active, <= 4 on the fallback path."""
        bs = batch_size if batch_size is not None else int(data.shape[0])
        if self._fallback_reason is not None:
            return self._fallback(data, label, bs)
        if not getenv("MXNET_WHOLE_STEP", False):
            self._warn_amp_without_wholestep()
            return self._fallback(data, label, bs)
        if autograd.is_recording():
            raise MXNetError(
                "WholeStepCompiler.step() must not be called inside "
                "autograd.record() — it manages forward/backward itself")
        policy = amp_policy()
        try:
            built = self._ensure_built()
            return self._run(built, data, label, bs, policy)
        except DeferredInitializationError:
            # shapes materialize on the eager path; retry the build on
            # the next step
            return self._fallback(data, label, bs)
        except _AmpIneligible as e:
            # config-dependent, NOT permanent: re-checked every step, so
            # unsetting MXNET_AMP resumes the whole-step program
            if not self._amp_warned:
                logger.warning(
                    "MXNET_AMP requested but %s — running the fused f32 "
                    "path while the policy is set", e)
                self._amp_warned = True
            return self._fallback(data, label, bs)
        except _ShardIneligible as e:
            # per-batch, NOT permanent: a ragged final batch runs the
            # fused path once; the next full batch dispatches sharded
            if not self._shard_warned:
                logger.warning(
                    "sharded whole-step skipped for this batch (%s) — "
                    "running the fused path for it", e)
                self._shard_warned = True
            return self._fallback(data, label, bs)
        except _Ineligible as e:
            self._note_fallback(str(e))
            return self._fallback(data, label, bs)
        except Exception as e:  # noqa: BLE001 — tracing arbitrary user graphs
            if self._ran or self._is_execution_failure(e) \
                    or self._is_transient(e):
                # runtime failure (e.g. the typed OOM that
                # memory.oom_guard re-raises after its post-mortem): the
                # counters were rolled back by _run, but the failed call
                # may have consumed donated buffers — eagerly retrying
                # could read dead arrays, and the user must see the
                # error.  Applies on the FIRST call too: jit executes
                # (and donates) right after tracing, so an
                # execution-typed error means buffers were at risk even
                # though _ran is still False
                raise
            self._note_fallback(f"{type(e).__name__}: {e}")
            return self._fallback(data, label, bs)

    @staticmethod
    def _is_execution_failure(e: Exception) -> bool:
        """True when the exception came from EXECUTING the compiled
        program (device OOM, XLA runtime) rather than from tracing it —
        execution implies the donated buffers were in play, so eager
        fallback is unsafe; trace failures happen before donation and
        may fall back freely."""
        if isinstance(e, (_memory.DeviceMemoryError,
                          _memory.HBMBudgetError)):
            return True
        # injected faults and transient device losses (the resilience
        # classes' "transient") must NEVER demote the compiler
        # to a permanent fused fallback: the condition is recoverable —
        # propagate so a TrainingSupervisor (or the user) can restore
        # state and retry the same whole-step program
        if isinstance(e, (_InjectedFault, _DeviceUnavailableError)):
            return True
        if type(e).__name__ == "XlaRuntimeError":
            return True
        return "RESOURCE_EXHAUSTED" in str(e) or "UNAVAILABLE" in str(e)

    @staticmethod
    def _is_transient(e: Exception) -> bool:
        """The resilience module's transient class (plain OSError /
        ConnectionError / timeout included): RECOVERABLE conditions
        must propagate — even on the first call, before ``_ran`` —
        never permanently demote the compiler to the fused fallback."""
        from ..resilience import TRANSIENT, classify
        return classify(e) is TRANSIENT

    __call__ = step

    @property
    def active(self) -> bool:
        """True once a whole-step program has been built and no
        permanent fallback was taken."""
        return self._built is not None and self._fallback_reason is None

    @property
    def fallback_reason(self):
        return self._fallback_reason

    # -- fallback (the PR 2 fused path) --------------------------------------
    def _fallback(self, data, label, batch_size):
        # the fused/legacy path always runs f32 optimizer math — clear
        # any sticky whole-step AMP policy so update_all never keys
        # (and loudly "recompiles") under a precision it never traced
        for u in getattr(self.trainer, "_updaters", None) or []:
            if getattr(u, "dtype_policy", "f32") != "f32":
                u.dtype_policy = "f32"
        if self.mesh is not None and self.mesh.size > 1:
            # params already committed to the mesh: replicate the batch
            # onto it so the eager CachedOp jit sees ONE device set (a
            # ragged _ShardIneligible batch lands here; every device
            # computes the full batch — slower, but correct)
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            from ..ndarray import NDArray as _ND
            repl = NamedSharding(self.mesh, PartitionSpec())
            data = _ND(jax.device_put(data._data, repl), data.context)  # graft-lint: disable=memory-hygiene
            label = _ND(jax.device_put(label._data, repl), label.context)  # graft-lint: disable=memory-hygiene
        with autograd.record():
            out = self.net(data)
            loss = self.loss_fn(out, label)
        loss.backward()
        self.trainer.step(batch_size)
        return loss

    def _warn_amp_without_wholestep(self) -> None:
        """MXNET_AMP only exists inside the whole-step program; setting
        it without MXNET_WHOLE_STEP=1 silently trains f32 — say so."""
        if self._amp_env_checked:
            return
        self._amp_env_checked = True
        try:
            policy = amp_policy()
        except MXNetError:
            return
        if policy != "f32":
            logger.warning(
                "MXNET_AMP=%s is set but MXNET_WHOLE_STEP is not enabled "
                "— autocast and loss scaling only exist inside the "
                "whole-step program; training runs full f32", policy)

    def _note_fallback(self, reason: str) -> None:
        self._fallback_reason = reason
        if not self._warned:
            try:
                policy = amp_policy()
            except MXNetError:
                policy = "f32"
            amp_note = "" if policy == "f32" else (
                f"; MXNET_AMP={policy} is INERT on the fallback path — "
                "training runs full f32 with no loss scaling")
            logger.warning(
                "MXNET_WHOLE_STEP=1 requested but this model/trainer is "
                "not whole-step compilable (%s) — using the fused "
                "multi-program path%s", reason, amp_note)
            self._warned = True

    # -- build ---------------------------------------------------------------
    def _ensure_built(self):
        if self._built is not None:
            return self._built
        tr = self.trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        self._check_trainer(tr)
        from ..parallel import mesh as _pmesh
        self.mesh = _pmesh.resolve_mesh(
            self._mesh_arg if self._mesh_arg is not None
            else getattr(tr, "_mesh", None))
        plan, out_sym = self._trace_graph()
        built = self._bind_graph(tr, plan)
        built["symbol"] = out_sym  # hold the graph alive (id-keyed cache)
        self._built = built
        return built

    def _check_trainer(self, tr) -> None:
        from ..optimizer import FusedUpdater
        if tr._update_on_kvstore:
            raise _Ineligible("update_on_kvstore trainers push per key "
                              "through the kvstore")
        if not tr._fused:
            raise _Ineligible("MXNET_FUSED_TRAINER=0 pins the legacy path")
        upd = tr._updaters[0]
        if not isinstance(upd, FusedUpdater) or \
                not getattr(upd.optimizer, "fused", False):
            raise _Ineligible(
                f"optimizer {type(upd.optimizer).__name__} has no "
                "fused_step")
        if tr._kv is not None and tr._kv.num_workers > 1:
            raise _Ineligible("multi-host kvstore collectives are not "
                              "jit-inlinable yet")
        for p in tr._params:
            st = getattr(p, "_grad_stype", "default")
            if st not in ("default", "row_sparse"):
                raise _Ineligible(f"grad_stype={st!r} parameter {p.name}")
            # row_sparse params are eligible (ISSUE 20) but validate
            # against the traced graph in _bind_graph: the weight must
            # be a pure sparse_grad Embedding table fed ids straight
            # from the data input, with row-gatherable optimizer state
            if p.grad_req not in ("write", "null"):
                raise _Ineligible(
                    f"grad_req={p.grad_req!r} on {p.name} (vjp gives "
                    "write semantics)")
            if p.grad_req != "null" and len(p.list_data()) != 1:
                raise _Ineligible(f"multi-device copies of {p.name}")

    def _trace_graph(self):
        """Compose net + loss symbolically into one GraphPlan (the same
        machinery hybridize() uses, extended through the loss)."""
        dsym = sym_mod.Variable(_DATA)
        lsym = sym_mod.Variable(_LABEL)
        out = self.net(dsym)
        if isinstance(out, (list, tuple)):
            if len(out) != 1:
                raise _Ineligible("multi-output networks")
            out = out[0]
        loss_sym = self.loss_fn(out, lsym)
        if isinstance(loss_sym, (list, tuple)):
            if len(loss_sym) != 1:
                raise _Ineligible("multi-output losses")
            loss_sym = loss_sym[0]
        plan = GraphPlan(loss_sym)
        for s in plan.steps:
            if s.op.name == "Custom" or not s.op.differentiable:
                raise _Ineligible(
                    f"op {s.op.name} is not whole-step traceable")
        return plan, loss_sym

    def _bind_graph(self, tr, plan):
        """Map graph inputs onto trainer parameters; freeze the live
        order, bucket layout, and updater keys the program will use —
        all IDENTICAL to the fused path's so optimizer/residual state is
        interchangeable between the two."""
        params_by_name = {p.name: p for p in tr._params}
        gset, cnames = set(), []
        for n in plan.arg_names:
            if n in (_DATA, _LABEL):
                continue
            p = params_by_name.get(n)
            if p is None:
                raise _Ineligible(
                    f"graph input {n!r} is not a trainer parameter")
            (gset.add(n) if p.grad_req != "null" else cnames.append(n))
        for n in plan.aux_names:
            if n not in params_by_name:
                raise _Ineligible(
                    f"auxiliary state {n!r} is not a trainer parameter")
        if not gset:
            raise _Ineligible("no trainable parameters in the graph")
        # live order = trainer param order, exactly like Trainer._step
        live = [(i, p) for i, p in enumerate(tr._params)
                if p.grad_req != "null"]
        missing = [p.name for _, p in live if p.name not in gset]
        if missing:
            raise _Ineligible(
                f"trainable parameters unused by the graph: {missing[:3]}"
                " (their gradients would go stale)")
        idx = tuple(i for i, _ in live)
        gnames = [p.name for _, p in live]
        sig = tuple((tuple(p.data().shape), str(p.data().dtype))
                    for _, p in live)
        # sparse-embedding params (ISSUE 20): a row-sparse grad is
        # whole-step eligible only when the traced graph proves the
        # rows-only rewrite is exact — the weight feeds nothing but ONE
        # sparse_grad Embedding step whose ids come straight from the
        # data input (so the in-program unique/scatter sees every
        # touched row)
        sga = plan.sparse_grad_args()
        embed = {}
        for _, p in live:
            if getattr(p, "_grad_stype", "default") == "default":
                continue
            uses = sga.get(p.name)
            if not uses:
                raise _Ineligible(
                    f"row-sparse parameter {p.name} is not a pure "
                    "sparse_grad Embedding weight")
            if len(uses) != 1 or uses[0][1] != _DATA:
                raise _Ineligible(
                    f"sparse embedding {p.name} must be looked up exactly "
                    "once, with ids straight from the data input")
            # shape[0]/step index are host ints already — no device read
            embed[p.name] = {"step": uses[0][0],
                             "vocab": p.data().shape[0]}
        # the bucketer (and so compression residuals) covers DENSE
        # params only — row-sparse grads never flatten into buckets, on
        # this path or the trainer's fused path, so residual layouts
        # stay interchangeable between the two
        dlive = [(i, p) for i, p in live if p.name not in embed]
        dsig = tuple((tuple(p.data().shape), str(p.data().dtype))
                     for _, p in dlive)
        didx = tuple(i for i, _ in dlive)
        bk = tr._ensure_bucketer(dsig, didx) if dlive else None
        upd = tr._updaters[0]
        if self.mesh is not None:
            # annotate BEFORE the updater seeds optimizer state: the
            # zeros_like slots inherit each param's committed
            # NamedSharding, so momentum/adam state shards exactly like
            # its weight.  Trainable >=2-D tensors take the model-axis
            # default unless the user pinned a spec via set_sharding;
            # consts and aux (BN running stats) replicate — XLA then
            # inserts whatever collectives the annotated dataflow needs.
            from ..parallel import mesh as _pmesh
            from jax.sharding import PartitionSpec as _P
            for _, p in live:
                spec = p.sharding_spec
                if spec is None:
                    # a parameter may pin its own layout rule (the
                    # sharded-embedding row partition along
                    # MXNET_EMBED_SHARD_AXIS) ahead of the generic
                    # largest-dim default
                    hint = getattr(p, "_spec_hint", None)
                    spec = hint(self.mesh) if hint is not None else \
                        _pmesh.default_param_spec(
                            self.mesh, tuple(p.data().shape))
                p.set_sharding(self.mesh, spec)
            for n in itertools.chain(cnames, plan.aux_names):
                p = params_by_name[n]
                spec = p.sharding_spec
                p.set_sharding(self.mesh,
                               spec if spec is not None else _P())
        for i, p in live:
            upd._ensure_state(i, p.data())
            if self.mesh is not None:
                # states may predate the sharding (e.g. the first step
                # fell back on DeferredInitializationError and the fused
                # path seeded them on one device) — conform them to the
                # weight's committed NamedSharding so the donated program
                # sees one placement
                from ..optimizer import _conform_state_sharding
                upd.states[i] = _conform_state_sharding(
                    upd.states[i], p.data())
            if p.name in embed and not upd._rowable_state(
                    upd.states[i], p.data().shape[0]):
                raise _Ineligible(
                    f"optimizer state for embedding {p.name} is not "
                    "row-gatherable (leaves must be table-shaped or "
                    "None)")
        return {"plan": plan, "idx": idx, "gnames": gnames,
                "cnames": tuple(cnames),
                "aux_names": tuple(plan.aux_names),
                "params": params_by_name, "bk": bk, "sig": sig,
                "embed": embed, "uid": next(_PLAN_UID)}

    # -- the compiled program ------------------------------------------------
    def _make_ftrain(self, built, opt_, policy, thr, window):
        """The raw (un-jitted) whole-step function:

        ftrain(gparams, states, residuals, scaler, aux, consts, data,
               label, key, lrs, wds, ts)
          -> (loss, new_aux, new_params, new_states, new_residuals,
              new_scaler, new_ts)

        ``_build_fn`` jits it with donation for the 1-dispatch step;
        ``autotune.SuperStepCompiler`` wraps the SAME function in a
        ``lax.scan`` over K batches (the scan body must be the exact op
        sequence of one whole step — the superstep/whole-step bitwise
        parity contract hangs on sharing this tracer)."""
        plan = built["plan"]
        gnames = built["gnames"]
        idx = built["idx"]
        bk = built["bk"]
        embed = built.get("embed") or {}
        dnames = [n for n in gnames if n not in embed]
        lp = _LP_DTYPES.get(policy)
        overrides = _amp_overrides(plan, lp) if lp is not None else None
        use_comp = thr is not None and bk is not None and bool(dnames)
        use_scaler = policy == "fp16"
        flatten_inline = bk.flatten_inline if use_comp else None
        unflatten_inline = bk.unflatten_inline if use_comp else None
        if use_comp:
            from ..kvstore import reduce_buckets_inline
        fused_step = opt_._fused_step_mp

        def ftrain(gparams, states, residuals, scaler, aux, consts,
                   data, label, key, lrs, wds, ts):
            # -- sparse-embedding pre-pass (ISSUE 20): batch ids ->
            # shared sorted-unique rows.  jnp.unique pads its static
            # output with fill_value=vocab — a POSITIVELY out-of-range
            # sentinel every mode="drop" scatter below discards (never
            # -1, which .at[] would wrap onto the last real row).
            elook = {}
            for n, info in embed.items():
                vocab = info["vocab"]
                ids = jnp.clip(data.astype(jnp.int32), 0,
                               vocab - 1).ravel()
                uids, uinv = jnp.unique(ids, size=ids.shape[0],
                                        fill_value=vocab,
                                        return_inverse=True)
                elook[n] = (uids, jnp.ravel(uinv))
            # one zero dummy per embedding, shaped like the lookup
            # OUTPUT (tokens x dim, not vocab x dim) — the executor's
            # rows-only rewrite idiom: differentiating the dummy yields
            # the per-token cotangent rows, so the table's O(vocab)
            # dense cotangent never materializes in the program
            dums = {n: jnp.zeros(tuple(data.shape)
                                 + tuple(gparams[n].shape[1:]),
                                 gparams[n].dtype) for n in embed}
            dparams = {n: gparams[n] for n in dnames}

            def fwd(p, dm):
                m = dict(consts)
                m[_DATA] = data
                m[_LABEL] = label
                m.update(p)
                ov = dict(overrides) if overrides else {}
                for n, info in embed.items():
                    # the weight var must still resolve (plan.run binds
                    # every in_ref before consulting overrides), but it
                    # is NOT a vjp primal — its gradient flows through
                    # the dummy instead
                    m[n] = gparams[n]

                    def _lookup(params, ins, _n=n):
                        vsz = ins[1].shape[0]
                        iid = jnp.clip(ins[0].astype(jnp.int32), 0,
                                       vsz - 1)
                        return (jnp.take(jax.lax.stop_gradient(ins[1]),
                                         iid, axis=0) + dm[_n],)

                    ov[info["step"]] = _lookup
                outs, new_aux = plan.run(m, aux, key, True,
                                         step_overrides=ov or None)
                total = jnp.sum(outs[0].astype(jnp.float32))
                if use_scaler:
                    total = total * scaler["scale"]
                return total, (outs[0], new_aux)

            _, vjp_fn, (loss, new_aux) = jax.vjp(fwd, dparams, dums,
                                                 has_aux=True)
            gd, gdum = vjp_fn(jnp.asarray(1.0, jnp.float32))
            glist = [gd[n] for n in dnames]
            # segment-sum the per-token rows onto the unique ids — the
            # same unique + .at[inv].add the eager rsp deposit
            # (_dedup_rows) runs, so the two paths' row grads match
            # bitwise in f32
            egrads = {}
            for n in embed:
                uids, uinv = elook[n]
                rows = gdum[n].reshape((uinv.shape[0],)
                                       + tuple(gparams[n].shape[1:]))
                egrads[n] = jnp.zeros(rows.shape, rows.dtype) \
                    .at[uinv].add(rows)
            finite = None
            if use_scaler:
                inv = 1.0 / scaler["scale"]
                glist = [(g.astype(jnp.float32) * inv).astype(g.dtype)
                         for g in glist]
                egrads = {n: (g.astype(jnp.float32) * inv)
                          .astype(g.dtype) for n, g in egrads.items()}
                finite = jnp.asarray(True)
                for g in itertools.chain(glist, egrads.values()):
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(g)))
            new_res = residuals
            if use_comp:
                # literal named scopes over the non-graph step stages:
                # HLO metadata then attributes the bucketed reduce and
                # the fused optimizer math to their own per_layer()
                # rows, next to the graph nodes' layer scopes.  The
                # buckets hold DENSE grads only — row-sparse grads stay
                # rows-only and never compress
                with _introspect.layer_scope("allreduce"):
                    flats = flatten_inline(glist)
                    red, new_res, _errs = reduce_buckets_inline(
                        flats, residuals, thr)
                    glist = unflatten_inline(red)
            with _introspect.layer_scope("optimizer"):
                new_p, new_s = {}, []
                di = 0
                for k, n in enumerate(gnames):
                    if n in embed:
                        # sparse leg: gather the touched rows (weight +
                        # lazy per-row optimizer state), step them, and
                        # scatter back IN PROGRAM — the table-shaped
                        # output aliases the donated input buffer, so
                        # the update is a true in-place scatter
                        # (audit_programs checks the alias survived)
                        uids, _ = elook[n]
                        wr = jnp.take(gparams[n], uids, axis=0,
                                      mode="clip")
                        srows = jax.tree_util.tree_map(
                            lambda s: jnp.take(s, uids, axis=0,
                                               mode="clip"), states[k])
                        nwr, nsr = fused_step(idx[k], wr, egrads[n],
                                              srows, lrs[k], wds[k],
                                              ts[k])
                        new_p[n] = gparams[n].at[uids].set(
                            _cast_like(nwr, wr), mode="drop")
                        new_s.append(jax.tree_util.tree_map(
                            lambda s, r: s.at[uids].set(
                                _cast_like(r, s), mode="drop"),
                            states[k], nsr))
                        continue
                    nw, ns = fused_step(idx[k], gparams[n], glist[di],
                                        states[k], lrs[k], wds[k], ts[k])
                    di += 1
                    new_p[n] = _cast_like(nw, gparams[n])
                    new_s.append(_cast_like(ns, states[k]))
            new_scaler = scaler
            if use_scaler:
                # skip-step: a nonfinite gradient anywhere keeps params,
                # states, residuals, aux (BN running stats — an
                # overflowing batch must not poison them forever), and
                # the bias-correction counter at their pre-step values —
                # only the scaler moves (backoff)
                new_aux = {n: jnp.where(finite, a, aux[n])
                           for n, a in new_aux.items()}
                new_p = {n: jnp.where(finite, new_p[n], gparams[n])
                         for n in gnames}
                new_s = [_sel(finite, a, b) for a, b in zip(new_s, states)]
                if use_comp:
                    new_res = [jnp.where(finite, a, b)
                               for a, b in zip(new_res, residuals)]
                nts = jnp.where(finite, ts + 1, ts)
                good = jnp.where(finite, scaler["good"] + 1, 0)
                grow = good >= window
                scale = jnp.where(grow,
                                  jnp.minimum(scaler["scale"]
                                              * _SCALE_GROWTH,
                                              _SCALE_MAX),
                                  scaler["scale"])
                scale = jnp.where(finite, scale,
                                  jnp.maximum(scaler["scale"]
                                              * _SCALE_BACKOFF, 1.0))
                good = jnp.where(grow, jnp.zeros_like(good), good)
                new_scaler = {"scale": scale, "good": good}
            else:
                nts = ts + 1
            return loss, new_aux, new_p, new_s, new_res, new_scaler, nts

        return ftrain

    def _build_fn(self, built, opt_, policy, thr, window):
        """One donated jitted whole-step program: gparams/states/
        residuals/scaler/aux are DONATED — the step updates the model
        truly in place on backends with donation."""
        ftrain = self._make_ftrain(built, opt_, policy, thr, window)
        mesh = self.mesh
        if mesh is None or mesh.size <= 1:
            return jax.jit(ftrain, donate_argnums=(0, 1, 2, 3, 4))
        # GSPMD propagation is free to pick DIFFERENT shardings for the
        # updated params/states than their inputs carry — and a donated
        # buffer whose output layout differs cannot alias (donation
        # silently degrades to a copy + reshard).  Pin every donated
        # output to its input's committed NamedSharding so the alias
        # table stays complete; same-shape state leaves take their
        # weight's sharding (momentum/adam moments shard like the
        # weight), everything else replicates.
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
                   data, label, key, lrs, wds, ts):
            (loss, new_aux, new_p, new_s, new_res, new_scaler,
             nts) = ftrain(gparams, states, residuals, scaler, aux,
                           consts, data, label, key, lrs, wds, ts)
            new_p = {n: _wsc(v, psh[n] if psh[n] is not None else repl)
                     for n, v in new_p.items()}
            new_s = [_pin_state(s, psh[gnames[k]],
                                tuple(gparams[gnames[k]].shape))
                     for k, s in enumerate(new_s)]
            new_aux = {n: _wsc(v, repl) for n, v in new_aux.items()}
            new_scaler = {n: _wsc(v, repl)
                          for n, v in new_scaler.items()} \
                if isinstance(new_scaler, dict) else new_scaler
            return (loss, new_aux, new_p, new_s, new_res, new_scaler,
                    nts)

        return jax.jit(fshard, donate_argnums=(0, 1, 2, 3, 4))

    # -- per-step driver -----------------------------------------------------
    def _hyper_arrays(self, opt_, idx):
        """Device-cached lr/wd vectors + the device-resident step
        counter — ``optimizer.HyperDeviceCache``, the same
        implementation ``FusedUpdater.hyper_arrays`` uses (under fp16
        the counter advances only on applied steps).  A checkpointed
        APPLIED-step vector takes re-seed precedence: the schedule
        counts include skipped steps, so reseeding Adam's
        bias-correction t from them would diverge from the
        uninterrupted run after any skip."""
        def _pending():
            pend = getattr(self.trainer, "_applied_ts_pending", None)
            if pend is not None and pend[0] == idx:
                # consumed only when a (re)seed actually happens —
                # HyperDeviceCache calls this inside its reseed branch
                self.trainer._applied_ts_pending = None
                return pend[1]
            return None

        return self._hyper_cache.arrays(opt_, idx, pending_ts=_pending)

    def _run(self, built, data, label, bs, policy):
        tr = self.trainer
        # chaos site, fired BEFORE the schedule counters advance and
        # before any donated buffer is touched: an injected raise is a
        # cleanly-retryable failed step (the fused path fires the same
        # site in Trainer._step — exactly one per training step)
        _fi_fire("trainer.step", step=tr._step_id)
        upd = tr._updaters[0]
        opt_ = upd.optimizer
        idx = built["idx"]
        if policy != "f32" and any(d != "float32" for _, d in built["sig"]):
            raise _AmpIneligible(
                f"MXNET_AMP={policy} needs float32 master weights")
        gc = getattr(tr._kv, "_gc", None) if tr._kv is not None else None
        thr = gc.threshold if gc is not None else None
        if thr is not None and self.mesh is not None \
                and self.mesh.size > 1:
            # GSPMD supersedes the explicit 2-bit bucketed allreduce on
            # a real mesh: jit inserts the cross-shard collectives
            # itself, so compressing an in-program reduce that no
            # longer carries the cross-device traffic would change
            # numerics for nothing.  A 1-chip mesh keeps compression —
            # the bitwise-parity pin vs the replicated path covers it.
            if not self._mesh_comp_warned:
                self._mesh_comp_warned = True
                from ..parallel.mesh import mesh_signature
                logger.warning(
                    "2-bit gradient compression is disabled inside the "
                    "whole-step program on a multi-device mesh (%s) — "
                    "GSPMD collectives replace the bucketed allreduce",
                    mesh_signature(self.mesh))
            thr = None
        if built["bk"] is None:
            # every trainable param is a sparse embedding (ISSUE 20):
            # no dense buckets exist for compression to act on
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
            window = st["window"]  # a python int, set at creation
            scaler = {"scale": st["scale"], "good": st["good"]}

        opt_.rescale_grad = tr._scale / bs
        # snapshot the schedule counters: the program traces lazily on
        # its first call below, and a trace-time failure routes step()
        # to the fallback path whose Trainer.step counts the SAME step
        # again — without rollback num_update would be off by one
        # forever (lr schedules, Adam bias correction)
        prev_nu = opt_.num_update
        prev_counts = {i: opt_._index_update_count.get(i) for i in idx}
        for i in idx:
            opt_._update_count(i)
        try:
            return self._dispatch(built, opt_, upd, policy, thr, window,
                                  scaler, residuals, data, label, bs)
        except Exception:
            opt_.num_update = prev_nu
            for i, c in prev_counts.items():
                if c is None:
                    opt_._index_update_count.pop(i, None)
                else:
                    opt_._index_update_count[i] = c
            raise

    def _dispatch(self, built, opt_, upd, policy, thr, window, scaler,
                  residuals, data, label, bs):
        tr = self.trainer
        params = built["params"]
        gnames = built["gnames"]
        idx = built["idx"]
        mesh = self.mesh
        data_j, label_j = data._data, label._data
        if mesh is not None:
            from ..parallel import mesh as _pmesh
            daxis = _pmesh.data_axis(mesh)
            dsize = int(mesh.shape[daxis])
            if int(data.shape[0]) % dsize != 0:
                raise _ShardIneligible(
                    f"batch of {int(data.shape[0])} does not divide "
                    f"the mesh's {daxis} axis (size {dsize})")
            # committed batch placement: jit reads in_shardings off
            # these arrays and compiles the sharded program.  A raw
            # placement the runtime folds into the dispatch, not a
            # tracked host transfer — the 1-dispatch gate stands.
            bsh = _pmesh.batch_sharding(mesh)
            data_j = jax.device_put(data_j, bsh)  # graft-lint: disable=memory-hygiene
            label_j = jax.device_put(label_j, bsh)  # graft-lint: disable=memory-hygiene
        lrs, wds, ts, counts_t = self._hyper_arrays(opt_, idx)
        gparams = {n: params[n].list_data()[0]._data for n in gnames}
        consts = {n: params[n].list_data()[0]._data
                  for n in built["cnames"]}
        aux = {n: params[n].list_data()[0]._data
               for n in built["aux_names"]}
        if mesh is not None and mesh.size > 1:
            # a supervisor/checkpoint restore (set_states_bytes)
            # rehydrates optimizer state on the default device while
            # _load_init re-commits the weights to their NamedSharding
            # — conform the states back to their weights' placement
            # (device_put is an identity when already placed)
            from ..optimizer import _conform_state_sharding
            for j, n in enumerate(gnames):
                upd.states[idx[j]] = _conform_state_sharding(
                    upd.states[idx[j]], params[n].list_data()[0])
        svals = [upd._state_data(upd.states[i]) for i in idx]

        upd.dtype_policy = policy
        # the key's policy component carries EVERYTHING policy-derived
        # (fp16 folds the loss-scale window in): lookup_program's loud
        # recompile detection compares the policy-independent tail, so a
        # policy-derived field there would mask e.g. the f32->fp16 flip
        pol_key = policy if policy != "fp16" else f"fp16/w{window}"
        from ..parallel.mesh import mesh_signature as _mesh_sig
        msig = _mesh_sig(mesh)
        key = ("whole_step", pol_key, type(opt_).__name__,
               opt_.fused_hyper_key(), idx,
               tuple(d for _, d in built["sig"]),
               built["uid"], thr,
               built["bk"].sizes if thr is not None else None,
               jax.tree_util.tree_structure(svals), msig)
        fn = upd.lookup_program(
            key, lambda: self._build_fn(built, opt_, policy, thr,
                                        window))
        note_key = (key, tuple(data.shape), tuple(label.shape))
        if _introspect.ENABLED and note_key not in self._noted_keys:
            # once per program cache key, BEFORE the donated dispatch
            # (every argument is still live): capture the whole-step
            # program's analytical flops/bytes — the MFU numerator and
            # the per_layer() subject.  A retrace only (no XLA compile
            # unless MXNET_INTROSPECT_HLO=1), no dispatch, so the
            # 1-dispatch perf_smoke gate is unaffected.  The signature
            # keys the perf-regression baseline per (model, optimizer,
            # precision, batch shape) on this platform; a new data
            # shape re-notes (jax retraces per shape anyway), keeping
            # the recorded flops honest for the running batch size.
            self._noted_keys.add(note_key)
            import hashlib
            # data/label shapes fold into the signature: step time
            # scales with batch size, so a legitimate bs change must
            # select a DIFFERENT perf baseline file, not fire a false
            # regression against the old batch's numbers
            # mesh_signature folds in too: the perf sentinel then keys
            # its baseline per mesh SHAPE — a resharded run measures
            # against its own history, not the replicated path's
            sig = hashlib.sha1(repr(
                (built["sig"], type(opt_).__name__, policy,
                 thr is not None, tuple(data.shape),
                 tuple(label.shape), msig)).encode()).hexdigest()[:16]
            # the program CONTRACT the post-compile auditor
            # (analysis.audit_programs, ISSUE 15) verifies against the
            # lowered HLO: every donated leaf must become an
            # input-output alias, AMP must leave no f32 dot/conv, a
            # whole-step program contains zero host callbacks (Custom
            # ops are ineligible by construction), and the collective
            # story matches the mesh — zero collectives replicated
            # (single-process inline bucketed reduce; multi-host
            # kvstore is ineligible), or the per-axis GSPMD plan on a
            # multi-device mesh
            contracts = {
                "donate_argnums": (0, 1, 2, 3, 4),
                "donated_leaves": len(jax.tree_util.tree_leaves(
                    (gparams, svals, residuals, scaler, aux))),
                "amp": policy,
                "host_callbacks": 0,
                "buckets": len(built["bk"].sizes)
                if thr is not None else 0,
            }
            if mesh is not None and mesh.size > 1:
                # the GSPMD collective plan the auditor verifies
                # against the sharded HLO: every mesh axis of size > 1
                # must carry at least one XLA-inserted collective
                # (gradient reduce over batch, partial-sum reduce over
                # model) — and donation must STILL alias under sharding
                contracts["mesh_axes"] = {
                    a: int(mesh.shape[a]) for a in mesh.axis_names}
                contracts["collective_plan"] = {
                    a: 1 for a in mesh.axis_names
                    if int(mesh.shape[a]) > 1}
            else:
                # single-process inline bucketed reduce (multi-host
                # kvstore is ineligible): zero collective ops
                contracts["collectives"] = 0
            _introspect.note_jit(
                "whole_step", fn, gparams, svals, residuals, scaler, aux,
                consts, data_j, label_j,
                jax.random.PRNGKey(0), lrs, wds, ts, signature=sig,
                contracts=contracts)

        # chaos site for transient device loss at the dispatch boundary:
        # fires before fn() executes, so the donated buffers are still
        # live and a supervisor restore+retry reuses the built program
        _fi_fire("device.unavailable", step=tr._step_id)
        from .. import random as _random
        rkey = _random.next_key()
        on = _metrics.ENABLED
        d0 = _metrics.step_dispatches() if on else 0.0
        if on:
            _metrics.XLA_LAUNCHES.inc(kind="whole_step")
            _metrics.OPTIMIZER_STEPS.inc()
        try:
            with span("whole_step", cat="trainer", step=tr._step_id,
                      watch=True, mem=True), \
                    _memory.oom_guard("wholestep.step"):
                loss, new_aux, new_p, new_s, new_res, new_scaler, nts = \
                    fn(gparams, svals, residuals, scaler, aux, consts,
                       data_j, label_j, rkey, lrs, wds, ts)
        except BaseException:
            # MXNET_SANITIZE runtime twin of the use-after-donate
            # static rule: an exception out of the donated program
            # means the params/states/aux buffers may already be
            # consumed by XLA.  Poison their wrappers so any touch
            # before a restore raises a typed DonatedBufferError
            # (naming this dispatch) instead of jax's opaque
            # deleted-array error; the supervisor's snapshot restore
            # (_load_init / set_states_bytes) replaces _data and
            # thereby clears the poison.  One boolean test when the
            # sanitizer is off.
            if _san.ENABLED:
                _san.poison_donated(
                    "whole_step",
                    *[params[n].list_data() for n in gnames],
                    *[params[n].list_data()
                      for n in built["aux_names"]],
                    *[upd.states[i] for i in idx])
            raise
        tr._step_id += 1
        if on:
            _metrics.TRAINER_STEP_DISPATCHES.set(
                _metrics.step_dispatches() - d0)
        if _introspect.ENABLED:
            # perf-regression sentinel heartbeat: one counter bump per
            # step; every SENTINEL_EVERY steps the warmed whole_step
            # EWMA compares against the persisted baseline
            _introspect.sentinel_tick("whole_step")
        if _journal.ENABLED:
            _journal.maybe_milestone(tr._step_id, source="whole_step")

        self._commit_outputs(built, upd, policy, thr, new_p, new_aux,
                             new_s, new_res, new_scaler, nts, counts_t)
        self._ran = True
        return NDArray(loss, data.context)

    def _commit_outputs(self, built, upd, policy, thr, new_p, new_aux,
                        new_s, new_res, new_scaler, nts, counts_t):
        """Write the program's functional outputs back onto the live
        model/trainer — shared verbatim by the whole-step dispatch and
        the superstep's scan dispatch (K fused steps commit exactly
        like one)."""
        tr = self.trainer
        params = built["params"]
        idx = built["idx"]
        for n in built["gnames"]:
            params[n].list_data()[0]._set_data(new_p[n])
        for n in built["aux_names"]:
            params[n].list_data()[0]._set_data(new_aux[n])
        for k, i in enumerate(idx):
            upd.states[i] = upd._state_writeback(upd.states[i], new_s[k])
        if thr is not None:
            # the program returns FRESH residual arrays (functional
            # update) — re-register so ledger attribution follows the
            # live ones, same as the fused allreduce does
            if _memory.ENABLED:
                tr._residuals = [_memory.register(
                    r, tag="compression_residual") for r in new_res]
            else:
                tr._residuals = list(new_res)
        if policy == "fp16":
            st = tr._scaler
            st["scale"], st["good"] = new_scaler["scale"], \
                new_scaler["good"]
        self._hyper_cache.commit(idx, nts, counts_t)
        # mirror the device-side applied-step vector onto the trainer so
        # save_states can persist it with the scaler (fp16 kill-resume:
        # ts lags the schedule counts by one per skipped step)
        tr._applied_ts = (idx, nts)
