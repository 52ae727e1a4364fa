"""Executor: a bound, compiled symbolic graph.

Reference parity: `include/mxnet/executor.h:53` + `src/executor/
graph_executor.cc` (GraphExecutor::Init/Forward/Backward, memory planning,
op bulking) + `python/mxnet/executor.py`.  TPU-native realization:
  - bind-time nnvm passes → one `jax.jit` of the whole-graph interpreter
    (forward) and one of forward+vjp (fused forward-backward).  XLA does
    shape specialization, memory planning, fusion, and scheduling — the
    reference's PlanMemory/AttachOpExecs/segment-bulking machinery
    (graph_executor.cc:908,913,1350) has no hand-written analog.
  - gradient graph (nnvm Gradient pass) → `jax.vjp` over the interpreter.
  - `MXNET_BACKWARD_DO_MIRROR` recompute → `jax.checkpoint` (remat) when
    env MXNET_BACKWARD_DO_MIRROR=1 (parity: graph_executor.cc:282-305).
  - `forward_backward()` runs outputs+grads+aux in ONE compiled call — the
    path Module.fit uses, giving a single XLA executable per training step.
  - separate forward()/backward() keep exact reference semantics (same
    dropout mask, aux updated once) by snapshotting forward's inputs/key.
  - group2ctx model parallelism: per-group `jax.device_put` in an eager
    per-node mode (PlaceDevice-pass analog, graph_executor.cc:411).
"""
from __future__ import annotations

import operator
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .base import MXNetError, enable_compile_cache, getenv
from .context import Context
from .faultinject import fire as _fi_fire
from .ndarray import NDArray
from .observability import introspect as _introspect
from .observability import memory as _memory
from .observability import metrics as _metrics
from .observability.tracing import span
from .symbol.graph import GraphPlan
from . import random as _random


def _device_of(a):
    """Single device an array lives on, or None if sharded/unknown."""
    try:
        devs = a.devices()
        return next(iter(devs)) if len(devs) == 1 else None
    except Exception:
        return None


class _HeldLaunch(NamedTuple):
    """A fused launch issued ahead of its step (`Executor.launch_ahead`)."""
    batch: Any      # the caller's batch object it was issued for
    reads: list     # every array bound when it was issued
    snapshot: tuple  # (arg_vals, aux_vals, key), as forward() keeps them
    results: Any    # what the program returned, or the error it raised


class Executor:
    def __init__(self, symbol, ctx, args: Dict[str, NDArray],
                 args_grad: Dict[str, NDArray], grad_req: Dict[str, str],
                 aux_states: Dict[str, NDArray], group2ctx=None,
                 shared_exec: Optional["Executor"] = None,
                 mesh=None, data_shard_args=()):
        # persistent XLA compile cache (JAX_COMPILATION_CACHE_DIR): the
        # persist-everything thresholds go on at bind time so training
        # executors share the on-disk cache the serving path uses
        enable_compile_cache()
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        self.arg_dict = dict(args)
        self.grad_dict = dict(args_grad or {})
        self.grad_req = dict(grad_req)
        self.aux_dict = dict(aux_states or {})
        self.group2ctx = group2ctx
        self._plan = GraphPlan(symbol)
        self._plan.specialize_init_shapes(
            {n: a.shape for n, a in self.arg_dict.items() if a is not None})
        # bucketing / reshape: share the compiled-function cache so XLA
        # executables are reused across executors of the same symbol family
        self._jit_cache = shared_exec._jit_cache if shared_exec is not None else {}
        self._grad_names = [n for n in self._plan.arg_names
                            if self.grad_req.get(n, "null") != "null"]
        self._monitor = None
        self._monitor_all = False
        self._outputs_cache: Optional[List[NDArray]] = None
        self._snapshot = None  # (arg_vals, aux_vals, key) of last forward
        self._pending_grads = None  # grads held by a train-mode forward()
        # a fused launch issued ahead of its step, nothing deposited yet
        self._held: Optional[_HeldLaunch] = None
        # the key of a held launch that was dropped: the launch that
        # replaces it runs with it, so the random stream does not shift
        self._held_key = None
        # lazy train-mode forward (VERDICT r3 #6): until this executor's
        # backward() is seen once, forward(is_train=True) runs ONLY the
        # forward program — Monitor taps / MC eval never pay the vjp.
        # After the first backward() the fused fwd+vjp runs eagerly again
        # so the forward(); backward() training pattern stays one
        # compiled step.
        self._seen_backward = False
        self._remat = bool(getenv("MXNET_BACKWARD_DO_MIRROR", 0))
        # sqrt(N) contiguous jax.checkpoint segments (graph.py
        # _run_segmented) — a WHOLE-graph checkpoint saves nothing;
        # MXNET_MIRROR_SEGMENTS overrides the sqrt default
        nsteg = int(getenv("MXNET_MIRROR_SEGMENTS", 0) or 0)
        self._mirror_segments = nsteg or max(
            2, int(len(self._plan.steps) ** 0.5))
        # rows-only embedding grads (VERDICT r3 #8): args eligible for
        # the in-graph rsp rewrite — weight of Embedding(sparse_grad)
        # steps, grad_req 'write', no remat/group2ctx interplay.  The
        # fused program differentiates an injected zero 'dummy' of the
        # lookup's OUTPUT shape instead of the O(vocab) weight, so the
        # dense V×D gradient buffer never exists on device.
        self._rsp_grad_args = {}
        if not self._remat and not group2ctx:
            for n, lst in self._plan.sparse_grad_args().items():
                if self.grad_req.get(n) == "write":
                    self._rsp_grad_args[n] = tuple(lst)
        # SPMD data parallelism: batch args sharded on 'dp' over the mesh,
        # params replicated; XLA all-reduces gradients over ICI.  This is the
        # TPU redesign of DataParallelExecutorGroup (SURVEY.md §2.3).
        self._mesh = mesh
        self._data_shard_args = set(data_shard_args)
        # introspection captures done, keyed like the _jit_cache entries
        # so a plan-key change (re-specialized shapes) re-notes the new
        # program instead of keeping the first one's flops forever
        self._noted = set()

    @property
    def _plan_key(self):
        """Cache key for shared _jit_cache entries: same symbol + same
        init-shape specialization → same executable family (reshape of the
        same symbol reuses jax's per-shape cache; distinct bucket symbols
        or begin-state specializations get their own closures)."""
        ov = getattr(self._plan, "init_overrides", {})
        # the symbol object itself (identity hash) — kept alive by the
        # cache entry, so ids can't be recycled across dead symbols
        return (self._symbol,
                tuple(sorted((si, tuple(p.get("shape", ())))
                             for si, p in ov.items())))

    # -- compiled entry points ---------------------------------------------
    @property
    def _fwd(self):
        key = ("fwd", self._plan_key)
        if key not in self._jit_cache:
            if _metrics.ENABLED:
                _metrics.JIT_CACHE_MISSES.inc()
            plan = self._plan

            def mx_executor_fwd(arg_vals, aux_vals, key_, is_train):
                return plan.run(arg_vals, aux_vals, key_, is_train)

            self._jit_cache[key] = jax.jit(mx_executor_fwd,
                                           static_argnums=(3,))
        elif _metrics.ENABLED:
            _metrics.JIT_CACHE_HITS.inc()
        return self._jit_cache[key]

    @property
    def _fwd_bwd(self):
        key = ("fwd_bwd", self._plan_key, tuple(self._grad_names),
               tuple(sorted(self._rsp_grad_args)))
        if key not in self._jit_cache:
            if _metrics.ENABLED:
                _metrics.JIT_CACHE_MISSES.inc()
            plan = self._plan
            rsp_map = dict(self._rsp_grad_args)
            grad_names = [n for n in self._grad_names if n not in rsp_map]
            remat = self._remat
            segN = self._mirror_segments

            def mx_executor_fwd_bwd(arg_vals, aux_vals, key_, ograds):
                others = {k: v for k, v in arg_vals.items() if k not in grad_names}
                # one zero dummy per sparse-embedding step, shaped like
                # the lookup OUTPUT (tokens × dim, not vocab × dim)
                dummies = {}
                for n, lst in sorted(rsp_map.items()):
                    w = arg_vals[n]
                    for si, dvar in lst:
                        dummies[si] = jnp.zeros(
                            tuple(arg_vals[dvar].shape) + tuple(w.shape[1:]),
                            w.dtype)

                def fwd(gvals, dums):
                    merged = dict(others)
                    merged.update(gvals)
                    overrides, ids_out = {}, {}

                    def make_ov(si):
                        def ov(p, ins):
                            # clip BEFORE recording: the recorded ids are
                            # the rsp row indices, and an unclipped OOB id
                            # would drop/misroute its gradient where the
                            # dense vjp of take(mode='clip') scatters it
                            # into the clipped row
                            ids = jnp.clip(ins[0].astype(jnp.int32), 0,
                                           ins[1].shape[0] - 1)
                            ids_out[si] = ids
                            return (jnp.take(
                                jax.lax.stop_gradient(ins[1]), ids,
                                axis=0) + dums[si],)
                        return ov

                    for n, lst in rsp_map.items():
                        for si, _ in lst:
                            overrides[si] = make_ov(si)
                    res = plan.run(merged, aux_vals, key_, True,
                                   step_overrides=overrides or None,
                                   segments=segN if remat else 1)
                    return res, ids_out

                (outs, new_aux), vjp_fn, ids_out = jax.vjp(
                    fwd, {n: arg_vals[n] for n in grad_names}, dummies,
                    has_aux=True)
                cots = [og if og is not None else jnp.ones(o.shape, o.dtype)
                        for og, o in zip(ograds, outs)]
                zero_aux = jax.tree_util.tree_map(jnp.zeros_like, new_aux)
                grads, gdum = vjp_fn((cots, zero_aux))
                rsp_grads = {}
                for n, lst in sorted(rsp_map.items()):
                    rowdim = tuple(arg_vals[n].shape[1:])
                    ids = jnp.concatenate(
                        [ids_out[si].reshape(-1) for si, _ in lst])
                    vals = jnp.concatenate(
                        [gdum[si].reshape((-1,) + rowdim) for si, _ in lst])
                    rsp_grads[n] = (ids, vals)
                return outs, new_aux, grads, rsp_grads

            self._jit_cache[key] = jax.jit(mx_executor_fwd_bwd)
        elif _metrics.ENABLED:
            _metrics.JIT_CACHE_HITS.inc()
        return self._jit_cache[key]

    # -- public API ---------------------------------------------------------
    def _gather(self, kwargs):
        with span("mx.executor.gather", cat="executor"):
            return self._gather_impl(kwargs)

    def _gather_impl(self, kwargs):
        dev = None if self._mesh is not None else self._ctx.jax_device()
        for k, v in kwargs.items():
            if k in self.arg_dict:
                val = (v._data if isinstance(v, NDArray)
                       else jnp.asarray(v)).astype(self.arg_dict[k].dtype)
                # batch data may arrive on another device (e.g. a CPU-side
                # iterator feeding a TPU-bound executor) — move it to the
                # executor's context, like the reference's load_data copyto
                # (src/executor exec_group _load_general)
                if dev is not None and _device_of(val) != dev:
                    val = jax.device_put(val, dev)
                    if _metrics.ENABLED:
                        _metrics.DEVICE_PUTS.inc()
                        _metrics.TRANSFER_BYTES.inc(
                            getattr(val, "nbytes", 0))
                self.arg_dict[k]._set_data(val)
            else:
                raise MXNetError(f"unknown forward argument {k}")
        arg_vals = {k: v._data for k, v in self.arg_dict.items()}
        aux_vals = {k: v._data for k, v in self.aux_dict.items()}
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            axis = self._mesh.axis_names[0]
            shard = NamedSharding(self._mesh, P(axis))
            repl = NamedSharding(self._mesh, P())
            # these sharded/replicated copies outlive the call — they sit
            # in self._snapshot until the next forward (a model-plus-aux
            # block of HBM), so the ledger must see them
            arg_vals = {k: _memory.register(
                jax.device_put(v, shard if k in self._data_shard_args
                               and v.ndim >= 1 else repl), tag="executor")
                        for k, v in arg_vals.items()}
            aux_vals = {k: _memory.register(jax.device_put(v, repl),
                                            tag="executor")
                        for k, v in aux_vals.items()}
        key, self._held_key = self._held_key, None
        return arg_vals, aux_vals, _random.next_key() if key is None else key

    # -- a launch held for its step -----------------------------------------
    def _bound_data(self):
        """Every array a launch reads, as bound right now."""
        return [v._data for v in self.arg_dict.values()] + \
            [v._data for v in self.aux_dict.values()]

    def launch_ahead(self, batch=None) -> None:
        """The first half of `forward_backward()`, issued before its step
        (Module.prepare, for the `batch` it has just loaded): gather, key
        and the launch of the fused program.
        What comes back is HELD, nothing is deposited: outputs, auxiliary
        states and gradients stay the last step's until `forward_backward()`
        or `forward(is_train=True)` takes the slot.  A launch that raises is
        held too and raises from the step that takes it, where a supervised
        fit can retry it."""
        self.drop_held()
        reads = self._bound_data()
        snapshot = self._gather({})
        try:
            results = self._launch_fused(*snapshot, None)
        except Exception as exc:  # noqa: BLE001 - re-raised by the taker
            results = exc
        self._held = _HeldLaunch(batch, reads, snapshot, results)

    def holds_launch(self, batch=None) -> bool:
        """Whether a held launch is still good: it was issued for this very
        `batch` object, and every array it read is the one bound now
        (identity of the immutable buffers, so any write to a parameter, an
        input or an auxiliary state shows).  Another one is dropped here."""
        if self._held is None:
            return False
        reads, now = self._held.reads, self._bound_data()
        if self._held.batch is batch and len(reads) == len(now) \
                and all(map(operator.is_, reads, now)):
            return True
        self.drop_held()
        return False

    def drop_held(self) -> None:
        """Forget a held launch and keep its key for the next one."""
        if self._held is not None:
            self._held_key, self._held = self._held.snapshot[2], None
            if _metrics.ENABLED:
                _metrics.HELD_LAUNCHES.inc(result="dropped")

    def _take_held(self, same_launch: bool):
        """What a good held launch returned, with the slot emptied and its
        inputs and key as the snapshot; None where there is none.  A call
        that asks for another launch than the held one (`same_launch`
        False: an inference forward, head gradients, arguments) drops it."""
        if not same_launch:
            self.drop_held()
        held = self._held
        if held is None or not self.holds_launch(held.batch):
            return None
        self._held = None
        if _metrics.ENABLED:
            _metrics.HELD_LAUNCHES.inc(result="taken")
        if isinstance(held.results, Exception):
            raise held.results
        self._snapshot = held.snapshot
        return held.results

    def forward(self, is_train: bool = False, **kwargs) -> List[NDArray]:
        held = self._take_held(is_train and not kwargs)
        if held is not None:
            # the fused program has run for these very arrays: the outputs
            # now, the gradients when backward() asks
            outs, new_aux, grads, rsp_grads = held
            with span("mx.executor.deposit", cat="executor"):
                self._set_results(outs, new_aux)
            self._pending_grads = (grads, rsp_grads)
            return self._outputs_cache
        arg_vals, aux_vals, key = self._gather(kwargs)
        self._snapshot = (arg_vals, aux_vals, key)
        self._pending_grads = None
        if self.group2ctx:
            return self._forward_placed(arg_vals, aux_vals, key, is_train)
        if is_train and self._grad_names and self._seen_backward:
            # training forward on an executor that trains: run the fused
            # fwd+vjp program now and hold the grads — forward();
            # backward() costs ONE compiled step, not a forward plus a
            # recomputing vjp.  Until the first backward() the vjp is
            # deferred (lazy path below): a forward-only train-mode call
            # costs one forward, and the first backward() replays the
            # fused program from the snapshot (same RNG key → same
            # dropout mask; aux restored → stats not double-updated).
            ograds = [None] * len(self._plan.out_refs)
            if _metrics.ENABLED:
                _metrics.XLA_LAUNCHES.inc(kind="fwd_bwd")
            fwd_bwd = self._fwd_bwd
            with span("mx.executor.launch", cat="executor"), \
                    _memory.oom_guard("executor.forward_backward"):
                outs, new_aux, grads, rsp_grads = fwd_bwd(
                    arg_vals, aux_vals, key, ograds)
            nk = ("fwd_bwd", self._plan_key)
            if _introspect.ENABLED and nk not in self._noted:
                self._noted.add(nk)
                _introspect.note_jit("executor:fwd_bwd", fwd_bwd,
                                     arg_vals, aux_vals, key, ograds)
            with span("mx.executor.deposit", cat="executor"):
                self._set_results(outs, new_aux)
            self._pending_grads = (grads, rsp_grads)
            return self._outputs_cache
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="fwd")
        fwd = self._fwd
        with span("mx.executor.launch", cat="executor"), \
                _memory.oom_guard("executor.forward"):
            outs, new_aux = fwd(arg_vals, aux_vals, key, is_train)
        nk = ("fwd", self._plan_key)
        if _introspect.ENABLED and nk not in self._noted:
            # Executor-bind chokepoint (ISSUE 13): once per compiled
            # program, note the forward's analytical cost (a retrace,
            # no XLA compile — and no dispatch, so the perf_smoke
            # gates are unaffected)
            self._noted.add(nk)
            _introspect.note_jit("executor:fwd", fwd, arg_vals,
                                 aux_vals, key, is_train)
        with span("mx.executor.deposit", cat="executor"):
            self._set_results(outs, new_aux)
        return self._outputs_cache

    def backward(self, out_grads=None, is_train: bool = True) -> None:
        """Gradient pass.  Deposits the grads computed by a train-mode
        forward(); with custom head gradients it re-runs the compiled vjp
        on the forward snapshot (same RNG key → same dropout mask; aux
        values restored → moving stats not double-updated)."""
        if self._snapshot is None:
            raise MXNetError("backward called before forward")
        self._seen_backward = True
        if out_grads is None and self._pending_grads is not None:
            with span("mx.executor.deposit", cat="executor"):
                self._deposit_grads(*self._pending_grads)
            self._pending_grads = None
            return
        arg_vals, aux_vals, key = self._snapshot
        # replay: outputs/aux were already set by forward() — don't set
        # them again (a Monitor would record every output stat twice)
        self._run_fused(arg_vals, aux_vals, key, out_grads,
                        set_results=False)

    def forward_backward(self, out_grads=None, **kwargs) -> List[NDArray]:
        """Fused training step: outputs + grads + aux in ONE compiled call
        (the Module.fit hot path)."""
        held = self._take_held(out_grads is None and not kwargs)
        self._pending_grads = None
        if held is not None:
            self._finish_fused(held)
            return self._outputs_cache
        arg_vals, aux_vals, key = self._gather(kwargs)
        self._snapshot = (arg_vals, aux_vals, key)
        self._run_fused(arg_vals, aux_vals, key, out_grads)
        return self._outputs_cache

    def _run_fused(self, arg_vals, aux_vals, key, out_grads,
                   set_results=True):
        self._finish_fused(
            self._launch_fused(arg_vals, aux_vals, key, out_grads),
            set_results)

    def _launch_fused(self, arg_vals, aux_vals, key, out_grads):
        """First half of the fused step: the launch.  Returns what the
        program returns (outputs, new auxiliary states, gradients,
        row-sparse gradients) and writes nothing."""
        if out_grads is None:
            ograds = [None] * len(self._plan.out_refs)
        elif isinstance(out_grads, NDArray):
            ograds = [out_grads._data]
        else:
            ograds = [g._data if isinstance(g, NDArray) else jnp.asarray(g)
                      for g in out_grads]
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="fwd_bwd")
        # OOM post-mortem chokepoint: a RESOURCE_EXHAUSTED out of the
        # fused training program dumps ledger+ring and re-raises typed;
        # the memory.oom chaos site injects a synthetic one here
        fwd_bwd = self._fwd_bwd
        with span("mx.executor.launch", cat="executor"), \
                _memory.oom_guard("executor.forward_backward"):
            _fi_fire("memory.oom", at="executor")
            results = fwd_bwd(arg_vals, aux_vals, key, ograds)
        nk = ("fwd_bwd", self._plan_key)
        if _introspect.ENABLED and nk not in self._noted:
            self._noted.add(nk)
            _introspect.note_jit("executor:fwd_bwd", fwd_bwd,
                                 arg_vals, aux_vals, key, ograds)
        return results

    def _finish_fused(self, results, set_results=True):
        """Second half of the fused step: the deposit."""
        outs, new_aux, grads, rsp_grads = results
        with span("mx.executor.deposit", cat="executor"):
            if set_results:
                self._set_results(outs, new_aux)
            self._deposit_grads(grads, rsp_grads)

    def _deposit_grads(self, grads, rsp_grads=None):
        from .ndarray.sparse import RowSparseNDArray
        for name, (ids, vals) in (rsp_grads or {}).items():
            tgt = self.grad_dict.get(name)
            if tgt is None:
                continue
            if isinstance(tgt, RowSparseNDArray):
                # rows-only deposit; duplicate token rows segment-sum in
                # the constructor's dedup (grad_req 'write')
                tgt._assign_rows(ids, vals.astype(tgt.dtype))
            else:
                # caller bound a dense grad buffer: honor it (dense
                # scatter at the boundary, still no dense grad in-graph)
                tgt._set_data(jnp.zeros(tgt.shape, tgt.dtype).at[ids].add(
                    vals.astype(tgt.dtype)))
        for name in self._grad_names:
            if rsp_grads and name in rsp_grads:
                continue
            g = grads[name]
            tgt = self.grad_dict.get(name)
            if tgt is None:
                continue
            if self.grad_req.get(name) == "add":
                tgt._set_data(tgt._data + g.astype(tgt.dtype))
            else:
                tgt._set_data(g.astype(tgt.dtype))

    def memory_analysis(self, train: bool = True) -> dict:
        """XLA buffer-assignment footprint of this executor's compiled
        program, in bytes.  TPU redesign of the reference's allocation
        planner/estimator (GraphExecutor::InitDataEntryMemory,
        src/executor/graph_executor.cc; demoed by example/memcost): the
        inplace/sharing plan the reference computes on its own graph is
        made here by XLA's buffer assignment, so the numbers come from
        the compiler that actually allocates.  `temp` is the transient
        activation/workspace pool (what remat shrinks), `argument` the
        bound params+inputs, `peak` the high-water mark."""
        arg_vals = {k: v._data for k, v in self.arg_dict.items()}
        aux_vals = {k: v._data for k, v in self.aux_dict.items()}
        # fixed key: only shapes/dtypes matter for lowering, and a
        # diagnostic must not advance the global RNG stream
        key = jax.random.PRNGKey(0)
        if train and self._grad_names:
            ograds = [None] * len(self._plan.out_refs)
            lowered = self._fwd_bwd.lower(arg_vals, aux_vals, key, ograds)
        else:
            lowered = self._fwd.lower(arg_vals, aux_vals, key, train)
        compiled = lowered.compile()
        # one structured shape for EVERY jax version (memory.
        # compiled_stats_dict inside introspect.note_program): same
        # keys whether or not the stats carry peak_memory_in_bytes
        # (jax < 0.5 estimates it as the live-buffer sum and flags
        # peak_estimated); {} only when the backend reports nothing
        # (older PJRT).  note_program is the ONE compiled-stats surface
        # (ISSUE 13): it files the result under the HBM ledger's
        # "executor" entry (report()["compiled"]) AND the program
        # registry (snapshot()["programs"]) in the same call.
        if _introspect.ENABLED:
            return _introspect.note_program(
                "executor", compiled=compiled).get("memory", {})
        out = _memory.compiled_stats_dict(compiled.memory_analysis())
        _memory.note_compiled("executor", out)
        return out

    @property
    def outputs(self) -> List[NDArray]:
        if self._outputs_cache is None:
            raise MXNetError("call forward() first")
        return self._outputs_cache

    def _set_results(self, outs, new_aux):
        # HBM ledger: the executor HOLDS its outputs until the next
        # forward — attributable memory, not transient (sparse re-wraps
        # stay inside the scope: cast_storage builds NEW wrappers that
        # would otherwise register untagged while the tagged ones die)
        with _memory.memory_scope("output"):
            self._outputs_cache = [NDArray(o, self._ctx) for o in outs]
            stypes = self._plan.out_stypes()
            if any(s != "default" for s in stypes):
                from .ndarray.sparse import cast_storage as _cast
                self._outputs_cache = [
                    _cast(o, st) if st != "default" else o
                    for o, st in zip(self._outputs_cache, stypes)]
        for k, v in new_aux.items():
            if k in self.aux_dict:
                self.aux_dict[k]._set_data(v)
        if self._monitor is not None:
            if self._monitor_all:
                # monitor_all taps inputs too (parity: MonitorExecution
                # monitor_all records both op inputs and outputs; the
                # fused-graph analog is the bound argument set)
                for name, arr in self.arg_dict.items():
                    self._monitor(name + "_input", arr)
                    if _metrics.ENABLED:
                        _metrics.MONITOR_STATS.inc(io="input")
            names = self._plan.symbol.list_outputs()
            for i, o in enumerate(self._outputs_cache):
                self._monitor(names[i], o)
                if _metrics.ENABLED:
                    _metrics.MONITOR_STATS.inc(io="output")

    def _forward_placed(self, arg_vals, aux_vals, key, is_train):
        """group2ctx model parallelism: eager per-node execution with
        device placement by ctx_group attr (PlaceDevice-pass analog)."""
        from .ops.registry import apply_op
        plan = self._plan
        devmap = {g: (c if isinstance(c, Context) else Context(c)).jax_device()
                  for g, c in (self.group2ctx or {}).items()}
        values = [None] * len(plan.steps)
        new_aux = dict(aux_vals)

        def resolve(ref):
            if ref[0] == "var":
                return arg_vals.get(ref[1], new_aux.get(ref[1]))
            si, oi = ref[1]
            return values[si][oi]

        for si, step in enumerate(plan.steps):
            ins = [resolve(r) for r in step.in_refs]
            grp = step.node.attrs.get("ctx_group")
            if grp and grp in devmap:
                # eager D2D hop of values already attributed at their
                # creation (group2ctx placement, not a new allocation)
                ins = [jax.device_put(x, devmap[grp]) for x in ins]  # graft-lint: disable=memory-hygiene
            p = dict(step.params)
            if step.op.takes_is_train:
                p["__is_train__"] = is_train
            if step.op.needs_rng:
                ins.append(jax.random.fold_in(key, si))
            out = apply_op(step.op, tuple(sorted(p.items())), ins)
            n_vis = len(out) - len(step.op.aux_inputs)
            values[si] = out[:n_vis]
            for pos, nm in step.aux_var_names.items():
                new_aux[nm] = out[n_vis + pos]
        outs = [resolve(r) for r in plan.out_refs]
        self._set_results(outs, new_aux)
        return self._outputs_cache

    # -- utilities ----------------------------------------------------------
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params: bool = False) -> None:
        def _assign(tgt: NDArray, v):
            if v._data is tgt._data:
                # pointer-handoff roundtrip (fit()'s per-epoch
                # get_params/set_params): already the same buffer
                return
            # preserve the target's sharding (mesh-replicated stay replicated)
            sh = getattr(tgt._data, "sharding", None)
            data = v._data.astype(tgt.dtype)
            if sh is not None and getattr(data, "sharding", None) != sh:
                data = jax.device_put(data, sh)
            tgt._set_data(data)

        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                _assign(self.arg_dict[k], v)
            elif not allow_extra_params:
                raise MXNetError(f"unknown argument {k}")
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                _assign(self.aux_dict[k], v)
            elif not allow_extra_params:
                raise MXNetError(f"unknown aux state {k}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes (XLA caches per-shape executables —
        the bucketing memory-sharing analog)."""
        from . import ndarray as nd
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for name, shp in zip(self._plan.arg_names, arg_shapes):
            cur = self.arg_dict[name]
            new_args[name] = cur if tuple(cur.shape) == tuple(shp) else \
                nd.zeros(shp, ctx=self._ctx, dtype=cur.dtype)
        new_aux = {}
        for name, shp in zip(self._plan.aux_names, aux_shapes):
            cur = self.aux_dict[name]
            new_aux[name] = cur if tuple(cur.shape) == tuple(shp) else \
                nd.zeros(shp, ctx=self._ctx, dtype=cur.dtype)
        grads = {n: nd.zeros(new_args[n].shape, ctx=self._ctx)
                 for n in self._grad_names}
        new = Executor(self._symbol, self._ctx, new_args, grads, self.grad_req,
                       new_aux, group2ctx=self.group2ctx, shared_exec=self)
        # a launch held for the old shapes goes; its key goes along
        self.drop_held()
        new._held_key, self._held_key = self._held_key, None
        return new

    def set_monitor_callback(self, callback, monitor_all=False) -> None:
        # a monitored step launches inside the step, after the monitor's tic()
        self.drop_held()
        self._monitor = callback
        self._monitor_all = bool(monitor_all)

    @property
    def output_dict(self):
        return dict(zip(self._plan.symbol.list_outputs(), self.outputs))

    def debug_str(self):
        return self._symbol.debug_str()
