"""Shape-bucketed AOT inference executor — the serving fast path.

What the naive path costs: `Predictor` compiles one executable per
EXACT input shape, so the first request at any unseen batch size or
sequence length pays a full XLA compile on the hot path (seconds), and
every request is its own dispatch.  This module is the TPU realization
of the reference design pair the ROADMAP's serving north star points
at — MXNet's bucketing executors (arxiv 1512.01274) and TVM's
ahead-of-time compiled deployment modules (arxiv 1802.04799):

  - a small fixed lattice of padded shape buckets (`buckets.BucketSpec`,
    pow2-derived, `MXNET_SERVE_BUCKETS` override);
  - each bucket AOT-compiled ONCE via `jax.jit(...).lower(...).compile()`
    — `warmup()` moves every compile off the request path;
  - JAX's persistent compilation cache (`JAX_COMPILATION_CACHE_DIR`) so a
    process restart re-loads executables from disk instead of
    recompiling;
  - requests pad on host into the bucket shape (one device transfer,
    ONE XLA dispatch per request/coalesced batch) and slice the valid
    rows back out;
  - the padded input buffer is donated to the executable
    (`donate_argnums`) — on TPU the input HBM block is released to the
    program instead of held across the call.
"""
from __future__ import annotations

import threading
import time
import warnings
from contextlib import nullcontext as _nullcontext
from typing import Dict, List, Optional, Sequence

import numpy as _np

import jax

from ..analysis import hot_path
from ..analysis import sanitizer as _sanitizer
from ..base import MXNetError, enable_compile_cache, np_dtype
from ..context import cpu
from ..faultinject import fire as _fi_fire
from ..ndarray import NDArray
from ..observability import flight as _flight
from ..observability import introspect as _introspect
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from .. import symbol as sym_mod
from ..symbol import Symbol
from ..symbol.graph import GraphPlan
from .buckets import BucketSpec, bucket_label, pad_to_shape

__all__ = ["BucketedPredictor", "ModelEvictedError"]


class ModelEvictedError(MXNetError):
    """A dispatch/compile reached a predictor whose device weights are
    evicted.  The registry readmits at submit, so this surfacing to a
    caller means a request raced an eviction (or bypassed the registry)
    — readmit() and retry."""


class BucketedPredictor:
    """Forward-only serving executor over a fixed shape-bucket lattice.

    Parameters
    ----------
    symbol : Symbol or str
        The inference graph (a Symbol, or its JSON as from
        `Symbol.tojson()`).
    params : dict / bytes / str
        `{name: NDArray-or-numpy}` (optionally `arg:`/`aux:` prefixed),
        a serialized param blob (parsed in memory), or a param file
        path.
    input_shapes : dict
        `{input_name: shape}` — axis 0 is the batch axis; the declared
        sizes are the maxima the default pow2 bucket ladders are
        derived from.
    seq_axes : dict, optional
        `{input_name: axis}` marking a second bucketed (sequence) axis.
        Sequence padding is exact only for position-independent models
        (see docs/inference.md for the caveat).
    donate : bool
        Donate the padded input buffer to the compiled program
        (default True; a no-op on backends without donation support).
    """

    def __init__(self, symbol, params, input_shapes: Dict[str, tuple],
                 dev=None, batch_buckets=None, seq_axes=None,
                 seq_buckets=None, input_dtypes=None,
                 output_names: Optional[Sequence[str]] = None,
                 donate: bool = True, resident: bool = True):
        from ..predictor import load_param_payload, split_arg_aux
        enable_compile_cache()
        if isinstance(symbol, Symbol):
            sym = symbol
        else:
            sym = sym_mod.load_json(symbol)
        if output_names:
            internals = sym.get_internals()
            sym = sym_mod.Group([internals[n] for n in output_names])
        self._symbol = sym
        self._ctx = dev or cpu()
        self._plan = GraphPlan(sym)
        self._donate = bool(donate)

        # a dict payload stays host-side as-is (load_param_payload
        # would wrap numpy values in DEVICE NDArrays — a transient
        # second copy of the whole model that pollutes the HBM ledger
        # a multi-model budgeter admits against); blob/path payloads
        # still load through it, and the transient is dropped below
        # before the served weights allocate
        payload = dict(params) if isinstance(params, dict) \
            else load_param_payload(params)
        arg_params, aux_params = split_arg_aux(payload)
        arg_names = sym.list_arguments()
        self._input_names = [n for n in arg_names if n not in arg_params]
        for name in input_shapes:
            if name not in self._input_names:
                raise MXNetError(
                    f"'{name}' is not a free input of the symbol; free "
                    f"inputs: {self._input_names}")
        dev_j = self._ctx.jax_device()

        def _host_copy(v):
            # an OWNED copy, never an alias: np.asarray on a caller's
            # numpy array is no-copy, and registering caller-owned
            # buffers under our tag would misattribute them for as
            # long as the caller holds them (and retag ones the caller
            # already registered)
            arr = v.asnumpy() if isinstance(v, NDArray) else \
                _np.array(v, copy=True)
            # host twin of the served weights: the restart-free
            # readmission source after evict() — a reload costs one
            # device_put per array, never a training-checkpoint round
            # trip (ledger tag serve_host_params, space=host)
            return _memory.register_host(arr, tag="serve_host_params")

        # the host param payload outlives the device weights: evict()
        # drops the device copies (and the AOT executables) but keeps
        # this, so readmit() is a reload + cache-hit compile
        self._host_payload = (
            {k: _host_copy(v) for k, v in arg_params.items()},
            {k: _host_copy(v) for k, v in aux_params.items()})
        # drop any loader-made device NDArrays NOW — the served
        # weights below must be the payload's only device copy
        del payload, arg_params, aux_params

        def _to_dev(v):
            arr = jax.device_put(_np.asarray(v), dev_j)
            # HBM ledger: served weights are the long-lived buffers a
            # multi-model budgeter evicts against — always attributed
            return _memory.register(arr, tag="serve_weights")

        # one tuple holds the live (params, aux) pair: hot_reload swaps
        # it with a single reference assignment, so no reader can ever
        # see params of one checkpoint with aux of another.
        # resident=False constructs straight onto the weights_evicted
        # ladder rung — host payload only, NO device allocation, so a
        # registry can admit a model that does not currently fit the
        # HBM budget without transiently blowing that same budget
        self._closed = False
        # distinguishes a first admission from a true readmission:
        # only the latter counts in SERVE_READMITS
        self._was_evicted = False
        if resident:
            self._weights = (
                {k: _to_dev(v)
                 for k, v in self._host_payload[0].items()},
                {k: _to_dev(v)
                 for k, v in self._host_payload[1].items()})
            self._resident = True
        else:
            self._weights = ({}, {})
            self._resident = False
        self._input_dtypes = {
            n: np_dtype((input_dtypes or {}).get(n, "float32"))
            for n in input_shapes}

        self.spec = BucketSpec(input_shapes, batch_buckets=batch_buckets,
                               seq_axes=seq_axes, seq_buckets=seq_buckets)
        # serving must be deterministic across identical requests — a
        # fixed key, never the global stream (is_train=False consumes no
        # randomness in stock models anyway)
        self._rng = jax.random.PRNGKey(0)
        self._compiled: Dict[tuple, object] = {}
        self._extra: Dict[tuple, dict] = {}  # per-bucket zero placeholders
        # LRU clock per bucket (stamped at precompile and every
        # dispatch) + the set of keys EVER compiled in this process:
        # a rebuild of an evicted bucket is a readmission (a
        # persistent-cache hit when JAX_COMPILATION_CACHE_DIR is set),
        # not an escape from the bucket set, so it must not count
        # against the stay-flat SERVE_COMPILES contract
        self._bucket_used: Dict[tuple, float] = {}
        self._ever_compiled: set = set()
        # per-bucket CompiledMemoryStats (memory.compiled_stats_dict
        # shape), filled at precompile — feeds readyz + the
        # SERVE_BUCKET_HBM_BYTES gauge (docs/memory.md)
        self._mem_stats: Dict[tuple, dict] = {}
        # compiles may be triggered concurrently by batcher + direct
        # callers; one lock keeps "compile each bucket once" true.  It
        # also guards the weights/payload lifecycle swaps (hot_reload
        # on the auto-reload thread vs evict/readmit/close from a
        # registry) — reentrant because evict() nests evict_bucket()
        from ..analysis import sanitizer as _san
        self._compile_lock = _san.make_rlock("serving.predictor.compile")

        plan = self._plan

        def _serve(data, extra, params, aux, key):
            merged = dict(params)
            merged.update(extra)
            merged.update(data)
            outs, _ = plan.run(merged, aux, key, False)
            return list(outs)

        self._jit = jax.jit(
            _serve, donate_argnums=(0,) if self._donate else ())

    @property
    def _params(self) -> dict:
        return self._weights[0]

    @property
    def _aux(self) -> dict:
        return self._weights[1]

    # -- compilation ---------------------------------------------------------
    def _placeholder_shapes(self, in_shapes: dict) -> dict:
        """Zero placeholders for free args not served as inputs (label
        heads of training symbols — MXPredCreate parity)."""
        missing = [n for n in self._input_names if n not in in_shapes]
        if not missing:
            return {}
        arg_shapes, _, _ = self._symbol.infer_shape_partial(**in_shapes)
        inferred = dict(zip(self._symbol.list_arguments(), arg_shapes or []))
        out = {}
        for name in missing:
            shp = inferred.get(name)
            if shp is None:
                raise MXNetError(
                    f"input '{name}' has no declared shape and shape "
                    f"inference could not determine one")
            out[name] = tuple(shp)
        return out

    def precompile(self, key: tuple):
        """AOT-compile one bucket (idempotent).  The compile happens via
        lower().compile() so it also lands in the persistent compilation
        cache when JAX_COMPILATION_CACHE_DIR is set."""
        if key in self._compiled:
            return self._compiled[key]
        with self._compile_lock:
            if key in self._compiled:
                return self._compiled[key]
            if not self._resident:
                raise ModelEvictedError(
                    "model weights are evicted — readmit() before "
                    "compiling/serving (a ModelRegistry does this at "
                    "submit; see docs/multi_model.md)")
            in_shapes = self.spec.bucket_input_shapes(key)
            extra = {n: _memory.register(jax.device_put(
                _np.zeros(s, _np.float32), self._ctx.jax_device()),
                tag="serve_weights")
                for n, s in self._placeholder_shapes(in_shapes).items()}
            data_avals = {n: jax.ShapeDtypeStruct(s, self._input_dtypes[n])
                          for n, s in in_shapes.items()}
            to_aval = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
            extra_avals = {k: to_aval(v) for k, v in extra.items()}
            param_avals = {k: to_aval(v) for k, v in self._params.items()}
            aux_avals = {k: to_aval(v) for k, v in self._aux.items()}
            # bucket padding is only sound for batch-major outputs
            # (valid rows slice back out on axis 0) — reject scalar /
            # non-batch-major outputs HERE with a clear error instead of
            # silently serving corrupted values (a batch-diluted mean,
            # a time-major RNN output) or crashing at slice time
            out_shapes = [o.shape for o in jax.eval_shape(
                self._jit, data_avals, extra_avals, param_avals,
                aux_avals, self._rng)]
            bad = [s for s in out_shapes
                   if len(s) < 1 or s[0] != key[0]]
            if bad:
                raise MXNetError(
                    f"output shapes {out_shapes} are not batch-major "
                    f"(axis 0 != bucket batch {key[0]}): this symbol "
                    f"cannot be served through bucket padding "
                    f"(docs/inference.md)")
            with warnings.catch_warnings():
                # CPU/odd backends report "donated buffers were not
                # usable" when no output aliases the input shape; the
                # donation is a best-effort HBM release, not a contract
                warnings.filterwarnings(
                    "ignore", message=".*donated buffers.*")
                _t0_compile = time.perf_counter()
                compiled = self._jit.lower(
                    data_avals, extra_avals, param_avals, aux_avals,
                    self._rng).compile()
            from ..observability import goodput as _goodput
            if _goodput.ENABLED:
                # measured XLA compile (or persistent-cache load) time
                # books as recompile badput: seconds a request spent
                # waiting on program build, not dispatch
                _goodput.attribute("recompile",
                                   time.perf_counter() - _t0_compile)
            from .. import base as _base
            readmission = (key in self._ever_compiled
                           and _base.compile_cache_active())
            if _metrics.ENABLED:
                if readmission:
                    # rebuilding an evicted bucket with the persistent
                    # compile cache warm: the lower().compile() above
                    # was a disk hit, not a fresh XLA compile — counted
                    # as a readmission so SERVE_COMPILES keeps meaning
                    # "requests escaped the bucket set"
                    _metrics.SERVE_READMITS.inc(kind="bucket")
                else:
                    _metrics.SERVE_COMPILES.inc()
                    if key in self._ever_compiled:
                        # evicted bucket rebuilt WITHOUT the persistent
                        # cache: a real recompile AND a readmission
                        _metrics.SERVE_READMITS.inc(kind="bucket")
            self._ever_compiled.add(key)
            # compiled cost + HBM table per bucket, straight from XLA's
            # own analyses — what serving this bucket COSTS before any
            # request runs.  note_program is the ONE compiled-stats
            # surface (ISSUE 13): it files the memory stats into the
            # HBM ledger's report()["compiled"] AND the program
            # registry; the label rides the bounded bucket lattice,
            # the flight recorder's bucket_label discipline.
            try:
                label = bucket_label(key)
                mem = _introspect.note_program(
                    "serve_bucket", compiled=compiled,
                    label=label).get("memory", {})
                if not mem and not _introspect.ENABLED:
                    # introspection off: keep the PR 9 stats path alive
                    mem = _memory.compiled_stats_dict(
                        compiled.memory_analysis())
                    if mem:
                        _memory.note_compiled("serve_bucket:" + label, mem)
            except Exception:  # noqa: BLE001 — stats are best-effort
                mem = {}
            if mem:
                self._mem_stats[key] = mem
                if _metrics.ENABLED:
                    _metrics.SERVE_BUCKET_HBM_BYTES.set(
                        mem["peak_bytes"], bucket=label)
            self._extra[key] = extra
            self._compiled[key] = compiled
            self._bucket_used[key] = time.monotonic()
            return compiled

    def warmup(self, keys=None) -> "BucketedPredictor":
        """Compile every bucket (or the given keys) ahead of traffic —
        after this, serving any request within the bucket set performs
        ZERO XLA compiles."""
        for key in (keys if keys is not None else self.spec.all_keys()):
            self.precompile(tuple(key))
        return self

    @property
    def num_compiled(self) -> int:
        return len(self._compiled)

    def memory_stats(self) -> dict:
        """Per-bucket compiled HBM costs + live weight bytes: the
        budgeting surface for a shared-HBM multi-model registry (and
        ``ResilientServer.readyz()``'s ``bucket_hbm`` detail).
        ``peak_bytes`` is XLA's own buffer-assignment high-water mark
        per bucket executable; ``weights_bytes`` is THIS instance's
        live served weights + bucket placeholders — per-model, so a
        multi-model budgeter sees what evicting this predictor would
        actually free (the process-wide ``serve_weights`` ledger tag
        sums over every predictor)."""
        # GIL-atomic snapshots first: precompile on another thread
        # (batcher, warmup) inserts new buckets concurrently; the inner
        # stat dicts are write-once at insert so copying them is safe
        stats = dict(self._mem_stats)
        resident = set(self._compiled)
        per_bucket = {}
        for k, v in sorted(stats.items()):
            d = dict(v)
            # evicted buckets keep their stats entry (it is the
            # registry's readmission cost estimate) but are flagged so
            # peak totals below only count executables that are LIVE
            d["resident"] = k in resident
            per_bucket[bucket_label(k)] = d
        live = [v for v in per_bucket.values() if v["resident"]]
        params, aux = self._weights
        weights = sum(_memory.nbytes_of(a) for d in (params, aux)
                      for a in d.values())
        weights += sum(_memory.nbytes_of(a)
                       for ph in dict(self._extra).values()
                       for a in ph.values())
        return {
            "buckets": per_bucket,
            "resident": self._resident,
            "peak_bytes_max": max(
                (v["peak_bytes"] for v in live), default=0),
            "peak_bytes_total": sum(v["peak_bytes"] for v in live),
            "weights_bytes": int(weights),
        }

    # -- serving -------------------------------------------------------------
    def _as_host(self, name: str, value) -> _np.ndarray:
        """Request payloads normalize to host numpy in the declared input
        dtype (the C predict API hands over host buffers; device-resident
        NDArrays are fetched — serving's contract is host-in/host-out)."""
        if isinstance(value, NDArray):
            value = value.asnumpy()
        arr = _np.asarray(value)
        dt = self._input_dtypes[name]
        if arr.dtype != dt:
            arr = arr.astype(dt)
        return arr

    def _served_names(self) -> list:
        return [n for n in self._input_names
                if n in self.spec.input_shapes]

    def _check_names(self, inputs) -> None:
        served = self._served_names()
        if set(inputs) != set(served):
            raise MXNetError(
                f"request needs exactly inputs {served}, got "
                f"{sorted(inputs)}")

    def _check_request(self, inputs: Dict[str, _np.ndarray]) -> None:
        """Validate one request's input set and geometry up front: exact
        served-input names, fixed (non-bucketed) dims matching the
        declared template, sequence inside the largest seq bucket, and
        one agreed batch size.  Raises MXNetError.  The micro-batcher
        runs this at submit() so a malformed request fails ALONE instead
        of poisoning the coalesced group it would have joined."""
        self._check_names(inputs)
        for n, a in inputs.items():
            tmpl = self.spec.input_shapes[n]
            if len(a.shape) != len(tmpl):
                raise MXNetError(
                    f"input '{n}': rank {len(a.shape)} != declared "
                    f"rank {len(tmpl)} {tmpl}")
            ax_seq = self.spec.seq_axes.get(n)
            for i in range(1, len(tmpl)):
                if i != ax_seq and a.shape[i] != tmpl[i]:
                    raise MXNetError(
                        f"input '{n}' dim {i} is {a.shape[i]}, declared "
                        f"{tmpl[i]} (only batch/seq axes may vary)")
        # one agreed batch size + seq inside the largest bucket
        self.spec.route({n: a.shape for n, a in inputs.items()})

    @hot_path
    def _dispatch(self, key: tuple, padded: dict) -> list:
        compiled = self.precompile(key)
        # snapshot the placeholders WITH the executable: a concurrent
        # registry bucket eviction between precompile and here drops
        # _extra[key]; one rebuild pass keeps the failure typed instead
        # of a KeyError poisoning the whole dispatch group
        extra = self._extra.get(key)
        if extra is None:
            compiled = self.precompile(key)
            extra = self._extra.get(key)
            if extra is None:
                raise ModelEvictedError(
                    f"bucket {key} evicted mid-dispatch — retry")
        self._bucket_used[key] = time.monotonic()  # LRU clock
        # the flight span opens BEFORE the chaos site: an injected
        # delay models a slow model under load, so it must show up as a
        # long serve_dispatch phase in the timeline — exactly what the
        # slow-request watchdog's auto-dump exists to attribute
        with _flight.phase_span("serve_dispatch", cat="serving",
                                labels={"bucket": bucket_label(key)},
                                mem=True), \
                _memory.oom_guard("serving.dispatch"):
            # chaos sites: delay = slow model under load, raise = failed
            # dispatch (surfaces to the caller/future); memory.oom = a
            # synthetic RESOURCE_EXHAUSTED exercising the post-mortem
            # (catch → ledger+ring dump → typed DeviceMemoryError)
            _fi_fire("serving.dispatch", key=key)
            _fi_fire("memory.oom", at="serving")
            if _metrics.ENABLED:
                _metrics.XLA_LAUNCHES.inc(kind="serve")
                _metrics.SERVE_BATCHES.inc()
            # one read: a mid-call hot_reload can't tear the pair
            params, aux = self._weights
            if not params and not aux and not self._resident:
                raise ModelEvictedError(
                    "model weights were evicted between precompile and "
                    "dispatch — readmit() and retry")
            try:
                return compiled(padded, extra, params, aux,
                                self._rng)
            except BaseException:
                # MXNET_SANITIZE twin (ISSUE 15): with donation on,
                # a failed dispatch may have consumed the padded
                # input buffers — poison the batch dict in place so
                # a retry that erroneously reuses it fails typed
                # (DonatedBufferError) instead of serving deleted
                # arrays.  One boolean test when off.
                if self._donate and _sanitizer.ENABLED:
                    _sanitizer.poison_mapping("serve_dispatch",
                                              padded)
                raise

    @hot_path
    def _predict_routed(self, inputs: Dict[str, _np.ndarray]) -> list:
        shapes = {n: a.shape for n, a in inputs.items()}
        key = self.spec.route(shapes)
        rows = next(iter(shapes.values()))[0]
        if key[0] is None:
            # request larger than the biggest bucket: chunk over it
            cap = self.spec.max_batch
            outs_per_chunk = []
            for lo in range(0, rows, cap):
                chunk = {n: a[lo:lo + cap] for n, a in inputs.items()}
                outs_per_chunk.append(self._predict_routed(chunk))
            return [_np.concatenate(parts, axis=0)
                    for parts in zip(*outs_per_chunk)]
        bucket_shapes = self.spec.bucket_input_shapes(key)
        with _flight.phase_span("serve_pad", cat="serving",
                                labels={"bucket": bucket_label(key)}):
            padded = {n: pad_to_shape(a, bucket_shapes[n])
                      for n, a in inputs.items()}
        if _metrics.ENABLED:
            _metrics.SERVE_PADDING_WASTE.set(
                self.spec.waste_fraction(key, shapes))
        outs = self._dispatch(key, padded)
        # valid-row mask: batch padding is dead rows at the tail; the
        # sequence axis (if any) is NOT sliced here — output seq layout
        # is model-defined (docs/inference.md).  The asarray below is
        # the request's ONE contractual device->host sync (serving is
        # host-in/host-out), not a hidden stall:
        with _flight.phase_span("serve_slice", cat="serving"):
            return [_np.asarray(o)[:rows] for o in outs]  # graft-lint: disable=host-sync

    def predict(self, *args, **kwargs) -> List[_np.ndarray]:
        """Run one request: positional args follow the symbol's input
        order, kwargs go by input name.  Returns host numpy outputs
        sliced to the request's valid rows."""
        served = self._served_names()
        if args:
            if kwargs or len(args) > len(served):
                raise MXNetError(
                    f"predict takes inputs {served} (got {len(args)} "
                    f"positional + {sorted(kwargs)})")
            kwargs = dict(zip(served, args))
        self._check_names(kwargs)  # before _as_host's dtype lookup
        t0 = time.perf_counter()
        inputs = {n: self._as_host(n, v) for n, v in kwargs.items()}
        self._check_request(inputs)
        fl = _flight.ENABLED
        trace_id = _flight.new_trace_id() if fl else None
        with _flight.trace_scope(trace_id) if fl \
                else _nullcontext():
            outs = self._predict_routed(inputs)
        dt = time.perf_counter() - t0
        if _metrics.ENABLED:
            _metrics.SERVE_REQUESTS.inc()
            _metrics.SERVE_LATENCY_SECONDS.observe(dt, exemplar=trace_id)
        if fl:
            _flight.note("serve_request", dt)
        return outs

    # C-predict-API-shaped alias (MXPredForward parity for callers
    # porting off `Predictor`)
    forward = predict

    # -- eviction / readmission (the multi-model HBM budget surface) ---------
    @property
    def resident(self) -> bool:
        """False after evict(): device weights (and every AOT bucket
        executable) are dropped; only the host param payload remains."""
        return self._resident

    def resident_bucket_ages(self) -> List[tuple]:
        """``[(key, last_used_monotonic)]`` for every RESIDENT bucket —
        the registry's LRU candidate list (stamped at precompile and at
        every dispatch)."""
        used = dict(self._bucket_used)
        return [(k, used.get(k, 0.0)) for k in list(self._compiled)]

    def bucket_cost_estimate(self, key: tuple) -> int:
        """Expected compiled peak HBM bytes of ``key`` — the admission
        question a budgeter asks BEFORE a precompile.  Exact for
        previously-compiled (evicted) buckets via their retained
        CompiledMemoryStats; a never-compiled bucket borrows the
        largest known peak of this model (0 when nothing is known yet —
        the ledger's hard budget stays the backstop)."""
        st = self._mem_stats.get(key)
        if st:
            return int(st.get("peak_bytes", 0))
        return int(max((v.get("peak_bytes", 0)
                        for v in dict(self._mem_stats).values()),
                       default=0))

    def host_payload_bytes(self) -> int:
        """Bytes the device weights would occupy on readmission (the
        host payload mirrors their shapes/dtypes exactly)."""
        p, a = self._host_payload
        return int(sum(_memory.nbytes_of(v) for d in (p, a)
                       for v in d.values()))

    def evict_bucket(self, key: tuple, blocking: bool = True) -> int:
        """Drop one bucket's AOT executable + zero placeholders (LRU
        bucket eviction).  The bucket's CompiledMemoryStats entry is
        kept as the readmission cost estimate.  Returns the estimated
        device bytes freed (compiled peak + tracked placeholders);
        idempotent.  ``blocking=False`` returns 0 when the compile
        lock is busy — a registry sweep must not stall every model's
        admission behind one model's in-flight XLA compile (a model
        mid-compile is not cold anyway)."""
        if not self._compile_lock.acquire(blocking=blocking):
            return 0
        try:
            return self._evict_bucket_locked(key)
        finally:
            self._compile_lock.release()

    def _evict_bucket_locked(self, key: tuple) -> int:
        if key not in self._compiled:
            return 0
        freed = int(self._mem_stats.get(key, {}).get("peak_bytes", 0))
        freed += sum(_memory.nbytes_of(a)
                     for a in self._extra.get(key, {}).values())
        del self._compiled[key]
        self._extra.pop(key, None)
        self._bucket_used.pop(key, None)
        if _metrics.ENABLED:
            # the per-bucket HBM gauge must not advertise an
            # executable that no longer exists
            _metrics.SERVE_BUCKET_HBM_BYTES.remove(
                bucket=bucket_label(key))
        return freed

    def evict(self, blocking: bool = True) -> int:
        """Full model eviction: every bucket executable, every zero
        placeholder, and the device weights are dropped — the host
        param payload stays, so ``readmit()`` is a reload + (cache-hit)
        recompile, never a restart.  Returns estimated device bytes
        freed.  In-flight dispatches that already read the weights pair
        finish on the old buffers (freed when they complete); new
        dispatches raise a typed ``ModelEvictedError``.
        ``blocking=False`` returns 0 when the compile lock is busy —
        a model mid-compile is not a cold victim, and a registry sweep
        holding its own lock must not stall every admission behind
        this model's XLA compile."""
        if not blocking:
            # probe-then-recurse: the RLock makes the blocking branch's
            # `with` nest inside this probe hold, so the busy check and
            # the eviction are one atomic acquisition
            if not self._compile_lock.acquire(blocking=False):
                return 0
            try:
                return self.evict()
            finally:
                self._compile_lock.release()
        with self._compile_lock:
            freed = 0
            # residency flips first: a dispatch racing this sees either
            # the full old pair (serves fine) or the empty pair + flag
            self._resident = False
            self._was_evicted = True
            for key in list(self._compiled):
                freed += self.evict_bucket(key)
            params, aux = self._weights
            freed += sum(_memory.nbytes_of(a) for d in (params, aux)
                         for a in d.values())
            self._weights = ({}, {})
            return freed

    # back-compat-friendly alias: "weights eviction" in the ladder docs
    evict_weights = evict

    def readmit(self) -> None:
        """Re-upload the host param payload to the device and mark the
        model servable again.  Bucket executables rebuild lazily at the
        next dispatch per key — a persistent-compile-cache hit when
        ``JAX_COMPILATION_CACHE_DIR`` is wired (counted as
        ``mxnet_serve_readmissions_total{kind="bucket"}``, never as a
        ``SERVE_COMPILES`` escape).  Idempotent."""
        with self._compile_lock:
            if self._resident:
                return
            if self._closed:
                raise MXNetError("predictor is closed")
            dev_j = self._ctx.jax_device()

            def _to_dev(v):
                return _memory.register(jax.device_put(v, dev_j),
                                        tag="serve_weights")

            host_p, host_a = self._host_payload
            # oom_guard: on a genuinely full device the upload fails
            # TYPED (DeviceMemoryError + post-mortem), never a raw
            # backend RESOURCE_EXHAUSTED — the ladder contract holds
            # at the readmission chokepoint too, and a registry can
            # map it to ModelUnavailable
            with _memory.oom_guard("serving.readmit"):
                self._weights = (
                    {k: _to_dev(v) for k, v in host_p.items()},
                    {k: _to_dev(v) for k, v in host_a.items()})
            self._resident = True
            was_evicted = self._was_evicted
        if was_evicted and _metrics.ENABLED:
            # a resident=False construction admitting for the first
            # time is not churn — only an evict->readmit cycle counts
            _metrics.SERVE_READMITS.inc(kind="model")

    def close(self) -> None:
        """Tear the predictor down completely: auto-reload stopped,
        device weights + executables + placeholders dropped, host
        payload released — every ledger-tagged byte (serve_weights
        device-side, serve_host_params host-side) returns to baseline
        once the caller drops its reference.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self.stop_auto_reload()
        with self._compile_lock:
            self.evict()
            self._host_payload = ({}, {})
            self._mem_stats.clear()
            self._ever_compiled.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- checkpoint hot reload ----------------------------------------------
    @property
    def loaded_step(self):
        """Step of the last hot-reloaded checkpoint (None = construction
        params still serving)."""
        return getattr(self, "_loaded_step", None)

    def _as_checkpoint_manager(self, source):
        from ..checkpoint import CheckpointManager
        if isinstance(source, CheckpointManager):
            return source
        return CheckpointManager(str(source))

    def hot_reload(self, source, step=None) -> int:
        """Swap the served weights for those of the newest valid
        checkpoint under ``source`` (a checkpoint directory or
        ``CheckpointManager``) WITHOUT recompiling — shapes/dtypes must
        match the serving graph, so every AOT bucket executable keeps
        working.  Torn/corrupt checkpoints are skipped by the manager's
        validated restore; a checkpoint missing any served parameter
        raises and the old weights keep serving (no partial swap).
        Returns the loaded step."""
        from ..checkpoint import (ARG_PREFIX, AUX_PREFIX, PARAM_PREFIX)
        # chaos site: a raise here proves the old-weights-keep-serving
        # contract — auto-reload catches, counts, and keeps polling
        _fi_fire("serving.hot_reload")
        if not self._resident:
            # an evicted model has no served weights to swap; auto-reload
            # counts this as a failed poll and retries — the next poll
            # after readmit() picks the checkpoint up
            raise MXNetError(
                "hot_reload: model weights are evicted — readmit() first")
        mgr = self._as_checkpoint_manager(source)
        res = mgr.restore(step)
        if res is None:
            raise MXNetError(
                f"hot_reload: no valid checkpoint under {mgr.directory!r}")
        got_step, state = res

        # prefix-respecting lookup: a parameter loads from param:/arg:
        # entries only, aux state from aux: (falling back to param: —
        # gluon checkpoints carry BN running stats as Parameters).  An
        # arg: entry can never silently satisfy an aux name or vice
        # versa even when base names collide.
        new_host = ({}, {})

        def _lookup(name, prefixes, what, cur, host_out):
            for prefix in prefixes:
                if prefix + name in state:
                    arr = _np.asarray(state[prefix + name])
                    if tuple(arr.shape) != tuple(cur.shape):
                        raise MXNetError(
                            f"hot_reload: {what} '{name}' shape "
                            f"{arr.shape} != serving shape "
                            f"{tuple(cur.shape)}")
                    arr = arr.astype(cur.dtype, copy=False)
                    host_out[name] = _memory.register_host(
                        arr, tag="serve_host_params")
                    return _memory.register(jax.device_put(arr, dev_j),
                                            tag="serve_weights")
            raise MXNetError(
                f"hot_reload: checkpoint step {got_step} lacks served "
                f"{what} '{name}' — old weights keep serving")

        dev_j = self._ctx.jax_device()
        old_params, old_aux = self._weights
        new_params = {name: _lookup(name, (PARAM_PREFIX, ARG_PREFIX),
                                    "parameter", cur, new_host[0])
                      for name, cur in old_params.items()}
        new_aux = {name: _lookup(name, (AUX_PREFIX, PARAM_PREFIX),
                                 "aux state", cur, new_host[1])
                   for name, cur in old_aux.items()}
        # ONE reference assignment commits both dicts together:
        # in-flight _dispatch calls hold the old pair, new requests see
        # the new pair — never params of one step with aux of another.
        # Committed under the lifecycle lock: an evict/close racing
        # this swap must not be clobbered by a late reload commit
        with self._compile_lock:
            if not self._resident:
                raise MXNetError(
                    "hot_reload: model was evicted mid-reload — "
                    "readmit() first")
            self._weights = (new_params, new_aux)
            # the readmission source must follow the served weights, or
            # an evict/readmit cycle would resurrect pre-reload params
            self._host_payload = new_host
            self._loaded_step = got_step
        return got_step

    def start_auto_reload(self, source, interval_s: float = 30.0) -> None:
        """Poll ``source`` every ``interval_s`` and hot-reload whenever
        a newer valid checkpoint lands — the training-to-serving
        weight pipeline with no restarts.  Polling cost is one
        directory scan.

        Failure contract: a transiently missing/corrupt checkpoint dir
        or a failed weight swap is logged, counted in
        ``mxnet_serve_reload_failures_total``
        (``snapshot()["serving"]["reload_failures"]``), and the
        PREVIOUS weights keep serving — the poll thread never dies.
        ``_last_reload_ok`` tracks the last successful poll so
        ``ResilientServer.readyz()`` can flag hot-reload staleness."""
        import logging
        if getattr(self, "_reload_thread", None) is not None:
            raise MXNetError("auto-reload already running")
        mgr = self._as_checkpoint_manager(source)
        stop = threading.Event()
        self._reload_interval_s = float(interval_s)
        # a just-started poller is healthy by definition: staleness is
        # measured from here until the first (possibly failing) poll
        self._last_reload_ok = time.monotonic()
        self._last_reload_error: Optional[str] = None

        def _poll():
            while not stop.wait(interval_s):
                try:
                    newest = mgr.latest_step()
                    if newest is not None and newest != self.loaded_step:
                        self.hot_reload(mgr)
                    # a clean poll — including "nothing new" — refreshes
                    # the staleness clock
                    self._last_reload_ok = time.monotonic()
                    self._last_reload_error = None
                except Exception as e:  # noqa: BLE001 — keep serving
                    self._last_reload_error = f"{type(e).__name__}: {e}"
                    if _metrics.ENABLED:
                        _metrics.SERVE_RELOAD_FAILURES.inc()
                    logging.getLogger(__name__).warning(
                        "auto-reload failed (serving old weights): %s", e)

        self._reload_stop = stop
        self._reload_thread = threading.Thread(
            target=_poll, name="mxt-serve-reload", daemon=True)
        self._reload_thread.start()

    def stop_auto_reload(self) -> None:
        t = getattr(self, "_reload_thread", None)
        if t is None:
            return
        self._reload_stop.set()
        t.join(timeout=5)
        self._reload_thread = None
