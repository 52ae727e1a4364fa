"""Metrics registry: counters, gauges, histograms with labels +
Prometheus-text / JSON exporters.

Design rules (set by the round-2 regression this subsystem exists to
catch — instrumentation must never become the overhead it measures):

  - module-level fast-path flag: every runtime hook reads `ENABLED`
    (plain module global) before touching a metric, so
    MXNET_METRICS_ENABLED=0 costs one boolean test per hook;
  - stable identity: metrics are created ONCE at import and looked up by
    attribute, never by name on the hot path — `inc()` on the unlabeled
    fast path is a single float add, no dict allocation;
  - on-demand expensive data: HBM usage (`device.memory_stats()`) is
    sampled inside `collect()`/`snapshot()`, never per-step.

Prometheus text format follows the exposition format spec close enough
for a scrape endpoint (`# TYPE` lines, `{label="v"}` selectors,
histogram `_bucket`/`_sum`/`_count` series with cumulative `le`).
"""
from __future__ import annotations

import json as _json
import threading
import weakref
from typing import Dict, List, Optional, Tuple

from ..base import getenv
from ..analysis.sanitizer import make_lock as _make_lock

# -- the fast-path switch ----------------------------------------------------
# Hooks across engine/executor/kvstore/io read this module global directly:
#   if metrics.ENABLED: metrics.XLA_LAUNCHES.inc(...)
# bool default activates getenv's tolerant parsing ("0"/"false"/"" off)
ENABLED: bool = getenv("MXNET_METRICS_ENABLED", True)


def enabled() -> bool:
    return ENABLED


def enable() -> None:
    global ENABLED
    ENABLED = True


def disable() -> None:
    global ENABLED
    ENABLED = False


# -- metric primitives -------------------------------------------------------
# One shared mutation lock: hooks fire from the training thread AND from
# data-pipeline producer threads (PrefetchingIter, DataLoader pools); an
# unguarded read-modify-write would drop increments and corrupt the
# exact-count invariant dispatch_counts() advertises.  Contention is a
# few acquisitions per training step — noise next to an XLA dispatch.
# (sanitizer factory: a plain threading.Lock unless MXNET_SANITIZE=1,
# in which case it joins the lock-order graph as "metrics.mut")
_MUT_LOCK = _make_lock("metrics.mut")


def _label_key(labels: dict) -> Tuple:
    return tuple(sorted(labels.items()))


class Metric:
    """Base: name + help + label-set → value(s)."""

    kind = "untyped"

    def __init__(self, name: str, help: str = "", registry=None):
        self.name = name
        self.help = help
        (registry if registry is not None else REGISTRY)._register(self)

    def reset(self) -> None:
        raise NotImplementedError

    def samples(self) -> List[Tuple[str, Tuple, float]]:
        """[(series_name, label_items, value)] for the exporters."""
        raise NotImplementedError


class Counter(Metric):
    """Monotonic counter.  The unlabeled path is one float add (hot-path
    safe); labeled children live in a dict keyed by sorted label items."""

    kind = "counter"

    def __init__(self, name, help="", registry=None):
        self._value = 0.0
        self._children: Dict[Tuple, float] = {}
        super().__init__(name, help, registry)

    def inc(self, value: float = 1.0, **labels) -> None:
        if labels:
            k = _label_key(labels)
            with _MUT_LOCK:
                self._children[k] = self._children.get(k, 0.0) + value
        else:
            with _MUT_LOCK:
                self._value += value

    @property
    def value(self) -> float:
        # list() snapshots in one GIL-atomic C copy: hook threads may
        # insert a new label key while we read
        return self._value + sum(list(self._children.values()))

    def get(self, **labels) -> float:
        return self._children.get(_label_key(labels), 0.0) if labels \
            else self._value

    def reset(self) -> None:
        self._value = 0.0
        self._children.clear()

    def fold_label(self, label: str, value, replacement) -> None:
        """Merge every child whose ``label`` equals ``value`` into the
        same label set with ``label=replacement`` — bounds label
        cardinality (e.g. evicted serving tenants fold into
        ``tenant="_evicted"``) while preserving the counter's total."""
        with _MUT_LOCK:
            for k in [k for k in list(self._children)
                      if dict(k).get(label) == value]:
                v = self._children.pop(k)
                d = dict(k)
                d[label] = replacement
                nk = _label_key(d)
                self._children[nk] = self._children.get(nk, 0.0) + v

    def samples(self):
        out = []
        if self._value or not self._children:
            out.append((self.name, (), self._value))
        for k, v in sorted(list(self._children.items())):
            out.append((self.name, k, v))
        return out


class Gauge(Metric):
    """Point-in-time value; optional callback makes it computed-on-read
    (used for HBM usage so device RPCs only happen at export time)."""

    kind = "gauge"

    def __init__(self, name, help="", registry=None, fn=None):
        self._value = 0.0
        self._children: Dict[Tuple, float] = {}
        self._fn = fn
        super().__init__(name, help, registry)

    def set(self, value: float, **labels) -> None:
        if labels:
            k = _label_key(labels)
            # same lock as replace_children(): a labeled set racing the
            # full-child-set swap must not land in the orphaned old dict
            # and vanish from every future export
            with _MUT_LOCK:
                self._children[k] = float(value)
        else:
            self._value = float(value)

    def inc(self, value: float = 1.0, **labels) -> None:
        if labels:
            k = _label_key(labels)
            with _MUT_LOCK:
                self._children[k] = self._children.get(k, 0.0) + value
        else:
            with _MUT_LOCK:
                self._value += value

    def dec(self, value: float = 1.0, **labels) -> None:
        self.inc(-value, **labels)

    def get(self, **labels) -> float:
        if self._fn is not None and not labels:
            return float(self._fn())
        return self._children.get(_label_key(labels), 0.0) if labels \
            else self._value

    def remove(self, **labels) -> None:
        """Drop one labeled child (gauges are point-in-time, so removal
        is semantically clean — used to keep per-tenant gauge
        cardinality bounded when a tenant is evicted)."""
        with _MUT_LOCK:
            self._children.pop(_label_key(labels), None)

    def replace_children(self, items) -> None:
        """Atomically swap the FULL labeled-child set from an iterable
        of ``(labels_dict, value)`` — one reference assignment, so an
        export racing the rebuild sees either the old or the new
        complete set, never a half-built one (the export-time pull
        refresh idiom, e.g. the memory ledger's per-tag gauge)."""
        children = {_label_key(labels): float(v) for labels, v in items}
        with _MUT_LOCK:
            # same lock discipline as inc/dec/remove — a concurrent
            # labeled mutator must not land its write in the orphaned
            # old dict and vanish from every future export
            self._children = children

    def reset(self) -> None:
        self._value = 0.0
        self._children.clear()

    def samples(self):
        if self._fn is not None:
            try:
                out = [(self.name, (), float(self._fn()))]
            except Exception:
                out = [(self.name, (), 0.0)]
            # computed gauges may ALSO carry labeled children (the
            # per-mesh-axis MFU/flops splits refreshed by the fn pull)
            for k, v in sorted(list(self._children.items())):
                out.append((self.name, k, v))
            return out
        out = []
        if self._value or not self._children:
            out.append((self.name, (), self._value))
        for k, v in sorted(list(self._children.items())):
            out.append((self.name, k, v))
        return out


# default: latency-ish spread from 100us to ~100s
_DEFAULT_BUCKETS = (1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2, 0.1, 0.5, 1.0,
                    5.0, 10.0, 60.0)


class Histogram(Metric):
    """Fixed-bucket histogram (cumulative `le` buckets on export, like
    Prometheus); tracks sum + count so mean is recoverable.

    ``observe(value, exemplar=...)`` additionally remembers the latest
    exemplar (a flight-recorder trace_id) per bucket — the OpenMetrics
    exemplar idea: a p99 bucket links to one concrete recorded request
    timeline instead of an anonymous count (``exemplars()``,
    ``snapshot()["serving"]["latency_exemplars"]``)."""

    kind = "histogram"

    def __init__(self, name, help="", buckets=_DEFAULT_BUCKETS,
                 registry=None):
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # +inf tail
        self._sum = 0.0
        self._count = 0
        self._exemplars: Dict[int, Tuple[float, object]] = {}
        super().__init__(name, help, registry)

    def observe(self, value: float, exemplar=None) -> None:
        with _MUT_LOCK:
            self._sum += value
            self._count += 1
            for i, b in enumerate(self.buckets):
                if value <= b:
                    self._counts[i] += 1
                    if exemplar is not None:
                        self._exemplars[i] = (value, exemplar)
                    return
            self._counts[-1] += 1
            if exemplar is not None:
                self._exemplars[len(self.buckets)] = (value, exemplar)

    def exemplars(self) -> Dict[str, dict]:
        """{le: {"value", "trace_id"}} for buckets that have one —
        the hop from a latency percentile to `flight` dump spans."""
        with _MUT_LOCK:
            items = list(self._exemplars.items())
        out = {}
        for i, (v, ex) in sorted(items):
            le = "+Inf" if i >= len(self.buckets) \
                else repr(float(self.buckets[i]))
            out[le] = {"value": v, "trace_id": ex}
        return out

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0

    def reset(self) -> None:
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._count = 0
        self._exemplars.clear()

    def samples(self):
        out, cum = [], 0
        for b, c in zip(self.buckets, self._counts):
            cum += c
            out.append((self.name + "_bucket", (("le", repr(float(b))),), cum))
        cum += self._counts[-1]
        out.append((self.name + "_bucket", (("le", "+Inf"),), cum))
        out.append((self.name + "_sum", (), self._sum))
        out.append((self.name + "_count", (), self._count))
        return out


class MetricsRegistry:
    """Name → Metric; collect/export/reset over the whole set."""

    def __init__(self):
        self._metrics: Dict[str, Metric] = {}
        self._lock = _make_lock("metrics.registry")

    def _register(self, metric: Metric) -> None:
        with self._lock:
            if metric.name in self._metrics:
                raise ValueError(f"duplicate metric {metric.name}")
            self._metrics[metric.name] = metric

    def get(self, name: str) -> Optional[Metric]:
        return self._metrics.get(name)

    def metrics(self) -> List[Metric]:
        return list(self._metrics.values())

    def reset(self) -> None:
        for m in self._metrics.values():
            m.reset()

    # -- exporters ----------------------------------------------------------
    def render_prometheus(self) -> str:
        lines = []
        for m in self._metrics.values():
            if m.help:
                lines.append(f"# HELP {m.name} {m.help}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            for series, labels, value in m.samples():
                sel = ""
                if labels:
                    sel = "{" + ",".join(f'{k}="{v}"' for k, v in labels) + "}"
                v = repr(float(value)) if isinstance(value, float) \
                    else str(value)
                lines.append(f"{series}{sel} {v}")
        return "\n".join(lines) + "\n"

    def render_json(self) -> str:
        return _json.dumps(self.to_dict(), sort_keys=True)

    def to_dict(self) -> dict:
        out = {}
        for m in self._metrics.values():
            if isinstance(m, Histogram):
                out[m.name] = {"type": "histogram", "sum": m.sum,
                               "count": m.count, "mean": m.mean,
                               "buckets": {repr(float(b)): c for b, c in
                                           zip(m.buckets, m._counts)},
                               "inf": m._counts[-1]}
            else:
                series = {}
                for name_, labels, value in m.samples():
                    key = ",".join(f"{k}={v}" for k, v in labels) or "_"
                    series[key] = value
                out[m.name] = {"type": m.kind, "values": series}
        return out


REGISTRY = MetricsRegistry()

# -- the runtime metric set ---------------------------------------------------
# Stable module-level objects: hooks reference these directly (no registry
# lookup on the hot path) and tests may assert identity stays put across
# enable/disable flips.
XLA_LAUNCHES = Counter(
    "mxnet_xla_launches_total",
    "Compiled XLA program launches by kind (fwd, bwd, fwd_bwd, fused_step, "
    "kvstore_merge, allreduce, optimizer, data)")
HELD_LAUNCHES = Counter(
    "mxnet_module_held_launch_total",
    "Forward-backward launches Module.prepare issued ahead of their step, "
    "by result: taken by the step, or dropped (another batch, a written "
    "array, a reshape, a monitor) and run again with the same key")
DEVICE_PUTS = Counter(
    "mxnet_device_put_total",
    "Explicit jax.device_put host->device / device->device transfers")
TRANSFER_BYTES = Counter(
    "mxnet_device_transfer_bytes_total",
    "Bytes moved by instrumented device transfers")
JIT_CACHE_HITS = Counter(
    "mxnet_jit_cache_hits_total",
    "Executor compiled-entry-point cache hits")
JIT_CACHE_MISSES = Counter(
    "mxnet_jit_cache_misses_total",
    "Executor compiled-entry-point cache misses (new jit closures)")
HOST_SYNC_READS = Counter(
    "mxnet_host_sync_reads_total",
    "Blocking reads of a device buffer by the host (NDArray.asnumpy / "
    "asscalar / wait_to_read): counted beside the mx.sync.read span")
PROGRAM_LOADS = Counter(
    "mxnet_program_loads_total",
    "XLA programs made ready to run, by how: compile (the backend "
    "compiled it) or cache (read from JAX's persistent compilation "
    "cache); from JAX's own monitoring events")
PROGRAM_LOAD_SECONDS = Counter(
    "mxnet_program_load_seconds_total",
    "Seconds JAX reports for those compiles and cache reads")
ENGINE_WAITS = Counter(
    "mxnet_engine_wait_total",
    "Engine blocking waits by kind (wait_for_var, wait_for_all)")
ENGINE_WAIT_SECONDS = Counter(
    "mxnet_engine_wait_seconds_total",
    "Seconds spent blocked in engine waits")
KVSTORE_PUSH_BYTES = Counter(
    "mxnet_kvstore_push_bytes_total",
    "Gradient bytes pushed into the kvstore")
KVSTORE_PULL_BYTES = Counter(
    "mxnet_kvstore_pull_bytes_total",
    "Parameter bytes pulled out of the kvstore")
KVSTORE_ALLREDUCE_SECONDS = Histogram(
    "mxnet_kvstore_allreduce_seconds",
    "Wall-clock latency of kvstore push/pushpull aggregation "
    "(includes cross-host allreduce when num_workers > 1)")
DATA_WAIT_SECONDS = Histogram(
    "mxnet_data_batch_wait_seconds",
    "Time the training loop waited for the next data batch")
OPTIMIZER_STEPS = Counter(
    "mxnet_optimizer_steps_total",
    "Optimizer step applications (fused multi-tensor update = 1)")
MONITOR_STATS = Counter(
    "mxnet_monitor_stats_total",
    "Executor monitor-callback stat records, by io direction")
FIT_STEP_DISPATCHES = Gauge(
    "mxnet_fit_step_dispatches",
    "XLA program launches + device_puts issued by the most recent "
    "steady-state Module.fit step, excluding async data-pipeline "
    "launches (the round-2 O(1)-dispatch invariant, now queryable)")
TRAINER_STEP_DISPATCHES = Gauge(
    "mxnet_trainer_step_dispatches",
    "XLA program launches + device_puts issued by the most recent "
    "gluon training step.  Fused path: Trainer.step's allreduce + "
    "optimizer (forward/backward are outside step() and counted under "
    "xla:fwd / xla:bwd).  Whole-step path (MXNET_WHOLE_STEP=1): the "
    "ENTIRE step — fwd+bwd+reduce+update ride one donated program "
    "(xla:whole_step), so this gauge reads 1")
SUPERSTEP_DISPATCHES = Gauge(
    "mxnet_superstep_dispatches",
    "XLA program launches + device_puts issued by the most recent "
    "superstep (K whole-steps lax.scan-compiled into one donated "
    "program, mxnet_tpu/autotune/superstep.py).  Scanned: 1 for the "
    "whole K-step superstep.  Reads ~K when the superstep silently "
    "demoted to K sequential whole-step dispatches — the perf "
    "sentinel's dispatches_per_step baseline for the 'superstep' "
    "phase trips on exactly that")
ALLREDUCE_BUCKETS = Gauge(
    "mxnet_allreduce_buckets",
    "Gradient buckets the most recent bucketed allreduce fused into "
    "(size-capped by MXNET_BUCKET_SIZE_MB; O(total grad bytes), "
    "independent of parameter count)")
PREFETCH_WAIT_SECONDS = Histogram(
    "mxnet_prefetch_wait_seconds",
    "Time the consumer blocked on the prefetch-to-device queue; near "
    "zero when the input pipeline keeps ahead of the device")
KVSTORE_WIRE_BYTES = Gauge(
    "mxnet_kvstore_wire_bytes",
    "PER-WORKER PAYLOAD bytes of the most recent compressed bucketed "
    "allreduce, by leg (intra = device-copy merge within a host, always "
    "full precision; dist = cross-host DCN) and stage (raw = what full "
    "precision would contribute, compressed = the packed 2-bit payload "
    "actually contributed, ~1/16 on float32).  NOTE: the compressed "
    "dist leg is an all-gather, so each worker RECEIVES "
    "(num_workers-1) x this payload — compare against a raw ring "
    "allreduce's ~2x raw bytes/worker when sizing pods (the 2-bit win "
    "holds up to ~32 workers)")
SERVE_REQUESTS = Counter(
    "mxnet_serve_requests_total",
    "Inference requests served by the serving fast path "
    "(mxnet_tpu.serving), coalesced or not")
SERVE_BATCHES = Counter(
    "mxnet_serve_batches_total",
    "Bucket dispatches issued by the serving fast path — one compiled "
    "XLA launch each; requests/batches is the coalescing factor")
SERVE_COMPILES = Counter(
    "mxnet_serve_compiles_total",
    "AOT bucket compiles (lower().compile()).  After warmup() this must "
    "stay FLAT under traffic — growth means requests are escaping the "
    "bucket set and paying hot-path compiles")
SERVE_QUEUE_DEPTH = Gauge(
    "mxnet_serve_queue_depth",
    "Requests waiting in the micro-batcher queue (sampled at "
    "submit/drain)")
SERVE_PADDING_WASTE = Gauge(
    "mxnet_serve_padding_waste",
    "Fraction of the most recent serving dispatch's input elements that "
    "were bucket padding (dead compute).  Persistently high means the "
    "bucket ladder is too coarse for the traffic: widen "
    "MXNET_SERVE_BUCKETS")
SERVE_COALESCED_ROWS = Gauge(
    "mxnet_serve_coalesced_rows",
    "Rows in the most recent coalesced micro-batch (before bucket "
    "padding)")
SERVE_LATENCY_SECONDS = Histogram(
    "mxnet_serve_request_seconds",
    "End-to-end request latency through the serving fast path (includes "
    "micro-batcher queue wait on the coalesced path)",
    buckets=(1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2,
             5e-2, 0.1, 0.25, 1.0, 5.0))
SERVE_ADMITTED = Counter(
    "mxnet_serve_admitted_total",
    "Requests admitted past ResilientServer admission control, by "
    "tenant (shed requests never count here)")
SERVE_SHED = Counter(
    "mxnet_serve_shed_total",
    "Requests rejected by admission control with a typed Overloaded "
    "error, by tenant and reason (queue_full = per-tenant bound hit, "
    "deadline_unmeetable = estimated wait already exceeds the request's "
    "deadline).  Shedding here is the DESIGN under overload: bounded "
    "p99 + rejections instead of tail-latency collapse")
SERVE_EXPIRED = Counter(
    "mxnet_serve_expired_total",
    "Admitted requests dropped before dispatch because their deadline "
    "passed in queue (typed DeadlineExceeded to the caller; expired "
    "work is NEVER padded or dispatched), by tenant")
SERVE_GOODPUT = Gauge(
    "mxnet_serve_goodput",
    "served / admitted fraction per tenant since process start — the "
    "overload acceptance gauge (>= 0.9 of admitted work must complete "
    "under 2x flood; shed requests are excluded by construction)")
SERVE_READY = Gauge(
    "mxnet_serve_ready",
    "1 when the most recently evaluated ResilientServer readyz() "
    "passes (warmup complete, dispatch latency / failure rate / stall "
    "/ hot-reload staleness within thresholds), else 0")
SERVE_READY_TRANSITIONS = Counter(
    "mxnet_serve_ready_transitions_total",
    "readyz flips, by direction (up = became ready, down = became "
    "unready).  A flapping counter is the page-the-oncall signal that "
    "the replica is oscillating around a threshold")
SERVE_EVICTIONS = Counter(
    "mxnet_serve_evictions_total",
    "LRU evictions by the multi-model HBM budgeter (serving."
    "ModelRegistry), by kind (bucket = one AOT executable + its zero "
    "placeholders dropped, model = device weights dropped too — host "
    "param payload kept for restart-free readmission) and model.  "
    "Eviction churn under a tight MXNET_HBM_BUDGET_MB is the DESIGN: "
    "the k+1'th model degrades by policy instead of OOMing the process "
    "(docs/multi_model.md)")
SERVE_READMITS = Counter(
    "mxnet_serve_readmissions_total",
    "Readmissions of evicted serving state, by kind (model = weights "
    "re-uploaded from the host payload, bucket = an evicted bucket's "
    "executable rebuilt — a persistent-compile-cache hit when "
    "JAX_COMPILATION_CACHE_DIR is set, so it never counts against the "
    "stay-flat SERVE_COMPILES contract).  readmissions/evictions is "
    "the churn ratio: high means the budget is too tight for the "
    "working set")
SERVE_RESIDENT_MODELS = Gauge(
    "mxnet_serve_resident_models",
    "Registered serving models whose device weights are currently "
    "resident (ModelRegistry; total registered minus weights-evicted).  "
    "Bounded by MXNET_SERVE_MAX_MODELS")
SERVE_MODEL_HBM_BYTES = Gauge(
    "mxnet_serve_model_hbm_bytes",
    "Tracked device bytes per registered serving model (its served "
    "weights + bucket placeholders; 0 while weights-evicted), by model "
    "label — the bounded per-model slice of the process-wide "
    "serve_weights ledger tag, refreshed on every eviction/readmission "
    "and at snapshot()")
SERVE_RELOAD_FAILURES = Counter(
    "mxnet_serve_reload_failures_total",
    "Serving auto-reload poll failures (missing/corrupt checkpoint "
    "dir, failed weight swap).  Each one kept serving the OLD weights; "
    "a climbing counter means the training->serving pipeline is broken "
    "while the replica still looks healthy")
DECODE_STEPS = Counter(
    "mxnet_decode_steps_total",
    "Continuous-batching decode steps (serving.DecodeEngine) — each is "
    "exactly ONE donated XLA dispatch over the whole in-flight slot "
    "set; compare against dispatch_counts()['decode'] to catch a step "
    "that silently multi-dispatched")
DECODE_TOKENS = Counter(
    "mxnet_decode_tokens_total",
    "Tokens generated by continuous-batching decode (prompt-consuming "
    "steps excluded)")
DECODE_KV_EVICTIONS = Counter(
    "mxnet_decode_kv_evictions_total",
    "Sequences whose paged KV state was reclaimed under HBM pressure "
    "(typed SequenceEvicted with retry-after to the caller).  KV pages "
    "are the CHEAPEST victims in the multi-model eviction ladder — "
    "churn here under a tight MXNET_HBM_BUDGET_MB is the design, a "
    "generative tenant bending before any classifier's weights do")
DECODE_INFLIGHT = Gauge(
    "mxnet_decode_inflight_sequences",
    "Sequences currently holding a decode slot (joined, not yet "
    "finished/retired) — refreshed every decode step")
DECODE_KV_OCCUPANCY = Gauge(
    "mxnet_decode_kv_page_occupancy",
    "Fraction of the currently-routed KV page lattice key's token "
    "capacity holding live sequence state.  Persistently low means the "
    "lattice is over-provisioned for the traffic (shrink "
    "MXNET_DECODE_SLOTS / MXNET_DECODE_MAX_PAGES)")
DECODE_TOKENS_PER_S = Gauge(
    "mxnet_decode_tokens_per_second",
    "Instantaneous decode throughput: active sequences advanced by the "
    "most recent step / its wall-clock (continuous batching's win over "
    "request-level coalescing is exactly this gauge under mixed-length "
    "traffic)")
FAULTS_INJECTED = Counter(
    "mxnet_faults_injected_total",
    "Faults fired by the mxnet_tpu.faultinject harness, by site and "
    "mode.  Nonzero in production means someone left MXNET_FAULT_PLAN "
    "set")
CHECKPOINT_SAVE_SECONDS = Histogram(
    "mxnet_checkpoint_save_seconds",
    "Full wall-clock of each checkpoint save, snapshot through atomic "
    "commit (async saves: measured on the writer thread)")
CHECKPOINT_SAVE_BLOCKED_SECONDS = Histogram(
    "mxnet_checkpoint_save_blocked_seconds",
    "Time CheckpointManager.save() blocked its caller — the step "
    "critical-path cost.  Async mode: just the device->host snapshot; "
    "sync mode: the whole write")
CHECKPOINT_RESTORE_SECONDS = Histogram(
    "mxnet_checkpoint_restore_seconds",
    "Wall-clock of each successful checkpoint restore (CRC validation "
    "included)")
CHECKPOINT_BYTES_WRITTEN = Counter(
    "mxnet_checkpoint_bytes_written_total",
    "Payload bytes committed by checkpoint saves (shard files)")
CHECKPOINT_LAST_STEP = Gauge(
    "mxnet_checkpoint_last_step",
    "Step of the most recent successfully committed checkpoint — a "
    "flat-lining value under traffic is the page-the-oncall signal "
    "that durable state has stopped advancing")
CHECKPOINT_FAILURES = Counter(
    "mxnet_checkpoint_failures_total",
    "Checkpoint subsystem failures by stage (save_attempt = retried "
    "transient IO error, save = retries exhausted, restore = torn/"
    "corrupt checkpoint skipped, gc = retention sweep error) and "
    "reason")
ANALYSIS_LOCK_VIOLATIONS = Counter(
    "mxnet_analysis_lock_order_violations_total",
    "Concurrency-sanitizer lock findings under MXNET_SANITIZE=1, by "
    "kind (cycle = ABBA ordering hazard across subsystem locks, "
    "reentry = same-thread re-acquisition of a non-reentrant lock — "
    "the PR 5 SIGTERM-mid-save deadlock class).  Nonzero anywhere, "
    "including chaos runs, is a bug")
ANALYSIS_SYNC_VIOLATIONS = Counter(
    "mxnet_analysis_sync_violations_total",
    "Device->host syncs observed inside analysis.no_sync() regions "
    "(runtime complement of the static host-sync graft-lint rule)")
FLIGHT_DUMPS = Counter(
    "mxnet_flight_dumps_total",
    "Flight-recorder timeline dumps by reason (manual = flight.dump() "
    "call, anomaly = slow-phase watchdog trip [k x EWMA, "
    "MXNET_FLIGHT_SLOW_FACTOR], signal = SIGUSR2, oom = device "
    "RESOURCE_EXHAUSTED post-mortem via memory.oom_guard).  A climbing "
    "anomaly "
    "count is the page-the-oncall signal that steps/requests keep "
    "blowing their own baseline — each dump under MXNET_FLIGHT_DIR "
    "holds the timeline of the moments before it")
MEMORY_LEDGER_BYTES = Gauge(
    "mxnet_memory_ledger_bytes",
    "Tracked live bytes by ledger tag and space (mxnet_tpu."
    "observability.memory; bounded tag set — param/grad/output/executor/"
    "optimizer_state/grad_bucket/compression_residual/serve_weights/"
    "kvstore/prefetch/data/checkpoint_host/serve_host_params, "
    "space=device|host [host = e.g. checkpoint snapshot twins and the "
    "serve_host_params readmission payload evicted serving models "
    "reload from], and "
    "_untagged for the unattributed remainder).  Bytes are LOGICAL "
    "(global) array bytes; on a GSPMD mesh memory.report() breaks each "
    "buffer into per-shard bytes (shard_bytes / spec fields) and "
    "per-tag shard totals — the per-device HBM cost, NOT the "
    "replicated sum.  Refreshed at export "
    "time from the weakref ledger, never on the hot path")
SERVE_BUCKET_HBM_BYTES = Gauge(
    "mxnet_serve_bucket_hbm_bytes",
    "Compiled peak HBM bytes per serving bucket (CompiledMemoryStats "
    "of the AOT executable, set once at precompile; labels are the "
    "bounded bucket-lattice set).  The multi-model HBM budgeter's "
    "per-bucket cost table — what an LRU bucket eviction would free")
# -- mixture-of-experts layers ------------------------------------------------
# Every expert block (gluon.model_zoo.transformer.MoEFeedForward) keeps a
# float32 load counter on its device, which its forward pass adds to through
# the auxiliary path (as BatchNorm's moving statistics travel).  Nothing is
# read in the step: `refresh_moe()` stacks the counters of the live blocks
# and reads them in ONE transfer when the registry is exported or a reader
# asks.
_moe_layers = weakref.WeakKeyDictionary()  # block -> its counter as last read

MOE_ASSIGNMENTS = Counter(
    "mxnet_moe_assignments_total",
    "(token, choice) pairs the routers of this process's expert layers "
    "assigned, by where the chosen expert lives (held = one of the experts "
    "this device holds, absent = an expert of another device, whose part "
    "of the result is not computed here, skipped = a layer's skip choice, "
    "which no device holds and which adds nothing; neither of the last two "
    "enters mxnet_moe_expert_load_max_over_mean).  Filled from the layers' "
    "device-side load counters at export (one device read), never in the "
    "step")
MOE_LOAD_MAX_OVER_MEAN = Gauge(
    "mxnet_moe_expert_load_max_over_mean",
    "Largest assignment count of any held expert of any layer since the "
    "layers were built, over the mean of all of them: 1.0 is even routing; "
    "the busiest expert sets a grouped product's critical path and, across "
    "devices, the straggler.  Refreshed with "
    "mxnet_moe_assignments_total")
MOE_BUFFER_ROWS = Counter(
    "mxnet_moe_buffer_rows_total",
    "Rows of the expert layers' buffers, by kind: live = the (token, choice) "
    "pairs routed to a held expert (mxnet_moe_assignments_total{where=held}), "
    "processed = the rows the buffers around the grouped products were as "
    "long as (gathered, masked, cast and added back), the whole T x top_k "
    "where a layer keeps its products, else the rung of ops/decoder.py "
    "buffer_rungs the device chose.  processed over live is what the row "
    "plumbing does for each row that counts.  Filled as "
    "mxnet_moe_assignments_total is")
MOE_ROWS = Gauge(
    "mxnet_moe_rows",
    "Rows of one expert layer's grouped products, set from shapes when the "
    "op is traced, by kind: required = tokens x top_k x held / num_experts "
    "(the expectation under even routing), multiplied = the rows the "
    "products are issued for, padding included (in expectation where the "
    "product skips the row tiles its group sizes leave empty; tokens x "
    "top_k, the dropless bound, where it multiplies the whole buffer)")

# -- a recorded CachedOp call -------------------------------------------------
# Set from shapes when gluon/block.py traces a recording forward program, as
# MOE_ROWS is: nothing runs in the step.  They hold the program traced last.
CACHEDOP_RESIDUAL_BYTES = Gauge(
    "mxnet_cachedop_residual_bytes",
    "Bytes of the residuals a recorded CachedOp call hands its backward "
    "program, by kind: kept = what the forward program writes for it and "
    "the tape holds until backward clears it (outputs of matrix products, "
    "convolutions, grouped products and kernel calls), primal = the "
    "forward program's own inputs it reuses (parameters, data, key), "
    "passed again and never copied")
CACHEDOP_BACKWARDS = Counter(
    "mxnet_cachedop_backward_total",
    "Launches of a CachedOp backward program that ran from the forward "
    "program's residuals (every recorded CachedOp call that backward "
    "reaches)")

# -- the forward attention kernel ---------------------------------------------
# Set from shapes when ops/flash_attention.py traces a call, as MOE_ROWS is:
# nothing runs in the step.  They hold the call traced last.
FLASH_FWD_BLOCKS = Gauge(
    "mxnet_flash_fwd_blocks",
    "(query tile, key tile) pairs of one forward flash-attention call, over "
    "all its batch entries and heads, by kind: grid = the pairs the kernel's "
    "grid holds, computed = those it multiplies (under a causal mask the "
    "pairs wholly above the diagonal are neither fetched nor computed; "
    "without one the two are equal)")
FLASH_FWD_TILE = Gauge(
    "mxnet_flash_fwd_tile",
    "Rows of the query tile (dim=q) and of the key / value tile (dim=k) "
    "that the call counted in mxnet_flash_fwd_blocks ran with: chosen from "
    "the shapes (ops/flash_attention.py _fa_tiles) unless the caller of "
    "flash_attention gave block_q / block_k")
FLASH_FWD_TILES = Counter(
    "mxnet_flash_fwd_tiles_total",
    "(query tile, key tile) pairs of the forward flash-attention calls "
    "traced so far, over all their batch entries and heads, by kind: "
    "visited = the pairs the kernel's loops run (mxnet_flash_fwd_blocks' "
    "computed, added up over the calls), needed = the pairs in which the "
    "call's mask (causal, window) leaves at least one query a key, counted "
    "from the mask.  Added to when a call is traced, as mxnet_moe_rows is "
    "set: nothing runs in the step, and only the ratio of the two means "
    "anything (a program traced twice adds to both).  1.0 is a kernel "
    "that visits no tile the mask empties")
FLASH_BWD = Counter(
    "mxnet_flash_bwd_total",
    "Backward flash-attention calls traced so far, by path: kernel = the "
    "two Pallas kernels (ops/flash_attention.py _bwd_kernels), reference = "
    "the plain float32 pass, taken where the tiles cannot cover the shapes "
    "(the forward then took the dense reference and kept no statistics).  "
    "Added to when a call is traced: nothing runs in the step")
FLASH_BWD_TILES = Counter(
    "mxnet_flash_bwd_tiles_total",
    "Scores tiles of the backward flash-attention kernels traced so far "
    "(the dQ kernel's and the dK/dV kernel's together), over all their "
    "batch entries and query heads, by kind: visited = the tiles the "
    "kernels' loops run, needed = those in which the call's mask (causal, "
    "window) leaves at least one query a key, counted from the mask.  "
    "Added to when a call is traced, as mxnet_flash_fwd_tiles_total is")


def watch_moe_layer(block) -> None:
    """Register an expert block whose `load` parameter `refresh_moe` reads."""
    _moe_layers[block] = None


def _read_states(blocks, attr):
    """[(block, its float32 state `attr` as a float64 row)] of the blocks
    whose state is initialized, all read in ONE transfer."""
    import numpy as np
    found = [(b, data._data.reshape(-1)) for b, data in
             ((b, getattr(getattr(b, attr), "_data", None)) for b in blocks)
             if data is not None]
    if not found:
        return []
    import jax.numpy as jnp
    flat = np.asarray(jnp.concatenate([a for _b, a in found]),
                      dtype=np.float64)
    rows = np.split(flat, np.cumsum([a.shape[0] for _b, a in found])[:-1])
    return [(b, row) for (b, _a), row in zip(found, rows)]


def refresh_moe() -> None:
    """Pull the load counters of every live expert layer (one stacked
    device read; a row is each held expert's assignments, absent ones',
    skipped ones' where the layer is scored, the buffer's rows)
    into MOE_ASSIGNMENTS, MOE_BUFFER_ROWS and
    MOE_LOAD_MAX_OVER_MEAN."""
    import numpy as np
    states = _read_states(list(_moe_layers), "load")
    if not states:
        return
    held_all = []
    for block, row in states:
        last = _moe_layers.get(block)
        delta = row - last if last is not None and (row >= last).all() \
            else row
        _moe_layers[block] = row
        # a scored layer has a skip choice: one slot more, before the
        # buffer's
        skip = bool(getattr(block, "scored", False))
        held = row.shape[0] - 2 - skip
        live = float(delta[:held].sum())
        MOE_ASSIGNMENTS.inc(live, where="held")
        MOE_ASSIGNMENTS.inc(float(delta[held]), where="absent")
        if skip:
            MOE_ASSIGNMENTS.inc(float(delta[held + 1]), where="skipped")
        MOE_BUFFER_ROWS.inc(live, kind="live")
        MOE_BUFFER_ROWS.inc(float(delta[-1]), kind="processed")
        held_all.append(row[:held])
    held_all = np.concatenate(held_all)
    if held_all.sum() > 0:
        MOE_LOAD_MAX_OVER_MEAN.set(float(held_all.max() / held_all.mean()))


# -- looped models -------------------------------------------------------------
# A looped model (gluon.model_zoo.decoder.LoopedLM) applies one stack of layers
# several times a step.  The first two gauges are set from shapes when the
# model's graph is traced, as MOE_ROWS is; the third is filled as the expert
# layers' load is: the model's `ExitDistribution` keeps one stacked float32
# state on its device, the forward pass adds to it through the auxiliary
# path, and `refresh_loop()` reads it in ONE transfer at export.
_loop_exits = weakref.WeakKeyDictionary()  # ExitDistribution blocks alive
_LOOP_STEP_LABELS = tuple(str(t) for t in range(1, 65))  # a bounded set

LOOP_APPLICATIONS = Gauge(
    "mxnet_loop_applications",
    "Layer applications of one forward pass of a looped model: layers of "
    "the stack x loop steps.  Set from shapes when the model is traced")
LOOP_STACK_COPIES = Gauge(
    "mxnet_loop_stack_copies",
    "Copies of a looped model's stack of layers in the text of the program "
    "traced last: 1 where the loop is a node of the graph "
    "(contrib.foreach), the number of loop steps where it is unrolled.  "
    "Counted while the model is traced: the times the stack was traced")
LOOP_EXIT_MASS = Gauge(
    "mxnet_loop_exit_mass",
    "Mean over the tokens seen since the model was built of the "
    "probability that a token leaves a looped model at loop step `step` "
    "(1..R; they sum to 1).  Filled from the model's device-side state at "
    "export (one device read), never in the step")


def watch_loop_exits(block) -> None:
    """Register a block whose `mass` parameter `refresh_loop` reads."""
    _loop_exits[block] = None


def refresh_loop() -> None:
    """Pull the exit mass of the live looped models (one stacked device
    read) into LOOP_EXIT_MASS."""
    import numpy as np
    rows = [row for _b, row in _read_states(list(_loop_exits), "mass")]
    if not rows:
        return
    steps = min(max(len(r) for r in rows) - 1, len(_LOOP_STEP_LABELS))
    mass, tokens = np.zeros(steps), 0.0
    for row in rows:
        mass[:len(row) - 1] += row[:-1][:steps]
        tokens += row[-1]
    if tokens > 0:
        for t in range(steps):
            LOOP_EXIT_MASS.set(float(mass[t] / tokens),
                               step=_LOOP_STEP_LABELS[t])


FUSED_DTYPE_RECOMPILES = Counter(
    "mxnet_fused_dtype_policy_recompiles_total",
    "Compiled-step program recompiles caused by a dtype-policy "
    "(MXNET_AMP) change, by step mode (update_all / whole_step).  Each "
    "is deliberate and LOUD (FusedUpdater.lookup_program logs it): the "
    "alternative — silently reusing a program traced under another "
    "precision for bf16/fp16 gradients — would train in the wrong "
    "dtype without ever erroring.  A count that climbs every step "
    "means something is flapping MXNET_AMP mid-run")
SUPERVISOR_SNAPSHOTS = Counter(
    "mxnet_supervisor_snapshots_total",
    "Rolling host snapshots the TrainingSupervisor took (every "
    "MXNET_SUPERVISE_SNAPSHOT_STEPS) — the donation-safe restore points "
    "transient-step retries rebuild from")
SUPERVISOR_RETRIES = Counter(
    "mxnet_supervisor_retries_total",
    "Supervised training steps re-executed after a transient failure "
    "(restore last snapshot -> replay window -> retry).  A climbing "
    "count with training still progressing is the supervisor doing its "
    "job; pair with faults_injected to tell chaos from real faults")
SUPERVISOR_REWINDS = Counter(
    "mxnet_supervisor_rewinds_total",
    "Snapshot restores performed by the TrainingSupervisor, by reason "
    "(retry = transient-step recovery, divergence = "
    "MXNET_SUPERVISE_ON_DIVERGE=rewind)")
SUPERVISOR_WATCHDOG_TRIPS = Counter(
    "mxnet_supervisor_watchdog_trips_total",
    "Training watchdog firings by kind (divergence = "
    "MXNET_SUPERVISE_DIVERGE_PATIENCE consecutive nonfinite losses, "
    "stall = a step blew its EWMA-derived deadline).  Each trip leaves "
    "one rate-limited post-mortem (report + flight ring) under "
    "MXNET_FLIGHT_DIR")
SUPERVISOR_LAST_SNAPSHOT_STEP = Gauge(
    "mxnet_supervisor_last_snapshot_step",
    "Step id of the TrainingSupervisor's most recent rolling host "
    "snapshot — how far back a donation-safe retry would rewind")
PREFETCH_RESPAWNS = Counter(
    "mxnet_prefetch_respawns_total",
    "AsyncPrefetcher worker threads respawned after a transient IO "
    "error (one respawn per prefetcher lifetime; a second transient "
    "surfaces to the consumer)")
DATA_RECORDS_SKIPPED = Counter(
    "mxnet_data_records_skipped_total",
    "Corrupt input records skipped by the prefetcher's "
    "MXNET_DATA_SKIP_BUDGET (typed DataSkipBudgetError on exhaustion)")
COMPRESSION_ERROR = Histogram(
    "mxnet_compression_error",
    "Mean |quantization error| per gradient bucket per compressed "
    "allreduce (the error-feedback residual magnitude; bounded by the "
    "2-bit threshold).  Growing means the threshold is too coarse for "
    "the gradient scale",
    buckets=(1e-4, 1e-3, 1e-2, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 10.0))
PERF_REGRESSIONS = Counter(
    "mxnet_perf_regressions_total",
    "Perf-regression sentinel firings (mxnet_tpu.observability."
    "introspect), by kind (step_time = warmed step-time EWMA blew the "
    "persisted baseline p50 by REGRESSION_FACTOR, dispatches = "
    "steady-state dispatches/step grew past the baseline) and phase "
    "(whole_step / trainer_step).  Each firing is rate-limited to once "
    "per regression episode; the regression also fails the "
    "perf_regression readyz() check until it clears or the baseline is "
    "refreshed (docs/introspection.md)")


def _goodput_ratio() -> float:
    """Export-time pull of the goodput fraction from the run ledger
    (lazy/guarded — a scrape must never fail because of it; 0.0 until
    any span is attributed)."""
    try:
        from . import goodput as _gp
        if not _gp.ENABLED:
            return 0.0
        return float(_gp.ratio())
    except Exception:  # noqa: BLE001
        return 0.0


GOODPUT_RATIO = Gauge(
    "mxnet_goodput_ratio",
    "Fraction (0..1) of this run's wall-clock attributed to useful "
    "compute (flight trainer_step/whole_step/serve_dispatch spans) by "
    "the goodput ledger (mxnet_tpu.observability.goodput) — the rest "
    "is badput (mxnet_badput_seconds_total) or unattributed.  Computed "
    "at export; docs/goodput.md",
    fn=lambda: _goodput_ratio())
BADPUT_SECONDS = Counter(
    "mxnet_badput_seconds_total",
    "Wall-clock seconds lost to each badput class, by reason "
    "(data_wait / checkpoint_block / retry_replay / rewind / recompile "
    "/ eviction_churn / stall / shed — the closed goodput class list; "
    "docs/goodput.md)")
SLO_BURN = Counter(
    "mxnet_slo_burn_total",
    "Rate-limited SLO burn firings, by slo (goodput = run goodput %% "
    "fell below MXNET_SLO_GOODPUT_PCT, serve_p99 = sliding-window "
    "serve p99 exceeded MXNET_SLO_SERVE_P99_MS).  Each firing also "
    "warns, journals an slo_burn entry, and fails the slo_burn "
    "readyz() check until the window recovers (docs/goodput.md)")


def _introspect_mfu(key: str) -> float:
    """Export-time pull of one MFU/roofline field from the introspect
    layer (lazy/guarded — a scrape must never fail because of it;
    0.0 until both a program capture and a warmed step EWMA exist).
    The "mfu" pull also refreshes the per-mesh-axis children: on a
    GSPMD mesh the MFU gauge gains a {mesh=<signature>} child and the
    flops gauge per-axis {mesh_axis=...} splits (the sharded run's
    flops divided by each axis size)."""
    try:
        from . import introspect as _int
        if not _int.ENABLED:
            return 0.0
        d = _int.mfu()
        if key == "mfu":
            msig = d.get("mesh")
            MFU.replace_children(
                [({"mesh": msig}, float(d.get("mfu") or 0.0))]
                if msig else [])
            STEP_FLOPS_PER_S.replace_children(
                [({"mesh_axis": a}, float(v)) for a, v in
                 sorted((d.get("per_axis_flops_per_s") or {}).items())])
        return float(d.get(key) or 0.0)
    except Exception:  # noqa: BLE001
        return 0.0


MFU = Gauge(
    "mxnet_mfu",
    "Model flops utilization of the training step, 0..1: analytical "
    "flops/step of the captured step program(s) / the flight "
    "recorder's warmed step-time EWMA / platform peak flops "
    "(MXNET_PEAK_FLOPS override, else the chip.py table for this "
    "TPU's device_kind; 0 on a CPU, which has no peak).  On a GSPMD mesh a {mesh=<axis=size,...>} child "
    "carries the same value keyed by mesh shape so dashboards can "
    "group sharded vs replicated runs.  Computed at export only",
    fn=lambda: _introspect_mfu("mfu"))
STEP_FLOPS_PER_S = Gauge(
    "mxnet_step_flops_per_s",
    "Achieved flops/s of the training step (analytical flops/step / "
    "warmed step-time EWMA) — the roofline y-axis.  On a GSPMD mesh, "
    "per-mesh-axis {mesh_axis=batch|model|...} children split the "
    "total by axis size (the per-shard share along each axis).  "
    "Computed at export",
    fn=lambda: _introspect_mfu("flops_per_s"))
STEP_BYTES_PER_S = Gauge(
    "mxnet_step_bytes_per_s",
    "Achieved HBM bytes/s of the training step (cost_analysis bytes "
    "accessed / warmed step-time EWMA).  Computed at export",
    fn=lambda: _introspect_mfu("bytes_per_s"))
STEP_ARITH_INTENSITY = Gauge(
    "mxnet_step_arithmetic_intensity",
    "Analytical flops per byte accessed of the training step — the "
    "roofline x-axis (compare against the platform's ridge point to "
    "see compute- vs memory-bound).  Computed at export",
    fn=lambda: _introspect_mfu("arithmetic_intensity"))


def _hbm_stats_all() -> List[dict]:
    """Per-device memory_stats() — TPU backends report bytes_in_use /
    peak_bytes_in_use / bytes_limit; CPU returns nothing."""
    out = []
    try:
        import jax
        for d in jax.local_devices():
            try:
                s = d.memory_stats()
            except Exception:
                s = None
            if s:
                out.append({"device": str(d.id), "platform": d.platform,
                            **{k: v for k, v in s.items()
                               if isinstance(v, (int, float))}})
    except Exception:
        pass
    return out


def hbm_stats() -> List[dict]:
    return _hbm_stats_all()


def _hbm_in_use_total() -> float:
    return float(sum(s.get("bytes_in_use", 0) for s in _hbm_stats_all()))


HBM_BYTES_IN_USE = Gauge(
    "mxnet_hbm_bytes_in_use",
    "Sum of bytes_in_use over jax.local_devices() (sampled at export)",
    fn=_hbm_in_use_total)


# -- product API --------------------------------------------------------------
def step_dispatches() -> float:
    """Launch + transfer tally EXCLUDING kind=\"data\" launches — the
    windowed delta `Module.fit` publishes as FIT_STEP_DISPATCHES.  Data
    launches are excluded because a PrefetchingIter producer thread
    issues them mid-step, which would make the per-step delta
    nondeterministic."""
    return (XLA_LAUNCHES.value - XLA_LAUNCHES.get(kind="data")
            + DEVICE_PUTS.value)


def step_counts() -> Tuple[float, float, float, float]:
    """(launches without kind="data", device_puts, host sync reads,
    program loads) so far: two calls bracket a step."""
    return (XLA_LAUNCHES.value - XLA_LAUNCHES.get(kind="data"),
            DEVICE_PUTS.value, HOST_SYNC_READS.value, PROGRAM_LOADS.value)


def step_deltas(since, now=None) -> Dict[str, float]:
    """What a step's ring record carries: the ``step_counts()`` from
    ``since`` to ``now`` (default: this moment)."""
    now = now or step_counts()
    return {"launches": now[0] - since[0], "device_puts": now[1] - since[1],
            "sync_reads": now[2] - since[2],
            "program_loads": now[3] - since[3]}


def dispatch_counts() -> Dict[str, float]:
    """Per-kind dispatch tally since process start (or the last
    `REGISTRY.reset()`): compiled-program launches keyed `xla:<kind>`
    plus `device_put`.  The per-step delta of this dict is the invariant
    `tests/test_dispatch_count.py` pins; `fit_step_dispatches` (a gauge,
    also in `snapshot()`) carries the most recent fit step's total."""
    out: Dict[str, float] = {}
    # list() snapshots the dict in one C-level copy (GIL-atomic) so a
    # producer thread inserting a new label kind mid-call cannot raise
    # "dictionary changed size during iteration"
    for labels, v in list(XLA_LAUNCHES._children.items()):
        kind = dict(labels).get("kind", "other")
        out["xla:" + kind] = out.get("xla:" + kind, 0.0) + v
    if XLA_LAUNCHES._value:
        out["xla:other"] = out.get("xla:other", 0.0) + XLA_LAUNCHES._value
    out["device_put"] = DEVICE_PUTS.value
    out["total"] = XLA_LAUNCHES.value + DEVICE_PUTS.value
    return out


def _sum_by_label(counter: Counter, label: str) -> Dict[str, float]:
    """Aggregate a labeled counter's children over one label (the
    snapshot()-friendly marginal, e.g. evictions by kind summed over
    models).  list() snapshots against concurrent label inserts."""
    out: Dict[str, float] = {}
    for k, v in list(counter._children.items()):
        key = dict(k).get(label, "_")
        out[key] = out.get(key, 0.0) + v
    return out


def _flight_snapshot() -> dict:
    """snapshot()["flight"]: ring/watchdog state + per-phase p50/p99 +
    slowest-record exemplars (docs/observability.md).  Lazy/guarded —
    the metrics layer must never fail because of the recorder."""
    try:
        from . import flight as _fl
        return _fl.snapshot_summary()
    except Exception:  # noqa: BLE001
        return {"enabled": False}


def _memory_snapshot() -> dict:
    """snapshot()["memory"]: per-tag live/peak bytes, attribution pct,
    untagged remainder, budget + OOM state (docs/memory.md).  Lazy/
    guarded — the metrics layer must never fail because of the
    ledger."""
    try:
        from . import memory as _mem
        return _mem.snapshot_summary()
    except Exception:  # noqa: BLE001
        return {"enabled": False}


def _programs_snapshot() -> dict:
    """snapshot()["programs"]: per-program flops/bytes/peak + MFU +
    perf-sentinel state (docs/introspection.md).  Lazy/guarded — the
    metrics layer must never fail because of the introspector."""
    try:
        from . import introspect as _int
        return _int.snapshot_summary()
    except Exception:  # noqa: BLE001
        return {"enabled": False}


def _goodput_snapshot() -> dict:
    """snapshot()["goodput"]: per-class seconds/events, goodput %,
    unattributed slack, SLO targets + burn state, and the active run
    journal id/path (docs/goodput.md).  Lazy/guarded — the metrics
    layer must never fail because of the ledger."""
    try:
        from . import goodput as _gp
        out = _gp.report()
        if out.get("enabled"):
            out["slo"] = _gp.slo_state()
        from . import journal as _jr
        out["run_id"] = _jr.run_id()
        out["journal_path"] = _jr.path()
        return out
    except Exception:  # noqa: BLE001
        return {"enabled": False}


def _analysis_snapshot() -> dict:
    """snapshot()["analysis"]: sanitizer state + violation counters
    (docs/static_analysis.md).  The sanitizer import is lazy/guarded —
    the metrics layer must never fail because of it."""
    out = {"lock_order_violations": ANALYSIS_LOCK_VIOLATIONS.value,
           "sync_violations": ANALYSIS_SYNC_VIOLATIONS.value}
    try:
        from ..analysis import sanitizer as _san
        out.update(_san.state())
    except Exception:  # noqa: BLE001
        out["enabled"] = False
    return out


def snapshot() -> dict:
    """One JSON-able dict with the numbers a perf PR needs: dispatch
    accounting, transfer volume, data-wait, engine stalls, HBM."""
    return {
        "dispatch_counts": dispatch_counts(),
        "fit_step_dispatches": FIT_STEP_DISPATCHES.get(),
        "trainer_step_dispatches": TRAINER_STEP_DISPATCHES.get(),
        "superstep_dispatches": SUPERSTEP_DISPATCHES.get(),
        "allreduce_buckets": ALLREDUCE_BUCKETS.get(),
        "prefetch_wait_ms_total": PREFETCH_WAIT_SECONDS.sum * 1e3,
        "transfer_bytes": TRANSFER_BYTES.value,
        "kvstore_push_bytes": KVSTORE_PUSH_BYTES.value,
        "kvstore_pull_bytes": KVSTORE_PULL_BYTES.value,
        "kvstore_wire_bytes": {
            "dist_raw": KVSTORE_WIRE_BYTES.get(leg="dist", stage="raw"),
            "dist_compressed": KVSTORE_WIRE_BYTES.get(
                leg="dist", stage="compressed"),
            "intra_raw": KVSTORE_WIRE_BYTES.get(leg="intra", stage="raw"),
        },
        "compression_error_mean": COMPRESSION_ERROR.mean,
        "data_wait_ms_total": DATA_WAIT_SECONDS.sum * 1e3,
        "data_wait_ms_mean": DATA_WAIT_SECONDS.mean * 1e3,
        "engine_wait_seconds": ENGINE_WAIT_SECONDS.value,
        "jit_cache": {"hits": JIT_CACHE_HITS.value,
                      "misses": JIT_CACHE_MISSES.value},
        "held_launches": {"taken": HELD_LAUNCHES.get(result="taken"),
                          "dropped": HELD_LAUNCHES.get(result="dropped")},
        "host_sync_reads": HOST_SYNC_READS.value,
        "program_loads": {"compile": PROGRAM_LOADS.get(how="compile"),
                          "cache": PROGRAM_LOADS.get(how="cache"),
                          "seconds": PROGRAM_LOAD_SECONDS.value},
        "optimizer_steps": OPTIMIZER_STEPS.value,
        "fused_dtype_recompiles": FUSED_DTYPE_RECOMPILES.value,
        "serving": {
            "requests": SERVE_REQUESTS.value,
            "batches": SERVE_BATCHES.value,
            "compiles": SERVE_COMPILES.value,
            "queue_depth": SERVE_QUEUE_DEPTH.get(),
            "padding_waste": SERVE_PADDING_WASTE.get(),
            "coalesced_rows": SERVE_COALESCED_ROWS.get(),
            "latency_ms_mean": SERVE_LATENCY_SECONDS.mean * 1e3,
            "admitted": SERVE_ADMITTED.value,
            "shed": SERVE_SHED.value,
            "expired": SERVE_EXPIRED.value,
            # list() snapshots against hook threads inserting tenants
            "goodput": {dict(k).get("tenant", "_"): v for k, v in
                        sorted(list(SERVE_GOODPUT._children.items()))},
            "ready": SERVE_READY.get(),
            "ready_transitions": SERVE_READY_TRANSITIONS.value,
            "reload_failures": SERVE_RELOAD_FAILURES.value,
            "faults_injected": FAULTS_INJECTED.value,
            # multi-model registry (docs/multi_model.md): eviction
            # churn by kind, the resident-model gauge, and the
            # per-model HBM slice — list() snapshots against the
            # registry mutating label sets mid-export
            "evictions": _sum_by_label(SERVE_EVICTIONS, "kind"),
            "readmissions": SERVE_READMITS.value,
            "resident_models": SERVE_RESIDENT_MODELS.get(),
            "model_hbm_bytes": {
                dict(k).get("model", "_"): v for k, v in
                sorted(list(SERVE_MODEL_HBM_BYTES._children.items()))},
            # exemplar hop: p99 bucket -> trace_id -> flight dump spans
            "latency_exemplars": SERVE_LATENCY_SECONDS.exemplars(),
            # continuous-batching decode (docs/decode_serving.md):
            # steps == dispatch_counts()['decode'] is the 1-dispatch
            # contract; kv_evictions is the budget arbiter choosing
            # pages over weights
            "decode": {
                "steps": DECODE_STEPS.value,
                "tokens": DECODE_TOKENS.value,
                "inflight": DECODE_INFLIGHT.get(),
                "kv_page_occupancy": DECODE_KV_OCCUPANCY.get(),
                "tokens_per_s": DECODE_TOKENS_PER_S.get(),
                "kv_evictions": DECODE_KV_EVICTIONS.value,
            },
        },
        "flight": _flight_snapshot(),
        "goodput": _goodput_snapshot(),
        "memory": _memory_snapshot(),
        "programs": _programs_snapshot(),
        "analysis": _analysis_snapshot(),
        "supervisor": {
            "snapshots": SUPERVISOR_SNAPSHOTS.value,
            "last_snapshot_step": SUPERVISOR_LAST_SNAPSHOT_STEP.get(),
            "retries": SUPERVISOR_RETRIES.value,
            "rewinds": {dict(k).get("reason", "_"): v for k, v in
                        sorted(list(SUPERVISOR_REWINDS._children.items()))},
            "watchdog_trips": {
                dict(k).get("kind", "_"): v for k, v in
                sorted(list(SUPERVISOR_WATCHDOG_TRIPS._children.items()))},
            "prefetch_respawns": PREFETCH_RESPAWNS.value,
            "data_records_skipped": DATA_RECORDS_SKIPPED.value,
        },
        "checkpoint": {
            "last_step": CHECKPOINT_LAST_STEP.get(),
            "saves": CHECKPOINT_SAVE_SECONDS.count,
            "save_ms_mean": CHECKPOINT_SAVE_SECONDS.mean * 1e3,
            "save_blocked_ms_mean":
                CHECKPOINT_SAVE_BLOCKED_SECONDS.mean * 1e3,
            "restores": CHECKPOINT_RESTORE_SECONDS.count,
            "restore_ms_mean": CHECKPOINT_RESTORE_SECONDS.mean * 1e3,
            "bytes_written": CHECKPOINT_BYTES_WRITTEN.value,
            "failures": CHECKPOINT_FAILURES.value,
        },
        "hbm": hbm_stats(),
    }


def _refresh_export_gauges() -> None:
    """Pull-style gauges that aren't ``fn=``-driven refresh here so the
    render paths export fresh values even when ``snapshot()`` never
    runs (the documented Prometheus scrape wiring).  Lazy/guarded — a
    render must never fail because of the ledger."""
    try:
        from . import memory as _mem
        _mem.refresh_gauge()
    except Exception:  # noqa: BLE001
        pass
    for refresh in (refresh_moe, refresh_loop):
        try:
            refresh()
        except Exception:  # noqa: BLE001
            pass


def render_prometheus() -> str:
    _refresh_export_gauges()
    return REGISTRY.render_prometheus()


def render_json() -> str:
    _refresh_export_gauges()
    return REGISTRY.render_json()


# -- program loads: JAX's own monitoring events ------------------------------
# JAX reports "/jax/core/compile/backend_compile_duration" around every
# compile-or-read-from-cache of a program (with the program's name), and
# "/jax/compilation_cache/cache_retrieval_time_sec" inside it when the
# persistent cache had the program.
_load_tls = threading.local()


def _on_jax_duration(event: str, secs: float, **kw) -> None:
    if not ENABLED:
        return
    if event.endswith("/cache_retrieval_time_sec"):
        _load_tls.from_cache = True
        return
    if not event.endswith("/backend_compile_duration"):
        return
    how = "cache" if getattr(_load_tls, "from_cache", False) else "compile"
    _load_tls.from_cache = False
    PROGRAM_LOADS.inc(how=how)
    PROGRAM_LOAD_SECONDS.inc(secs)
    from . import flight, tracing
    if flight.ENABLED:
        t1 = flight.now_us()
        parent, step = tracing.context()
        flight.record("mx.program.load", "compile", t1 - secs * 1e6, t1,
                      step=step, parent=parent,
                      labels={"how": how, "program": kw.get("fun_name")})


import jax.monitoring  # noqa: E402

jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
