"""Runtime-wide observability: structured tracing + metrics + dispatch
accounting (the TPU redesign of the reference's engine profiler,
`src/engine/profiler.cc`).

The reference wired per-op exec stats into the engine because a training
stack you cannot see cannot be optimized — the single worst perf bug in
this port (193 `jax.device_put` calls per Module.fit step, round 2) was invisible until dispatches were hand-counted.  This
package makes that visibility a product API:

  - `mxnet_tpu.observability.metrics` — a process-wide registry of
    counters / gauges / histograms (XLA program launches by kind,
    device_put count + transfer bytes, jit cache hits/misses, engine
    wait stalls, kvstore push/pull bytes + allreduce latency, dataloader
    batch-wait time, HBM usage) with Prometheus-text and JSON exporters.
  - `mxnet_tpu.observability.tracing` — `with span("mx.x"):`, the one
    span primitive (`trace_span` and `flight.phase_span` are its older
    names): a `jax.profiler.TraceAnnotation` in ANY profiler session
    (host spans on the same clock as the device's operations), a
    flight-ring record with parent and step, and the python-side
    Chrome-trace mirror (`profiler._events`) while `mx.profiler` runs.
  - `dispatch_counts()` — the queryable per-kind XLA-launch/transfer
    tally that `tests/test_dispatch_count.py` pins as an invariant.
  - `mxnet_tpu.observability.flight` — the always-on flight recorder:
    `phase_span(...)` ring-records per-phase step/request timelines
    (data-wait/h2d/allreduce/fused-update, queue-wait/pad/dispatch/
    slice with end-to-end trace ids), `flight.dump()` exports a
    Perfetto-loadable Chrome trace merging training + serving +
    profiler `_events`, and a slow-step/slow-request watchdog
    auto-dumps the ring on anomaly and on SIGUSR2
    (`MXNET_FLIGHT=0` disables; see docs/observability.md).
  - `mxnet_tpu.observability.memory` — the HBM ledger: weakref-tracked
    device/host byte attribution by `memory_scope` tag
    (`memory.report()`, `snapshot()["memory"]`), per-phase net-delta
    memory records in the flight ring, an `MXNET_HBM_BUDGET_MB` soft
    budget, and an OOM post-mortem (`oom_guard` catches
    RESOURCE_EXHAUSTED at the dispatch chokepoints, dumps ledger +
    ring, re-raises typed; `MXNET_MEMORY_LEDGER=0` disables; see
    docs/memory.md).
  - `mxnet_tpu.observability.introspect` — program introspection:
    every compile chokepoint notes its program's analytical cost
    (flops, bytes) + CompiledMemoryStats through one
    `note_program()` surface (`snapshot()["programs"]`,
    `introspect.report()`); `jax.named_scope` layer names thread
    through the graph interpreter so `per_layer()` attributes the
    donated whole-step program's flops to named blocks and reports
    measured `device_ms` per layer when handed a trace's seconds;
    `op_scopes(jit_name)` names every instruction of a compiled
    program by graph node, registered operator and pass, the join
    between a device trace and the model (programs keep their
    `Lowered`; `MXNET_INTROSPECT_HLO=1` captures the HLO text at
    the first read of `programs()`, not at a program's first call); MFU /
    roofline gauges (`mxnet_mfu`, `MXNET_PEAK_FLOPS` override) and a
    persisted perf-regression sentinel (`MXNET_PERF_BASELINE_DIR`)
    compare the warmed step-time EWMA against a per-(model, platform)
    baseline (`MXNET_INTROSPECT=0` disables; see
    docs/introspection.md).

Overhead discipline: every hot-path hook is guarded by the module-level
`metrics.ENABLED` flag (env `MXNET_METRICS_ENABLED`, default on; set 0
to compile the whole layer down to one boolean test per hook — no dict
allocation, no label formatting, no timestamps).
"""
from __future__ import annotations

from . import metrics
from . import tracing
from . import goodput
from . import journal
from . import flight
from . import timeline
from . import memory
from . import introspect
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      enabled, enable, disable, dispatch_counts,
                      step_dispatches, snapshot, render_prometheus,
                      render_json, hbm_stats)
from .tracing import span, trace_span, step_span
from .flight import phase_span, trace_scope, new_trace_id
from .memory import memory_scope, oom_guard, DeviceMemoryError, HBMBudgetError

__all__ = [
    "metrics", "tracing", "flight", "timeline", "memory", "introspect",
    "goodput", "journal",
    "Counter",
    "Gauge", "Histogram", "MetricsRegistry", "REGISTRY", "enabled",
    "enable", "disable", "dispatch_counts", "step_dispatches", "snapshot",
    "render_prometheus", "render_json", "hbm_stats",
    "span", "trace_span", "step_span",
    "phase_span", "trace_scope", "new_trace_id",
    "memory_scope", "oom_guard", "DeviceMemoryError", "HBMBudgetError",
]
