"""Observability layer: metrics registry, tracing spans, dispatch
accounting, profiler façade (pause/resume, atomic dump), monitor_all.

The subsystem under test exists because of VERDICT r2 #3: 193 invisible
device_put RPCs per fit step.  These tests pin that the accounting layer
(a) measures the product training path correctly, (b) exports cleanly,
and (c) costs nothing when disabled.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym, observability as obs
from mxnet_tpu.observability import metrics as M
from mxnet_tpu.io import DataDesc, NDArrayIter


def _small_module(batch=8):
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (batch, 32), np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch,), np.float32)])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    return mod


def _data(batch=8, nbatch=4, seed=0):
    rs = np.random.RandomState(seed)
    x = mx.nd.array(rs.normal(0, 1, (batch * nbatch, 32)).astype("f"))
    y = mx.nd.array(rs.randint(0, 10, batch * nbatch).astype("f"))
    return NDArrayIter(x, y, batch_size=batch)


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test sees zeroed counters and an enabled layer."""
    was = M.ENABLED
    M.enable()
    M.REGISTRY.reset()
    yield
    M.REGISTRY.reset()
    (M.enable if was else M.disable)()


# ------------------------------------------------------------------ metrics

def test_counter_gauge_histogram_basics():
    c = M.XLA_LAUNCHES
    c.inc(kind="fwd")
    c.inc(2, kind="fwd")
    c.inc()  # unlabeled fast path
    assert c.get(kind="fwd") == 3
    assert c.value == 4
    g = M.FIT_STEP_DISPATCHES
    g.set(7)
    g.inc()
    assert g.get() == 8
    h = M.DATA_WAIT_SECONDS
    h.observe(0.002)
    h.observe(1.5)
    assert h.count == 2
    assert abs(h.sum - 1.502) < 1e-9
    assert h.mean == pytest.approx(0.751)


def test_counters_increment_across_fit(tmp_path):
    mod = _small_module()
    nbatch = 4
    mod.fit(_data(nbatch=nbatch), num_epoch=2, eval_metric="acc")
    dc = obs.dispatch_counts()
    # fused fwd+bwd and fused optimizer update: exactly one launch each
    # per batch, every epoch
    assert dc["xla:fwd_bwd"] == 2 * nbatch, dc
    assert dc["xla:optimizer"] == 2 * nbatch, dc
    assert dc["device_put"] == 0, dc
    # the fit loop published the steady-state per-step dispatch gauge
    assert M.FIT_STEP_DISPATCHES.get() == 2.0
    # batch-wait observed for each non-first batch fetch
    assert M.DATA_WAIT_SECONDS.count >= 2 * (nbatch - 1)
    # jit closures created once, then cache hits
    assert M.JIT_CACHE_MISSES.value >= 1
    assert M.JIT_CACHE_HITS.value > M.JIT_CACHE_MISSES.value
    # snapshot carries the accounting a perf PR needs
    snap = obs.snapshot()
    for k in ("dispatch_counts", "fit_step_dispatches", "transfer_bytes",
              "data_wait_ms_total", "jit_cache", "hbm", "checkpoint"):
        assert k in snap, snap.keys()
    for k in ("last_step", "saves", "save_blocked_ms_mean", "bytes_written",
              "failures"):
        assert k in snap["checkpoint"], snap["checkpoint"]
    json.dumps(snap)  # JSON-able end to end


def test_kvstore_byte_accounting():
    kv = mx.kv.create("local")
    shape = (16, 8)
    kv.init("w", mx.nd.zeros(shape))
    g = mx.nd.ones(shape)
    kv.push("w", g)
    out = mx.nd.zeros(shape)
    kv.pull("w", out=out)
    nbytes = int(np.prod(shape)) * 4
    assert M.KVSTORE_PUSH_BYTES.value == nbytes
    assert M.KVSTORE_PULL_BYTES.value == nbytes
    assert M.KVSTORE_ALLREDUCE_SECONDS.count == 1


def test_prometheus_export_roundtrip():
    M.XLA_LAUNCHES.inc(3, kind="fwd_bwd")
    M.DEVICE_PUTS.inc(2)
    M.DATA_WAIT_SECONDS.observe(0.25)
    text = obs.render_prometheus()
    # format sanity: TYPE lines present, series parse as "name{sel} value"
    assert "# TYPE mxnet_xla_launches_total counter" in text
    assert "# TYPE mxnet_data_batch_wait_seconds histogram" in text
    parsed = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        series, _, val = line.rpartition(" ")
        parsed[series] = float(val)
    assert parsed['mxnet_xla_launches_total{kind="fwd_bwd"}'] == 3.0
    assert parsed["mxnet_device_put_total"] == 2.0
    # histogram: cumulative buckets, +Inf == count
    assert parsed['mxnet_data_batch_wait_seconds_bucket{le="+Inf"}'] == 1.0
    assert parsed["mxnet_data_batch_wait_seconds_count"] == 1.0
    assert parsed["mxnet_data_batch_wait_seconds_sum"] == 0.25
    # JSON exporter round-trips through json.loads
    d = json.loads(obs.render_json())
    assert d["mxnet_xla_launches_total"]["values"]["kind=fwd_bwd"] == 3.0


def test_disabled_path_is_inert_and_identity_stable():
    c_before = M.XLA_LAUNCHES
    g_before = M.FIT_STEP_DISPATCHES
    M.disable()
    assert not obs.enabled()
    mod = _small_module()
    it = _data()
    mod.fit(it, num_epoch=1, eval_metric="acc")
    # nothing recorded anywhere with the flag down
    assert M.XLA_LAUNCHES.value == 0
    assert M.DEVICE_PUTS.value == 0
    assert M.DATA_WAIT_SECONDS.count == 0
    assert M.FIT_STEP_DISPATCHES.get() == 0.0
    # metric objects are module-level singletons: disable/enable flips a
    # flag, it never rebuilds metric state (hot-path hooks keep direct
    # references, so identity MUST be stable)
    M.enable()
    assert M.XLA_LAUNCHES is c_before
    assert M.FIT_STEP_DISPATCHES is g_before
    assert obs.REGISTRY.get("mxnet_xla_launches_total") is c_before
    # no stale label children were allocated while disabled
    assert M.XLA_LAUNCHES._children == {}


def test_dispatch_counts_constant_per_step():
    """Steady-state fit steps issue a CONSTANT number of launches — the
    acceptance-criteria form of the round-2 invariant, via product API."""
    mod = _small_module()
    it = _data()
    mod.fit(it, num_epoch=1, eval_metric="acc")  # compile+warm
    deltas = []
    for _ in range(3):
        before = obs.dispatch_counts()["total"]
        it.reset()
        mod.fit(it, num_epoch=1, eval_metric="acc")
        deltas.append(obs.dispatch_counts()["total"] - before)
    assert deltas[0] == deltas[1] == deltas[2], deltas
    assert M.FIT_STEP_DISPATCHES.get() == 2.0


# ------------------------------------------------------------------ tracing

def test_trace_span_nesting_chrome_events(tmp_path):
    fname = str(tmp_path / "trace.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.set_state("run")
    with obs.trace_span("outer"):
        with obs.trace_span("inner"):
            pass
        with obs.trace_span("inner2"):
            pass
    mx.profiler.set_state("stop")
    evs = [e for e in mx.profiler._events if e["cat"] == "runtime"]
    assert [e["name"] for e in evs] == ["inner", "inner2", "outer"]
    by_name = {e["name"]: e for e in evs}
    for e in evs:  # well-formed complete events
        assert e["ph"] == "X" and e["dur"] >= 0 and "ts" in e
    # nesting: children fully contained in the parent on the same tid
    out = by_name["outer"]
    for child in ("inner", "inner2"):
        c = by_name[child]
        assert c["tid"] == out["tid"]
        assert c["ts"] >= out["ts"]
        assert c["ts"] + c["dur"] <= out["ts"] + out["dur"] + 1e-3
        assert c["args"]["depth"] == out["args"]["depth"] + 1
    # the whole timeline dumps as valid chrome-trace JSON
    mx.profiler.dump_profile()
    with open(fname) as f:
        trace = json.load(f)
    assert any(e["name"] == "outer" for e in trace["traceEvents"])


def test_step_span_records_step_boundary(tmp_path):
    fname = str(tmp_path / "trace.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.set_state("run")
    with obs.step_span(7):
        pass
    mx.profiler.set_state("stop")
    steps = [e for e in mx.profiler._events if e["cat"] == "step"]
    assert len(steps) == 1
    assert steps[0]["args"]["step"] == 7


def test_trace_span_noop_when_stopped():
    n0 = len(mx.profiler._events)
    with obs.trace_span("ghost"):
        pass
    assert len(mx.profiler._events) == n0


def test_fit_trace_contains_nested_training_spans(tmp_path):
    """Training with profiling on produces a valid Chrome trace with the
    data/forward-backward/update span hierarchy (acceptance criteria)."""
    fname = str(tmp_path / "fit_trace.json")
    mod = _small_module()
    it = _data()
    mx.profiler.set_config(mode="all", filename=fname)
    mx.profiler.set_state("run")
    mod.fit(it, num_epoch=1, eval_metric="acc")
    mx.profiler.set_state("stop")
    mx.profiler.dump_profile()
    with open(fname) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    for expected in ("mx.step", "mx.module.forward_backward",
                     "mx.module.update", "mx.fit.data_fetch",
                     "mx.kvstore.pushpull", "mx.optimizer.update_all"):
        assert expected in names, (expected, sorted(names))
    # spans nest: fwd_bwd + update inside their mx.step
    steps = sorted((e for e in trace["traceEvents"]
                    if e["name"] == "mx.step"), key=lambda e: e["ts"])
    fb = sorted((e for e in trace["traceEvents"]
                 if e["name"] == "mx.module.forward_backward"),
                key=lambda e: e["ts"])
    assert steps and fb
    s0 = steps[0]
    assert s0["ts"] <= fb[0]["ts"]
    assert fb[0]["ts"] + fb[0]["dur"] <= s0["ts"] + s0["dur"] + 1e-3


# ----------------------------------------------------------------- profiler

def test_pause_resume_preserves_events(tmp_path):
    fname = str(tmp_path / "p.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.set_state("run")
    mx.profiler.record_event("kept", 0.0, 1.0)
    mx.profiler.pause()
    assert mx.profiler.is_running()       # parity: paused still 'run'
    assert not mx.profiler.is_recording()
    mx.profiler.record_event("dropped", 1.0, 2.0)
    mx.profiler.resume()
    mx.profiler.record_event("kept2", 2.0, 3.0)
    mx.profiler.set_state("stop")
    names = [e["name"] for e in mx.profiler._events]
    assert names == ["kept", "kept2"], names


def test_dump_profile_atomic_and_valid(tmp_path):
    fname = str(tmp_path / "prof.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.set_state("run")
    mx.profiler.record_event("op", 0.0, 5.0)
    mx.profiler.dump_profile()
    # no temp residue, and the dump parses
    assert not os.path.exists(fname + ".tmp")
    with open(fname) as f:
        d = json.load(f)
    assert d["traceEvents"][0]["name"] == "op"
    # a second dump REPLACES atomically (previous content never mixes)
    mx.profiler.set_state("run")
    mx.profiler.record_event("op2", 0.0, 1.0)
    mx.profiler.dump_profile()
    with open(fname) as f:
        d2 = json.load(f)
    assert [e["name"] for e in d2["traceEvents"]] == ["op2"]


# ------------------------------------------------------------------ monitor

def test_monitor_all_taps_inputs():
    seen = []
    mon = mx.Monitor(1, stat_func=lambda x: x.size, monitor_all=True)
    mon.stat_func = lambda x: mx.nd.array([x.size])
    mod = _small_module()
    mod.install_monitor(mon)
    it = _data(nbatch=1)
    batch = next(iter(it))
    mon.tic()
    mod.forward(batch, is_train=False)
    res = mon.toc()
    names = [k for _, k, _ in res]
    assert any(n.endswith("_input") for n in names), names   # inputs tapped
    assert any("softmax" in n for n in names), names         # outputs still
    assert M.MONITOR_STATS.get(io="input") > 0
    assert M.MONITOR_STATS.get(io="output") > 0


def test_monitor_default_outputs_only():
    mon = mx.Monitor(1, stat_func=lambda x: mx.nd.array([x.size]))
    mod = _small_module()
    mod.install_monitor(mon)
    it = _data(nbatch=1)
    batch = next(iter(it))
    mon.tic()
    mod.forward(batch, is_train=False)
    res = mon.toc()
    # toc() itself stats arg arrays by design (reference parity); the
    # _input taps from the executor callback must NOT appear
    assert not any(k.endswith("_input") for _, k, _ in res)
