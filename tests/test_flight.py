"""Flight recorder (ISSUE 8): ring wraparound/drops, trace-id
propagation across serving threads, Perfetto/Chrome-trace schema,
anomaly + SIGUSR2 auto-dump, MXNET_FLIGHT=0 no-op, sanitizer-clean
concurrent writers, exemplar -> timeline linkage.

Acceptance pinned here: a slow-request injection (faultinject
serving.dispatch delay) auto-produces a Perfetto-loadable dump whose
per-request spans (queue -> pad -> dispatch -> slice) share one
trace_id; the fused trainer step keeps the <=4-dispatch gate with the
recorder enabled.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject as fi
from mxnet_tpu import serving, sym
from mxnet_tpu.base import unique_path
from mxnet_tpu.observability import flight, metrics as m, timeline

pytestmark = pytest.mark.flight

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_flight():
    """Each test gets an enabled recorder with a fresh ring and the
    default knobs back afterwards."""
    ring0, factor0, min_s0 = flight.RING, flight.SLOW_FACTOR, \
        flight.AUTO_DUMP_MIN_S
    flight.enable()
    flight.reset()
    yield
    flight.RING, flight.SLOW_FACTOR = ring0, factor0
    flight.AUTO_DUMP_MIN_S = min_s0
    flight.enable()
    flight.reset()


# -- helpers (serving fixture shared with test_serving idiom) ---------------

def _mlp_symbol(nin=8, nhid=16, nout=4):
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=nhid,
                             name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=nout, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _mlp_predictor(max_batch=8, **kw):
    net = _mlp_symbol()
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(max_batch, 8))
    params = {}
    for n, s in zip(net.list_arguments(), arg_shapes):
        if n == "data" or n.endswith("_label"):
            continue
        params["arg:" + n] = mx.nd.array(rs.normal(0, 0.1, s).astype("f"))
    return serving.BucketedPredictor(net, params,
                                     {"data": (max_batch, 8)}, **kw)


def _spans(name=None):
    out = [r for _, r in flight.records()]
    return out if name is None else [r for r in out if r[0] == name]


def _wait_for(pred, timeout=5.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


def _dumps(d):
    """COMMITTED dump files only: atomic_write's same-dir tmp is
    transiently visible, and polling must never json.load a partial."""
    return sorted(n for n in os.listdir(str(d))
                  if n.endswith(".json") and ".tmp" not in n)


# -- ring basics -------------------------------------------------------------

def test_phase_span_records_fields():
    with flight.phase_span("unit_phase", cat="testcat", step=7,
                           labels={"k": "v"}):
        time.sleep(0.001)
    (rec,) = _spans("unit_phase")
    name, cat, t0, t1, step, trace_id, labels, parent = rec
    assert cat == "testcat" and step == 7 and labels == {"k": "v"}
    assert parent is None
    assert t1 > t0 and (t1 - t0) >= 1e3  # >= 1ms in microseconds
    assert trace_id is None


def test_ring_wraparound_and_drop_count():
    flight.configure(ring=8)
    for i in range(20):
        flight.record("wrap_phase", "t", float(i), float(i) + 0.5)
    st = flight.stats()
    assert st["written"] == 20 and st["drops"] == 12
    assert st["records"] == 8
    kept = _spans("wrap_phase")
    assert len(kept) == 8
    # the ring keeps the NEWEST 8 records
    assert sorted(r[2] for r in kept) == [float(i) for i in range(12, 20)]


def test_disabled_is_noop():
    flight.disable()
    with flight.phase_span("never_recorded"):
        pass
    flight.record("never_recorded", "t", 0.0, 1.0)
    flight.note("never_recorded", 100.0)  # no EWMA, no dump
    assert flight.stats()["records"] == 0
    assert flight.stats()["enabled"] is False
    assert flight.watch_state() == {}


def test_flight_env_off_subprocess(tmp_path):
    """MXNET_FLIGHT=0 at import: hooks reduce to one boolean test and
    record nothing — and a later enable() restores full function,
    including the SIGUSR2 handler the import-time path skipped."""
    code = (
        "import os, signal, time\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.observability import flight\n"
        "assert flight.ENABLED is False\n"
        "with flight.phase_span('x'):\n"
        "    pass\n"
        "assert flight.stats()['records'] == 0\n"
        "flight.enable()   # must also arm kill -USR2 retroactively\n"
        "with flight.phase_span('late_phase'):\n"
        "    pass\n"
        "os.kill(os.getpid(), signal.SIGUSR2)\n"
        "d = os.environ['MXNET_FLIGHT_DIR']\n"
        "for _ in range(100):\n"
        "    if [n for n in os.listdir(d)\n"
        "            if n.endswith('.json') and '.tmp' not in n]:\n"
        "        break\n"
        "    time.sleep(0.05)\n"
        "else:\n"
        "    raise AssertionError('late-enabled SIGUSR2 never dumped')\n"
        "print('OK')\n")
    env = dict(os.environ, MXNET_FLIGHT="0", JAX_PLATFORMS="cpu",
               MXNET_FLIGHT_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-500:], out.stderr[-2000:])


def test_reset_isolates_other_threads_segments():
    done = threading.Event()
    go_again = threading.Event()

    def worker():
        with flight.phase_span("thread_phase"):
            pass
        done.set()
        go_again.wait(5)
        with flight.phase_span("thread_phase_2"):
            pass

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    assert done.wait(5)
    assert len(_spans("thread_phase")) == 1
    flight.reset()
    assert flight.stats()["records"] == 0
    # the worker's stale thread-local segment must NOT resurrect into
    # the cleared registry — a new epoch gives it a fresh segment
    go_again.set()
    t.join(5)
    assert len(_spans("thread_phase")) == 0
    assert len(_spans("thread_phase_2")) == 1


def test_dead_thread_segments_bounded():
    """Thread churn (a prefetcher per epoch, pool restarts) must not
    grow the segment registry forever: dead-thread segments are pruned
    past MAX_DEAD_SEGMENTS at registration, recent ones kept for
    post-mortem."""
    flight.configure(ring=4)

    def spin(i):
        flight.record("churn_phase", "t", float(i), float(i) + 1.0)

    n = flight.MAX_DEAD_SEGMENTS + 12
    for i in range(n):
        t = threading.Thread(target=spin, args=(i,))
        t.start()
        t.join(5)
    st = flight.stats()
    # every registration after the cap pruned the oldest dead segments
    assert st["segments"] <= flight.MAX_DEAD_SEGMENTS + 2, st
    # the NEWEST dead threads' records survive for post-mortem
    kept = sorted(r[2] for r in _spans("churn_phase"))
    assert kept and kept[-1] == float(n - 1)


# -- chrome trace schema -----------------------------------------------------

def test_dump_chrome_trace_schema(tmp_path):
    with flight.trace_scope("tid-1"):
        with flight.phase_span("schema_phase", cat="c", step=3):
            pass
    path = flight.dump(path=str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)   # loadable = valid JSON
    assert "traceEvents" in doc and doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    xs = [e for e in evs if e.get("ph") == "X"]
    ms = [e for e in evs if e.get("ph") == "M"]
    assert xs and ms
    for e in xs:
        # the trace-event fields Perfetto requires for a complete event
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}, e
    ev = next(e for e in xs if e["name"] == "schema_phase")
    assert ev["cat"] == "c"
    assert ev["args"]["step"] == 3 and ev["args"]["trace_id"] == "tid-1"
    # thread_name metadata names the row
    assert any(e["name"] == "thread_name" and "name" in e["args"]
               for e in ms)
    # complete events are time-sorted (one coherent timeline)
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)


def test_dump_merges_profiler_events(tmp_path):
    mx.profiler.set_config(filename=str(tmp_path / "prof.json"))
    mx.profiler.set_state("run")
    try:
        with mx.observability.trace_span("prof_side_span"):
            with flight.phase_span("flight_side_span"):
                pass
    finally:
        mx.profiler.set_state("stop")
    path = flight.dump(path=str(tmp_path / "merged.json"))
    with open(path) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]]
    assert "prof_side_span" in names and "flight_side_span" in names


def test_dump_default_dir_and_unique_name(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "dumps"))
    clock = lambda: 1700000000.0  # noqa: E731 — injected, deterministic
    p1 = flight.dump(clock=clock)
    p2 = flight.dump(clock=clock)
    assert os.path.dirname(p1) == str(tmp_path / "dumps")
    assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)
    assert m.FLIGHT_DUMPS.get(reason="manual") >= 2.0


def test_unique_path_collision_policy(tmp_path):
    """profiler + flight share ONE filename policy: timestamped via an
    injected clock, collision -> .N suffix (no ambient-time races)."""
    clock = lambda: 1700000000.0  # noqa: E731
    p1 = unique_path(str(tmp_path), "flight", ".json", clock=clock)
    open(p1, "w").close()
    p2 = unique_path(str(tmp_path), "flight", ".json", clock=clock)
    assert p2 != p1 and p2.endswith(".1.json")
    open(p2, "w").close()
    p3 = unique_path(str(tmp_path), "flight", ".json", clock=clock)
    assert p3.endswith(".2.json")
    assert "20231114" in os.path.basename(p1)  # stamp comes from clock


def test_dump_profile_is_atomic_via_base(tmp_path):
    """dump_profile routes through base.atomic_write (the shared
    policy): the committed file is valid JSON, no .tmp residue."""
    fname = str(tmp_path / "prof.json")
    mx.profiler.set_config(filename=fname)
    mx.profiler.set_state("run")
    with mx.observability.trace_span("x"):
        pass
    mx.profiler.dump_profile()
    with open(fname) as f:
        assert "traceEvents" in json.load(f)
    assert not [p for p in os.listdir(tmp_path) if ".tmp" in p]


# -- tracing satellite: depth accounting + paused-profiler fallback ----------

def test_trace_span_depth_exception_safe(tmp_path):
    from mxnet_tpu.observability import tracing
    mx.profiler.set_config(filename=str(tmp_path / "p.json"))
    mx.profiler.set_state("run")
    try:
        with pytest.raises(RuntimeError):
            with mx.observability.trace_span("outer"):
                with mx.observability.trace_span("inner"):
                    raise RuntimeError("boom")
        # depth restored through BOTH unwinds, events still recorded
        assert tracing._depth() == 0
        names = [e["name"] for e in mx.profiler._events]
        assert names.count("inner") == 1 and names.count("outer") == 1
        inner = next(e for e in mx.profiler._events
                     if e["name"] == "inner")
        outer = next(e for e in mx.profiler._events
                     if e["name"] == "outer")
        # nesting invariant: inner's range inside outer's
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    finally:
        mx.profiler.set_state("stop")


def test_step_span_monotonic_fallback_when_paused(tmp_path):
    """While the profiler is PAUSED, step_span still lands a correctly
    ordered flight record (same perf_counter clock) and adds nothing to
    the suppressed profiler buffer — the two timelines cannot disagree
    on t0/t1 ordering across a pause/resume cycle."""
    mx.profiler.set_config(filename=str(tmp_path / "p.json"))
    mx.profiler.set_state("run")
    try:
        with mx.observability.step_span(1):
            pass
        mx.profiler.pause()
        with mx.observability.step_span(2):
            pass
        mx.profiler.resume()
        with mx.observability.step_span(3):
            pass
    finally:
        mx.profiler.set_state("stop")
    prof_steps = [e["args"]["step"] for e in mx.profiler._events
                  if e["cat"] == "step"]
    assert prof_steps == [1, 3]          # paused step suppressed (parity)
    fl = _spans("train_step")
    assert [r[4] for r in fl] == [1, 2, 3]   # flight saw all three
    t0s = [r[2] for r in fl]
    assert t0s == sorted(t0s)            # monotonic ordering held
    # cross-timeline ordering: step 3's profiler ts >= step 2's flight t1
    step3 = next(e for e in mx.profiler._events
                 if e["cat"] == "step" and e["args"]["step"] == 3)
    assert step3["ts"] >= fl[1][3]


# -- trainer / fit integration ----------------------------------------------

def _one_gluon_step(net=None):
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    rs = np.random.RandomState(0)
    if net is None:
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(8, activation="relu"))
            net.add(nn.Dense(1))
        net.hybridize()
        net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01}, kvstore="tpu_sync",
                            update_on_kvstore=False)
    x = mx.nd.array(rs.normal(0, 1, (4, 8)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (4, 1)).astype("f"))
    loss_fn = gluon.loss.L2Loss()
    for _ in range(3):
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        trainer.step(4)
    return trainer


def test_trainer_step_phases_recorded():
    _one_gluon_step()
    steps = _spans("mx.trainer.step")
    assert len(steps) == 3
    assert [r[4] for r in steps] == [0, 1, 2]      # step ids
    assert len(_spans("mx.trainer.allreduce")) == 3
    assert len(_spans("mx.optimizer.update_all")) == 3
    # sub-phases nest inside their step's window, name it as their
    # parent and share its step id
    s0 = steps[0]
    ar0 = _spans("mx.trainer.allreduce")[0]
    assert s0[2] <= ar0[2] and ar0[3] <= s0[3] and ar0[4] == 0
    assert ar0[7] == "mx.trainer.step"
    # watched: mx.trainer.step feeds the watchdog EWMA
    assert flight.watch_state()["mx.trainer.step"]["count"] == 3


@pytest.mark.perf_smoke
def test_fused_step_dispatch_gate_with_recorder_enabled():
    """Acceptance: the recorder is ON (default) and the fused trainer
    step still fits the <=4-dispatch budget — instrumentation must
    never become the overhead (or the dispatches) it measures."""
    assert flight.ENABLED
    from mxnet_tpu import observability as obs
    # steady-state: one net/trainer, warm, then measure per-step deltas
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    rs = np.random.RandomState(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01}, kvstore="tpu_sync",
                            update_on_kvstore=False)
    x = mx.nd.array(rs.normal(0, 1, (4, 8)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (4, 1)).astype("f"))
    loss_fn = gluon.loss.L2Loss()

    def step():
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        trainer.step(4)

    for _ in range(3):
        step()
    c0 = obs.dispatch_counts()
    for _ in range(3):
        step()
    c1 = obs.dispatch_counts()
    per = (c1["total"] - c0["total"]) / 3
    assert per <= 4.0, (per, c0, c1)
    assert m.TRAINER_STEP_DISPATCHES.get() <= 2.0


# -- serving: trace ids end to end -------------------------------------------

def test_trace_id_propagates_across_microbatcher_threads():
    pred = _mlp_predictor().warmup()
    with serving.MicroBatcher(pred, max_wait_ms=0) as mb:
        fut = mb.submit(data=np.zeros((2, 8), np.float32))
        fut.result(timeout=10)
    waits = _spans("serve_queue_wait")
    assert len(waits) == 1
    tid = waits[0][5]
    assert tid is not None
    # the group phases ran on the DISPATCHER thread; the request's id
    # reached them through trace_scope
    for phase in ("serve_submit", "serve_stack", "serve_pad",
                  "serve_dispatch", "serve_slice"):
        recs = _spans(phase)
        assert recs, phase
        assert any(r[5] is not None and tid in r[5] for r in recs), \
            (phase, tid, recs)
    # serve_submit ran on the CALLER thread, serve_dispatch on the
    # dispatcher — same trace id across two segments/threads
    segs = {id(s) for s, r in flight.records()
            if r[0] == "serve_submit"}
    dsegs = {id(s) for s, r in flight.records()
             if r[0] == "serve_dispatch"}
    assert segs and dsegs and segs != dsegs


def test_coalesced_group_ids_joined():
    pred = _mlp_predictor().warmup()
    with serving.MicroBatcher(pred, max_wait_ms=40, max_batch=8) as mb:
        f1 = mb.submit(data=np.zeros((2, 8), np.float32))
        f2 = mb.submit(data=np.ones((2, 8), np.float32))
        f1.result(timeout=10), f2.result(timeout=10)
    waits = _spans("serve_queue_wait")
    ids = {r[5] for r in waits}
    assert len(ids) == 2
    disp = _spans("serve_dispatch")
    # both requests' ids joinable against the group dispatch span
    joined = ",".join(sorted(i for r in disp for i in (r[5] or "").split(",")))
    for i in ids:
        assert i in joined, (i, disp)


def test_resilient_server_admission_and_exemplars():
    pred = _mlp_predictor().warmup()
    m.SERVE_LATENCY_SECONDS.reset()
    with serving.ResilientServer(pred, max_wait_ms=0) as srv:
        srv.predict(data=np.zeros((2, 8), np.float32))
    adm = _spans("serve_admission")
    assert len(adm) == 1 and adm[0][5] is not None
    tid = adm[0][5]
    waits = _spans("serve_queue_wait")
    assert waits and waits[0][5] == tid
    # exemplar: some latency bucket carries this request's trace id
    ex = m.SERVE_LATENCY_SECONDS.exemplars()
    assert any(v["trace_id"] == tid for v in ex.values()), (tid, ex)
    snap = mx.observability.snapshot()
    assert snap["serving"]["latency_exemplars"] == ex
    assert snap["flight"]["enabled"] is True
    assert "serve_dispatch" in snap["flight"]["phases"]


# -- watchdog / auto-dump ----------------------------------------------------

def test_slow_phase_anomaly_autodump(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    flight.SLOW_FACTOR = 3.0
    flight.AUTO_DUMP_MIN_S = 0.0
    for _ in range(6):
        flight.note("unit_step", 0.010)
    assert not _dumps(tmp_path)          # warmed, nothing anomalous
    flight.note("unit_step", 0.200)      # 20x the EWMA
    assert _wait_for(lambda: _dumps(tmp_path))
    (name,) = _dumps(tmp_path)
    with open(tmp_path / name) as f:
        doc = json.load(f)
    assert doc["metadata"]["reason"] == "anomaly"
    assert doc["metadata"]["anomaly"]["phase"] == "unit_step"
    assert m.FLIGHT_DUMPS.get(reason="anomaly") >= 1.0
    st = flight.stats()
    assert st["last_anomaly"]["phase"] == "unit_step"


def test_autodump_rate_limited(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    flight.SLOW_FACTOR = 3.0
    flight.AUTO_DUMP_MIN_S = 3600.0
    for _ in range(6):
        flight.note("rl_step", 0.010)
    flight.note("rl_step", 0.500)
    assert _wait_for(lambda: _dumps(tmp_path))
    n1 = len(_dumps(tmp_path))
    for _ in range(6):
        flight.note("rl_step", 0.500)    # would re-trigger, rate-limited
    time.sleep(0.1)
    assert len(_dumps(tmp_path)) == n1


@pytest.mark.chaos
def test_slow_request_injection_autoproduces_linked_dump(tmp_path,
                                                         monkeypatch):
    """THE acceptance drill: a faultinject serving.dispatch delay makes
    one request slow; the watchdog auto-dumps a Perfetto-loadable
    timeline in which that request's queue/pad/dispatch/slice spans
    share one trace_id."""
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    # beside busy workers one warm-up request of a millisecond now and then
    # takes four times the others: nothing may dump while the base is taken
    flight.SLOW_FACTOR = float("inf")
    flight.AUTO_DUMP_MIN_S = 0.0
    pred = _mlp_predictor().warmup()
    with serving.MicroBatcher(pred, max_wait_ms=0) as mb:
        for _ in range(8):   # warm the serve_request EWMA
            mb.submit(data=np.zeros((2, 8), np.float32)).result(timeout=10)
        assert not _dumps(tmp_path)
        # the threshold lies at half the injected delay, in units of the
        # base as this load has it (never under the default factor)
        base = flight.watch_ewma("serve_request")
        assert base is not None and base < 0.125 / 4.0, base
        flight.SLOW_FACTOR = 0.125 / base
        with fi.active(fi.FaultPlan().add("serving.dispatch", "delay",
                                          delay_s=0.25)):
            mb.submit(data=np.zeros((2, 8), np.float32)).result(timeout=10)
    assert _wait_for(lambda: _dumps(tmp_path)), \
        "slow request did not auto-dump"
    newest = max((tmp_path / n for n in _dumps(tmp_path)),
                 key=os.path.getmtime)
    with open(newest) as f:
        doc = json.load(f)
    assert doc["metadata"]["anomaly"]["phase"] == "serve_request"
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    # find the slow dispatch, take its trace_id, demand the full chain
    slow = max((e for e in evs if e["name"] == "serve_dispatch"),
               key=lambda e: e["dur"])
    tid = slow["args"]["trace_id"].split(",")[0]
    chain = {"serve_queue_wait", "serve_pad", "serve_dispatch",
             "serve_slice"}
    got = {e["name"] for e in evs
           if tid in (e.get("args", {}).get("trace_id") or "")}
    assert chain <= got, (tid, sorted(got))
    assert slow["dur"] >= 0.2 * 1e6      # the injected 250ms is visible


# -- SIGUSR2 -----------------------------------------------------------------

@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="platform has no SIGUSR2")
def test_sigusr2_dump_in_subprocess(tmp_path):
    code = (
        "import os, signal, time, json\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.observability import flight\n"
        "with flight.phase_span('sig_phase'):\n"
        "    pass\n"
        "os.kill(os.getpid(), signal.SIGUSR2)\n"
        "for _ in range(100):\n"
        "    names = [n for n in os.listdir(os.environ['MXNET_FLIGHT_DIR'])\n"
        "             if n.endswith('.json') and '.tmp' not in n]\n"
        "    if names: break\n"
        "    time.sleep(0.05)\n"
        "doc = json.load(open(os.path.join(\n"
        "    os.environ['MXNET_FLIGHT_DIR'], names[0])))\n"
        "assert doc['metadata']['reason'] == 'signal', doc\n"
        "assert any(e['name'] == 'sig_phase'\n"
        "           for e in doc['traceEvents']), doc\n"
        "print('OK')\n")
    env = dict(os.environ, MXNET_FLIGHT_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-1000:], out.stderr[-2000:])


# -- concurrency -------------------------------------------------------------

def test_sanitizer_clean_concurrent_writers():
    """The drill the 'lock-cheap ring writes' claim must survive:
    N writer threads + a concurrent dumper/summarizer under
    MXNET_SANITIZE=1 — no lock-order violations, no lost segments,
    consistent written counts."""
    from mxnet_tpu.analysis import sanitizer as san
    san.reset()
    san.enable()
    try:
        flight.configure(ring=64)   # rebuilds flight locks as tracked
        per_thread, n_threads = 200, 6
        errs = []

        def writer(k):
            try:
                for i in range(per_thread):
                    with flight.phase_span("conc_phase", cat="t",
                                           step=i, watch=(i % 10 == 0)):
                        pass
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        def reader():
            try:
                for _ in range(20):
                    flight.summary()
                    flight.stats()
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=writer, args=(k,))
              for k in range(n_threads)] + [threading.Thread(target=reader)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert not errs, errs
        assert san.violations() == [], san.violations()
        st = flight.stats()
        assert st["written"] == per_thread * n_threads
        assert st["segments"] == n_threads   # reader wrote nothing
        assert st["drops"] == n_threads * (per_thread - 64)
    finally:
        san.disable()
        san.reset()
        flight.configure(ring=int(mx.base.getenv("MXNET_FLIGHT_RING",
                                                 4096)))


# -- snapshot / summary schema ----------------------------------------------

def test_snapshot_flight_schema():
    with flight.phase_span("snap_phase", step=1):
        pass
    blk = mx.observability.snapshot()["flight"]
    assert set(blk) >= {"enabled", "ring", "records", "written", "drops",
                        "segments", "dumps", "phases", "watch"}
    ph = blk["phases"]["snap_phase"]
    assert set(ph) >= {"count", "total_ms", "p50_ms", "p99_ms", "max_ms",
                       "slowest"}
    assert ph["count"] == 1 and ph["slowest"][0]["step"] == 1
    json.dumps(blk)   # JSON-able end to end


def test_summary_percentiles_and_slowest():
    for i in range(100):
        flight.record("pctl_phase", "t", 0.0, float(i + 1) * 1e3)
    s = flight.summary(top=2)["pctl_phase"]
    assert s["count"] == 100
    assert 45.0 <= s["p50_ms"] <= 55.0
    assert 95.0 <= s["p99_ms"] <= 100.0
    assert s["max_ms"] == 100.0
    assert [r["dur_ms"] for r in s["slowest"]] == [100.0, 99.0]


def test_phase_name_cardinality_rule():
    """The new graft-lint facet: a dynamically built phase name is a
    finding; literal names pass."""
    from mxnet_tpu.analysis.checkers import MetricsHygieneChecker
    from mxnet_tpu.analysis.core import FileCtx
    import ast as _ast
    bad = ("from mxnet_tpu.observability import flight\n"
           "def f(key, prof):\n"
           "    with flight.phase_span(f'phase_{key}'):\n"
           "        pass\n"
           "    flight.record('ok_literal', 't', 0, 1)\n"
           "    with flight.phase_span('fine'):\n"
           "        pass\n"
           "    with prof.phase_span('p_' + key):\n"   # any alias/base
           "        pass\n"
           "    fl = flight\n"
           "    fl.record(key.format(), 't', 0, 1)\n")
    ctx = FileCtx("x.py", "x.py", bad, _ast.parse(bad))
    findings = MetricsHygieneChecker().check_file(ctx)
    assert len(findings) == 3, findings
    assert all("phase name" in f.message for f in findings)
    assert sorted(f.line for f in findings) == [3, 8, 11]
