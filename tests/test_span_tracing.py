"""The one span primitive (observability/tracing.py) on both training step
paths, seen where a reader of any JAX profiler session sees it: the host
plane of the `*.xplane.pb` that a plain `jax.profiler.start_trace` writes,
with `mx.profiler` stopped; and in the flight ring, with parent and step.

One traced run of two `Module.fit` steps and two Gluon steps of a tiny net
feeds most tests here (module-scoped fixture).  The name tables below are
what chipbench's readers are held to (chipbench/tests/test_span_reduce.py
loads them from this file): a rename in the program fails here first.
"""
import glob
import os
import tempfile

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.observability import flight, metrics, tracing

# child -> parents, as the step paths nest them (ISSUE 25's table).  The
# Module step's first half (gather, key, launch) runs under
# `mx.module.prepare` from the second step on (ISSUE 34: fit launches the
# next batch's program before it reads this step's metric); a first step and
# a step whose held launch was dropped keep it under
# `mx.module.forward_backward`, which otherwise holds only the deposit.
MODULE_NESTING = {
    "mx.module.forward_backward": ("mx.step",),
    "mx.executor.gather": ("mx.module.prepare",
                           "mx.module.forward_backward"),
    "mx.executor.launch": ("mx.module.prepare",
                           "mx.module.forward_backward"),
    "mx.executor.deposit": ("mx.module.forward_backward",),
    "mx.module.update": ("mx.step",),
    "mx.kvstore.pushpull": ("mx.module.update",),
    "mx.optimizer.update_all": ("mx.kvstore.pushpull",),
    "mx.rng.next_key": ("mx.executor.gather",),
    "mx.sync.read": ("mx.module.update_metric",),
}
MODULE_TOP = ("mx.step", "mx.fit.data_fetch", "mx.module.prepare",
              "mx.module.update_metric", "mx.fit.callbacks",
              "mx.fit.epoch_end")
GLUON_NESTING = {
    "mx.rng.next_key": ("mx.cachedop.forward",),
    "mx.cachedop.backward": ("mx.autograd.backward",),
    "mx.trainer.allreduce": ("mx.trainer.step",),
    "mx.optimizer.update_all": ("mx.trainer.step",),
}
GLUON_TOP = ("mx.cachedop.forward", "mx.autograd.backward",
             "mx.trainer.step", "mx.sync.read")
# step-level spans go to the ring only: on the host plane they would take
# every idle gap's label (tracing.py; chipbench's label_gap test)
RING_ONLY = ("mx.step", "mx.fit.epoch")
MODULE_SPANS = set(MODULE_NESTING) | set(MODULE_TOP)
GLUON_SPANS = set(GLUON_NESTING) | set(GLUON_TOP)
# programs as JAX names their launches (`PjitFunction(<name>)` on the host
# plane, `jit_<name>` on the device's)
MODULE_PROGRAMS = {"mx_executor_fwd_bwd", "mx_fused_update"}
GLUON_PROGRAMS = {"mx_cachedop_fwd", "mx_cachedop_bwd", "mx_kv_flatten",
                  "mx_fused_update"}


def _module():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.rand(8, 4).astype("f"),
                           (np.arange(8) % 8).astype("f"), batch_size=4)
    return mx.mod.Module(net), it


def _fit(mod, it):
    it.reset()
    mod.fit(it, num_epoch=1, eval_metric="acc", kvstore="tpu_sync",
            batch_end_callback=lambda p: None)


def _gluon_step():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8), gluon.nn.Dense(2))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore="tpu_sync",
                            update_on_kvstore=False)
    loss_fn = gluon.loss.L2Loss()
    rs = np.random.RandomState(1)
    x, y = mx.nd.array(rs.rand(4, 4)), mx.nd.array(rs.rand(4, 2))

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(4)
        return loss.asnumpy()

    step.trainer = trainer
    return step


def _host_plane(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def _inside(events, marker):
    (lo, hi), = [(s, e) for n, s, e in events if n == marker]
    return [ev for ev in events if lo <= ev[1] and ev[2] <= hi
            and ev[0] != marker]


@pytest.fixture(scope="module")
def traced():
    """Both paths under a plain JAX profiler session, `mx.profiler`
    stopped: {"module" / "gluon": {"host": events, "ring": records}}."""
    if mx.profiler.is_running():  # an earlier test's failure left it on
        mx.profiler.set_state("stop")
    was = flight.ENABLED
    flight.enable()
    mod, it = _module()
    _fit(mod, it)            # compiles
    step = _gluon_step()
    step()
    step()
    flight.reset()
    tdir = tempfile.mkdtemp(prefix="mxt-span-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("path_module"):
            _fit(mod, it)    # two steps of batch 4 over 8 rows
        ring_module = [r for _seg, r in flight.records()]
        flight.reset()
        first_id = step.trainer._step_id
        tracing.set_step(first_id)
        with jax.profiler.TraceAnnotation("path_gluon"):
            step()
            step()
        ring_gluon = [r for _seg, r in flight.records()]
    finally:
        jax.profiler.stop_trace()
        if not was:
            flight.disable()
    host = _host_plane(tdir)
    return {"module": {"host": _inside(host, "path_module"),
                       "ring": ring_module},
            "gluon": {"host": _inside(host, "path_gluon"),
                      "ring": ring_gluon, "first_id": first_id}}


def _mx(events):
    return [ev for ev in events if ev[0].startswith("mx.")]


@pytest.mark.parametrize("path,want", [("module", MODULE_SPANS),
                                       ("gluon", GLUON_SPANS)])
def test_host_plane_holds_exactly_the_paths_spans(traced, path, want):
    names = {n for n, _s, _e in _mx(traced[path]["host"])}
    assert names == want - set(RING_ONLY)
    # the ring holds the same spans, and the step-level ones besides
    ring = {r[0] for r in traced[path]["ring"]}
    extra = {"mx.fit.epoch"} if path == "module" else set()
    assert ring == want | extra
    assert ring | names <= set(tracing.SPAN_NAMES)


@pytest.mark.parametrize("path,nesting", [("module", MODULE_NESTING),
                                          ("gluon", GLUON_NESTING)])
def test_children_lie_inside_their_parents_on_the_host_plane(
        traced, path, nesting):
    spans = _mx(traced[path]["host"])
    for child, parents in nesting.items():
        if set(parents) & set(RING_ONLY):
            continue
        kids = [ev for ev in spans if ev[0] == child]
        assert kids, child
        for _n, s, e in kids:
            holders = [p for p in spans if p[0] in parents
                       and p[1] <= s and e <= p[2]]
            if child in ("mx.sync.read", "mx.rng.next_key") and \
                    not holders:
                continue  # also opened elsewhere (the loop's own read)
            assert holders, (child, parents)


def test_ring_records_carry_parent_and_step_module(traced):
    ring = traced["module"]["ring"]
    steps = [r for r in ring if r[0] == "mx.step"]
    assert [r[4] for r in steps] == [0, 1] and all(r[7] is None
                                                   for r in steps)
    for r in ring:
        if r[0] in MODULE_NESTING and r[7] is not None:
            assert r[7] in MODULE_NESTING[r[0]], r
    for name in MODULE_NESTING:
        if name not in ("mx.sync.read", "mx.rng.next_key"):
            assert all(r[7] in MODULE_NESTING[name] for r in ring
                       if r[0] == name), name
    # the first step launches inside itself; the second step's program was
    # launched by prepare() while the first one's metric was still unread,
    # and its forward_backward holds the deposit alone
    for name in ("mx.executor.gather", "mx.executor.launch"):
        assert [r[7] for r in ring if r[0] == name] == \
            ["mx.module.forward_backward", "mx.module.prepare"], name
    assert [r[7] for r in ring if r[0] == "mx.executor.deposit"] == \
        ["mx.module.forward_backward"] * 2
    assert [r[0] for r in ring if r[7] == "mx.module.forward_backward"
            and r[4] == 1] == ["mx.executor.deposit"]
    # every span carries the id of the step it works for: prepare() and
    # the launch under it that of the step to come
    per_step = [r for r in ring if r[0] == "mx.executor.launch"]
    assert [r[4] for r in per_step] == [0, 1]
    assert [r[4] for r in ring if r[0] == "mx.module.prepare"] == [1]
    after = [r for r in ring if r[0] in ("mx.module.update_metric",
                                         "mx.fit.callbacks")]
    assert sorted(r[4] for r in after) == [0, 0, 1, 1]
    # the step's record carries its deltas: one forward-backward and one
    # update launched, nothing read back, nothing compiled
    assert steps[1][6] == {"launches": 2.0, "device_puts": 0.0,
                           "sync_reads": 0.0, "program_loads": 0.0}


def test_ring_records_carry_parent_and_step_gluon(traced):
    ring, first = traced["gluon"]["ring"], traced["gluon"]["first_id"]
    tsteps = [r for r in ring if r[0] == "mx.trainer.step"]
    assert [r[4] for r in tsteps] == [first, first + 1]
    # a step runs from one Trainer.step return to the next: forward and
    # backward of an iteration carry the id its Trainer.step will
    for name in ("mx.cachedop.forward", "mx.autograd.backward"):
        assert [r[4] for r in ring if r[0] == name] == [first, first + 1]
    for name, parents in GLUON_NESTING.items():
        got = {r[7] for r in ring if r[0] == name}
        assert got == set(parents), (name, got)
    # launches of the second whole step: split, unstack, forward, ones,
    # backward, flatten, update ... and the loop's one read
    deltas = tsteps[1][6]
    assert deltas["sync_reads"] == 1.0 and deltas["program_loads"] == 0.0
    assert deltas["launches"] >= 4.0


@pytest.mark.parametrize("path,programs", [("module", MODULE_PROGRAMS),
                                           ("gluon", GLUON_PROGRAMS)])
def test_programs_launch_under_their_mx_names(traced, path, programs):
    calls = {n[len("PjitFunction("):-1] for n, _s, _e in
             traced[path]["host"] if n.startswith("PjitFunction(")}
    assert programs <= calls, (programs - calls, calls)
    # no accidental name is left on a step path
    assert not calls & {"fb", "_apply", "_lambda", "<lambda>", "bwd",
                        "_flat", "_unflat", "ftrain", "fwd_d"}


def _lowered_name(jitted, *args):
    text = jitted.lower(*args).as_text()
    return text.split("module @", 1)[1].split(None, 1)[0]


def test_programs_lower_under_their_mx_names():
    mod, it = _module()
    _fit(mod, it)
    ex = mod._exec
    arg_vals = {k: v._data for k, v in ex.arg_dict.items()}
    aux_vals = {k: v._data for k, v in ex.aux_dict.items()}
    key = jax.random.PRNGKey(0)
    assert _lowered_name(ex._fwd, arg_vals, aux_vals, key, False) \
        == "jit_mx_executor_fwd"
    assert _lowered_name(ex._fwd_bwd, arg_vals, aux_vals, key, [None]) \
        == "jit_mx_executor_fwd_bwd"
    from mxnet_tpu.kvstore import GradBucketer
    bk = GradBucketer((((3,), "float32"), ((2, 2), "float32")), 1 << 20)
    grads = [np.zeros((3,), "f"), np.zeros((2, 2), "f")]
    assert _lowered_name(bk._flatten, grads) == "jit_mx_kv_flatten"
    flats = bk._flatten(grads)
    assert _lowered_name(bk._unflatten, flats) == "jit_mx_kv_unflatten"


def test_fused_module_step_has_its_mx_name(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    mod, it = _module()
    _fit(mod, it)
    assert mod._fstep["fn"].__name__ == "mx_module_fused_step"


def test_flight_disabled_leaves_no_record_and_the_body_runs():
    flight.enable()
    flight.reset()
    flight.disable()
    try:
        ran = []
        with tracing.span("mx.sync.read", cat="sync") as sp:
            ran.append(tracing._depth())
        assert ran == [1] and tracing._depth() == 0 and sp.seconds >= 0
        assert flight.stats()["records"] == 0
        step = _gluon_step()
        assert np.isfinite(step()).all()
        assert flight.stats()["records"] == 0
    finally:
        flight.enable()


def test_a_fresh_compile_moves_the_program_load_counters():
    flight.enable()
    flight.reset()
    loads, secs = metrics.PROGRAM_LOADS.value, \
        metrics.PROGRAM_LOAD_SECONDS.value

    def mx_test_fresh_program(x):
        return x * 3.0 + 1.0

    with tracing.span("mx.executor.launch", step=41):
        jax.jit(mx_test_fresh_program)(np.arange(5.0)).block_until_ready()
    assert metrics.PROGRAM_LOADS.value == loads + 1
    assert metrics.PROGRAM_LOAD_SECONDS.value > secs
    by_how = metrics.PROGRAM_LOADS.get(how="compile") + \
        metrics.PROGRAM_LOADS.get(how="cache")
    assert by_how == metrics.PROGRAM_LOADS.value
    (rec,) = [r for _s, r in flight.records() if r[0] == "mx.program.load"]
    assert rec[6]["program"] == "jit(mx_test_fresh_program)"
    assert rec[6]["how"] in ("compile", "cache")
    assert rec[4] == 41 and rec[7] == "mx.executor.launch"
    assert rec[3] - rec[2] > 0
    text = mx.observability.render_prometheus()
    assert "mxnet_program_loads_total" in text
    assert "mxnet_host_sync_reads_total" in text


def test_sync_reads_are_counted_beside_their_span():
    flight.enable()
    flight.reset()
    before = metrics.HOST_SYNC_READS.value
    a = mx.nd.array([1.0, 2.0])
    a.asnumpy()
    a.wait_to_read()
    (a.sum()).asscalar()
    assert metrics.HOST_SYNC_READS.value == before + 3
    assert len([r for _s, r in flight.records()
                if r[0] == "mx.sync.read"]) == 3


def test_data_fetch_span_feeds_the_data_wait_histogram():
    mod, it = _module()
    _fit(mod, it)
    flight.enable()
    flight.reset()
    before = metrics.DATA_WAIT_SECONDS.count
    total = metrics.DATA_WAIT_SECONDS.sum
    _fit(mod, it)
    fetched = [r for _s, r in flight.records()
               if r[0] == "mx.fit.data_fetch"]
    # two batches; the fetch that ends the epoch raises and is not counted
    assert len(fetched) == 3
    assert metrics.DATA_WAIT_SECONDS.count == before + 2
    # one clock pair: the histogram took the spans' own durations
    spans_s = sorted((r[3] - r[2]) / 1e6 for r in fetched)
    assert metrics.DATA_WAIT_SECONDS.sum - total <= sum(spans_s) + 1e-9


def test_older_names_are_the_one_primitive():
    from mxnet_tpu.observability import phase_span, step_span, trace_span
    assert trace_span is tracing.span and phase_span is tracing.span
    assert flight.phase_span is tracing.span
    assert not hasattr(tracing, "annotate")
    flight.enable()
    flight.reset()
    with step_span(5):
        pass
    (rec,) = [r for _s, r in flight.records()]
    assert rec[0] == "train_step" and rec[1] == "step" and rec[4] == 5


def test_default_dump_directory_is_not_the_working_directory(
        monkeypatch, tmp_path):
    from mxnet_tpu.base import flight_dir
    monkeypatch.delenv("MXNET_FLIGHT_DIR", raising=False)
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.chdir(tmp_path)
    want = os.path.join(str(tmp_path), "mxnet_flight")
    assert flight_dir() == want
    path = flight.dump()
    assert os.path.dirname(path) == want
    assert not glob.glob(os.path.join(str(tmp_path), "flight-*.json"))
