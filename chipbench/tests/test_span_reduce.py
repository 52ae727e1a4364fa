"""span_reduce and the four readers on hand-built windows whose answers are
known from how they were made (CPU, synthetic event lists: names and times
as `trace_reduce.load` gives them).

The window: 100 ms under `chipbench_window`, two steps of the Module path.
Per step the host opens `mx.module.forward_backward` (10 ms: gather 2,
launch 6, deposit 1, 1 ms of its own), `mx.module.update` (4 ms, 3 of them
under `mx.optimizer.update_all`), then `mx.module.update_metric` (30 ms, of which
24 under `mx.sync.read`), 6 ms outside every span.  The device runs the
forward-backward program for 20 ms and the update for 5 ms, and its clock
is 0.6 ms ahead of the host's (its timestamps come out 0.6 ms early).
"""
import importlib.util
import os

import pytest

from chipbench import cell as cellmod
from chipbench import span_reduce as sr
from chipbench import trace_reduce as tr

MS = 1e6
AHEAD = 0.6 * MS
HERE = os.path.dirname(os.path.abspath(__file__))


def _reader(name):
    return cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", name + ".py"),
        "chipbench_metric_test_" + name)


def _step(t0, host, modules, ops, launch_latency):
    """One step starting at host time `t0` (ns).  The forward-backward
    program starts `launch_latency` after `mx.executor.launch` opens."""
    ms = MS
    host += [
        ("mx.module.forward_backward", t0, t0 + 10 * ms),
        ("mx.executor.gather", t0 + 0.5 * ms, t0 + 2.5 * ms),
        ("mx.rng.next_key", t0 + 1 * ms, t0 + 2 * ms),
        ("mx.executor.launch", t0 + 2.5 * ms, t0 + 8.5 * ms),
        ("PjitFunction(mx_executor_fwd_bwd)", t0 + 2.6 * ms, t0 + 8.4 * ms),
        ("mx.executor.deposit", t0 + 8.5 * ms, t0 + 9.5 * ms),
        ("mx.module.update", t0 + 10 * ms, t0 + 14 * ms),
        ("mx.optimizer.update_all", t0 + 10.5 * ms, t0 + 13.5 * ms),
        ("mx.module.update_metric", t0 + 14 * ms, t0 + 44 * ms),
        ("mx.sync.read", t0 + 15 * ms, t0 + 39 * ms),
    ]
    fb0 = t0 + 2.5 * ms + launch_latency
    up0 = fb0 + 20 * ms
    modules += [("jit_mx_executor_fwd_bwd(11)", fb0 - AHEAD,
                 fb0 + 20 * ms - AHEAD),
                ("jit_mx_fused_update(12)", up0 - AHEAD,
                 up0 + 5 * ms - AHEAD)]
    ops += [("%fusion.1 = f32[8] fusion(...)", fb0 - AHEAD,
             fb0 + 20 * ms - AHEAD),
            ("%fusion.2 = f32[8] fusion(...)", up0 - AHEAD,
             up0 + 5 * ms - AHEAD)]


def _trace(with_spans=True, names=None):
    host, modules, ops = [], [], []
    # the first launch starts the instant its span opens: the bound that
    # gives the offset away; the second 3 ms after
    _step(0.0, host, modules, ops, launch_latency=0.0)
    _step(50 * MS, host, modules, ops, launch_latency=3 * MS)
    host.append((tr.WINDOW_SPAN, 0.0, 100 * MS))
    if not with_spans:
        host = [h for h in host if not h[0].startswith("mx.")]
    if names:
        modules = [(names.get(sr.program_of(n), n), s, e)
                   for n, s, e in modules]
    host.sort(key=lambda ev: ev[1])
    return {"host": host,
            "devices": {"/device:TPU:0": {"modules": modules, "ops": ops}}}


def _ctx(trace, steps=2):
    return {"reduced": tr.reduce(trace),
            "window": {"attempted": steps, "completed": steps}}


def test_offset_is_recovered_from_the_launches():
    trace = _trace()
    _lo, _hi, spans = sr.window_spans(trace)
    shift, paired = sr.clock_offset_ns(
        spans, trace["devices"]["/device:TPU:0"]["modules"])
    assert shift == pytest.approx(AHEAD)
    # two forward-backwards against mx.executor.launch, two updates
    # against mx.optimizer.update_all
    assert paired == 4


def test_innermost_segments_tile_the_spans_once():
    _lo, _hi, spans = sr.window_spans(_trace())
    segs = sr.innermost_segments(spans)
    own = sr.self_seconds(segs)
    # per step: forward_backward 1 ms of its own (0.5 before gather, 0.5
    # after deposit), gather 2 - 1 under next_key, launch 6, deposit 1,
    # update 4 - 3 under update_all, update_metric 30 - 24
    assert own["mx.module.forward_backward"] == pytest.approx(2 * 1e-3)
    assert own["mx.executor.gather"] == pytest.approx(2 * 1e-3)
    assert own["mx.rng.next_key"] == pytest.approx(2 * 1e-3)
    assert own["mx.executor.launch"] == pytest.approx(2 * 6e-3)
    assert own["mx.executor.deposit"] == pytest.approx(2 * 1e-3)
    assert own["mx.module.update"] == pytest.approx(2 * 1e-3)
    assert own["mx.optimizer.update_all"] == pytest.approx(2 * 3e-3)
    assert own["mx.module.update_metric"] == pytest.approx(2 * 6e-3)
    assert own["mx.sync.read"] == pytest.approx(2 * 24e-3)
    # nothing counted twice: the self times add up to the spans' union
    union = sum(e - s for s, e in tr.union([(s, e) for _n, s, e in spans]))
    assert sum(own.values()) == pytest.approx(union / 1e9)
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))


def test_idle_is_split_by_what_the_host_did():
    an = sr.analyse(_trace())
    shares = sr.idle_shares(an)
    by = {k: v / MS for k, v in an["idle_ns_by_span"].items()}
    # step 1: idle 0 - 2.5 ms (forward_backward 0.5, gather 1, next_key 1),
    # busy 2.5 - 27.5, idle 27.5 - 55.5: sync.read 11.5, update_metric 5,
    # outside 6, then step 2's forward_backward 0.5, gather 1, next_key 1,
    # launch 3; busy 55.5 - 80.5, idle to 100: sync.read 8.5,
    # update_metric 5, outside 6
    assert by["mx.sync.read"] == pytest.approx(20.0)
    assert by["mx.module.update_metric"] == pytest.approx(10.0)
    assert by["mx.executor.launch"] == pytest.approx(3.0)
    assert by["mx.module.forward_backward"] == pytest.approx(1.0)
    assert by["mx.executor.gather"] == pytest.approx(2.0)
    assert by["mx.rng.next_key"] == pytest.approx(2.0)
    assert by[sr.OUTSIDE] == pytest.approx(12.0)
    assert shares["host_work"] == pytest.approx(18.0)
    assert shares["sync_read"] == pytest.approx(20.0)
    assert shares["outside"] == pytest.approx(12.0)
    # the three parts are the device's idle share
    ctx = _ctx(_trace())
    idle = _reader("device_idle_share").read(ctx)
    assert idle == pytest.approx(50.0)
    assert sum(shares.values()) == pytest.approx(idle)


def test_a_gap_over_two_sibling_spans_goes_to_both():
    # idle from 5 to 25: 3 ms left of launch (5 - 8), 4 ms of deposit
    # (8 - 12), 13 ms of update_metric (12 - 25)
    segs = [(2 * MS, 8 * MS, "mx.executor.launch"),
            (8 * MS, 12 * MS, "mx.executor.deposit"),
            (12 * MS, 40 * MS, "mx.module.update_metric")]
    by = sr.overlap_by_name([(5 * MS, 25 * MS), (50 * MS, 51 * MS)], segs)
    assert by == {"mx.executor.launch": 3 * MS, "mx.executor.deposit": 4 * MS,
                  "mx.module.update_metric": 13 * MS, sr.OUTSIDE: 1 * MS}


def test_readers_on_the_known_window():
    ctx = _ctx(_trace())
    assert _reader("idle_host_work_share").read(ctx) == pytest.approx(18.0)
    # busy host time a step: 1 + 1 + 1 + 6 + 1 + 1 + 3 + 6 (no sync.read)
    assert _reader("host_ms_per_step").read(ctx) == pytest.approx(20.0)
    assert _reader("update_device_ms").read(ctx) == pytest.approx(5.0)


def test_without_the_programs_spans_the_readers_return_none():
    """The parent commit's trace: JAX's and the entry's spans only, and
    programs under their old names."""
    old = {"jit_mx_executor_fwd_bwd": "jit_fb(11)",
           "jit_mx_fused_update": "jit__apply(12)"}
    ctx = _ctx(_trace(with_spans=False, names=old))
    for name in ("idle_host_work_share", "host_ms_per_step",
                 "update_device_ms"):
        assert _reader(name).read(ctx) is None, name
    # spans but no jit_mx_* launch to set the clocks by: still nothing
    ctx = _ctx(_trace(names=old))
    assert _reader("idle_host_work_share").read(ctx) is None
    assert _reader("update_device_ms").read(ctx) is None
    assert _reader("host_ms_per_step").read(ctx) == pytest.approx(20.0)
    # and a run that was not traced
    bare = {"window": {"attempted": 2}}
    for name in ("idle_host_work_share", "host_ms_per_step",
                 "update_device_ms"):
        assert _reader(name).read(bare) is None


def test_launches_that_cannot_be_paired_leave_the_offset_at_zero():
    trace = _trace()
    dev = trace["devices"]["/device:TPU:0"]
    dev["modules"].append(("jit_mx_executor_fwd_bwd(11)", 99 * MS, 99.5 * MS))
    dev["modules"].append(("jit_mx_fused_update(12)", 99.5 * MS, 99.6 * MS))
    _lo, _hi, spans = sr.window_spans(trace)
    assert sr.clock_offset_ns(spans, dev["modules"]) == (0.0, 0)
    assert sr.analyse(trace) is not None


def test_program_load_s_reads_the_programs_counter(monkeypatch):
    from mxnet_tpu.observability import metrics
    reader = _reader("program_load_s")
    monkeypatch.setattr(metrics, "PROGRAM_LOAD_SECONDS",
                        type("C", (), {"value": 7.25})())
    assert reader.read({}) == 7.25
    monkeypatch.setattr(metrics, "PROGRAM_LOAD_SECONDS",
                        type("C", (), {"value": 0.0})())
    assert reader.read({}) is None
    monkeypatch.delattr(metrics, "PROGRAM_LOAD_SECONDS")
    assert reader.read({}) is None


def test_an_enclosing_step_span_would_take_every_gaps_label():
    """Why `mx.step` goes to the ring and not to the host plane: the
    accepted reduction gives a gap to the span that overlaps it most (so
    `mx.module.update_metric` here, not the `mx.sync.read` inside it: the
    split by innermost span is `idle_ns_by_span`'s)."""
    trace = _trace()
    gap = (27.5 * MS, 55.5 * MS)
    assert tr.label_gap(trace["host"], *gap) == "mx.module.update_metric"
    with_step = sorted(trace["host"] + [("mx.step", 0.0, 49 * MS),
                                        ("mx.step", 49.5 * MS, 99 * MS)],
                       key=lambda ev: ev[1])
    assert tr.label_gap(with_step, *gap) == "mx.step"


def test_report_for_the_builder():
    rep = sr.report(_ctx(_trace()))
    assert rep["clock_offset_ms"] == pytest.approx(0.6)
    assert rep["device_idle_share"] == pytest.approx(50.0)
    assert sum(rep["idle_shares"].values()) == pytest.approx(50.0)
    assert rep["idle_ms_a_step_by_innermost_span"]["mx.sync.read"] == \
        pytest.approx(10.0)
    assert rep["ops_over_1ms_a_step"] == [["fusion.1", pytest.approx(20.0)],
                                          ["fusion.2", pytest.approx(5.0)]]
    # a trace without the spans still reports the device's side
    rep = sr.report(_ctx(_trace(with_spans=False)))
    assert "idle_shares" not in rep and rep["ops_over_1ms_a_step"]


def _tier1_names():
    path = os.path.join(os.path.dirname(os.path.dirname(HERE)), "tests",
                        "test_span_tracing.py")
    spec = importlib.util.spec_from_file_location("_tier1_span_names", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_name_a_reader_looks_for_is_one_the_program_emits():
    """tests/test_span_tracing.py sees these names emitted by the two step
    paths; a rename there cannot silently null a metric here."""
    t1 = _tier1_names()
    emitted = t1.MODULE_SPANS | t1.GLUON_SPANS
    programs = {"jit_" + p for p in t1.MODULE_PROGRAMS | t1.GLUON_PROGRAMS}
    assert sr.SYNC_SPAN in emitted
    assert all(n.startswith(sr.SPAN_PREFIX) for n in emitted)
    assert set(sr.LAUNCHED_UNDER.values()) <= emitted
    # the programs the cells launch are known to the pairing, and the
    # update's own program is read
    cell_programs = {"jit_mx_executor_fwd_bwd", "jit_mx_fused_update",
                     "jit_mx_cachedop_fwd", "jit_mx_cachedop_bwd"}
    assert cell_programs <= programs
    assert cell_programs <= set(sr.LAUNCHED_UNDER)
    assert "jit_mx_fused_update" in sr.UPDATE_PROGRAMS
    from mxnet_tpu.observability import tracing
    assert set(sr.LAUNCHED_UNDER.values()) | {sr.SYNC_SPAN} <= \
        set(tracing.SPAN_NAMES)


def test_benchmark_lists_a_reader_file_for_every_new_metric():
    names = {m["name"] for m in cellmod.benchmark()["per_layer"]}
    for reader in ("idle_host_work_share", "host_ms_per_step",
                   "update_device_ms", "program_load_s"):
        assert {reader + ".images", reader + ".tokens"} <= names
        assert os.path.isfile(os.path.join(cellmod.HERE, "metrics",
                                           reader + ".py"))
