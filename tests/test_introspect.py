"""Program introspection (ISSUE 13): per-layer cost attribution inside
the donated whole-step program, MFU/roofline telemetry, and the
persisted perf-regression sentinel (mxnet_tpu.observability.introspect).

Contracts pinned here:
  * every compile chokepoint (Executor, CachedOp, FusedUpdater,
    WholeStepCompiler, serving bucket precompile) notes its program
    through ONE note_program surface with uniform memory-stats keys
    across jax versions;
  * jax.named_scope layer names round-trip from graph node names into
    the compiled HLO text, and per_layer() attributes >= 90% of the
    whole-step program's flops to named blocks on the pinned nets;
  * MFU math is exact under an injected peak; the sentinel fires
    exactly once (rate-limited) on a fabricated 2x step-time
    regression, flips the ResilientServer readyz() check, writes
    baselines atomically, and rejects corrupt baselines loudly;
  * MXNET_INTROSPECT=0 reduces every hook to one boolean test
    (in-process and at import);
  * whole-step training with introspection ON stays 1 steady-state
    dispatch (perf_smoke).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, sym, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.wholestep import WholeStepCompiler
from mxnet_tpu.observability import flight, introspect, memory
from mxnet_tpu.observability import metrics as m
from mxnet_tpu import observability as obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.introspect


@pytest.fixture(autouse=True)
def _clean():
    """Per-test isolation: fresh program registry / sentinel state /
    flight EWMAs; knobs restored both sides."""
    was_on, cap = introspect.ENABLED, introspect.HLO_CAP_BYTES
    introspect.enable()
    introspect.reset()
    introspect.configure(hlo=False, sentinel_every=1,
                         regression_factor=1.5, regression_min_s=300.0)
    flight.reset()
    yield
    introspect.reset()
    introspect.configure(hlo=False, hlo_cap_bytes=cap, sentinel_every=25,
                         regression_factor=1.5, regression_min_s=300.0)
    (introspect.enable if was_on else introspect.disable)()
    flight.reset()


# -- helpers -----------------------------------------------------------------

class _StubStats:
    """CompiledMemoryStats stand-in (both jax generations)."""

    def __init__(self, peak=None):
        self.temp_size_in_bytes = 10
        self.argument_size_in_bytes = 20
        self.output_size_in_bytes = 30
        self.alias_size_in_bytes = 0
        self.generated_code_size_in_bytes = 5
        if peak is not None:
            self.peak_memory_in_bytes = peak


class _StubCompiled:
    """jax Compiled stand-in: cost/memory/HLO surfaces only."""

    def __init__(self, flops=1000.0, bytes_=4000.0, peak=None,
                 hlo="HLO module stub\n", cost_as_list=True):
        self._cost = {"flops": flops, "bytes accessed": bytes_}
        self._list = cost_as_list
        self._stats = _StubStats(peak)
        self._hlo = hlo

    def cost_analysis(self):
        return [dict(self._cost)] if self._list else dict(self._cost)

    def memory_analysis(self):
        return self._stats

    def as_text(self):
        return self._hlo


def _mlp(depth=3, width=16, seed=11):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(depth):
            net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    return net


def _trainer(net):
    return gluon.Trainer(net.collect_params(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9},
                         kvstore="tpu_sync", update_on_kvstore=False)


def _data(shape=(8, 16), seed=0):
    rs = np.random.RandomState(seed)
    return (mx.nd.array(rs.normal(0, 1, shape).astype("f")),
            mx.nd.array(rs.normal(0, 1, (shape[0], 1)).astype("f")))


def _wholestep(monkeypatch, steps=3, depth=3, hlo=False):
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    if hlo:
        introspect.configure(hlo=True)
    net = _mlp(depth=depth)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), _trainer(net))
    x, y = _data()
    for _ in range(steps):
        st.step(x, y)
    return st


def _warm_ewma(phase, dur_s, n=6):
    for _ in range(n):
        flight.note(phase, dur_s)


# -- note_program: the one compiled-stats surface ----------------------------

def test_note_program_record_and_ledger_parity():
    rec = introspect.note_program("probe", compiled=_StubCompiled(peak=77),
                                  signature="sig1")
    assert rec["flops"] == 1000.0 and rec["bytes"] == 4000.0
    assert rec["memory"]["peak_bytes"] == 77
    assert rec["signature"] == "sig1"
    assert introspect.programs()["probe"]["captures"] == 1
    # the HBM ledger's compiled table is fed by the SAME call — one
    # surface, no second bookkeeping path
    assert memory.compiled_stats()["probe"]["peak_bytes"] == 77


def test_note_program_label_joins_name():
    rec = introspect.note_program("serve_bucket",
                                  compiled=_StubCompiled(), label="8")
    assert rec["name"] == "serve_bucket:8"
    assert "serve_bucket:8" in introspect.programs()


def test_uniform_memory_keys_across_jax_paths():
    """The PR 9 stubbed-stats regression, now through note_program:
    identical key set whether or not the stats carry
    peak_memory_in_bytes (jax < 0.5 estimates + flags)."""
    new = introspect.note_program("p_new",
                                  compiled=_StubCompiled(peak=999))
    old = introspect.note_program("p_old", compiled=_StubCompiled())
    assert set(new["memory"]) == set(old["memory"])
    assert new["memory"]["peak_bytes"] == 999
    assert new["memory"]["peak_estimated"] is False
    assert old["memory"]["peak_estimated"] is True
    assert old["memory"]["peak_bytes"] == 10 + 20 + 30 + 0


def test_cost_analysis_dict_and_list_forms():
    a = introspect.note_program("pa",
                                compiled=_StubCompiled(cost_as_list=True))
    b = introspect.note_program("pb",
                                compiled=_StubCompiled(cost_as_list=False))
    assert a["flops"] == b["flops"] == 1000.0


# -- chokepoint captures -----------------------------------------------------

def test_executor_capture_and_memory_analysis():
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fc")
    exe = net.simple_bind(mx.cpu(), data=(2, 6))
    exe.forward(is_train=False, data=mx.nd.ones((2, 6)))
    progs = introspect.programs()
    assert "executor:fwd" in progs and progs["executor:fwd"]["flops"] > 0
    # memory_analysis dedupes through note_program: uniform keys AND
    # both surfaces (program registry + ledger compiled table) filed
    stats = exe.memory_analysis(train=False)
    assert {"temp_bytes", "argument_bytes", "output_bytes", "alias_bytes",
            "generated_code_bytes", "peak_bytes",
            "peak_estimated"} <= set(stats)
    assert "executor" in introspect.programs()
    assert memory.compiled_stats()["executor"]["peak_bytes"] == \
        stats["peak_bytes"]


def test_serving_precompile_capture():
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4, name="fc")
    pred = serving.BucketedPredictor(net, {}, {"data": (8, 6)})
    pred.warmup()
    progs = introspect.programs()
    buckets = [k for k in progs if k.startswith("serve_bucket:")]
    assert buckets, progs.keys()
    assert all(progs[k]["memory"].get("peak_bytes", 0) >= 0
               for k in buckets)
    # the predictor's own budgeting surface still sees the stats
    assert pred.memory_stats()["buckets"]


def test_fused_path_captures_and_step_flops():
    net = _mlp()
    tr = _trainer(net)
    x, y = _data()
    loss_fn = gluon.loss.L2Loss()
    from mxnet_tpu import autograd
    for _ in range(2):
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        tr.step(x.shape[0])
    progs = introspect.programs()
    assert {"gluon:fwd", "gluon:bwd", "fused_update"} <= set(progs)
    flops, _bytes, phase = introspect.step_flops()
    assert phase == "mx.trainer.step"
    assert flops == sum(progs[n]["flops"] for n in
                        ("gluon:fwd", "gluon:bwd", "fused_update"))


def test_wholestep_capture_with_signature(monkeypatch):
    st = _wholestep(monkeypatch)
    assert st.active, st.fallback_reason
    rec = introspect.programs()["whole_step"]
    assert rec["flops"] > 0 and rec["bytes"] > 0
    assert isinstance(rec["signature"], str) and len(rec["signature"]) == 16
    flops, _b, phase = introspect.step_flops()
    assert phase == "whole_step" and flops == rec["flops"]


# -- named scopes & per-layer attribution ------------------------------------

def test_named_scope_roundtrip_into_hlo(monkeypatch):
    st = _wholestep(monkeypatch, hlo=True)
    assert st.active, st.fallback_reason
    dense0 = st.net._children[0].name  # e.g. hybridsequentialN_dense0
    hlo = introspect.programs()["whole_step"]["hlo"]
    assert hlo and dense0 + "_fwd" in hlo
    scopes = introspect.known_scopes()
    assert dense0 + "_fwd" in scopes
    assert "optimizer" in scopes


@pytest.mark.perf_smoke
def test_per_layer_attributes_90pct_on_pinned_net(monkeypatch):
    """ISSUE 13 acceptance: per_layer() attributes >= 90% of the
    whole-step program's flops to named blocks (graph layers + the
    optimizer/allreduce scopes)."""
    st = _wholestep(monkeypatch, hlo=True, depth=4)
    assert st.active, st.fallback_reason
    rows = introspect.per_layer("whole_step")
    layers = {r["layer"] for r in rows}
    assert st.net._children[0].name in layers  # denseN block rows
    assert "optimizer" in layers
    pct = introspect.attributed_pct("whole_step")
    assert pct >= 90.0, (pct, rows)
    # rows carry flops + pct; est_ms appears once the EWMA warmed
    total_pct = sum(r["pct"] for r in rows)
    assert 99.0 <= total_pct <= 101.0


def test_per_layer_device_ms_is_the_measured_time(monkeypatch):
    """per_layer() reports MEASURED time: the seconds handed in by
    instruction land on the layer op_scopes names for the instruction,
    none is lost, and without a measurement the column reads None (the
    FLOPs share of a host-clock step time, `est_ms`, is gone)."""
    st = _wholestep(monkeypatch, hlo=True)
    assert st.active
    assert all(r["device_ms"] is None and "est_ms" not in r
               for r in introspect.per_layer("whole_step"))
    (names,) = introspect.op_scopes("jit_ftrain")
    dense0 = st.net._children[0].name
    of_dense0 = [n for n, r in names.items()
                 if r["node"] == dense0 + "_fwd"]
    assert of_dense0
    measured = {n: 0.001 for n in names}          # 1 ms an instruction
    rows = introspect.per_layer("whole_step", measured=measured)
    by_layer = {r["layer"]: r for r in rows}
    assert abs(by_layer[dense0]["device_ms"] - len(of_dense0)) < 1e-6
    assert abs(sum(r["device_ms"] for r in rows) - len(names)) < 1e-3
    assert by_layer["optimizer"]["device_ms"] > 0
    # an instruction the text does not hold is shown, not dropped
    rows = introspect.per_layer("whole_step", measured={"nope.1": 0.5})
    assert {r["layer"]: r["device_ms"] for r in rows}[
        introspect.UNATTRIBUTED] == 500.0


def test_per_layer_requires_hlo(monkeypatch):
    _wholestep(monkeypatch, hlo=False)
    with pytest.raises(MXNetError, match="MXNET_INTROSPECT_HLO"):
        introspect.per_layer("whole_step")
    with pytest.raises(MXNetError, match="not been captured"):
        introspect.per_layer("nope")


def test_hlo_size_cap():
    introspect.configure(hlo=True, hlo_cap_bytes=16)
    rec = introspect.note_program(
        "capped", compiled=_StubCompiled(hlo="x" * 100))
    assert len(rec["hlo"]) == 16 and rec["hlo_truncated"] is True


def test_dump_hlo_atomic_unique(tmp_path):
    introspect.configure(hlo=True)
    introspect.note_program("dumpme",
                            compiled=_StubCompiled(hlo="HLO text here"))
    path = introspect.dump_hlo("dumpme", str(tmp_path))
    assert os.path.exists(path)
    with open(path) as f:
        assert f.read() == "HLO text here"
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]
    with pytest.raises(MXNetError, match="no HLO captured"):
        introspect.dump_hlo("never_noted", str(tmp_path))


def test_parse_hlo_flops_dot_model():
    """The per-instruction flops model: a dot is 2*M*N*K attributed to
    the innermost known scope (decorations unwrapped)."""
    introspect._scopes.update({"dense0_fwd": "FullyConnected",
                               "optimizer": None})
    text = textwrap.dedent("""\
      %dot.1 = f32[8,4]{1,0} dot(f32[8,16]{1,0} %a, f32[16,4]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/transpose(jvp(dense0_fwd))/dot_general"}
      %add.1 = f32[8,4]{1,0} add(f32[8,4]{1,0} %x, f32[8,4]{1,0} %y), metadata={op_name="jit(f)/optimizer/add"}
      %cp.1 = f32[8,4]{1,0} copy(f32[8,4]{1,0} %x), metadata={op_name="jit(f)/dense0_fwd/copy"}
      %mul.9 = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %q)
    """)
    by = introspect.parse_hlo_flops(text)
    assert by["dense0"] == 2 * 8 * 4 * 16      # _fwd stripped, copy free
    assert by["optimizer"] == 8 * 4
    assert by[introspect.UNATTRIBUTED] == 8    # no metadata -> remainder


# -- MFU / roofline ----------------------------------------------------------

def test_mfu_math_with_injected_peak(monkeypatch):
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "1e9")
    introspect.note_program(
        "whole_step", compiled=_StubCompiled(flops=1e6, bytes_=2e6))
    _warm_ewma("whole_step", 0.01)
    out = introspect.mfu()
    assert out["peak_source"] == "MXNET_PEAK_FLOPS"
    assert out["flops_per_step"] == 1e6
    assert abs(out["flops_per_s"] - 1e8) < 1e4
    assert abs(out["mfu_pct"] - 10.0) < 0.01
    assert abs(out["arithmetic_intensity"] - 0.5) < 1e-6
    assert abs(out["bytes_per_s"] - 2e8) < 2e4
    # the export gauges read the same math
    assert abs(m.MFU.get() - 0.1) < 1e-4
    assert m.STEP_FLOPS_PER_S.get() > 0


def test_fused_mfu_needs_explicit_step_time():
    """The fused path's 'trainer_step' span times only Trainer.step
    (allreduce+update) — never fwd/bwd — so automatic MFU must stay
    empty there (a partial-span denominator would overstate MFU
    severalfold) and the Perfetto flops track must not render it.
    An explicit measured step time (the bench rider) still works, and
    the fused_update record carries a baseline signature."""
    for n in introspect.FUSED_STEP_PROGRAMS:
        introspect.note_program(n, compiled=_StubCompiled(flops=1e6))
    _warm_ewma("mx.trainer.step", 0.001)   # warmed, but partial-span
    assert introspect.mfu() == {}
    assert introspect.phase_flops_map() == {}
    out = introspect.mfu(step_time_s=0.01)
    assert out and out["flops_per_step"] == 3e6
    # the live fused capture stamps a signature (per-model baselines)
    net = _mlp()
    tr = _trainer(net)
    x, y = _data()
    from mxnet_tpu import autograd
    with autograd.record():
        l = gluon.loss.L2Loss()(net(x), y)
    l.backward()
    tr.step(x.shape[0])
    rec = introspect.programs()["fused_update"]
    assert isinstance(rec["signature"], str) and len(rec["signature"]) == 16


def test_mfu_empty_until_measurable():
    assert introspect.mfu() == {}          # no program, no EWMA
    introspect.note_program("whole_step", compiled=_StubCompiled())
    assert introspect.mfu() == {}          # program but no warmed EWMA
    assert m.MFU.get() == 0.0


def test_peak_flops_override_beats_table(monkeypatch):
    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    # a CPU has no published peak: no placeholder to divide by
    assert introspect.peak_flops() == (None, "none:cpu")
    monkeypatch.setenv("MXNET_PEAK_FLOPS", "123.5e12")
    peak, src = introspect.peak_flops()
    assert peak == 123.5e12 and src == "MXNET_PEAK_FLOPS"


def test_peak_flops_reads_the_chip_table(monkeypatch):
    """On a TPU the peak is the chip.py row for the exact device_kind;
    a kind that is not in the table raises instead of guessing."""
    from mxnet_tpu.base import MXNetError

    class _Dev:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(introspect.jax, "local_devices",
                        lambda: [_Dev("TPU v5 lite")])
    assert introspect.peak_flops() == (197e12, "chip:TPU v5 lite")
    monkeypatch.setattr(introspect.jax, "local_devices",
                        lambda: [_Dev("TPU v5")])
    with pytest.raises(MXNetError, match="no published peaks"):
        introspect.peak_flops()


def test_mfu_reports_no_mfu_on_cpu(monkeypatch):
    monkeypatch.delenv("MXNET_PEAK_FLOPS", raising=False)
    out = introspect.mfu(step_time_s=0.01, flops=1e6)
    assert out["flops_per_s"] == 1e8 and out["peak_source"] == "none:cpu"
    assert not {"mfu", "mfu_pct", "peak_flops"} & set(out)


def test_flops_counter_track_in_perfetto_dump(tmp_path, monkeypatch):
    """Step phases with a captured program get an mxnet_flops_per_s
    counter track in the Chrome-trace export."""
    st = _wholestep(monkeypatch, steps=3)
    assert st.active
    path = flight.dump(str(tmp_path / "t.json"))
    with open(path) as f:
        trace = json.load(f)
    counters = [e for e in trace["traceEvents"]
                if e.get("name") == "mxnet_flops_per_s"]
    assert counters and all(e["ph"] == "C" and
                            e["args"]["flops_per_s"] > 0
                            for e in counters)


# -- perf-regression sentinel ------------------------------------------------

def _arm_baseline(tmp_path, monkeypatch, p50_s=0.01):
    monkeypatch.setenv("MXNET_PERF_BASELINE_DIR", str(tmp_path))
    introspect.configure(sentinel_every=1, regression_min_s=300.0)
    _warm_ewma("whole_step", p50_s)
    introspect.sentinel_tick("whole_step")
    path = introspect.baseline_path("whole_step")
    assert path and os.path.exists(path), "baseline not written"
    return path


def test_sentinel_baseline_atomic_write_and_roundtrip(tmp_path,
                                                      monkeypatch):
    path = _arm_baseline(tmp_path, monkeypatch)
    with open(path) as f:
        base = json.load(f)
    assert abs(base["step_time_p50_ms"] - 10.0) < 0.5
    assert base["phase"] == "whole_step"
    assert base["platform"] == "cpu"
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]
    # reread through the sentinel's own loader: state reports armed
    introspect.sentinel_tick("whole_step")
    assert introspect.sentinel_armed()
    assert not introspect.regression_active()


def test_sentinel_fires_exactly_once_on_2x_regression(tmp_path,
                                                      monkeypatch):
    _arm_baseline(tmp_path, monkeypatch, p50_s=0.01)
    before = m.PERF_REGRESSIONS.get(kind="step_time", phase="whole_step")
    # fabricated 2x step-time regression: fresh EWMA at 20ms
    flight.reset()
    _warm_ewma("whole_step", 0.02)
    for _ in range(5):
        introspect.sentinel_tick("whole_step")
    assert introspect.regression_active()
    after = m.PERF_REGRESSIONS.get(kind="step_time", phase="whole_step")
    assert after - before == 1.0  # exactly once, rate-limited
    st = introspect.sentinel_state()
    assert st["phases"]["whole_step"]["active"]
    assert st["phases"]["whole_step"]["kind"] == "step_time"


def test_sentinel_deferred_fire_after_rate_window(tmp_path, monkeypatch):
    """An episode that BEGINS inside the rate window is deferred, never
    dropped: readyz flips immediately (active), and the warning +
    counter fire on the first check after the window elapses."""
    _arm_baseline(tmp_path, monkeypatch, p50_s=0.01)
    before = m.PERF_REGRESSIONS.get(kind="step_time", phase="whole_step")
    # episode A fires (opens the rate window), then clears
    flight.reset()
    _warm_ewma("whole_step", 0.02)
    introspect.sentinel_tick("whole_step")
    assert m.PERF_REGRESSIONS.get(kind="step_time",
                                  phase="whole_step") - before == 1.0
    flight.reset()
    _warm_ewma("whole_step", 0.01)
    introspect.sentinel_tick("whole_step")
    assert not introspect.regression_active()
    # episode B trips INSIDE the window: active immediately, fire held
    flight.reset()
    _warm_ewma("whole_step", 0.03)
    introspect.sentinel_tick("whole_step")
    assert introspect.regression_active()
    assert m.PERF_REGRESSIONS.get(kind="step_time",
                                  phase="whole_step") - before == 1.0
    # window elapses (tests shrink it) -> the DEFERRED fire lands once
    introspect.configure(regression_min_s=0.0)
    introspect.sentinel_tick("whole_step")
    assert m.PERF_REGRESSIONS.get(kind="step_time",
                                  phase="whole_step") - before == 2.0
    introspect.sentinel_tick("whole_step")  # same episode: no re-fire
    assert m.PERF_REGRESSIONS.get(kind="step_time",
                                  phase="whole_step") - before == 2.0


def test_configure_none_leaves_knobs_unchanged():
    introspect.configure(hlo=True, hlo_cap_bytes=123)
    introspect.configure(sentinel_every=5)   # tune ONE knob...
    assert introspect.HLO is True            # ...others keep their value
    assert introspect.HLO_CAP_BYTES == 123
    assert introspect.SENTINEL_EVERY == 5


def test_wholestep_signature_varies_with_batch_shape(monkeypatch):
    """A legitimate batch-size change must select a DIFFERENT baseline
    file, not fire a false regression against the old batch's numbers."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = _mlp()
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), _trainer(net))
    x8, y8 = _data((8, 16))
    x4, y4 = _data((4, 16))
    st.step(x8, y8)
    st.step(x8, y8)
    sig_b8 = introspect.programs()["whole_step"]["signature"]
    st.step(x4, y4)  # same program cache key family, new data shape
    sig_b4 = introspect.programs()["whole_step"]["signature"]
    assert sig_b8 and sig_b4 and sig_b8 != sig_b4


def test_sentinel_reloads_on_signature_change(tmp_path, monkeypatch):
    """A mid-run program-signature change (a legitimate batch/config
    change re-noting the program) must re-resolve the baseline file —
    never compare the new workload against the old signature's
    numbers."""
    introspect.note_program("whole_step", compiled=_StubCompiled(),
                            signature="sigA")
    _arm_baseline(tmp_path, monkeypatch, p50_s=0.01)
    assert "sigA" in introspect.baseline_path("whole_step")
    # the program re-notes under a new signature; EWMA legitimately 3x
    introspect.note_program("whole_step", compiled=_StubCompiled(),
                            signature="sigB")
    flight.reset()
    _warm_ewma("whole_step", 0.03)
    introspect.sentinel_tick("whole_step")
    # no false regression: sigB got its OWN (fresh) baseline instead
    assert not introspect.regression_active()
    assert os.path.exists(introspect.baseline_path("whole_step"))
    assert "sigB" in introspect.baseline_path("whole_step")
    with open(introspect.baseline_path("whole_step")) as f:
        assert abs(json.load(f)["step_time_p50_ms"] - 30.0) < 2.0


def test_sentinel_clears_when_back_under(tmp_path, monkeypatch):
    _arm_baseline(tmp_path, monkeypatch, p50_s=0.01)
    flight.reset()
    _warm_ewma("whole_step", 0.02)
    introspect.sentinel_tick("whole_step")
    assert introspect.regression_active()
    flight.reset()
    _warm_ewma("whole_step", 0.01)
    introspect.sentinel_tick("whole_step")
    assert not introspect.regression_active()


def test_sentinel_corrupt_baseline_rejected(tmp_path, monkeypatch,
                                            caplog):
    monkeypatch.setenv("MXNET_PERF_BASELINE_DIR", str(tmp_path))
    introspect.configure(sentinel_every=1)
    _warm_ewma("whole_step", 0.01)
    path = introspect.baseline_path("whole_step")
    os.makedirs(tmp_path, exist_ok=True)
    with open(path, "w") as f:
        f.write("{not json")
    import logging
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.observability.introspect"):
        introspect.sentinel_tick("whole_step")
    assert any("corrupt" in r.message for r in caplog.records)
    # rejected: not armed, not silently overwritten, no crash
    assert not introspect.sentinel_armed()
    with open(path) as f:
        assert f.read() == "{not json"
    # refresh_baseline is the documented repair path
    assert introspect.refresh_baseline("whole_step") is not None
    with open(path) as f:
        assert json.load(f)["phase"] == "whole_step"
    assert introspect.sentinel_armed()


def test_sentinel_readyz_flip_and_refresh(tmp_path, monkeypatch):
    """A fabricated 2x regression fails the perf_regression readyz()
    check; refresh_baseline (the intentional-change lifecycle) brings
    the replica back."""
    _arm_baseline(tmp_path, monkeypatch, p50_s=0.01)
    flight.reset()
    _warm_ewma("whole_step", 0.025)
    introspect.sentinel_tick("whole_step")
    assert introspect.regression_active()
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4,
                             name="fc")
    pred = serving.BucketedPredictor(net, {}, {"data": (4, 3)}).warmup()
    from mxnet_tpu.serving import ResilientServer
    with ResilientServer(pred) as srv:
        rz = srv.readyz()
        assert rz["checks"]["perf_regression"] is False
        assert "perf_regression" in rz["reasons"]
        assert rz["detail"]["perf_sentinel"]["whole_step"]["kind"] == \
            "step_time"
        introspect.refresh_baseline("whole_step")
        rz = srv.readyz()
        assert rz["checks"]["perf_regression"] is True


def test_sentinel_disarmed_without_dir(monkeypatch, tmp_path):
    """Only MXNET_PERF_BASELINE_DIR arms the sentinel: a compile cache
    (which the entry points always have) must not."""
    monkeypatch.delenv("MXNET_PERF_BASELINE_DIR", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _warm_ewma("whole_step", 0.01)
    introspect.sentinel_tick("whole_step")
    assert introspect.baseline_dir() is None
    assert not introspect.sentinel_armed()
    assert not os.listdir(tmp_path)
    monkeypatch.setenv("MXNET_PERF_BASELINE_DIR", str(tmp_path / "own"))
    assert introspect.baseline_dir() == str(tmp_path / "own")


# -- the off switch ----------------------------------------------------------

def test_disabled_in_process_is_one_boolean_noop(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_PERF_BASELINE_DIR", str(tmp_path))
    introspect.disable()
    assert introspect.note_program("x", compiled=_StubCompiled()) == {}
    assert introspect.note_jit("y", None) == {}
    with introspect.layer_scope("layer_that_must_not_register"):
        pass
    assert "layer_that_must_not_register" not in introspect.known_scopes()
    _warm_ewma("whole_step", 0.01)
    introspect.sentinel_tick("whole_step")
    assert not os.listdir(tmp_path)  # no baseline written
    assert introspect.refresh_baseline("whole_step") is None
    snap = obs.snapshot()["programs"]
    assert snap["enabled"] is False and snap["programs"] == {}


def test_disabled_at_import_subprocess(tmp_path):
    code = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.observability import introspect\n"
        "assert introspect.ENABLED is False\n"
        "from mxnet_tpu.gluon import nn\n"
        "import numpy as np\n"
        "net = nn.HybridSequential()\n"
        "with net.name_scope():\n"
        "    net.add(nn.Dense(4))\n"
        "net.hybridize(); net.initialize(mx.init.Xavier())\n"
        "net(mx.nd.array(np.ones((2, 3), 'f')))\n"
        "assert introspect.programs() == {}\n"
        "assert introspect.known_scopes() == frozenset()\n"
        "introspect.enable()\n"
        "net2 = nn.Dense(4)\n"
        "net2.initialize(mx.init.Xavier())\n"
        "print('OK')\n")
    env = dict(os.environ, MXNET_INTROSPECT="0", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-500:], out.stderr[-2000:])


# -- schema & gates ----------------------------------------------------------

def test_snapshot_programs_schema(monkeypatch):
    st = _wholestep(monkeypatch)
    assert st.active
    snap = obs.snapshot()["programs"]
    assert {"enabled", "hlo", "programs", "mfu", "sentinel",
            "known_scopes"} <= set(snap)
    rec = snap["programs"]["whole_step"]
    assert {"flops", "bytes", "peak_bytes", "signature", "hlo_captured",
            "captures"} <= set(rec)
    sent = snap["sentinel"]
    assert {"dir", "armed", "regression_active", "phases"} <= set(sent)
    rep = introspect.report()
    assert "whole_step" in rep["programs"]
    assert "hlo" not in rep["programs"]["whole_step"]  # elided to bytes


@pytest.mark.perf_smoke
def test_wholestep_one_dispatch_with_introspection_on(monkeypatch):
    """ISSUE 13 acceptance gate: introspection ON (capture + named
    scopes + sentinel ticks) must not add a single steady-state
    dispatch to the whole-step program — note_jit is a retrace, never
    a launch."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    introspect.configure(sentinel_every=1)
    st = _wholestep(monkeypatch, steps=0)
    x, y = _data()
    for _ in range(3):
        st.step(x, y)
    assert st.active, st.fallback_reason
    c0 = obs.dispatch_counts()
    for _ in range(3):
        st.step(x, y)
    c1 = obs.dispatch_counts()
    per_step = {k: (c1.get(k, 0) - c0.get(k, 0)) / 3
                for k in c1 if c1.get(k, 0) != c0.get(k, 0)}
    assert per_step.get("device_put", 0) == 0, per_step
    assert per_step.get("total", 99) <= 2.0, per_step
    assert per_step.get("xla:whole_step", 0) >= 1.0, per_step
    assert "whole_step" in introspect.programs()


# -- graft-lint rule extension ----------------------------------------------

def test_lint_flags_dynamic_program_and_layer_names(tmp_path):
    from mxnet_tpu import analysis
    bad = textwrap.dedent("""\
        def f(introspect, jax, name, compiled, label):
            introspect.note_program(f"prog_{name}", compiled=compiled)
            introspect.note_jit("ok_literal" + name, None)
            introspect.note_program("serve_bucket", compiled=compiled,
                                    label="b%d" % label)
            with jax.named_scope("layer_" + name):
                pass
            with introspect.layer_scope(str(name + "x")):
                pass
    """)
    p = tmp_path / "bad_introspect.py"
    p.write_text(bad)
    findings = analysis.run(["metrics-hygiene"], [str(p)])
    assert len(findings) == 5, [f.message for f in findings]
    good = textwrap.dedent("""\
        def f(introspect, jax, compiled, bucket_label, key, node):
            introspect.note_program("serve_bucket", compiled=compiled,
                                    label=bucket_label(key))
            introspect.note_jit("whole_step", None)
            with jax.named_scope(node.name):
                pass
            with introspect.layer_scope("optimizer"):
                pass
    """)
    p2 = tmp_path / "good_introspect.py"
    p2.write_text(good)
    assert analysis.run(["metrics-hygiene"], [str(p2)]) == []
