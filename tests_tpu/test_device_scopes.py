"""Device time under the model's names (ISSUE 35), at the cells' own sizes:
one traced run each of `opt1.3b_train_gluon` (three programs a step, one
pass each) and `resnet50_train_module` (one program holds both passes)
through the benchmark's own `run_cell`.  The events' self times tile the
trace's busy time, forward + backward + update + unsplit + the recordless
programs tile the busy step, nine tenths of the step programs' time carry
a graph node's or a literal scope's name, and every instruction the trace
names is found in the record of the launch that ran it.

tests/test_consistency_harness.py runs this file on the CPU, where the
cells run at their rehearsal sizes, a trace has no device plane, and only
what the programs keep for the join is held."""
import jax
import pytest

from chipbench import run, scope_reduce
from mxnet_tpu.observability import introspect

CELLS = {"opt1.3b_train_gluon": {"jit_mx_cachedop_fwd", "jit_mx_cachedop_bwd",
                                 "jit_mx_fused_update"},
         "resnet50_train_module": {"jit_mx_executor_fwd_bwd",
                                   "jit_mx_fused_update"}}
SCOPED_SHARE_MIN = 90.0  # percent of the step programs' device time
TILING = 0.01            # of the busy time


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_device_time_lands_under_the_models_names(cell, monkeypatch):
    on_chip = jax.default_backend() == "tpu"
    seen = {}
    read_metrics = run.read_metrics

    memory_peak = run.memory_peak

    def read_and_keep(cell_, kind, ctx):
        seen["report"] = scope_reduce.report(ctx)
        return read_metrics(cell_, kind, ctx)

    def peak_and_sources(device):   # once the window has closed, in a
        seen.setdefault("sources", introspect.program_sources())
        return memory_peak(device)  # rehearsal too (it reads no metric)

    monkeypatch.setattr(run, "read_metrics", read_and_keep)
    monkeypatch.setattr(run, "memory_peak", peak_and_sources)
    res = run.run_cell(cell, 2147480035, 3.0, True, rehearsal=not on_chip)
    assert res["failed"] == 0 and res["attempted"] > 1
    # the step's programs kept what names their instructions, and an
    # untraced step path has read none of it
    kept = {s["jit_name"] for s in seen["sources"]}
    assert CELLS[cell] <= kept, kept
    if not on_chip:
        assert "report" not in seen     # a CPU's trace has no device plane
        assert all(s["text_bytes"] == 0 for s in seen["sources"])
        return
    assert res["correct"] is True, res["compared"]
    rep = seen["report"]
    suffix = ".images" if "resnet" in cell else ".tokens"
    got = {k: v["value"] for k, v in res["metrics"].items()}
    print(cell, {k: got.get(k + suffix) for k in (
        "fwd_device_ms", "bwd_device_ms", "update_device_ms",
        "scoped_device_share")}, rep["ms_a_step_by_pass"])
    # the leaves' time is the busy time (a loop is not counted beside
    # its body), and the rows tile it
    assert rep["leaf_s"] == pytest.approx(res["device"]["busy_s"],
                                          rel=TILING)
    assert rep["busy_s"] == pytest.approx(res["device"]["busy_s"],
                                          rel=TILING)
    by_pass = rep["ms_a_step_by_pass"]
    fwd = got["fwd_device_ms" + suffix]
    bwd = got["bwd_device_ms" + suffix]
    rest = sum(by_pass.get(p, 0.0) for p in (
        scope_reduce.UNSPLIT, scope_reduce.RECORDLESS))
    busy_ms = 1e3 * res["device"]["busy_s"] / res["attempted"]
    assert fwd + bwd + got["update_device_ms" + suffix] + rest == \
        pytest.approx(busy_ms, rel=TILING)
    assert got["scoped_device_share" + suffix] >= SCOPED_SHARE_MIN
    # every instruction the trace names is in its launch's record
    assert rep["unknown_instructions"] == 0, rep["unknown_instructions"]
