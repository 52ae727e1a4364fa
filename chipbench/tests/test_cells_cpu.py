"""Each cell's whole run at a tiny size on the CPU (`rehearsal`), with the
harness's look for a chip skipped: the references against the system, the
last line's keys, and the planted faults, which have to make `correct`
come out false.

The runs use float32 traffic (`dtype` overridden as the traffic file is
read): at the rehearsal's tiny
sizes batch normalisation over eight rows amplifies bfloat16 rounding far
beyond what the cells' limits, read at full size on the chip, allow.  In
float32 program and reference agree to 1e-5, so a sound run passes and
only the planted fault can fail it.
"""
import json
import os

import pytest

from chipbench import cell as cellmod
from chipbench import control, run

CELLS = [w["name"] for w in cellmod.benchmark()["workloads"]]


@pytest.fixture(autouse=True)
def float32_traffic(monkeypatch):
    real = cellmod.load_json

    def load(path):
        out = real(path)
        if os.path.basename(os.path.dirname(path)) == "traffic":
            out["dtype"] = "float32"
        return out

    monkeypatch.setattr(cellmod, "load_json", load)


def _run(name):
    return run.run_cell(name, 7, 0.5, False, rehearsal=True)


@pytest.mark.parametrize("name", CELLS)
def test_reference_agrees_with_system_and_line_has_its_keys(name):
    res = _run(name)
    json.dumps(res)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "compared"
    assert res["rehearsal"] is True and res["metrics"] == {}
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert len(res["compared"]) == 7
    for num in res["compared"]:
        # float32 on both sides; Adam turns round-off in elements whose
        # gradient is all but zero into a change of +-lr, so the change
        # agrees less closely than losses and gradients do
        tol = 1e-3 if num.startswith("dparam_norm_gap") else 1e-4
        assert res["compared"][num]["value"] < tol, (num, res["compared"])
    assert res["correct"] is True


def _state_unchanged(monkeypatch):
    """The optimizer's kernels return weight and state as they came."""
    from mxnet_tpu.ops.registry import OP_REGISTRY
    for op, keep in (("sgd_mom_update", lambda p, w, g, m: (w, m)),
                     ("mp_sgd_mom_update",
                      lambda p, w, g, m, w32: (w, m, w32)),
                     ("adam_update", lambda p, w, g, m, v: (w, m, v))):
        monkeypatch.setattr(OP_REGISTRY[op], "fn", keep)


def _half_batch(monkeypatch):
    """The second half of every batch repeats the first: the mean, and the
    batch statistics, are those of half the rows."""
    real = cellmod.Cell.batches

    def batches(self):
        import jax.numpy as jnp
        out = []
        for x, y in real(self):
            h = x.shape[0] // 2
            out.append((jnp.concatenate([x[:h], x[:h]]),
                        jnp.concatenate([y[:h], y[:h]])))
        return out

    monkeypatch.setattr(cellmod.Cell, "batches", batches)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_reads_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(name)
    assert res["correct"] is False, res["compared"]
    over = [n for n, r in res["compared"].items()
            if r["limit"] is not None and r["value"] > r["limit"]]
    assert over, res["compared"]
    if fault is _state_unchanged:
        # by the measure a leaf that did not move reads 1
        assert res["compared"]["dparam_norm_gap"]["value"] == \
            pytest.approx(1.0, abs=1e-3)


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_not_correct(name):
    """The reference in float8, put in the program's place at the tiny
    size, fails at least one of the cell's numbers."""
    (row,) = control.readings(name, 7, ["fp8"], rehearsal=True)
    assert row["correct"] is False, row["compared"]


@pytest.mark.parametrize("name", CELLS)
def test_program_reading_is_what_a_run_compares(name):
    """control.py's `program` reading, which the lower readings are set
    from, gives the numbers that a run of the same seed compares."""
    (row,) = control.readings(name, 7, ["program"], rehearsal=True)
    res = _run(name)
    assert row["correct"] is True, row["compared"]
    for num, rec in res["compared"].items():
        assert row["compared"][num]["value"] == pytest.approx(
            rec["value"], rel=1e-6, abs=1e-9), num


def test_no_chip_no_result(capsys):
    """Without --rehearsal a run on the CPU exits 2 and prints no result."""
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out.strip() == ""
