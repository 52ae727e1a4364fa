"""Every cell of BENCHMARK.json, run whole at its rehearsal's tiny size on
the CPU: what the driver runs on the chip for every PR has to start, take
its steps and agree with its reference here first.  The cells are read
from BENCHMARK.json when the file is collected, so a cell added later is
guarded without an edit.  A file of its own, because `--dist loadfile`
gives a file to one worker.

Float32 traffic: at the tiny size batch normalisation over eight rows
amplifies bfloat16 rounding far beyond what the cells' limits, read at
full size on the chip, allow.  In float32 program and reference agree to
1e-5, so a sound run passes (chipbench/tests/test_cells_cpu.py plants the
faults)."""
import json
import os

import pytest

from chipbench import cell as cellmod
from chipbench import run

CELLS = [w["name"] for w in cellmod.benchmark()["workloads"]]


@pytest.fixture
def float32_traffic(monkeypatch):
    real = cellmod.load_json

    def load(path):
        out = real(path)
        if os.path.basename(os.path.dirname(path)) == "traffic":
            out["dtype"] = "float32"
        return out

    monkeypatch.setattr(cellmod, "load_json", load)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_reads_correct(cell, float32_traffic):
    res = run.run_cell(cell, 7, 0.5, False, rehearsal=True)
    json.dumps(res)
    assert res["attempted"] > 0 and res["failed"] == 0
    for num, rec in res["compared"].items():
        # Adam turns round-off in elements whose gradient is all but zero
        # into a change of +-lr, so the change agrees less closely
        tol = 1e-3 if num.startswith("dparam_norm_gap") else 1e-4
        assert rec["value"] < tol, (num, res["compared"])
    assert res["correct"] is True, res["compared"]


FOUR_CHIP_CELLS = [w["name"] for w in cellmod.benchmark()["workloads"]
                   if w["chips"] == 4]


@pytest.mark.parametrize("cell", FOUR_CHIP_CELLS)
def test_gradient_exchange_left_out_reads_not_correct(cell, float32_traffic,
                                                      monkeypatch):
    """The fault that exists only across chips.  The exchange is the
    all-reduce XLA puts into the one program over the mesh, so leaving it
    out means that a chip steps on the gradient, and the batch statistics,
    of its own rows alone.  Planted as that: every chip's rows repeat the
    first chip's, and the mean over the batch is the first chip's own.  The
    reference makes its batches from the seed itself and keeps them whole."""
    real = cellmod.Cell.batches

    def batches(self):
        import jax.numpy as jnp
        out = []
        for x, y in real(self):
            own = x.shape[0] // self.chips
            out.append((jnp.concatenate([x[:own]] * self.chips),
                        jnp.concatenate([y[:own]] * self.chips)))
        return out

    monkeypatch.setattr(cellmod.Cell, "batches", batches)
    res = run.run_cell(cell, 7, 0.5, False, rehearsal=True)
    assert res["device"]["count"] >= 4
    assert res["correct"] is False, res["compared"]
    over = {n for n, r in res["compared"].items()
            if r["limit"] is not None and r["value"] > r["limit"]}
    # not by the loss alone: the gradient and the change of the weights
    assert {"grad_norm_gap_median", "dparam_norm_gap_median"} <= over, \
        res["compared"]
