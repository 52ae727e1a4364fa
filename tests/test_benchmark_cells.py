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
