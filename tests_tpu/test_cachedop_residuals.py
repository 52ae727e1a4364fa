"""A recorded Gluon step runs its forward once, on the chip, at the two
transformer cells' own sizes: each cell's whole run through the benchmark's
entry (`chipbench/run.py run_cell`, a traced window of 3 s), from which come
the forward-kernel events a step (one a layer: the backward program runs
the kernel no more), the backward kernels' events under their own names
(two a layer, and no `while` of plain XLA), the gradients against the cell's dense float32
reference (`grad_norm_gap` under the cell's limit: chipbench/checks/
train_steps.py), the peak of `memory_stats()` and the residual counters.

tests/test_consistency_harness.py runs this file on the CPU, where the
cells run at their rehearsal sizes and only what a CPU can show is held."""
import os

import jax
import pytest

from chipbench import cell as cellmod
from chipbench import run, trace_reduce
from mxnet_tpu.observability import metrics

# layers; `memory_peak_bytes` at the parent commit (PERF.md section 4); the
# room over it that the cell is held to.  A step now holds its residuals
# between the two launches: the OPT cell reads 2.0-2.2% above the parent
# (16,846,270,976 to 16,871,453,184) and the expert cell 0.1-0.15%
# (16,874,301,952 to 16,883,476,992; PERF.md section 6, PR 30), both
# under the 16,909,336,064 bytes `memory_stats()` gives as the device's
# limit: each bound lies between the readings and that limit, so a program
# that grows fails here before it fails to allocate.  The peaks are the
# process's own, and the harness adds two of them that a second cell in
# one process reaches at different moments: only the first cell a process
# runs is held to its memory (`-k glm4.7flash` holds the other).
CELLS = {"opt1.3b_train_gluon": (6, 16_513_295_360, 1.0225),
         "glm4.7flash_train_gluon": (5, 16_857_783_808, 1.0025)}
_cells_run = []


def _is_forward_attention():
    return cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", "flash_fwd_roofline.py"),
        "flash_fwd_roofline").is_forward_attention


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_its_forward_once(cell, monkeypatch):
    on_chip = jax.default_backend() == "tpu"
    layers, parent_peak, room = CELLS[cell]
    seen = {}
    reduce = trace_reduce.reduce
    monkeypatch.setattr(
        trace_reduce, "reduce",
        lambda loaded: seen.setdefault("reduced", reduce(loaded)))
    launches = metrics.CACHEDOP_BACKWARDS.value
    res = run.run_cell(cell, 2147480030, 3.0, True, rehearsal=not on_chip)
    assert res["failed"] == 0 and res["attempted"] > 0
    if on_chip:  # the rehearsal's bfloat16 reads outside the chip's limits
        assert res["correct"] is True, res["compared"]
        gap = res["compared"]["grad_norm_gap"]
        assert gap["value"] < gap["limit"]
    kept = metrics.CACHEDOP_RESIDUAL_BYTES.get(kind="kept")
    primal = metrics.CACHEDOP_RESIDUAL_BYTES.get(kind="primal")
    assert kept > 0 and primal > 0
    assert metrics.CACHEDOP_BACKWARDS.value - launches >= res["attempted"]
    print(cell, "kept", kept, "primal", primal, res["device"],
          (jax.devices()[0].memory_stats() or {}).get("bytes_limit"))
    if not on_chip:
        return
    is_fwd = _is_forward_attention()
    steps = res["run"]["steps_completed"]
    devices = seen["reduced"]["events"]["devices"].values()
    events = sum(is_fwd(name) for dev in devices
                 for name, _s, _e in dev["ops"])
    assert events == layers * steps, events
    # the attention backward is two kernels a layer, under names of their
    # own that no forward reader takes for a forward event
    for scope in ("flash_bwd_dkv", "flash_bwd_dq"):
        names = [trace_reduce.op_short_name(name) for dev in devices
                 for name, _s, _e in dev["ops"]
                 if scope in name and "tpu_custom_call" in name]
        assert len(names) == layers * steps, (scope, len(names))
        assert not any("attention" in n for n in names), names[:3]
    assert not [name for dev in devices for name, _s, _e in dev["ops"]
                if trace_reduce.op_short_name(name).startswith("while")]
    by_program = {}  # device ms a step, for PERF.md section 5
    for dev in devices:
        for name, s, e in dev["modules"]:
            name = name.split("(")[0]
            by_program[name] = by_program.get(name, 0) + (e - s) / 1e6 / steps
    print(cell, "device ms a step by program",
          {k: round(v, 3) for k, v in sorted(by_program.items())})
    print(cell, "metrics", {k: v["value"] for k, v in res["metrics"].items()},
          "step_ms_p50", res["run"]["step_ms_p50"])
    _cells_run.append(cell)
    if _cells_run == [cell]:
        assert res["device"]["memory_peak_bytes"] <= room * parent_peak
    if cell == "opt1.3b_train_gluon":
        assert kept < 1.6e9
