"""The Module cell's chip no longer waits for the host between steps
(ISSUE 34): one traced run of `resnet50_train_module` through the
benchmark's own `run_cell`.  `Module.prepare` launches the next batch's
forward-backward before fit reads this step's metric, so the device's idle
share, 11.5% of the window before (ledger, PR 33), reads under 3, and no
held launch is dropped: every step but an epoch's first takes its own.

tests/test_consistency_harness.py runs this file on the CPU, where the cell
runs at its rehearsal size and only the counters are held."""
import jax

from chipbench import run
from mxnet_tpu.observability import metrics

CELL = "resnet50_train_module"
IDLE_SHARE_MAX = 3.0  # percent of the traced window


def test_the_chip_does_not_wait_for_the_host_between_steps():
    on_chip = jax.default_backend() == "tpu"
    taken = metrics.HELD_LAUNCHES.get(result="taken")
    dropped = metrics.HELD_LAUNCHES.get(result="dropped")
    res = run.run_cell(CELL, 2147480034, 3.0, True, rehearsal=not on_chip)
    assert res["failed"] == 0 and res["attempted"] > 1
    assert metrics.HELD_LAUNCHES.get(result="dropped") == dropped
    # the window is one epoch: all of its steps but the first
    assert metrics.HELD_LAUNCHES.get(result="taken") - taken >= \
        res["attempted"] - 1
    if not on_chip:
        return
    assert res["correct"] is True, res["compared"]
    idle = res["metrics"]["device_idle_share.images"]["value"]
    print(CELL, "device_idle_share.images", idle,
          {k: v["value"] for k, v in res["metrics"].items()})
    assert idle < IDLE_SHARE_MAX, idle
