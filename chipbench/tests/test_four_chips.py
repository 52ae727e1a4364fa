"""The `chips: 4` branch, rehearsed on four virtual CPU devices: a cell
added to a copy of BENCHMARK.json (no file edited) runs through Module.fit
over four contexts and agrees with the reference.  It needs
XLA_FLAGS=--xla_force_host_platform_device_count=4 (or more) set before JAX
starts, so it runs in a process of its own."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHILD = r"""
import copy, json, sys
sys.path.insert(0, %(root)r)
from chipbench import cell as cellmod, run
bench = copy.deepcopy(cellmod.benchmark())
bench["workloads"].append({"name": "resnet50_train_module_4chip",
    "config": "resnet50_v1", "traffic": "module_fit_b256", "chips": 4,
    "why": "rehearsal"})
cellmod.benchmark = lambda: bench
real = cellmod.load_json
cellmod.load_json = lambda path: dict(real(path), dtype="float32") \
    if "/traffic/" in path else real(path)
res = run.run_cell("resnet50_train_module_4chip", 7, 0.5, False,
                   rehearsal=True)
print(json.dumps(res))
"""


def test_four_chip_branch_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", CHILD % {"root": ROOT}],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] >= 4
    assert res["attempted"] > 0 and res["failed"] == 0
    for name, rec in res["compared"].items():
        assert rec["value"] < 1e-4, (name, rec)
    # no limits file for a cell that BENCHMARK.json does not hold: not proven
    assert res["correct"] is False
