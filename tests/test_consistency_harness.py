"""CI validation of the TPU-vs-CPU consistency tier (tests_tpu/).

On a healthy TPU host `python -m pytest tests_tpu/` runs the real
cross-backend comparison (reference pattern: test_operator_gpu.py).  This
test keeps the harness itself green on CPU-only CI by running it in
cpu-vs-cpu self-test mode.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_consistency_suite_selftest():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "MXT_CONSISTENCY_SELFTEST": "1", "PYTHONPATH": REPO}
    # three workers, a file each at a time: the tier's files take some 300 s
    # in one process on the CPU, the whole of one test's limit
    r = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(REPO, "tests_tpu"),
         "-q", "--no-header", "-x", "-p", "xdist", "-n", "3",
         "--dist", "loadfile"],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert " passed" in r.stdout
