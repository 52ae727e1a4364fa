"""conftest's per-test time limit: a test that waits fails by name, and the
tests behind it on the same worker still run."""
import os

from example_runner import REPO, run_python

PROBE = '''
import subprocess, sys, time
import conftest
conftest.TEST_LIMIT_S = 1


def test_waits_on_a_sleep():
    time.sleep(60)


def test_waits_on_a_child():
    subprocess.run([sys.executable, "-c", "import time; time.sleep(60)"])


def test_behind_them():
    pass
'''


def test_a_test_past_its_limit_fails_by_name_and_the_next_runs(tmp_path):
    probe = tmp_path / "test_probe.py"
    probe.write_text(PROBE)
    proc = run_python(
        ["-m", "pytest", str(probe), "-q", "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "no:xdist", "--rootdir",
         str(tmp_path)],
        env={"PYTHONPATH": os.pathsep.join(
            [REPO, os.path.join(REPO, "tests")])},
        cwd=str(tmp_path), rc=1, timeout=120)
    out = proc.stdout
    assert "2 failed, 1 passed" in out, out
    for name in ("test_waits_on_a_sleep", "test_waits_on_a_child"):
        assert f"test_probe.py::{name} ran past 1 s" in out, out
