"""Test harness config: 8 virtual CPU devices so multi-chip sharding
(mesh DP, ring attention, group2ctx placement) is exercised without TPUs
— the strategy SURVEY.md §4 prescribes (reference ran multi-*CPU*-context
tests for device-placement logic, tests/python/unittest/test_multi_device_exec.py).

Tests never touch a chip: JAX_PLATFORMS=cpu is forced below, before
jax is imported."""
import os
import signal
import threading

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        flags + " --xla_force_host_platform_device_count=8"

os.environ["JAX_PLATFORMS"] = "cpu"
# in a noisy shared container a slow test step WILL trip the flight
# recorder's watchdog, so route its auto-dumps to a scratch directory of
# this run (tests that assert on dumps monkeypatch their own dir)
if "MXNET_FLIGHT_DIR" not in os.environ:
    import tempfile
    os.environ["MXNET_FLIGHT_DIR"] = tempfile.mkdtemp(
        prefix="mxt-test-flight-")
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

_cpus = jax.devices("cpu")
assert len(_cpus) >= 8, _cpus
jax.config.update("jax_default_device", _cpus[0])

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers",
        "perf_smoke: CPU-runnable dispatch-count regression gates — the "
        "perf analogue of a correctness test; runs in the tier-1 path")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / overload resilience drills "
        "(mxnet_tpu.faultinject).  The fast deterministic subset runs "
        "in the tier-1 path by default; `pytest -m chaos` (or `make "
        "chaos`) selects the full plan including the slow sustained "
        "legs")
    config.addinivalue_line(
        "markers",
        "analysis: graft-lint full-codebase static-analysis sweeps "
        "(mxnet_tpu.analysis; `make lint-graft` is the CLI twin).  "
        "Runs in tier-1 by default; skip on slow containers with "
        "`-m 'not analysis'`")
    config.addinivalue_line(
        "markers",
        "flight: flight-recorder timeline tests (mxnet_tpu."
        "observability.flight — ring recording, trace-id propagation, "
        "Perfetto export, anomaly auto-dump).  Runs in tier-1 by "
        "default; `pytest -m flight` selects just the recorder suite")
    config.addinivalue_line(
        "markers",
        "memory: HBM-ledger tests (mxnet_tpu.observability.memory — "
        "attribution/leak gates, budget watchdog, OOM post-mortem).  "
        "Runs in tier-1 by default; `pytest -m memory` selects just "
        "the ledger suite")
    config.addinivalue_line(
        "markers",
        "registry: multi-model serving registry tests (mxnet_tpu."
        "serving.registry — HBM-budget admission, LRU eviction, "
        "restart-free readmission, degradation ladder, chaos churn).  "
        "Runs in tier-1 by default; `pytest -m registry` (or `make "
        "chaos-serve`) selects this suite")
    config.addinivalue_line(
        "markers",
        "introspect: program-introspection tests (mxnet_tpu."
        "observability.introspect — compile-chokepoint cost capture, "
        "named-scope per-layer attribution, MFU/roofline math, "
        "perf-regression sentinel).  Runs in tier-1 by default; "
        "`pytest -m introspect` selects just this suite")
    config.addinivalue_line(
        "markers",
        "program_audit: compiled-program contract-audit tests "
        "(mxnet_tpu.analysis.program_audit — donation→aliasing, AMP "
        "cast coverage, host-callback and collective-count "
        "verification against captured HLO; `python -m "
        "mxnet_tpu.analysis --audit-programs` is the CLI twin).  Runs "
        "in tier-1 by default; `pytest -m program_audit` selects just "
        "this suite")


@pytest.fixture(autouse=True)
def _flight_dir(tmp_path, monkeypatch):
    """A test that trips the slow-phase watchdog or the OOM post-mortem
    writes its flight-*/oom-*.json under its own tmp_path.  Tests that
    care about the dir still monkeypatch their own."""
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "flight-dumps"))


#: seconds one test may take, set-up and tear-down included: a fifth of
#: tier-1's clock.  A child process gets less (example_runner.TIMEOUT).
TEST_LIMIT_S = 300


@pytest.fixture(autouse=True)
def _time_limit(request):
    """Fail a test that runs past TEST_LIMIT_S, by name, instead of letting
    it eat the clock of every test queued behind it on its worker (the
    suite's own limit then cuts the run, and what the clock did not reach
    guards nothing).  An interval timer on the main thread, where pytest
    and xdist's workers run tests; the handler runs between bytecodes, so
    a wait on a child, a lock or a socket is interrupted, a compile inside
    XLA only when it returns."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        pytest.fail(f"{request.node.nodeid} ran past {TEST_LIMIT_S} s",
                    pytrace=True)

    prev = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield


@pytest.fixture
def program_audit():
    """Arm opt-in HLO capture for the test's compiles and hand back a
    checker that verifies a captured program's declared contracts —
    donation really became input-output aliasing first among them — so
    dispatch-count gates can pin ALIASING on the same program whose
    1-dispatch budget they measure (ISSUE 15).  Usage::

        def test_x(program_audit):
            ...train...
            aliased = program_audit("whole_step")
    """
    from mxnet_tpu.observability import introspect
    # every knob the audit reads is set here and put back after, so the
    # result does not depend on what an earlier test of this process left
    # behind (a leaked 123-byte cap cut the captured program and the audit
    # read "donation degraded to copy" from the stub)
    prev = (introspect.ENABLED, introspect.HLO, introspect.HLO_CAP_BYTES)
    introspect.enable()
    introspect.configure(hlo=True, hlo_cap_bytes=8 << 20)

    def check(program="whole_step", min_aliased=1):
        from mxnet_tpu.analysis import program_audit as pa
        rec = introspect.programs().get(program)
        assert rec is not None, \
            f"program {program!r} was never captured " \
            f"(have: {sorted(introspect.programs())})"
        assert rec.get("hlo"), \
            f"no HLO captured for {program!r} — the program compiled " \
            f"before this fixture armed capture"
        assert not rec.get("hlo_truncated"), \
            f"HLO of {program!r} was cut at {introspect.HLO_CAP_BYTES} bytes"
        issues = pa.audit_program(rec)
        assert issues == [], issues
        aliased = pa.parse_alias_table(rec["hlo"])
        assert len(aliased) >= min_aliased, \
            f"only {len(aliased)} aliased param(s); donation did not " \
            f"become input-output aliasing"
        return aliased

    yield check
    (introspect.enable if prev[0] else introspect.disable)()
    introspect.configure(hlo=prev[1], hlo_cap_bytes=prev[2])
