"""Golden-logit zoo gate (VERDICT r3 #2; parity:
tests/python/gpu/test_forward.py).

Each case rebuilds a model-zoo net from fixed seeds and compares its
logits against the committed fixture at 1e-4 — ANY numeric drift in
init, ops, or the gluon stack fails here.  Regenerate intentionally with
tools/make_golden.py.  The on-chip twin runs in
tests_tpu/test_consistency.py (looser tol for bf16 MXU matmuls).
"""
import numpy as np
import pytest

from mxnet_tpu.test_utils import (golden_fixture_path, golden_forward,
                                  golden_model_cases)

CASES = sorted(golden_model_cases())


@pytest.mark.parametrize("name", CASES)
def test_golden_logits(name):
    fixture = np.load(golden_fixture_path(name))["logits"]
    got = golden_forward(name)
    assert got.shape == fixture.shape
    np.testing.assert_allclose(got, fixture, rtol=1e-4, atol=1e-4)


def test_golden_is_deterministic():
    """Two rebuilds in one process produce identical logits (the fixture
    contract is meaningless without this)."""
    a = golden_forward("mobilenet0_25")
    b = golden_forward("mobilenet0_25")
    np.testing.assert_array_equal(a, b)
