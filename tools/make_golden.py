"""Regenerate the committed golden-logit fixtures (VERDICT r3 #2).

    JAX_PLATFORMS=cpu python tools/make_golden.py

Writes tests/golden/<name>.npz holding the expected CPU logits for each
fixed-seed model-zoo case (params/inputs regenerate from seeds — see
mxnet_tpu.test_utils.golden_model_cases).  Run ONLY when an intentional
numeric change lands; CI (tests/test_golden_forward.py) fails on any
unintentional drift.  Parity: tests/python/gpu/test_forward.py.
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# golden logits are CPU f32 numbers: set before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

from mxnet_tpu.test_utils import (golden_fixture_path,  # noqa: E402
                                  golden_forward, golden_model_cases)


def main():
    os.makedirs(os.path.join(REPO, "tests", "golden"), exist_ok=True)
    for name in golden_model_cases():
        logits = golden_forward(name)
        path = golden_fixture_path(name)
        np.savez_compressed(path, logits=logits)
        print(f"{name}: logits {logits.shape} -> {path} "
              f"({os.path.getsize(path)} bytes)")


if __name__ == "__main__":
    main()
