"""The forward flash kernel on the chip at the two benchmark cells' call
shapes (opt1.3b_train_gluon: 2 x 32 heads of 64; glm4.7flash_train_gluon:
2 x 20 heads of 256; T 2048, bfloat16, causal) against the dense reference
in float32 at "highest", forward and gradients, and the counter the tile
choice sets."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (x64 on, as the program runs)
from mxnet_tpu.observability import metrics
from mxnet_tpu.ops import flash_attention as fa


@pytest.mark.parametrize("shape", [(2, 32, 2048, 64), (2, 20, 2048, 256)],
                         ids=["opt1.3b", "glm4.7flash"])
def test_flash_kernel_at_the_cells_shapes(shape):
    rng = np.random.default_rng(11)
    q, k, v, r = (jnp.asarray(rng.standard_normal(shape, dtype=np.float32))
                  for _ in range(4))
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    scale = shape[-1] ** -0.5

    def loss(attn):
        def f(a, b, c):
            o = attn(a, b, c)
            return jnp.sum(o.astype(jnp.float32) * r), o
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, out), grads = loss(lambda a, b, c: fa._flash_attention(
        a, b, c, scale, True))(q, k, v)
    lowered = jax.jit(lambda a, b, c: fa._flash_attention(
        a, b, c, scale, True)).lower(q, k, v).as_text()
    # (tests/test_consistency_harness.py runs this file on the CPU, where
    # the interpreter stands in for Mosaic)
    if jax.default_backend() == "tpu":
        assert "tpu_custom_call" in lowered
    grid = metrics.FLASH_FWD_BLOCKS.get(kind="grid")
    computed = metrics.FLASH_FWD_BLOCKS.get(kind="computed")
    assert 0 < grid / 2 < computed < grid, (grid, computed)

    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = loss(lambda a, b, c: fa._dense_reference(
            a, b, c, scale, True))(*(a.astype(jnp.float32)
                                     for a in (q, k, v)))
    for name, got, want in zip(("out", "dq", "dk", "dv"),
                               (out,) + tuple(grads),
                               (ref,) + tuple(ref_grads)):
        assert got.dtype == jnp.bfloat16, name
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want)
        assert np.isfinite(got).all(), name
        # bfloat16 operands and results: 2^-8 of the largest value
        err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        assert err <= 2e-2, (name, err)
