"""The expert buffer cut to a rung (ops/decoder.py `_run_again_in_backward`,
`buffer_rungs`) against the whole buffer of `T * top_k` rows, on the chip at
the grouped-query cell's layer: 16,384 tokens, D 2,560, F 768, 8 of 64
experts held, 6 a token, bfloat16.  The TPU's grouped product leaves the
rows it does not visit as they were in memory, both ways: only the chip
shows whether every rung masks what it must.  Prints the rung each share
ran on.

On the CPU (tests/test_consistency_harness.py) the same at a toy size."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu  # noqa: F401  (x64 on, as in every program)
from mxnet_tpu.ops import decoder as ops


def _args(shape, live, seed):
    T, D, F, held, _E, K = shape
    rs = np.random.RandomState(seed)
    key = np.full(T * K, held, np.int32)
    key[rs.choice(T * K, live, replace=False)] = rs.randint(0, held, live)
    bf = jnp.bfloat16
    return (jnp.asarray(rs.normal(0, 1, (T, D)), bf), jnp.asarray(key),
            jnp.asarray(rs.uniform(0.05, 0.3, (T, K)), jnp.float32),
            jnp.asarray(rs.normal(0, 0.02, (held, D, F)), bf),
            jnp.asarray(rs.normal(0, 0.02, (held, D, F)), bf),
            jnp.asarray(rs.normal(0, 0.02, (held, F, D)), bf),
            jnp.asarray(rs.normal(0, 1, (T, D)), jnp.float32))


def _step(experts):
    def loss(h, key, w, gate, up, down, r):
        y, sizes = experts(h, key, w, gate, up, down)
        return jnp.sum(y.astype(jnp.float32) * r), (y, sizes)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 2, 3, 4, 5),
                                      has_aux=True))


@pytest.mark.parametrize("share", [0.125, 0.22, 0.48, 0.6, 1.0])
def test_each_rung_equals_the_whole_buffer(share):
    on_chip = jax.default_backend() == "tpu"
    shape = (16384, 2560, 768, 8, 64, 6) if on_chip else \
        (1024, 32, 16, 8, 64, 6)
    T, _D, _F, held, E, K = shape
    rungs = ops.buffer_rungs(T * K, held, E)
    act = jax.nn.relu
    whole = _step(jax.checkpoint(functools.partial(
        ops._expert_rows, act=act, buffer=T * K)))
    cut = _step(ops._run_again_in_backward(functools.partial(
        ops._expert_rows, act=act), rungs))
    live = int(T * K * share)
    args = _args(shape, live, 36)
    (_l0, (y0, s0)), g0 = whole(*args)
    (_l1, (y1, s1)), g1 = cut(*args)
    s0, s1 = np.asarray(s0), np.asarray(s1)
    ran = int(s1[-1])
    print(f"share {share}: {live} live rows of {T * K}, rung {ran} of "
          f"{rungs}, the whole buffer {s0[-1]}")
    assert s0[-1] == T * K and ran == min(c for c in rungs if c >= live)
    np.testing.assert_array_equal(s0[:-1], s1[:-1])
    assert s1[:held].sum() == live

    def gap(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))

    # the same products on the same rows: the forward differs only in the
    # order the float32 sums take; a garbage row would be of order 1
    assert gap(y1, y0) < 1e-2, gap(y1, y0)
    for name, a, b in zip(("h", "w", "gate", "up", "down"), g1, g0):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert gap(a, b) < 2e-2, (name, gap(a, b))
