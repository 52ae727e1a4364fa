"""The flash kernels on the chip at the benchmark cells' call
shapes (opt1.3b_train_gluon: 2 x 32 heads of 64; glm4.7flash_train_gluon:
2 x 20 heads of 256; T 2048, bfloat16, causal; smallthinker21b_train_gluon:
2 x 28 query heads over 4 key/value heads of 128, T 8192, with a window of
4,096 and without) against the dense reference in float32 at "highest",
forward and gradients, and the counters the tile choice sets; the two
backward kernels on the forward's own residuals against the plain float32
pass `_bwd_banded`."""
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


def _blocked_reference(q, k, v, scale, window, block=1024):
    """Dense causal attention of one key/value head's group, (G, T, D)
    queries over (T, D) keys and values, the queries in blocks so that a
    block's scores against all keys is what is held."""
    T = q.shape[1]
    block = min(block, T)

    def one(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        s = jnp.einsum("gqd,kd->gqk", qs, k) * scale
        ahead = (i * block + jnp.arange(block))[:, None] \
            - jnp.arange(T)[None, :]
        mask = ahead >= 0
        if window is not None:
            mask &= ahead < window
        s = jnp.where(mask, s, fa.NEG_INF)
        return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(T // block))
    return jnp.moveaxis(out, 0, 1).reshape(q.shape)


@pytest.mark.parametrize("window", [None, 4096], ids=["global", "window"])
def test_flash_kernel_at_the_grouped_query_cells_shape(window):
    """2 x 28 query heads over 4 key/value heads of 128 at T 8,192: the
    whole call through the kernel and the backward kernels; the last batch
    entry's last group (7 query heads, 1 key/value head) against the
    blocked dense reference, whose gradients are that group's alone since
    the loss is a sum over heads.  On the CPU (the harness's self-test)
    the same layout at T 1,024 with a window of 512."""
    on_chip = jax.default_backend() == "tpu"
    T = 8192 if on_chip else 1024
    if window is not None and not on_chip:
        window = 512
    H, Hkv, D = 28, 4, 128
    rng = np.random.default_rng(12)
    q, r = (jnp.asarray(rng.standard_normal((2, H, T, D), dtype=np.float32))
            for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((2, Hkv, T, D),
                                            dtype=np.float32))
            for _ in range(2))
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    scale = D ** -0.5
    metrics.FLASH_FWD_TILES.reset()

    def f(a, b, c):
        o = fa._flash_attention(a, b, c, scale, True, None, None, window)
        return jnp.sum(o.astype(jnp.float32) * r), o

    (_, out), (dq, dk, dv) = jax.jit(jax.value_and_grad(
        f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    visited = metrics.FLASH_FWD_TILES.get(kind="visited")
    assert visited == metrics.FLASH_FWD_TILES.get(kind="needed") > 0
    if on_chip:  # 512 x 512 tiles: 136 a head under the causal mask, 108
        assert visited == 2 * H * (108 if window else 136)
    g = H // Hkv

    def ref(a, b, c):
        o = _blocked_reference(a, b, c, scale, window)
        return jnp.sum(o * r[1, -g:]), o

    with jax.default_matmul_precision("highest"):
        (_, want), (wq, wk, wv) = jax.jit(jax.value_and_grad(
            ref, argnums=(0, 1, 2), has_aux=True))(
                q[1, -g:].astype(jnp.float32), k[1, -1].astype(jnp.float32),
                v[1, -1].astype(jnp.float32))
    for name, got, want_ in (("out", out[1, -g:], want),
                             ("dq", dq[1, -g:], wq), ("dk", dk[1, -1], wk),
                             ("dv", dv[1, -1], wv)):
        assert got.dtype == jnp.bfloat16, name
        got = np.asarray(got.astype(jnp.float32))
        want_ = np.asarray(want_)
        assert np.isfinite(got).all(), name
        err = float(np.max(np.abs(got - want_)) / np.max(np.abs(want_)))
        assert err <= 2e-2, (name, err)


CALLS = {  # B, H, Hkv, T, D, window: one call of each kind the cells send
    "opt1.3b": (2, 32, 32, 2048, 64, None),
    "glm4.7flash": (2, 20, 20, 2048, 256, None),
    "smallthinker21b_global": (2, 28, 4, 8192, 128, None),
    "smallthinker21b_window": (2, 28, 4, 8192, 128, 4096),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_backward_kernels_against_the_plain_pass(call):
    """dQ, dK and dV of the two backward kernels at the cells' exact
    shapes, from the forward kernel's own output and row statistics,
    against `_bwd_banded` on the same residuals; the calls carry the
    `flash_bwd_*` names no forward reader matches; the kernels visit the
    tiles the mask needs and no other.  On the CPU (the harness's
    self-test) an eighth of the length."""
    on_chip = jax.default_backend() == "tpu"
    B, H, Hkv, T, D, window = CALLS[call]
    if not on_chip:
        B, T, window = 1, T // 8, window and window // 8
    rng = np.random.default_rng(13)
    q, g = (jnp.asarray(rng.standard_normal((B, H, T, D), dtype=np.float32)
                        ).astype(jnp.bfloat16) for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((B, Hkv, T, D),
                                            dtype=np.float32)
                        ).astype(jnp.bfloat16) for _ in range(2))
    scale = D ** -0.5
    o, lse = jax.jit(lambda a, b, c: fa._fa_call(
        a, b, c, scale, True, None, None, window, True))(q, k, v)
    assert lse.shape == (B * H, 1, T) and lse.dtype == jnp.float32
    metrics.FLASH_BWD.reset()
    metrics.FLASH_BWD_TILES.reset()
    kernels = jax.jit(lambda res, g_: fa._fa_bwd(
        scale, True, None, None, window, res, g_))
    got = kernels((q, k, v, o, lse), g)
    assert metrics.FLASH_BWD.get(path="kernel") == 1
    assert metrics.FLASH_BWD.get(path="reference") == 0
    assert metrics.FLASH_BWD_TILES.get(kind="visited") == \
        metrics.FLASH_BWD_TILES.get(kind="needed") > 0
    text = kernels.lower((q, k, v, o, lse), g).as_text(debug_info=True)
    assert "flash_bwd_dkv" in text and "flash_bwd_dq" in text
    if on_chip:
        assert text.count("tpu_custom_call") == 2
    want = jax.jit(lambda res, g_: fa._bwd_banded(
        scale, True, window, res, g_))((q, k, v, o), g)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape, name
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        assert np.isfinite(a).all(), name
        err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        assert err <= 1e-2, (name, err)
