"""Pallas flash-attention kernel tests (interpret mode on CPU; the same
kernel lowers through Mosaic on TPU)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu.ops.flash_attention import _dense_reference
from mxnet_tpu.test_utils import assert_almost_equal


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    rs = np.random.RandomState(0)
    B, H, T, D = 2, 3, 128, 32
    q, k, v = (nd.array(rs.normal(0, 1, (B, H, T, D)).astype("f"))
               for _ in range(3))
    out = nd.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = _dense_reference(q.handle, k.handle, v.handle, D ** -0.5, causal)
    assert_almost_equal(out.asnumpy(), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_flash_non_divisible_falls_back():
    rs = np.random.RandomState(1)
    q, k, v = (nd.array(rs.normal(0, 1, (1, 2, 100, 16)).astype("f"))
               for _ in range(3))
    out = nd.flash_attention(q, k, v, block_q=64, block_k=64)
    ref = _dense_reference(q.handle, k.handle, v.handle, 0.25, False)
    assert_almost_equal(out.asnumpy(), np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_flash_gradients():
    rs = np.random.RandomState(2)
    B, H, T, D = 1, 2, 64, 16
    q, k, v = (nd.array(rs.normal(0, 1, (B, H, T, D)).astype("f"))
               for _ in range(3))
    for a in (q, k, v):
        a.attach_grad()
    with autograd.record():
        o = nd.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        loss = (o * o).sum()
    loss.backward()

    def f(a, b, c):
        return (_dense_reference(a, b, c, D ** -0.5, True) ** 2).sum()

    gq, gk, gv = jax.grad(f, argnums=(0, 1, 2))(q.handle, k.handle, v.handle)
    assert_almost_equal(q.grad.asnumpy(), np.asarray(gq), rtol=1e-3, atol=1e-4)
    assert_almost_equal(k.grad.asnumpy(), np.asarray(gk), rtol=1e-3, atol=1e-4)
    assert_almost_equal(v.grad.asnumpy(), np.asarray(gv), rtol=1e-3, atol=1e-4)


def test_flash_bf16():
    rs = np.random.RandomState(3)
    q, k, v = (nd.array(rs.normal(0, 1, (1, 2, 64, 32)).astype("f"))
               .astype("bfloat16") for _ in range(3))
    out = nd.flash_attention(q, k, v, block_q=32, block_k=32)
    assert str(out.dtype) == "bfloat16"
    ref = _dense_reference(q.handle, k.handle, v.handle, 32 ** -0.5, False)
    assert_almost_equal(out.asnumpy().astype("f"),
                        np.asarray(ref).astype("f"), rtol=3e-2, atol=3e-2)


def test_multi_head_attention_layer():
    from mxnet_tpu.gluon import nn
    B, T, E, H = 2, 32, 64, 4
    attn = nn.MultiHeadAttention(E, H)
    attn.initialize()
    x = nd.random.uniform(shape=(B, T, E))
    out = attn(x)
    assert out.shape == (B, T, E)
    # causal layer trains
    attn_c = nn.MultiHeadAttention(E, H, causal=True)
    attn_c.initialize()
    with autograd.record():
        loss = (attn_c(x) ** 2).sum()
    loss.backward()
    g = attn_c.collect_params()
    assert any((p.grad() is not None and
                float(np.abs(p.grad().asnumpy()).sum()) > 0)
               for p in g.values() if p.grad_req != "null")


def test_no_probe_no_dense_fallback_for_compile_trouble():
    """On TPU the kernel compiles (Mosaic) or raises: the op owns no
    child process, no availability flag and no interpret switch, and the
    interpreter is selected by the backend being the CPU and nothing
    else."""
    import inspect
    from mxnet_tpu.ops import flash_attention as fa
    src = inspect.getsource(fa)
    for gone in ("subprocess", "pallas_available", "_PALLAS_OK",
                 "MXT_FLASH_INTERPRET", "MXT_PALLAS_PROBE"):
        assert gone not in src, gone
    assert 'interpret=jax.default_backend() == "cpu"' in src


def test_flash_kernel_traces_without_64bit_types():
    """jax_enable_x64 is on package-wide and Mosaic has no f64/i64: the
    pallas_call (kernel body and index maps) must trace 32-bit."""
    from mxnet_tpu.ops import flash_attention as fa
    q = jnp.ones((1, 2, 64, 16), jnp.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda a: fa._flash_attention(a, a, a, 0.25, True, 32, 32))(q))
    assert "pallas_call" in jaxpr
    assert "f64" not in jaxpr and "i64" not in jaxpr, jaxpr


def test_mha_decode_step_matches_full_attention():
    """Feeding a sequence token-by-token through mha_decode_step (cache
    write at t + masked attention over columns <= t) must reproduce the
    full-sequence fused multihead_attention output at every position —
    the op-level pin under the gluon KV-decode path."""
    rs = np.random.RandomState(3)
    B, H, T, D = 2, 4, 10, 32       # D = model dim; dh = D // H
    dh = D // H
    qkv = nd.array(rs.normal(0, 1, (B, T, 3 * D)).astype("f"))
    full = nd.multihead_attention(qkv, num_heads=H, causal=True).asnumpy()

    kc = nd.zeros((B, H, T, dh))
    vc = nd.zeros((B, H, T, dh))
    for t in range(T):
        step_qkv = nd.slice_axis(qkv, axis=1, begin=t, end=t + 1)
        out, kc, vc = nd.mha_decode_step(
            step_qkv, kc, vc, nd.array([float(t)]), num_heads=H)
        assert_almost_equal(out.asnumpy()[:, 0], full[:, t],
                            rtol=1e-4, atol=1e-5)


def test_mha_decode_step_mask_excludes_future():
    """Garbage already sitting beyond position t in the cache must not
    influence the step output (the iota<=t mask is the causal frontier)."""
    rs = np.random.RandomState(4)
    B, H, T, D = 1, 2, 8, 16
    dh = D // H
    qkv = nd.array(rs.normal(0, 1, (B, 1, 3 * D)).astype("f"))
    clean_k = nd.zeros((B, H, T, dh))
    clean_v = nd.zeros((B, H, T, dh))
    dirty_k = nd.array(rs.normal(0, 1, (B, H, T, dh)).astype("f"))
    dirty_v = nd.array(rs.normal(0, 1, (B, H, T, dh)).astype("f"))
    # position 0: only column 0 (this token's own K/V) may matter
    o_clean, _, _ = nd.mha_decode_step(qkv, clean_k, clean_v,
                                       nd.array([0.0]), num_heads=H)
    o_dirty, _, _ = nd.mha_decode_step(qkv, dirty_k, dirty_v,
                                       nd.array([0.0]), num_heads=H)
    assert_almost_equal(o_clean.asnumpy(), o_dirty.asnumpy(),
                        rtol=1e-5, atol=1e-6)
