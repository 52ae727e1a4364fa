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


def _reference(q, k, v, scale, causal):
    """The dense reference on the inputs' float32 values, at "highest"."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_dense_reference(
            *(a.astype(jnp.float32) for a in (q, k, v)), scale, causal))


def _rand_qkv(seed, shape, dtype):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.normal(0, 1, shape).astype("f")).astype(dtype)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T, D", [(1024, 64), (768, 32)])
def test_flash_bf16_chosen_tiles_match_dense(T, D, causal):
    """bfloat16 operands straight to the products, at lengths where the
    chosen tiles give several query tiles and several key sub-tiles
    (1024: 2 x 2 of 512) or sub-tiles of another width than the query
    tile (768: one tile of 768 over two sub-tiles of 384)."""
    from mxnet_tpu.ops import flash_attention as fa
    blk_q, blk_k, sub = fa._fa_tiles(T, T, D, jnp.bfloat16)
    assert (T // blk_q) * (blk_k // sub) > 1
    q, k, v = _rand_qkv(5, (1, 2, T, D), jnp.bfloat16)
    out = fa._flash_attention(q, k, v, D ** -0.5, causal)
    assert out.dtype == jnp.bfloat16
    assert_almost_equal(np.asarray(out.astype(jnp.float32)),
                        _reference(q, k, v, D ** -0.5, causal),
                        rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("block_q, block_k", [(64, 32), (32, 64)])
def test_flash_unequal_explicit_tiles(block_q, block_k, dtype):
    """Unequal tiles: the diagonal crosses blocks off the block diagonal,
    so masking and skipping go by position, not by block index."""
    q, k, v = (nd.array(np.asarray(a.astype(jnp.float32))).astype(dtype)
               for a in _rand_qkv(6, (1, 2, 256, 32), jnp.float32))
    out = nd.flash_attention(q, k, v, causal=True, block_q=block_q,
                             block_k=block_k)
    tol = dict(rtol=1e-4, atol=1e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    assert_almost_equal(
        out.asnumpy().astype("f"),
        _reference(q.handle, k.handle, v.handle, 32 ** -0.5, True), **tol)


@pytest.mark.parametrize("T, D", [(384, 32), (100, 16), (8, 12)])
def test_flash_lengths_no_power_of_two_tile_divides(T, D):
    """384 (128 divides it, 256 and 512 do not), 100 and 8 (below one
    tile): the kernel runs them whole, none falls to the dense reference,
    and the op's default blocks are the kernel's choice."""
    from mxnet_tpu.ops import flash_attention as fa
    q, k, v = _rand_qkv(7, (2, 2, T, D), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda a, b, c: fa._flash_attention(
        a, b, c, D ** -0.5, True))(q, k, v))
    assert "pallas_call" in jaxpr
    out = nd.flash_attention(nd.array(np.asarray(q)), nd.array(np.asarray(k)),
                             nd.array(np.asarray(v)), causal=True)
    assert_almost_equal(out.asnumpy(), _reference(q, k, v, D ** -0.5, True),
                        rtol=1e-4, atol=1e-5)


def test_flash_scale_that_is_no_power_of_two():
    """Such a scale multiplies the float32 scores, not bfloat16 q."""
    from mxnet_tpu.ops import flash_attention as fa
    q, k, v = _rand_qkv(8, (1, 2, 128, 48), jnp.bfloat16)
    out = fa._flash_attention(q, k, v, 48 ** -0.5, True)
    assert_almost_equal(np.asarray(out.astype(jnp.float32)),
                        _reference(q, k, v, 48 ** -0.5, True),
                        rtol=2e-2, atol=2e-2)


def test_flash_gradients_bf16():
    """Gradients after the forward change: the backward pass recomputes
    its own float32 softmax from (q, k, v, o)."""
    from mxnet_tpu.ops import flash_attention as fa
    q, k, v = _rand_qkv(9, (1, 2, 256, 64), jnp.bfloat16)
    r = _rand_qkv(10, (1, 2, 256, 64), jnp.float32)[0]

    def loss(attn):
        return lambda a, b, c: jnp.sum(attn(a, b, c).astype(jnp.float32) * r)

    got = jax.grad(loss(lambda a, b, c: fa._flash_attention(
        a, b, c, 0.125, True)), argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(lambda a, b, c: _dense_reference(
            a, b, c, 0.125, True)), argnums=(0, 1, 2))(
                *(a.astype(jnp.float32) for a in (q, k, v)))
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        w = np.asarray(w)
        assert_almost_equal(np.asarray(g.astype(jnp.float32)), w,
                            rtol=3e-2, atol=3e-2 * float(np.abs(w).max()))


def test_flash_backward_does_not_follow_the_forward_tiles():
    """The backward pass has its own block: its program is the same
    whatever tiles the forward kernel ran with."""
    from mxnet_tpu.ops import flash_attention as fa
    x = jnp.ones((1, 2, 256, 16), jnp.float32)
    programs = {str(jax.make_jaxpr(lambda res, g: fa._fa_bwd(
        0.25, True, bq, bk, None, res, g))((x, x, x, x), x))
        for bq, bk in ((32, 32), (64, 128), (None, None))}
    assert len(programs) == 1
    assert fa.BWD_BLOCK == 128


@pytest.mark.parametrize("BH, D", [(64, 64), (40, 256)])
def test_tile_rule_at_the_cells_shapes(BH, D):
    """T 2048 in bfloat16 at the two cells' head sizes: divisors of T,
    whole sub-tiles in the key block, inside the budget."""
    from mxnet_tpu.ops import flash_attention as fa
    T = 2048
    blk_q, blk_k, sub = fa._fa_tiles(T, T, D, jnp.bfloat16)
    assert T % blk_q == 0 and T % blk_k == 0 and blk_k % sub == 0
    assert blk_q % 128 == 0 and sub % 128 == 0
    assert fa._fa_vmem_bytes(blk_q, blk_k, sub, D, 2) <= fa.VMEM_BUDGET
    assert fa._fa_scores_bytes(blk_q, sub, 2) <= fa.VMEM_BUDGET // 3
    # a smaller budget gives smaller tiles, none gives none
    small = fa._fa_tiles(T, T, D, jnp.bfloat16, budget=2 ** 20)
    assert small is not None and small[0] * small[2] < blk_q * sub
    assert fa._fa_vmem_bytes(*small, D, 2) <= 2 ** 20 or small[1] == small[2]
    assert fa._fa_tiles(T, T, D, jnp.bfloat16, budget=1024) is None


def test_flash_fwd_blocks_counter_at_the_opt_cells_call():
    """Tracing the OPT cell's call (nothing runs) sets the gauges to what
    the chosen tiles imply: the upper triangle is not computed."""
    from mxnet_tpu.observability import metrics
    from mxnet_tpu.ops import flash_attention as fa
    x = jax.ShapeDtypeStruct((2, 32, 2048, 64), jnp.bfloat16)
    jax.eval_shape(lambda a: fa._flash_attention(a, a, a, 0.125, True), x)
    blk_q, _, sub = fa._fa_tiles(2048, 2048, 64, jnp.bfloat16)
    assert metrics.FLASH_FWD_TILE.get(dim="q") == blk_q
    assert metrics.FLASH_FWD_TILE.get(dim="k") == sub
    grid = metrics.FLASH_FWD_BLOCKS.get(kind="grid")
    computed = metrics.FLASH_FWD_BLOCKS.get(kind="computed")
    assert grid == 64 * (2048 // blk_q) * (2048 // sub)
    assert computed == 64 * sum(
        ((i + 1) * blk_q - 1) // sub + 1 for i in range(2048 // blk_q))
    assert grid / 2 < computed < grid
    jax.eval_shape(lambda a: fa._flash_attention(a, a, a, 0.125, False), x)
    assert metrics.FLASH_FWD_BLOCKS.get(kind="computed") == grid


def _kernel_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_eqns(sub)


def test_flash_bf16_operands_reach_the_products_as_given():
    """No float32 copy of a q, k or v block is made before the products:
    both `dot_general`s of the kernel take bfloat16 on both sides (the
    exponentials are handed over in v's dtype) and give float32."""
    from mxnet_tpu.ops import flash_attention as fa
    x = jnp.ones((1, 2, 256, 64), jnp.bfloat16)
    eqns = list(_kernel_eqns(jax.make_jaxpr(
        lambda a: fa._flash_attention(a, a, a, 0.125, True, 128, 128))(
            x).jaxpr))
    assert any(e.primitive.name == "pallas_call" for e in eqns)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) >= 2
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2, e
        assert e.outvars[0].aval.dtype == jnp.float32
    widened = [e for e in eqns if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.dtype == jnp.bfloat16
               and e.params["new_dtype"] == jnp.float32]
    assert not widened, widened


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
