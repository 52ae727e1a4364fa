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
    """Gradients through the rule's own tiles in bfloat16 against the dense
    reference's in float32."""
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


def _qkvg(seed, H, Hkv, T, D, dtype):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.normal(0, 1, (1, h, T, D)).astype("f")
                             ).astype(dtype) for h in (H, Hkv, Hkv, H))


# causal, group, window, D, T, (blk_q, blk_k) or None for the rule's tiles
BACKWARD_CALLS = {
    "full": (False, 1, None, 64, 256, (128, 128)),
    "causal": (True, 1, None, 64, 256, (128, 128)),
    "group7": (True, 7, None, 128, 256, (128, 128)),
    "window_in_a_tile": (True, 1, 50, 64, 384, (128, 128)),
    "window_over_tiles": (True, 7, 200, 128, 384, (128, 128)),
    "head256": (True, 1, None, 256, 256, (128, 128)),
    "tall_tiles": (True, 1, 129, 64, 512, (256, 128)),
    "wide_tiles": (True, 7, 129, 64, 512, (128, 256)),
    "rule_tiles": (True, 7, 300, 128, 512, None),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("call", sorted(BACKWARD_CALLS))
def test_backward_kernels_match_both_references(call, dtype):
    """dQ, dK and dV of the two backward kernels (the interpreter) against
    the plain float32 pass `_bwd_banded` on the same residuals and against
    `jax.grad` of the dense reference: with a causal mask or none, one
    query head a key/value head or seven, no window, one inside a tile and
    one across tiles, head sizes 64, 128 and 256, tiles given (equal,
    taller, wider) or chosen by the rule."""
    from mxnet_tpu.observability import metrics
    from mxnet_tpu.ops import flash_attention as fa
    causal, group, window, D, T, blocks = BACKWARD_CALLS[call]
    blk_q, blk_k = blocks or (None, None)
    q, k, v, g = _qkvg(11, group, 1, T, D, dtype)
    scale = D ** -0.5
    metrics.FLASH_BWD.reset()
    o, pull = jax.vjp(lambda a, b, c: fa._flash_attention(
        a, b, c, scale, causal, blk_q, blk_k, window), q, k, v)
    got = pull(g)
    assert metrics.FLASH_BWD.get(path="kernel") == 1
    assert metrics.FLASH_BWD.get(path="reference") == 0
    plain = fa._bwd_banded(scale, causal, window, (q, k, v, o), g)
    with jax.default_matmul_precision("highest"):
        dense = jax.vjp(lambda a, b, c: _dense_reference(
            a, b, c, scale, causal, window),
            *(a.astype(jnp.float32) for a in (q, k, v)))[1](
                g.astype(jnp.float32))
    tol = 2e-5 if dtype == "float32" else 2e-2
    for a, b, c, like in zip(got, plain, dense, (q, k, v)):
        assert a.shape == like.shape and a.dtype == like.dtype
        peak = float(jnp.max(jnp.abs(c)))
        for want in (b, c):
            np.testing.assert_allclose(
                np.asarray(a.astype(jnp.float32)),
                np.asarray(want.astype(jnp.float32)), rtol=0,
                atol=tol * peak)


@pytest.mark.parametrize("T, block", [(100, 64), (192, 128)])
def test_backward_takes_the_plain_pass_where_tiles_do_not_divide(T, block):
    """Given tiles that do not divide the length the forward is the dense
    reference and keeps no statistics; the backward is then the plain
    float32 pass, and `mxnet_flash_bwd_total{path=reference}` counts it."""
    from mxnet_tpu.observability import metrics
    from mxnet_tpu.ops import flash_attention as fa
    q, k, v, g = _qkvg(12, 2, 2, T, 32, "float32")
    scale = 32 ** -0.5
    metrics.FLASH_BWD.reset()
    f = lambda a, b, c: fa._flash_attention(a, b, c, scale, True, block,
                                            block)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda a, b, c: jax.vjp(f, a, b, c)[1](g))(q, k, v))
    got = jax.vjp(f, q, k, v)[1](g)
    assert metrics.FLASH_BWD.get(path="reference") == 2
    assert metrics.FLASH_BWD.get(path="kernel") == 0
    want = jax.vjp(lambda a, b, c: _dense_reference(a, b, c, scale, True),
                   q, k, v)[1](g)
    for a, b in zip(got, want):
        assert_almost_equal(np.asarray(a), np.asarray(b), rtol=1e-4,
                            atol=1e-5)


@pytest.mark.parametrize("causal, window, blocks", [
    (False, None, (64, 64)), (True, None, (128, 64)), (True, 40, (64, 128)),
    (True, 200, None)])
def test_row_statistics_are_the_logsumexp_of_the_dense_scores(
        causal, window, blocks):
    """What the forward keeps for the backward kernels: B * H * T floats,
    one row a head, equal to `logsumexp` over the keys each query sees;
    the output is the one the forward-only program gives."""
    from mxnet_tpu.ops import flash_attention as fa
    T, D = 256, 32
    q, k, v, _g = _qkvg(13, 4, 2, T, D, "float32")
    blk_q, blk_k = blocks or (None, None)
    o, lse = fa._fa_call(q, k, v, D ** -0.5, causal, blk_q, blk_k, window,
                         True)
    assert lse.shape == (4, 1, T) and lse.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(o), np.asarray(
        fa._flash_attention(q, k, v, D ** -0.5, causal, blk_q, blk_k,
                            window)))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1),
                   precision="highest") * D ** -0.5
    if causal:
        ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
        seen = ahead >= 0 if window is None else \
            (ahead >= 0) & (ahead < window)
        s = jnp.where(seen, s, -jnp.inf)
    assert_almost_equal(np.asarray(lse.reshape(1, 4, T)),
                        np.asarray(jax.nn.logsumexp(s, axis=-1)),
                        rtol=1e-5, atol=1e-5)


def test_forward_only_program_keeps_no_statistics():
    """Outside `record()` the forward program is the one it was: one
    output, and the kernel computes no log."""
    from mxnet_tpu.ops import flash_attention as fa
    x = jnp.ones((1, 2, 256, 64), jnp.bfloat16)
    for f, outs in ((lambda a: fa._flash_attention(a, a, a, 0.125, True), 1),
                    (lambda a: fa._fa_fwd(a, a, a, 0.125, True, None, None,
                                          None)[0], 2)):
        eqns = list(_kernel_eqns(jax.make_jaxpr(f)(x).jaxpr))
        (call,) = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert len(call.outvars) == outs
        assert any(e.primitive.name == "log" for e in eqns) is (outs == 2)


def test_backward_kernels_take_operands_as_given():
    """The forward kernel's rule in both backward kernels: q, k, v and dO
    reach the products in bfloat16, P and dS are cast to it for the second
    products, every product gives float32, and no block is widened."""
    from mxnet_tpu.ops import flash_attention as fa
    x = jnp.ones((1, 2, 256, 64), jnp.bfloat16)
    lse = jnp.ones((2, 1, 256), jnp.float32)
    eqns = list(_kernel_eqns(jax.make_jaxpr(lambda a, l: fa._fa_bwd(
        0.125, True, 128, 128, None, (a, a, a, a, l), a))(x, lse).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    inside = [e for c in calls for e in _kernel_eqns(c.params["jaxpr"])]
    dots = [e for e in inside if e.primitive.name == "dot_general"]
    assert len(dots) >= 7        # four in the dK/dV kernel, three in dQ's
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2, e
        assert e.outvars[0].aval.dtype == jnp.float32
    assert not [e for e in inside
                if e.primitive.name == "convert_element_type"
                and e.invars[0].aval.dtype == jnp.bfloat16
                and e.params["new_dtype"] == jnp.float32]


def test_backward_tile_rule_at_the_cells_shapes():
    """The backward's tiles at the three token cells' shapes: divisors,
    whole sub-tiles in the walked block, inside the backward's budget; a
    smaller budget gives smaller tiles, none gives none."""
    from mxnet_tpu.ops import flash_attention as fa
    for T, D in ((2048, 64), (2048, 256), (8192, 128)):
        blk, major, sub = fa._fa_bwd_tiles(T, T, D, jnp.bfloat16)
        assert T % blk == 0 and T % major == 0 and major % sub == 0
        assert blk % 128 == 0 and sub % 128 == 0
        assert fa._fa_bwd_vmem_bytes(blk, major, sub, D, 2) <= \
            fa.BWD_VMEM_BUDGET < fa.BWD_VMEM_LIMIT
        small = fa._fa_bwd_tiles(T, T, D, jnp.bfloat16, budget=2 ** 20)
        assert small is not None and small[0] * small[2] < blk * sub
    assert fa._fa_bwd_tiles(2048, 2048, 64, jnp.bfloat16, budget=1024) \
        is None


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A described, not attached, v5e chip: the TPU's compiler is installed
    here, so the kernels compile for it on a CPU-only box (nothing runs)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("H, Hkv, T, D, window", [
    (32, 32, 2048, 64, None), (20, 20, 2048, 256, None),
    (28, 4, 8192, 128, None), (28, 4, 8192, 128, 4096)],
    ids=["opt1.3b", "glm4.7flash", "smallthinker21b_global",
         "smallthinker21b_window"])
def test_kernels_compile_for_the_v5e_at_the_cells_shapes(
        H, Hkv, T, D, window, one_v5e_chip, monkeypatch):
    """Mosaic takes all three kernels at the token cells' exact shapes (the
    interpreter proves nothing about tiling, layouts or VMEM): the forward
    with its statistics and both backward kernels, as three custom calls
    whose backward names no forward reader matches, and the statistics
    compact in HBM."""
    from mxnet_tpu.ops import flash_attention as fa
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()  # a `jit` of the interpreter's lowering would answer

    def step(q, k, v, g):
        o, pull = jax.vjp(lambda a, b, c: fa._flash_attention(
            a, b, c, D ** -0.5, True, None, None, window), q, k, v)
        return o, pull(g)

    args = [jax.ShapeDtypeStruct((2, h, T, D), jnp.bfloat16,
                                 sharding=one_v5e_chip)
            for h in (H, Hkv, Hkv, H)]
    try:
        text = jax.jit(step).lower(*args).compile().as_text()
    finally:
        jax.clear_caches()
    calls = [line.split(" = ")[0].strip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3, calls
    assert sorted(c.lstrip("%").split(".")[0] for c in calls
                  if "flash_bwd" in c) == ["flash_bwd_dkv", "flash_bwd_dq"]
    assert not any("attention" in c for c in calls if "flash_bwd" in c)
    assert f"f32[{2 * H},1,{T}]" "{2,1,0:T(1,128)" in text    # B*H*T floats


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
