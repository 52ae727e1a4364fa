"""The current decoder blocks (ops/decoder.py, gluon/model_zoo/decoder.py)
against the plain references of chipbench/configs/glm-4.7-flash and
chipbench/configs/smallthinker-21b-a3b, at the rehearsals' sizes, float32,
seeded: latent and grouped-query attention, the expert op under both
routers and both gates and its shares, the load counters, the flash kernel
at head sizes 256 and 128 (seven query heads a key/value head, a window),
its tile counters, the cells' rehearsals under a planted fault (the sound
ones are in tests/test_benchmark_cells.py), and the cells' per-layer
readers."""
import gc
import os
import types
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.model_zoo import decoder
from mxnet_tpu.observability import metrics
from mxnet_tpu.ops.flash_attention import _dense_reference

from chipbench import cell as cellmod
from chipbench import run

from test_benchmark_cells import float32_traffic  # noqa: F401  (fixture)
from test_flash_attention import _kernel_eqns as _eqns


def _config(name):
    cdir = os.path.join(cellmod.HERE, "configs", name)
    ref = cellmod.load_module(os.path.join(cdir, "reference.py"),
                              "test_decoder_reference_" + name[:3])
    return ref, dict(cellmod.load_json(os.path.join(cdir, "config.json")),
                     **cellmod.load_json(os.path.join(cdir, "rehearsal.json")))


REF, CFG = _config("glm-4.7-flash")
CELL = "glm4.7flash_train_gluon"
D, F, E, K = (CFG["hidden_size"], CFG["moe_intermediate_size"],
              CFG["expert_parallel"]["router_outputs"],
              CFG["num_experts_per_tok"])
SCALE = CFG["routed_scaling_factor"]
HI = jax.lax.Precision.HIGHEST

ST_REF, ST_CFG = _config("smallthinker-21b-a3b")
ST_CELL = "smallthinker21b_train_gluon"

# what the expert layer's tests need of a configuration: sizes, the op's
# arguments, and its reference's routed part for inputs `a` (_expert_inputs)
def _glm_kit():
    return types.SimpleNamespace(
        d=D, f=F, e=E, k=K, shared=True,
        op=dict(scale=SCALE, norm_topk=True),
        routed=lambda a, first=0: REF.moe_routed(
            *(jnp.asarray(a[n]) for n in ("router", "gate", "up", "down",
                                          "h")),
            K, SCALE, True, first=first, bias=jnp.asarray(a["bias"])),
        routing=lambda h, router: REF.routing(h, router, jnp.zeros(E), K,
                                              SCALE, True))


def _st_kit(e=ST_CFG["expert_parallel"]["router_outputs"],
            k=ST_CFG["moe_num_active_primary_experts"]):
    return types.SimpleNamespace(
        d=ST_CFG["hidden_size"], f=ST_CFG["moe_ffn_hidden_size"], e=e, k=k,
        shared=False,
        op=dict(norm_topk=True, router="softmax_topk", activation="relu"),
        routed=lambda a, first=0: ST_REF.moe_routed(
            *(jnp.asarray(a[n]) for n in ("router", "gate", "up", "down",
                                          "h", "h")), k, True, first=first),
        routing=lambda h, router: ST_REF.routing(h, router, k))


KITS = {"sigmoid_silu": _glm_kit(), "softmax_topk_relu": _st_kit()}


@pytest.fixture(params=list(KITS))
def kit(request):
    return KITS[request.param]


def _rand(rs, *shape, scale=1.0):
    return (rs.normal(0, scale, shape)).astype("float32")


def _close(a, b, rtol=2e-4, atol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol)


# -- small ops ---------------------------------------------------------------
def test_rms_norm_matches_reference():
    rs = np.random.RandomState(0)
    x, g = _rand(rs, 2, 5, 16), _rand(rs, 16)
    _close(nd.rms_norm(nd.array(x), nd.array(g), eps=1e-5).asnumpy(),
           REF.rms_norm(jnp.asarray(x), jnp.asarray(g), 1e-5))


@pytest.mark.parametrize("shape", [(2, 6, 8), (2, 6, 3, 8)])
def test_rotary_matches_reference_and_keeps_norms(shape):
    x = _rand(np.random.RandomState(1), *shape)
    out = nd.rotary_embedding(nd.array(x), base=1e6).asnumpy()
    _close(out, REF.rope(jnp.asarray(x), 1e6))
    _close(np.linalg.norm(out, axis=-1), np.linalg.norm(x, axis=-1))
    _close(out[:, 0], x[:, 0])  # position 0 is not turned


def test_activation_silu():
    x = _rand(np.random.RandomState(2), 4, 7)
    _close(nd.Activation(nd.array(x), act_type="silu").asnumpy(),
           x / (1 + np.exp(-x)))


# -- latent attention --------------------------------------------------------
def _attention_block(impl):
    blk = decoder.LatentAttention(
        D, CFG["num_attention_heads"], CFG["q_lora_rank"],
        CFG["kv_lora_rank"], CFG["qk_nope_head_dim"],
        CFG["qk_rope_head_dim"], CFG["v_head_dim"], attn_type=impl,
        epsilon=CFG["rms_norm_eps"], rope_base=float(CFG["rope_theta"]))
    blk.initialize(mx.init.Normal(0.3))
    names = ["qa.weight", "qnorm.gamma", "qb.weight", "kva.weight",
             "kvnorm.gamma", "kvb.weight", "proj.weight"]
    return blk, dict(zip(names, blk.collect_params().values()))


@pytest.mark.parametrize("impl,hybrid", [("dense", False), ("dense", True),
                                         ("flash", True)])
def test_latent_attention_block_forward_and_gradients(impl, hybrid):
    rs = np.random.RandomState(3)
    blk, params = _attention_block(impl)
    for p in params.values():  # gammas away from 1
        p.set_data(p.data() + nd.array(_rand(rs, *p.shape, scale=0.1)))
    if hybrid:
        blk.hybridize()
    x = nd.array(_rand(rs, 2, 32, D))
    r = _rand(rs, 2, 32, D)
    x.attach_grad()
    with autograd.record():
        y = blk(x)
        loss = (y * nd.array(r)).sum()
    loss.backward()
    ref_p = {k: jnp.asarray(p.data().asnumpy()) for k, p in params.items()}

    def f(p, xx):
        return jnp.sum(REF.latent_attention(p, xx, CFG) * r)

    with jax.default_matmul_precision("highest"):
        want = REF.latent_attention(ref_p, jnp.asarray(x.asnumpy()), CFG)
        gp, gx = jax.grad(f, argnums=(0, 1))(ref_p, jnp.asarray(x.asnumpy()))
    _close(y.asnumpy(), want, rtol=1e-3, atol=1e-4)
    _close(x.grad.asnumpy(), gx, rtol=2e-3, atol=2e-4)
    for k, p in params.items():
        _close(p.grad().asnumpy(), gp[k], rtol=2e-3, atol=2e-4)


def test_latent_attention_flash_needs_one_head_size():
    q, kv, kr = (nd.zeros((1, 8, 2 * 24)), nd.zeros((1, 8, 2 * 32)),
                 nd.zeros((1, 8, 8)))
    with pytest.raises(ValueError, match="one head"):
        nd.latent_attention(q, kv, kr, num_heads=2, nope_dim=16, rope_dim=8,
                            v_dim=16, impl="flash")
    out = nd.latent_attention(q, kv, kr, num_heads=2, nope_dim=16,
                              rope_dim=8, v_dim=16, impl="dense")
    assert out.shape == (1, 8, 32)


# -- grouped-query attention -------------------------------------------------
@pytest.mark.parametrize("rope, window", [(False, None), (True, 48)],
                         ids=["global_no_positions", "window_rotary"])
@pytest.mark.parametrize("impl,hybrid", [("dense", False), ("dense", True),
                                         ("flash", False), ("flash", True)])
def test_grouped_query_block_forward_and_gradients(impl, hybrid, rope,
                                                   window):
    """Both kinds of layer of the period, the window shorter than the
    sequence, against the reference's attention."""
    rs = np.random.RandomState(30)
    cfg = dict(ST_CFG, sliding_window_size=window)
    d = cfg["hidden_size"]
    blk = decoder.GroupedQueryAttention(
        d, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], rope=rope, window=window, attn_type=impl,
        rope_base=float(cfg["rope_theta"]))
    blk.initialize(mx.init.Normal(0.3))
    params = dict(zip(["q.weight", "k.weight", "v.weight", "proj.weight"],
                      blk.collect_params().values()))
    if hybrid:
        blk.hybridize()
    x = nd.array(_rand(rs, 2, 128, d))
    r = _rand(rs, 2, 128, d)
    x.attach_grad()
    with autograd.record():
        y = blk(x)
        loss = (y * nd.array(r)).sum()
    loss.backward()
    ref_p = {k: jnp.asarray(p.data().asnumpy()) for k, p in params.items()}

    def f(p, xx):
        return ST_REF.attention(p, xx, cfg, rope, window)

    with jax.default_matmul_precision("highest"):
        want = f(ref_p, jnp.asarray(x.asnumpy()))
        gp, gx = jax.grad(lambda p, xx: jnp.sum(f(p, xx) * r),
                          argnums=(0, 1))(ref_p, jnp.asarray(x.asnumpy()))
    _close(y.asnumpy(), want, rtol=1e-3, atol=1e-4)
    _close(x.grad.asnumpy(), gx, rtol=2e-3, atol=2e-4)
    for k, p in params.items():
        _close(p.grad().asnumpy(), gp[k], rtol=2e-3, atol=2e-4)


def test_grouped_query_attention_checks_its_widths():
    q, kv = nd.zeros((1, 8, 4 * 16)), nd.zeros((1, 8, 2 * 16))
    out = nd.grouped_query_attention(q, kv, kv, num_heads=4, num_kv_heads=2)
    assert out.shape == (1, 8, 64)
    with pytest.raises(Exception, match="key/value"):
        nd.grouped_query_attention(q, q, kv, num_heads=4, num_kv_heads=2)
    with pytest.raises(Exception, match="key/value"):
        nd.grouped_query_attention(q, kv, kv, num_heads=4, num_kv_heads=3)
    with pytest.raises(Exception, match="impl"):
        nd.grouped_query_attention(q, kv, kv, num_heads=4, num_kv_heads=2,
                                   impl="ring")


def test_window_and_positions_change_the_result():
    """A window of the whole sequence is no window; a shorter one is not;
    a layer without rotary positions is not the layer with them."""
    rs = np.random.RandomState(31)
    q, k, v = (nd.array(_rand(rs, 1, 64, w)) for w in (64, 32, 32))
    kw = dict(num_heads=4, num_kv_heads=2, impl="flash")
    whole = nd.grouped_query_attention(q, k, v, **kw).asnumpy()
    _close(nd.grouped_query_attention(q, k, v, window=64, **kw).asnumpy(),
           whole)
    short = nd.grouped_query_attention(q, k, v, window=16, **kw).asnumpy()
    _close(short[:, :16], whole[:, :16])  # the first 16 see the same keys
    assert np.abs(short[:, 16:] - whole[:, 16:]).max() > 1e-3
    bare = nd.grouped_query_attention(q, k, v, rope=False, **kw).asnumpy()
    _close(bare[:, 0], whole[:, 0])  # position 0 is not turned
    assert np.abs(bare[:, 1:] - whole[:, 1:]).max() > 1e-3


@pytest.mark.parametrize("window", [None, 200], ids=["global", "window"])
def test_flash_kernel_at_head_size_128_seven_query_heads_a_kv_head(window):
    """The new cell's head layout (28 / 4 is 7 / 1) through the kernel's
    interpreter in several tiles, forward and backward, against the dense
    reference."""
    from mxnet_tpu.ops import flash_attention as fa
    rs = np.random.RandomState(32)
    q, r = (jnp.asarray(_rand(rs, 1, 7, 512, 128)) for _ in range(2))
    k, v = (jnp.asarray(_rand(rs, 1, 1, 512, 128)) for _ in range(2))
    scale = 128 ** -0.5

    def loss(attn):
        return lambda a, b, c: jnp.sum(attn(a, b, c) * r)

    flash = lambda a, b, c: fa._flash_attention(a, b, c, scale, True, 128,
                                                128, window)
    dense = lambda a, b, c: _dense_reference(a, b, c, scale, True, window)
    with jax.default_matmul_precision("highest"):
        _close(flash(q, k, v), dense(q, k, v), rtol=1e-4, atol=1e-5)
        got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        _close(g, w, rtol=1e-3, atol=1e-4)
    with pytest.raises(ValueError, match="causal"):
        fa._flash_attention(q, k, v, scale, False, 128, 128, 64)
    with pytest.raises(ValueError, match="heads"):
        fa._flash_attention(q, jnp.concatenate([k, k, k], 1),
                            jnp.concatenate([v, v, v], 1), scale, True)


def _hand_count(T, blk_q, sub, window):
    """(visited, needed) by looking at every (query, key) pair: a tile is
    needed when any pair in it is inside the mask; the kernel's loops must
    visit exactly those."""
    t, s = np.arange(T)[:, None], np.arange(T)[None, :]
    mask = (s <= t) & ((s > t - window) if window else True)
    tiles = mask.reshape(T // blk_q, blk_q, T // sub, sub).any(axis=(1, 3))
    return int(tiles.sum())


@pytest.mark.parametrize("T, blk_q, sub, window", [
    (1024, 128, 128, None), (1024, 128, 128, 300), (1024, 256, 128, 256),
    (1024, 128, 256, 129), (8192, 512, 512, 4096), (8192, 512, 512, None)])
def test_tile_counters_against_a_hand_count(T, blk_q, sub, window):
    from mxnet_tpu.ops import flash_attention as fa
    hand = _hand_count(T, blk_q, sub, window)
    assert fa._fa_needed(T, T, blk_q, sub, True, window) == hand
    assert fa._fa_blocks(T, T, blk_q, sub, True, window)[1] == hand
    if window and window < T:  # a band holds fewer tiles than the triangle
        assert hand < fa._fa_blocks(T, T, blk_q, sub, True)[1]


def test_tile_counter_adds_up_over_the_layers_of_the_new_cell():
    """Tracing the new cell's four calls (nothing runs): the global layer
    visits 136 tiles a head of 256, a window layer 108, and the kernel
    visits no tile the masks empty."""
    from mxnet_tpu.ops import flash_attention as fa
    metrics.FLASH_FWD_TILES.reset()
    q = jax.ShapeDtypeStruct((2, 28, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16)
    assert fa._fa_tiles(8192, 8192, 128, jnp.bfloat16) == (512, 8192, 512)
    for window in (None, 4096, 4096, 4096):
        jax.eval_shape(lambda a, b: fa._flash_attention(
            a, b, b, 128 ** -0.5, True, None, None, window), q, kv)
        if window is None:
            assert metrics.FLASH_FWD_TILES.get(kind="visited") == 56 * 136
    assert metrics.FLASH_FWD_TILES.get(kind="visited") == \
        56 * (136 + 3 * 108)
    assert metrics.FLASH_FWD_TILES.get(kind="needed") == \
        metrics.FLASH_FWD_TILES.get(kind="visited")
    assert metrics.FLASH_FWD_BLOCKS.get(kind="computed") == 56 * 108
    rd = _reader("attn_tiles_visited_over_needed")
    assert rd.read({}) == pytest.approx(1.0)
    metrics.FLASH_FWD_TILES.reset()
    assert rd.read({}) is None


def _loops(jaxpr):
    """Loops of plain XLA: not those inside a kernel's body."""
    return [e for e in jaxpr.eqns if e.primitive.name in ("scan", "while")]


CELL_CALLS = {  # (B, H, Hkv, T, D), the layers' windows
    "opt1.3b": ((2, 32, 32, 2048, 64), (None,)),
    "glm4.7flash": ((2, 20, 20, 2048, 256), (None,)),
    "smallthinker21b": ((2, 28, 4, 8192, 128), (None, 4096)),
}


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_cells_backward_is_two_kernels_on_the_needed_tiles(cell):
    """What the three token cells send (traced, nothing runs): the tiles
    PR 28 chose for the forward, a backward pass of two `pallas_call`s and
    no loop of plain XLA, each under a scope of its own that no forward
    reader matches, and kernels that visit exactly the tiles their masks
    leave something of."""
    from mxnet_tpu.ops import flash_attention as fa
    (B, H, Hkv, T, D), windows = CELL_CALLS[cell]
    assert fa._fa_tiles(T, T, D, jnp.bfloat16) == (512, T, 512)
    q = jax.ShapeDtypeStruct((B, H, T, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, Hkv, T, D), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32)
    for window in windows:
        metrics.FLASH_BWD_TILES.reset()
        metrics.FLASH_BWD.reset()
        jaxpr = jax.make_jaxpr(lambda q_, k, v, o, l, g: fa._fa_bwd(
            D ** -0.5, True, None, None, window, (q_, k, v, o, l), g))(
                q, kv, kv, q, lse, q).jaxpr
        assert not _loops(jaxpr)
        # each call a `jit` of its own (one lowering for all the layers
        # that make it), the kernel directly inside its scope
        assert [e.params["name"] for e in jaxpr.eqns
                if e.primitive.name in ("pjit", "jit")] == \
            ["_dkv_call", "_dq_call"]
        calls = [e for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]
        assert [str(e.source_info.name_stack) for e in calls] == \
            ["flash_bwd_dkv", "flash_bwd_dq"]
        assert [[v.aval.shape for v in e.outvars] for e in calls] == \
            [[(B * Hkv, T, D)] * 2, [(B * H, T, D)]]
        assert metrics.FLASH_BWD.get(path="kernel") == 1
        assert metrics.FLASH_BWD.get(path="reference") == 0
        visited = metrics.FLASH_BWD_TILES.get(kind="visited")
        assert visited == metrics.FLASH_BWD_TILES.get(kind="needed") > 0
        # both kernels' tiles by hand: a triangle, or a band of it
        dq, dkv = (fa._fa_bwd_tiles(T, T, D, jnp.bfloat16),) * 2
        assert visited == B * H * (
            _hand_count(T, dq[0], dq[2], window) +
            _hand_count(T, dkv[2], dkv[0], window))
    metrics.FLASH_BWD_TILES.reset()
    metrics.FLASH_BWD.reset()


@pytest.mark.parametrize("what", ["forward", "backward", "statistics"])
def test_flash_kernel_at_head_size_256(what):
    """The kernels' interpreter at the new model's head size against the
    dense reference: the output, the three gradients, and the rows'
    log-sum-exp the forward keeps for the backward kernels."""
    from mxnet_tpu.ops import flash_attention as fa
    rs = np.random.RandomState(4)
    q, k, v = (jnp.asarray(_rand(rs, 1, 2, 256, 256)) for _ in range(3))
    scale = 256 ** -0.5
    if what == "forward":
        _close(fa._flash_attention(q, k, v, scale, True, 128, 128),
               _dense_reference(q, k, v, scale, True), rtol=1e-4, atol=1e-5)
        return
    if what == "statistics":
        _, lse = fa._fa_call(q, k, v, scale, True, 128, 128, None, True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -jnp.inf)
        _close(lse.reshape(1, 2, 256), jax.nn.logsumexp(s, axis=-1),
               rtol=1e-5, atol=1e-5)
        return
    r = jnp.asarray(_rand(rs, 1, 2, 256, 256))
    got = jax.grad(lambda a, b, c: jnp.sum(
        fa._flash_attention(a, b, c, scale, True, 128, 128) * r),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda a, b, c: jnp.sum(
        _dense_reference(a, b, c, scale, True) * r), argnums=(0, 1, 2))(
            q, k, v)
    for g, w in zip(got, want):
        _close(g, w, rtol=1e-3, atol=1e-4)


# -- the expert op -----------------------------------------------------------
def _expert_inputs(seed, tokens=48, held=4, bias=None, kit=KITS["sigmoid_silu"]):
    rs = np.random.RandomState(seed)
    d, f, e = kit.d, kit.f, kit.e
    return dict(
        h=_rand(rs, tokens, d), router=_rand(rs, e, d, scale=0.5),
        bias=np.zeros(e, "float32") if bias is None else bias,
        gate=_rand(rs, held, d, f, scale=0.2),
        up=_rand(rs, held, d, f, scale=0.2),
        down=_rand(rs, held, f, d, scale=0.2),
        load=np.zeros(held + 2, "float32"))


def _moe(a, first=0, held=4, record=False, kit=KITS["sigmoid_silu"]):
    """(result, load counter after the call[, arrays with gradients])."""
    arrs = {k: nd.array(v) for k, v in a.items()}
    trainable = ("h", "router", "gate", "up", "down")
    if record:
        for k in trainable:
            arrs[k].attach_grad()
    args = [arrs[k] for k in ("h", "router", "bias", "gate", "up", "down",
                              "load")]
    kw = dict(num_experts=kit.e, top_k=kit.k, first=first, held=held,
              **kit.op)
    if not record:
        return nd.moe_ffn(*args, **kw).asnumpy(), arrs["load"].asnumpy()
    return (lambda: nd.moe_ffn(*args, **kw)), arrs


def _ref_routed(a, first=0, kit=KITS["sigmoid_silu"]):
    with jax.default_matmul_precision("highest"):
        return kit.routed(a, first)


def test_moe_forward_and_gradients_match_reference(kit):
    a = _expert_inputs(5, kit=kit)
    r = _rand(np.random.RandomState(6), 48, kit.d)
    call, arrs = _moe(a, record=True, kit=kit)
    with autograd.record():
        y = call()
        loss = (y * nd.array(r)).sum()
    loss.backward()
    _close(y.asnumpy(), _ref_routed(a, kit=kit), rtol=1e-3, atol=1e-4)

    keys = ("h", "router", "gate", "up", "down")

    def f(*vals):
        return jnp.sum(kit.routed(dict(a, **dict(zip(keys, vals)))) * r)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(f, argnums=tuple(range(5)))(
            *(jnp.asarray(a[k]) for k in keys))
    for k, w in zip(keys, want):
        _close(arrs[k].grad.asnumpy(), w, rtol=2e-3, atol=2e-4)


def test_selection_bias_changes_the_choice_not_the_weights():
    a = _expert_inputs(7)
    h, router = jnp.asarray(a["h"]), jnp.asarray(a["router"])
    bias = np.zeros(E, "float32")
    bias[5] = 10.0  # expert 5 is now everybody's first choice
    from mxnet_tpu.ops.decoder import route
    idx0, w0 = route(h, router, jnp.zeros(E), K, SCALE, True)
    idx1, w1 = route(h, router, jnp.asarray(bias), K, SCALE, True)
    assert (np.asarray(idx1)[:, 0] == 5).all()
    assert (np.asarray(idx0) != np.asarray(idx1)).any()
    # the weights come from the scores without the bias: a token that chose
    # the same experts either way weighs them the same, and every token's
    # weights sum to the scale
    same = (np.sort(idx0, axis=1) == np.sort(idx1, axis=1)).all(axis=1)
    assert same.any() and not same.all()
    _close(np.sort(np.asarray(w0)[same], axis=1),
           np.sort(np.asarray(w1)[same], axis=1))
    _close(np.asarray(w1).sum(axis=1), SCALE, rtol=1e-5)
    s = jax.nn.sigmoid(jnp.einsum("td,ed->te", h, router, precision=HI))
    _close(np.asarray(w1)[:, 0],
           np.asarray(s)[:, 5] / np.take_along_axis(
               np.asarray(s), np.asarray(idx1), 1).sum(1) * SCALE, rtol=1e-4)
    # and the op follows the reference under the bias
    a["bias"] = bias
    _close(_moe(a, first=4)[0], _ref_routed(a, first=4), rtol=1e-3,
           atol=1e-4)


def test_every_token_on_one_held_expert_loses_none(kit):
    """No capacity: all tokens choose experts 1 and 2, both held."""
    bias = np.zeros(kit.e, "float32")
    bias[[1, 2]] = 50.0
    a = _expert_inputs(8, tokens=64, bias=bias, kit=kit)
    y, load = _moe(a, kit=kit)
    # and the buffer held every row: this layer keeps its products
    np.testing.assert_array_equal(load, [0, 64, 64, 0, 0, 64 * kit.k])
    if kit.shared:  # the other reference has no selection bias to give
        _close(y, _ref_routed(a, kit=kit), rtol=1e-3, atol=1e-4)
    assert (np.abs(y).max(axis=1) > 0).all()  # no token came back empty


@pytest.mark.parametrize("kit, held", [
    (KITS["sigmoid_silu"], 4),
    # the real counts: 8 shares of 8 of 64 experts, 6 a token
    (_st_kit(e=64, k=6), 8)], ids=["sigmoid_silu_2x4of8",
                                   "softmax_topk_relu_8x8of64"])
def test_shares_add_up_to_the_uncut_layer(kit, held):
    """The parts of all shares of the experts, with what every chip
    computes alike (the shared expert, where there is one) counted once,
    are the uncut layer."""
    rs = np.random.RandomState(9)
    d, f = kit.d, kit.f
    full = _expert_inputs(10, held=kit.e, kit=kit)
    uncut = _ref_routed(full, kit=kit)
    total = np.zeros_like(full["h"])
    loads = []
    for first in range(0, kit.e, held):
        share = dict(full, load=np.zeros(held + 2, "float32"),
                     **{k: full[k][first:first + held]
                        for k in ("gate", "up", "down")})
        y, load = _moe(share, first=first, held=held, kit=kit)
        total += y
        loads.append(load)
        _close(y, _ref_routed(share, first=first, kit=kit), rtol=1e-3,
               atol=1e-4)
    if kit.shared:
        shared = {"gate.weight": _rand(rs, f, d, scale=0.2),
                  "up.weight": _rand(rs, f, d, scale=0.2),
                  "down.weight": _rand(rs, d, f, scale=0.2)}
        with jax.default_matmul_precision("highest"):
            uncut = uncut + REF.gated_ffn(
                {k: jnp.asarray(v) for k, v in shared.items()},
                jnp.asarray(full["h"]))
        ffn = decoder.GatedFeedForward(d, f)
        ffn.initialize()
        for p, v in zip(ffn.collect_params().values(), shared.values()):
            p.set_data(nd.array(v))
        total += ffn(nd.array(full["h"])).asnumpy()
    _close(total, uncut, rtol=1e-3, atol=2e-4)
    # what one share counts absent the others hold
    held_all = sum(load[:held].sum() for load in loads)
    for load in loads:
        assert load[held] == held_all - load[:held].sum()


def test_load_counter_splits_held_and_absent_as_the_routing_does(kit):
    a = _expert_inputs(11, kit=kit)
    _y, load = _moe(a, first=2, kit=kit)
    idx, _w = kit.routing(jnp.asarray(a["h"]), jnp.asarray(a["router"]))
    idx = np.asarray(idx)
    assert load[:5].sum() == 48 * kit.k
    want = [(idx == e).sum() for e in range(2, 6)]
    np.testing.assert_array_equal(load[:4], want)
    assert load[4] == 48 * kit.k - sum(want)
    assert load[5] == 48 * kit.k  # the buffer's rows: the whole T x top_k


# -- the second router, its own input, the other gate -------------------------
def test_softmax_over_the_chosen_is_softmax_over_all_renormalised():
    from mxnet_tpu.ops.decoder import route
    rs = np.random.RandomState(20)
    h, router = jnp.asarray(_rand(rs, 40, 16)), jnp.asarray(_rand(rs, 64, 16))
    zero = jnp.zeros(64)
    idx, w = route(h, router, zero, 6, 1.0, True, "softmax_topk")
    logits = jnp.einsum("td,ed->te", h, router, precision=HI)
    every = np.asarray(jax.nn.softmax(logits, axis=-1))
    chosen = np.take_along_axis(every, np.asarray(idx), 1)
    _close(w, chosen / chosen.sum(1, keepdims=True), rtol=1e-5, atol=1e-7)
    _close(np.asarray(w).sum(1), 1.0, rtol=1e-6)
    # the 6 largest logits, largest first
    np.testing.assert_array_equal(np.asarray(idx),
                                  np.argsort(-np.asarray(logits), 1)[:, :6])
    # without norm_topk: the chosen experts' part of the softmax over all
    idx2, w2 = route(h, router, zero, 6, 1.0, False, "softmax_topk")
    np.testing.assert_array_equal(np.asarray(idx2), np.asarray(idx))
    _close(w2, chosen, rtol=1e-5, atol=1e-7)
    with pytest.raises(ValueError, match="router"):
        route(h, router, zero, 6, 1.0, True, "softmax")


def test_router_reads_its_own_input():
    """`moe_ffn_routed_by`: the experts read `data`, the router
    `router_data`, of a width of its own; forward and gradients against the
    reference, and `moe_ffn` is the case where both are one tensor."""
    kit = KITS["softmax_topk_relu"]
    a = _expert_inputs(21, kit=kit)
    rs = np.random.RandomState(22)
    by = _rand(rs, 48, 24)
    a["router"] = _rand(rs, kit.e, 24, scale=0.5)
    r = _rand(rs, 48, kit.d)
    kw = dict(num_experts=kit.e, top_k=kit.k, first=2, held=4, **kit.op)
    names = ("h", "by", "router", "bias", "gate", "up", "down", "load")
    arrs = {k: nd.array(v) for k, v in dict(a, by=by).items()}
    for k in ("h", "by", "router", "gate", "up", "down"):
        arrs[k].attach_grad()
    with autograd.record():
        y = nd.moe_ffn_routed_by(*(arrs[k] for k in names), **kw)
        loss = (y * nd.array(r)).sum()
    loss.backward()

    def f(h, by_, router, gate, up, down):
        return ST_REF.moe_routed(router, gate, up, down, h, by_, kit.k, True,
                                 first=2)

    keys = ("h", "by", "router", "gate", "up", "down")
    vals = [jnp.asarray(dict(a, by=by)[k]) for k in keys]
    with jax.default_matmul_precision("highest"):
        want = f(*vals)
        grads = jax.grad(lambda *v: jnp.sum(f(*v) * r),
                         argnums=tuple(range(6)))(*vals)
    _close(y.asnumpy(), want, rtol=1e-3, atol=1e-4)
    for k, g in zip(keys, grads):
        _close(arrs[k].grad.asnumpy(), g, rtol=2e-3, atol=2e-4)
    assert np.abs(arrs["by"].grad.asnumpy()).max() > 0
    # the other input chooses other experts
    same = nd.moe_ffn_routed_by(
        arrs["h"], arrs["h"], nd.array(a["router"][:, :1].repeat(kit.d, 1)),
        *(arrs[k] for k in names[3:]), **kw).asnumpy()
    assert np.abs(same - y.asnumpy()).max() > 1e-3
    with pytest.raises(ValueError, match="tokens"):
        nd.moe_ffn_routed_by(arrs["h"], nd.array(by[:40]),
                             *(arrs[k] for k in names[2:]), **kw)


def test_relu_gate_is_not_the_silu_gate():
    kit = KITS["softmax_topk_relu"]
    a = _expert_inputs(23, kit=kit)
    relu, _ = _moe(a, kit=kit)
    silu, _ = _moe(a, kit=types.SimpleNamespace(
        **{**vars(kit), "op": dict(kit.op, activation="silu")}))
    _close(relu, _ref_routed(a, kit=kit), rtol=1e-3, atol=1e-4)
    assert np.abs(relu - silu).max() > 1e-3
    # by hand, one expert for every token: down(relu(gate x) * up x)
    # all logits equal: top_k picks experts 0..k-1, each weighs 1 / k
    one = dict(a, router=np.zeros_like(a["router"]),
               load=np.zeros(3, "float32"),
               **{k: a[k][:1] for k in ("gate", "up", "down")})
    y, _ = _moe(one, held=1, kit=kit)
    x = a["h"]
    hand = (np.maximum(x @ a["gate"][0], 0) * (x @ a["up"][0])) \
        @ a["down"][0] / kit.k
    _close(y, hand, rtol=1e-3, atol=1e-4)
    with pytest.raises(Exception, match="activation"):
        nd.moe_ffn(*(nd.array(a[k]) for k in (
            "h", "router", "bias", "gate", "up", "down", "load")),
            num_experts=kit.e, top_k=kit.k, held=4, activation="gelu")


def test_large_expert_buffers_are_recomputed_not_kept(monkeypatch, capsys):
    """Past KEEP_BYTES_MAX of grouped-product outputs a layer, the backward
    pass starts from the op's inputs: the same numbers, and under the
    recorded CachedOp call's rule no grouped product's output is kept."""
    from mxnet_tpu.gluon.block import _RESIDUAL_POLICY
    from mxnet_tpu.ops import decoder as ops
    kit = KITS["softmax_topk_relu"]
    a = _expert_inputs(24, kit=kit)
    p = dict(num_experts=kit.e, top_k=kit.k, first=0, held=4, scale=1.0,
             **kit.op)

    def make_loss():  # a new function each time: jax caches a trace by it
        def loss(h, router, gate, up, down):
            return jnp.sum(ops._moe_ffn(
                p, h, router, jnp.asarray(a["bias"]), gate, up, down,
                jnp.asarray(a["load"]))[0] ** 2)
        return loss

    vals = [jnp.asarray(a[k]) for k in ("h", "router", "gate", "up", "down")]

    def kept_grouped(fn):
        """The residuals as long as the experts' buffer (tokens x top_k
        rows), by jax's own list: under the rule only a grouped product's
        output is."""
        jax.ad_checkpoint.print_saved_residuals(
            jax.checkpoint(fn, policy=_RESIDUAL_POLICY), *vals)
        return [line for line in capsys.readouterr().out.splitlines()
                if line.startswith(f"f32[{48 * kit.k},")]

    want = jax.grad(make_loss(), argnums=tuple(range(5)))(*vals)
    assert len(kept_grouped(make_loss())) == 3
    monkeypatch.setattr(ops, "KEEP_BYTES_MAX", 0)
    got = jax.grad(make_loss(), argnums=tuple(range(5)))(*vals)
    for g, w in zip(got, want):
        _close(g, w, rtol=1e-5, atol=1e-6)
    assert kept_grouped(make_loss()) == []
    # and through a recorded CachedOp call, router input and all (the rows'
    # integer keys are an argument of the recomputed part, not a closure:
    # a closed-over tracer escaped the recording program on the chip)
    blk = decoder.MoEFeedForward(kit.d, kit.f, kit.e, kit.k, held_experts=4,
                                 shared_experts=0, router="softmax_topk",
                                 activation="relu")
    blk.initialize(mx.init.Normal(0.3))
    x = nd.array(a["h"].reshape(2, 24, kit.d))
    grads = []
    for hybrid in (False, True):
        if hybrid:
            blk.hybridize()
        x.attach_grad()
        with jax.checking_leaks(), autograd.record():
            y = blk(x, x * 0.5)
        y.backward()
        grads.append(x.grad.asnumpy().copy())
    assert np.abs(grads[0]).max() > 0
    _close(grads[1], grads[0], rtol=1e-4, atol=1e-5)
    # the accepted expert cell's layer stays under the bound, this one's
    # passes it (rows x (2 F + D) x 2 bytes)
    monkeypatch.undo()
    assert 4096 * 4 * (2 * 1536 + 2048) * 2 <= ops.KEEP_BYTES_MAX
    assert 16384 * 6 * (2 * 768 + 2560) * 2 > ops.KEEP_BYTES_MAX


def test_block_counters_ride_the_auxiliary_path_and_stay_float32(monkeypatch):
    """A hybridized expert block cast to bfloat16 keeps counting in
    float32 (a bfloat16 counter stops at 256), through CachedOp's auxiliary
    states; the registry reads them when asked."""
    monkeypatch.setattr(metrics, "_moe_layers", weakref.WeakKeyDictionary())
    metrics.MOE_ASSIGNMENTS.reset()
    metrics.MOE_BUFFER_ROWS.reset()
    blk = decoder.MoEFeedForward(D, F, E, K, held_experts=4, first_expert=0,
                                 routed_scale=SCALE)
    blk.initialize(mx.init.Normal(0.3))
    blk.cast("bfloat16")
    blk.hybridize()
    assert blk.load.dtype == np.float32 and \
        blk.select_bias.dtype == np.float32
    assert blk.load.grad_req == "null" and blk.select_bias.grad_req == "null"
    x = nd.array(_rand(np.random.RandomState(12), 2, 100, D)) \
        .astype("bfloat16")
    for _ in range(3):
        with autograd.record():
            y = blk(x)
        y.backward()
    load = blk.load.data().asnumpy()
    assert load.dtype == np.float32
    assert load.shape == (6,)
    assert load[:5].sum() == 3 * 200 * K and load[:4].sum() > 256
    assert load[5] == 3 * 200 * K  # a layer that keeps its products
    metrics.refresh_moe()
    assert metrics.MOE_ASSIGNMENTS.get(where="held") == load[:4].sum()
    assert metrics.MOE_ASSIGNMENTS.get(where="absent") == load[4]
    assert metrics.MOE_BUFFER_ROWS.get(kind="live") == load[:4].sum()
    assert metrics.MOE_BUFFER_ROWS.get(kind="processed") == load[5]
    assert metrics.MOE_ROWS.get(kind="required") == 200 * K * 4 / E
    assert metrics.MOE_ROWS.get(kind="multiplied") <= 200 * K
    metrics.refresh_moe()  # a second read adds what came since: nothing
    assert metrics.MOE_ASSIGNMENTS.get(where="held") == load[:4].sum()
    assert metrics.MOE_LOAD_MAX_OVER_MEAN.get() >= 1.0


def test_decoder_lm_has_no_decode_path_yet():
    net = decoder.DecoderLM(32, 16, 2, 2, 8, 8, 4, 4, 8, 24, 12, 4, 2)
    with pytest.raises(NotImplementedError, match="R1"):
        net.generate(None, 4)
    with pytest.raises(NotImplementedError, match="R6"):
        net._kv_forward(None, None, None)


def test_decoder_lm_repeats_its_attention_pattern_over_the_layers():
    """Layer i gets pattern[i % len(pattern)]: a global layer without
    positions, then windowed rotary ones, twice over; and the router of an
    expert layer is handed the attention's input."""
    cell = cellmod.Cell(ST_CELL, 1, rehearsal=True)
    cfg = dict(cell.cfg, num_hidden_layers=8)
    net = cell.model.build(cfg)
    kinds = [(b.attn._attn["rope"], b.attn._attn["window"])
             for b in net.blocks]
    assert kinds == [(False, -1)] + [(True, 64)] * 3 + [(False, -1)] \
        + [(True, 64)] * 3
    assert all(isinstance(b.attn, decoder.GroupedQueryAttention)
               and b._early_router and b.ffn.shared is None
               for b in net.blocks)
    assert cell.model.attention_pattern(cell.cfg) == \
        [(False, None)] + [(True, 64)] * 3
    # without a pattern every layer is latent attention, the router late
    glm = cellmod.Cell(CELL, 1, rehearsal=True)
    assert all(isinstance(b.attn, decoder.LatentAttention)
               and not b._early_router
               for b in glm.model.build(glm.cfg).blocks)
    with pytest.raises(ValueError, match="router_reads"):
        decoder.DecoderBlock(None, None, 8, router_reads="nowhere")
    # routing before attention is not routing after it
    net.initialize(mx.init.Normal(0.5))
    x = nd.array(np.random.RandomState(13).randint(0, 256, (2, 128))
                 .astype("float32"))
    early = net(x).asnumpy()
    for b in net.blocks:
        b._early_router = False
    assert np.abs(net(x).asnumpy() - early).max() > 1e-3


# -- the cells' rehearsals ---------------------------------------------------
@pytest.mark.parametrize("cell", [CELL, ST_CELL])
def test_cell_rehearsal_planted_fault_reads_not_correct(cell,
                                                        float32_traffic,
                                                        monkeypatch):
    """Half of every batch repeats the other half."""
    real = cellmod.Cell.batches

    def batches(self):
        return [(jnp.concatenate([x[:1], x[:1]]),
                 jnp.concatenate([y[:1], y[:1]])) for x, y in real(self)]

    monkeypatch.setattr(cellmod.Cell, "batches", batches)
    res = run.run_cell(cell, 7, 0.5, False, rehearsal=True)
    assert res["correct"] is False, res["compared"]


@pytest.mark.parametrize("cell, parameters, flops", [
    (CELL, 591_294_720, 1.9257e9), (ST_CELL, 370_547_200, 1.47771e9)])
def test_reference_leaves_are_the_programs_parameters(cell, parameters,
                                                      flops):
    small = cellmod.Cell(cell, 1, rehearsal=True)
    net = small.model.build(small.cfg)
    got = [tuple(p.shape) for p in small.model.trainable(net)]
    assert got == [tuple(s) for _n, s, _k in small.spec]
    full = cellmod.Cell(cell, 1)
    assert sum(int(np.prod(s)) for _n, s, _k in full.spec) == parameters
    assert full.flops.train_flops_per_unit(full.cfg, full.traffic) == \
        pytest.approx(flops, rel=1e-4)


def test_new_configuration_keeps_the_published_sizes():
    """Every number of the catalog's row under its own key, but the three
    that `reduced` names; the rehearsal's sequence is longer than its
    window; the required work is the issue's arithmetic."""
    cfg = cellmod.load_json(os.path.join(
        cellmod.HERE, "configs", "smallthinker-21b-a3b", "config.json"))
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1500000, "sliding_window_size": 4096,
        "vocab_size": 151936}
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"]) == sorted(cfg["published"])
    assert all(cfg["published"][k] == published[k] for k in differ)
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] == \
        [0, 1, 1, 1] * 13
    cell = cellmod.Cell(ST_CELL, 1, rehearsal=True)
    assert cell.traffic["seq"] > cell.cfg["sliding_window_size"]
    full = cellmod.Cell(ST_CELL, 1)
    fl = full.flops
    assert full.traffic["seq"] == 8192 and full.units_per_step() == 16384
    assert fl.keys_seen(8192) == 4096.5
    assert fl.keys_seen(8192, 4096) == 3072.25
    assert fl.layer_windows(full.cfg) == [None, 4096, 4096, 4096]
    assert fl.routed_experts_per_token(full.cfg) == 0.75
    assert fl.grouped_ffn_shape(full.cfg, full.traffic) == \
        (12288, 8, 2560, 768, 4)
    assert 6 * fl.matrix_params_per_token(full.cfg) == \
        pytest.approx(905.13e6, rel=1e-4)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_seeded_weights_let_the_token_decide_its_experts(seed):
    """What the routers of the new cell score has to be the token's own, on
    every seed: the part of the normalised input that all positions share
    (the mean over random tokens' values that position-free attention adds)
    makes most tokens choose alike, and then the rows this chip's experts
    are sent, and with them the step's length, are the seed's (the driver
    refused the cell for that spread, PR 31).  Its power stays at what the
    number of distinct tokens gives, in every layer; with embedding rows of
    0.02 it reads 0.03-0.1 by layers 2 and 3 at this size."""
    cfg = dict(ST_CFG, hidden_size=128, vocab_size=1024)
    p = ST_REF.init_weights(seed, cfg)
    tokens, _ = ST_REF.make_batches(seed, 1, 2, cfg, {"seq": 512})
    x = p["tok.weight"][tokens[0]]
    assert float(jnp.std(p["tok.weight"])) == pytest.approx(1.0, rel=0.02)
    for i in range(cfg["num_hidden_layers"]):
        lp = ST_REF._sub(p, f"l{i}.")
        h = ST_REF.rms_norm(x, lp["n1.gamma"], cfg["rms_norm_eps"])
        h = h.reshape(-1, h.shape[-1])
        shared = float(jnp.sum(jnp.mean(h, 0) ** 2)
                       / jnp.mean(jnp.sum(h ** 2, 1)))
        assert shared < 0.005, (i, shared)
        x = ST_REF._layer(
            lp, x, cfg, bool(cfg["rope_layout"][i]),
            cfg["sliding_window_size"] if cfg["sliding_window_layout"][i]
            else None, None)


# -- the new per-layer readers -----------------------------------------------
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    return cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", name + ".py"),
        "test_reader_" + name)


def _ctx(cell, ops, steps=2):
    return {"cell": cell, "peaks": PEAKS, "window": {"attempted": steps},
            "reduced": {"events": {"devices": {"d0": {"ops": ops}}}}}


def _call(name, args="", target="tpu_custom_call"):
    return (f"%{name} = bf16[8,8]{{1,0}} custom-call({args}), "
            f'custom_call_target="{target}"')


def test_mla_flash_reader_on_synthetic_events():
    rd = _reader("mla_flash_fwd_roofline")
    cell = cellmod.Cell(CELL, 1)
    ms = 1_000_000
    ops = [(_call("decoderlm0_l1_attn_latent_attention0.1"), 0, 20 * ms),
           (_call("jvp_decoderlm0_l1_attn_latent_attention0_.1"), 30 * ms,
            50 * ms),
           (_call("ragged-dot-none.3"), 60 * ms, 61 * ms),
           ("%fusion.7 = bf16[8]{0} fusion(%p)", 70 * ms, 90 * ms)]
    # B 2, H 20, T 2048, D 256, causal: 4 B H T^2 D (T + 1) / 2T operations
    flops = 4 * 2 * 20 * 2048 * 2048 * 256 * 2049 / 4096
    assert rd.read(_ctx(cell, ops)) == pytest.approx(
        100 * flops / 197e12 / 0.020, rel=1e-9)
    assert rd.read(_ctx(cell, ops[2:])) is None
    assert rd.read(_ctx(cellmod.Cell("opt1.3b_train_gluon", 1), ops)) is None
    assert rd.read({"cell": cell, "peaks": PEAKS, "window": {}}) is None


def test_expert_gmm_reader_on_synthetic_events():
    rd = _reader("expert_gmm_roofline")
    cell = cellmod.Cell(CELL, 1)
    ms = 1_000_000
    ops = [(_call("ragged-dot-none.3"), 0, 4 * ms),
           (_call("ragged-dot-metadata.1"), 5 * ms, 6 * ms),
           (_call("ragged-dot-none"), 10 * ms, 15 * ms),
           (_call("decoderlm0_l1_attn_latent_attention0.1"), 20 * ms,
            40 * ms),
           (_call("ragged-dot-none.9", target="other"), 50 * ms, 60 * ms)]
    flops, nbytes = rd.grouped_ffn_cost(2048, 8, 2048, 1536)
    assert flops == 18 * 2048 * 2048 * 1536
    assert nbytes == 2 * (9 * 8 * 2048 * 1536
                          + 9 * 2048 * (2048 + 1536))
    least = max(flops / 197e12, nbytes / 819e9)
    # 4 expert layers, 2 steps, 10 ms of grouped products in the trace
    assert rd.read(_ctx(cell, ops)) == pytest.approx(
        100 * least * 4 * 2 / 0.010, rel=1e-9)
    assert rd.read(_ctx(cell, ops[3:])) is None
    assert rd.read(_ctx(cellmod.Cell("opt1.3b_train_gluon", 1), ops)) is None


def test_expert_load_reader(monkeypatch):
    rd = _reader("expert_load_max_over_mean")
    # blocks of other tests may still be alive
    monkeypatch.setattr(metrics, "_moe_layers", weakref.WeakKeyDictionary())
    blk = decoder.MoEFeedForward(D, F, E, K, held_experts=4)
    blk.initialize()
    blk.load.set_data(nd.array(np.array([10., 30., 10., 10., 99., 480.],
                                        "f")))
    assert rd.read({}) == pytest.approx(2.0)
    del blk
    gc.collect()
    metrics.MOE_LOAD_MAX_OVER_MEAN.reset()
    assert rd.read({}) is None  # no expert layer alive
    monkeypatch.delattr(metrics, "refresh_moe")
    assert rd.read({}) is None  # a program without the counters


def test_gqa_flash_reader_on_synthetic_events():
    rd = _reader("gqa_flash_fwd_roofline")
    cost = cellmod.load_module(os.path.join(cellmod.HERE,
                                            "gqa_kernel_cost.py"), "gqa_cost")
    cell = cellmod.Cell(ST_CELL, 1)
    ms = 1_000_000
    scope = "decoderlm0_l%d_attn_grouped_query_attention0.1"
    # two steps of four layers: 8 events, 10 ms each
    ops = [(_call(scope % (i % 4)), i * 20 * ms, (i * 20 + 10) * ms)
           for i in range(8)]
    ops += [(_call("ragged-dot-none.3"), 200 * ms, 201 * ms),
            (_call("decoderlm0_l1_attn_latent_attention0.1"), 210 * ms,
             230 * ms),
            ("%while.7 = (s32[]) while(%p)", 240 * ms, 290 * ms)]
    # the pairs inside each mask: the triangle, and the band of 4,096
    assert cost.pairs_in_mask(8192) == 8192 * 8193 // 2
    assert cost.pairs_in_mask(8192, 4096) == 4096 * 4097 // 2 + 4096 * 4096
    assert cost.pairs_in_mask(8192, 8192) == cost.pairs_in_mask(8192)
    flops, nbytes = cost.attention_forward(2, 28, 4, 8192, 128, 4096)
    assert flops == 4 * 2 * 28 * 128 * cost.pairs_in_mask(8192, 4096)
    assert nbytes == 2 * 2 * (28 + 4) * 8192 * 128 * 2
    step = sum(cost.attention_forward(2, 28, 4, 8192, 128, w)[0]
               for w in (None, 4096, 4096, 4096)) / 197e12
    assert rd.read(_ctx(cell, ops)) == pytest.approx(
        100 * step * 2 / 0.080, rel=1e-9)
    assert rd.read(_ctx(cell, ops[8:])) is None
    assert rd.read(_ctx(cellmod.Cell(CELL, 1), ops)) is None
    assert rd.read({"cell": cell, "peaks": PEAKS, "window": {}}) is None
    # the accepted readers do not take the new cell's events for theirs
    assert _reader("mla_flash_fwd_roofline").read(_ctx(cell, ops)) is None
    assert _reader("expert_gmm_roofline").read(_ctx(cell, ops)) is None


@pytest.mark.parametrize("name, shapes", [
    ("opt1.3b_train_gluon", [(32, 32, 2048, 64, None)] * 6),
    ("glm4.7flash_train_gluon", [(20, 20, 2048, 256, None)] * 5),
    ("smallthinker21b_train_gluon",
     [(28, 4, 8192, 128, w) for w in (None, 4096, 4096, 4096)])])
def test_flash_bwd_reader_on_synthetic_events(name, shapes):
    """`flash_bwd_roofline` in each token cell: the required work of a
    step's attention backward (five products a pair inside each layer's
    mask) over the device time of ALL `flash_bwd*` kernel events divided
    by the window's steps, so two kernels a layer do not read as twice the
    roofline; no forward reader takes a backward event for its own, and a
    trace without such events (the parent's loops) reads None."""
    rd = _reader("flash_bwd_roofline")
    cost = cellmod.load_module(os.path.join(
        cellmod.HERE, "flash_bwd_cost.py"), "flash_bwd_cost")
    fwd = cellmod.load_module(os.path.join(
        cellmod.HERE, "gqa_kernel_cost.py"), "gqa_cost")
    cell = cellmod.Cell(name, 1)
    ms = 1_000_000
    # two steps: a dK/dV event of 3 ms and a dQ event of 2 ms a layer
    ops, t = [], 0
    for i in range(2 * len(shapes)):
        ops += [(_call("flash_bwd_dkv.%d" % i), t, t + 3 * ms),
                (_call("flash_bwd_dq.%d" % i), t + 4 * ms, t + 6 * ms)]
        t += 10 * ms
    others = [("%while.7 = (s32[]) while(%p)", t, t + 50 * ms),
              (_call("flash_bwd_dq.9", target="other"), t, t + ms),
              ("%flash_bwd_delta.1 = f32[8] fusion(%p)", t, t + ms)]
    flops, nbytes = cost.attention_backward(2, *shapes[-1])
    h, hkv, T, D, w = shapes[-1]
    assert flops == 2.5 * fwd.attention_forward(2, h, hkv, T, D, w)[0] \
        == 5 * 2 * 2 * h * D * fwd.pairs_in_mask(T, w)
    assert nbytes == 4 * 2 * (h + hkv) * T * D * 2
    step = sum(max(f / 197e12, b / 819e9) for f, b in
               (cost.attention_backward(2, *sh) for sh in shapes))
    assert step == sum(cost.attention_backward(2, *sh)[0]
                       for sh in shapes) / 197e12     # compute-bound
    got = rd.read(_ctx(cell, ops + others))
    assert got == pytest.approx(
        100 * step * 2 / (2 * len(shapes) * 0.005), rel=1e-9)
    assert rd.read(_ctx(cell, others)) is None
    assert rd.read({"cell": cell, "peaks": PEAKS, "window": {}}) is None
    assert rd.read(_ctx(cellmod.Cell("resnet50_train_module", 1), ops)) \
        is None
    for forward in ("flash_fwd_roofline", "mla_flash_fwd_roofline",
                    "gqa_flash_fwd_roofline"):
        assert _reader(forward).read(_ctx(cell, ops)) is None


def test_grouped_ffn_reader_on_synthetic_events():
    rd = _reader("grouped_ffn_roofline")
    gmm = _reader("expert_gmm_roofline")
    cell = cellmod.Cell(ST_CELL, 1)
    ms = 1_000_000
    ops = [(_call("ragged-dot-none.3"), 0, 4 * ms),
           (_call("ragged-dot-metadata.1"), 5 * ms, 6 * ms),
           (_call("decoderlm0_l1_attn_grouped_query_attention0.1"), 20 * ms,
            40 * ms),
           (_call("ragged-dot-none.9", target="other"), 50 * ms, 60 * ms)]
    flops, nbytes = gmm.grouped_ffn_cost(12288, 8, 2560, 768)
    least = max(flops / 197e12, nbytes / 819e9)
    # 4 expert layers, 2 steps, 5 ms of grouped products in the trace
    assert rd.read(_ctx(cell, ops)) == pytest.approx(
        100 * least * 4 * 2 / 0.005, rel=1e-9)
    assert rd.read(_ctx(cell, ops[2:])) is None
    # a configuration whose flops.py states no such shape
    assert rd.read(_ctx(cellmod.Cell(CELL, 1), ops)) is None


def test_collective_reader_on_synthetic_events():
    rd = _reader("collective_ms_per_step")
    ms = 1_000_000
    chip = [("%all-reduce.3 = f32[64]{0} all-reduce(%p)", 0, 2 * ms),
            ("%fusion.7 = bf16[8]{0} fusion(%p)", 2 * ms, 9 * ms),
            ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(%p)", 9 * ms,
             10 * ms),
            ("%all-gather.2 = f32[8]{0} all-gather(%p)", 12 * ms, 13 * ms)]
    other = [(n, s + ms, e + 2 * ms) for n, s, e in chip]
    ctx = {"window": {"attempted": 2}, "reduced": {"events": {"devices": {
        "d0": {"ops": chip}, "d1": {"ops": other}, "d2": {"ops": []}}}}}
    # 4 ms on one chip, 7 ms on the other, 2 steps
    assert rd.read(ctx) == pytest.approx((4 + 7) / 2 / 2)
    ctx["reduced"]["events"]["devices"] = {"d0": {"ops": chip[1:2]}}
    assert rd.read(ctx) is None
    assert rd.read({"window": {"attempted": 0}}) is None
