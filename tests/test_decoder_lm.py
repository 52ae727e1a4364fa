"""The current decoder blocks (ops/decoder.py, gluon/model_zoo/decoder.py)
against the plain reference of chipbench/configs/glm-4.7-flash, at the
rehearsal's sizes, float32, seeded: latent attention, the expert op and its
shares, the load counters, the flash kernel at head size 256, the cell's
rehearsal under a planted fault (the sound one is in
tests/test_benchmark_cells.py), and the cell's per-layer readers."""
import gc
import os
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

CDIR = os.path.join(cellmod.HERE, "configs", "glm-4.7-flash")
REF = cellmod.load_module(os.path.join(CDIR, "reference.py"),
                          "test_decoder_reference")
CFG = dict(cellmod.load_json(os.path.join(CDIR, "config.json")),
           **cellmod.load_json(os.path.join(CDIR, "rehearsal.json")))
CELL = "glm4.7flash_train_gluon"
D, F, E, K = (CFG["hidden_size"], CFG["moe_intermediate_size"],
              CFG["expert_parallel"]["router_outputs"],
              CFG["num_experts_per_tok"])
SCALE = CFG["routed_scaling_factor"]
HI = jax.lax.Precision.HIGHEST


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


@pytest.mark.parametrize("what", ["forward", "backward_stacked",
                                  "backward_carried"])
def test_flash_kernel_at_head_size_256(what, monkeypatch):
    """The kernel's interpreter at the new model's head size against the
    dense reference; the backward pass both ways it sums the key and value
    gradients of its query blocks (stacked, or in the loop's carry as it
    does where a stack would pass STACK_BYTES_MAX)."""
    from mxnet_tpu.ops import flash_attention as fa
    rs = np.random.RandomState(4)
    q, k, v = (jnp.asarray(_rand(rs, 1, 2, 256, 256)) for _ in range(3))
    scale = 256 ** -0.5
    if what == "forward":
        _close(fa._flash_attention(q, k, v, scale, True, 128, 128),
               _dense_reference(q, k, v, scale, True), rtol=1e-4, atol=1e-5)
        return
    if what == "backward_carried":
        monkeypatch.setattr(fa, "STACK_BYTES_MAX", 0)
    r = jnp.asarray(_rand(rs, 1, 2, 256, 256))
    got = jax.grad(lambda a, b, c: jnp.sum(
        fa._flash_attention(a, b, c, scale, True, 128, 128) * r),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda a, b, c: jnp.sum(
        _dense_reference(a, b, c, scale, True) * r), argnums=(0, 1, 2))(
            q, k, v)
    for g, w in zip(got, want):
        _close(g, w, rtol=1e-3, atol=1e-4)


def test_flash_backward_keeps_the_stack_at_the_other_cells_shape():
    """B*H 64, T 2048, D 64 (opt-1.3b's calls) stay under the bound, so
    that program is as it was; B*H 40, D 256 passes it."""
    from mxnet_tpu.ops.flash_attention import STACK_BYTES_MAX
    assert 64 * 16 * 2048 * 64 * 4 <= STACK_BYTES_MAX
    assert 40 * 16 * 2048 * 256 * 4 > STACK_BYTES_MAX


# -- the expert op -----------------------------------------------------------
def _expert_inputs(seed, tokens=48, held=4, bias=None):
    rs = np.random.RandomState(seed)
    return dict(
        h=_rand(rs, tokens, D), router=_rand(rs, E, D, scale=0.5),
        bias=np.zeros(E, "float32") if bias is None else bias,
        gate=_rand(rs, held, D, F, scale=0.2),
        up=_rand(rs, held, D, F, scale=0.2),
        down=_rand(rs, held, F, D, scale=0.2),
        load=np.zeros(held + 1, "float32"))


def _moe(a, first=0, held=4, record=False):
    """(result, load counter after the call[, arrays with gradients])."""
    arrs = {k: nd.array(v) for k, v in a.items()}
    trainable = ("h", "router", "gate", "up", "down")
    if record:
        for k in trainable:
            arrs[k].attach_grad()
    args = [arrs[k] for k in ("h", "router", "bias", "gate", "up", "down",
                              "load")]
    kw = dict(num_experts=E, top_k=K, first=first, held=held, scale=SCALE,
              norm_topk=True)
    if not record:
        return nd.moe_ffn(*args, **kw).asnumpy(), arrs["load"].asnumpy()
    return (lambda: nd.moe_ffn(*args, **kw)), arrs


def _ref_routed(a, first=0):
    with jax.default_matmul_precision("highest"):
        return REF.moe_routed(*(jnp.asarray(a[k]) for k in
                                ("router", "gate", "up", "down", "h")),
                              K, SCALE, True, first=first,
                              bias=jnp.asarray(a["bias"]))


def test_moe_forward_and_gradients_match_reference():
    a = _expert_inputs(5)
    r = _rand(np.random.RandomState(6), 48, D)
    call, arrs = _moe(a, record=True)
    with autograd.record():
        y = call()
        loss = (y * nd.array(r)).sum()
    loss.backward()
    _close(y.asnumpy(), _ref_routed(a), rtol=1e-3, atol=1e-4)

    def f(h, router, gate, up, down):
        return jnp.sum(REF.moe_routed(router, gate, up, down, h, K, SCALE,
                                      True) * r)

    keys = ("h", "router", "gate", "up", "down")
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


def test_every_token_on_one_held_expert_loses_none():
    """No capacity: all tokens choose experts 1 and 2, both held."""
    bias = np.zeros(E, "float32")
    bias[[1, 2]] = 10.0
    a = _expert_inputs(8, tokens=64, bias=bias)
    y, load = _moe(a)
    np.testing.assert_array_equal(load, [0, 64, 64, 0, 0])
    _close(y, _ref_routed(a), rtol=1e-3, atol=1e-4)
    assert (np.abs(y).max(axis=1) > 0).all()  # no token came back empty


def test_shares_add_up_to_the_uncut_layer():
    """The parts of all shares of the experts, with what every chip
    computes alike (the shared expert) counted once, are the uncut layer."""
    rs = np.random.RandomState(9)
    full = _expert_inputs(10, held=E)
    shared = {"gate.weight": _rand(rs, F, D, scale=0.2),
              "up.weight": _rand(rs, F, D, scale=0.2),
              "down.weight": _rand(rs, D, F, scale=0.2)}
    h = jnp.asarray(full["h"])
    with jax.default_matmul_precision("highest"):
        uncut = _ref_routed(full) + REF.gated_ffn(
            {k: jnp.asarray(v) for k, v in shared.items()}, h)
    total = np.zeros_like(full["h"])
    loads = []
    for first in (0, 4):
        share = dict(full, load=np.zeros(5, "float32"),
                     **{k: full[k][first:first + 4]
                        for k in ("gate", "up", "down")})
        y, load = _moe(share, first=first)
        total += y
        loads.append(load)
        _close(y, _ref_routed(share, first=first), rtol=1e-3, atol=1e-4)
    ffn = decoder.GatedFeedForward(D, F)
    ffn.initialize()
    for p, v in zip(ffn.collect_params().values(), shared.values()):
        p.set_data(nd.array(v))
    total += ffn(nd.array(full["h"])).asnumpy()
    _close(total, uncut, rtol=1e-3, atol=2e-4)
    # what one share counts absent the other holds
    assert loads[0][:4].sum() == loads[1][4] and \
        loads[1][:4].sum() == loads[0][4]


def test_load_counter_splits_held_and_absent_as_the_routing_does():
    a = _expert_inputs(11)
    _y, load = _moe(a, first=2)
    idx, _w = REF.routing(jnp.asarray(a["h"]), jnp.asarray(a["router"]),
                          jnp.zeros(E), K, SCALE, True)
    idx = np.asarray(idx)
    assert load.sum() == 48 * K
    want = [(idx == e).sum() for e in range(2, 6)]
    np.testing.assert_array_equal(load[:4], want)
    assert load[4] == 48 * K - sum(want)


def test_block_counters_ride_the_auxiliary_path_and_stay_float32(monkeypatch):
    """A hybridized expert block cast to bfloat16 keeps counting in
    float32 (a bfloat16 counter stops at 256), through CachedOp's auxiliary
    states; the registry reads them when asked."""
    monkeypatch.setattr(metrics, "_moe_layers", weakref.WeakKeyDictionary())
    metrics.MOE_ASSIGNMENTS.reset()
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
    assert load.sum() == 3 * 200 * K and load[:4].sum() > 256
    metrics.refresh_moe()
    assert metrics.MOE_ASSIGNMENTS.get(where="held") == load[:4].sum()
    assert metrics.MOE_ASSIGNMENTS.get(where="absent") == load[4]
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


# -- the cell's rehearsal ----------------------------------------------------
def test_cell_rehearsal_planted_fault_reads_not_correct(float32_traffic,
                                                        monkeypatch):
    """Half of every batch repeats the other half."""
    real = cellmod.Cell.batches

    def batches(self):
        return [(jnp.concatenate([x[:1], x[:1]]),
                 jnp.concatenate([y[:1], y[:1]])) for x, y in real(self)]

    monkeypatch.setattr(cellmod.Cell, "batches", batches)
    res = run.run_cell(CELL, 7, 0.5, False, rehearsal=True)
    assert res["correct"] is False, res["compared"]


def test_reference_leaves_are_the_programs_parameters():
    cell = cellmod.Cell(CELL, 1, rehearsal=True)
    net = cell.model.build(cell.cfg)
    got = [tuple(p.shape) for p in cell.model.trainable(net)]
    assert got == [tuple(s) for _n, s, _k in cell.spec]
    full = cellmod.Cell(CELL, 1)
    assert sum(int(np.prod(s)) for _n, s, _k in full.spec) == 591_294_720
    assert full.flops.train_flops_per_unit(full.cfg, full.traffic) == \
        pytest.approx(1.9257e9, rel=1e-4)


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
    blk.load.set_data(nd.array(np.array([10., 30., 10., 10., 99.], "f")))
    assert rd.read({}) == pytest.approx(2.0)
    del blk
    gc.collect()
    metrics.MOE_LOAD_MAX_OVER_MEAN.reset()
    assert rd.read({}) is None  # no expert layer alive
    monkeypatch.delattr(metrics, "refresh_moe")
    assert rd.read({}) is None  # a program without the counters
