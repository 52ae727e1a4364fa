"""`zaya1-8b` (chipbench/configs/zaya1-8b) through
`gluon.model_zoo.decoder.ZayaLM`: compressed convolutional attention
(`cca_mix`) against its plain formulation, causality, partial rotary, the
router state carried from layer to layer, the whole model's loss and every
gradient leaf against the configuration's reference, the share of the
experts between two chips with the skip, the skip's counter, and the
cell's two readers."""
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.model_zoo import decoder
from mxnet_tpu.observability import metrics
from mxnet_tpu.ops import decoder as ops

from chipbench import cell as cellmod

CELL = "zaya1.8b_train_gluon"
CDIR = os.path.join(cellmod.HERE, "configs", "zaya1-8b")
REF = cellmod.load_module(os.path.join(CDIR, "reference.py"),
                          "test_zaya_reference")
MODEL = cellmod.load_module(os.path.join(CDIR, "model.py"), "test_zaya_model")
CFG = dict(cellmod.load_json(os.path.join(CDIR, "config.json")),
           **cellmod.load_json(os.path.join(CDIR, "rehearsal.json")))
D, H, G, HD = CFG["hidden_size"], CFG["num_attention_heads"], \
    CFG["num_key_value_heads"], CFG["head_dim"]
R = int(HD * CFG["partial_rotary_factor"])
HI = jax.lax.Precision.HIGHEST


def _close(a, b, rtol=2e-4, atol=2e-5):
    np.testing.assert_allclose(np.asarray(a, "f"), np.asarray(b, "f"),
                               rtol=rtol, atol=atol)


def _weights(seed=3, cfg=CFG):
    """The reference's weights, with every scale, bias and temperature
    moved away from its initial 1 or 0, so that its gradient is held to
    the reference too."""
    w = REF.init_weights(seed, cfg)
    rs = np.random.RandomState(seed)
    return {k: (v + 0.2 * rs.randn(*v.shape).astype("f")
                if k.endswith(("gamma", "bias", "scale", "temperature"))
                else v) for k, v in w.items()}


# -- the mixing --------------------------------------------------------------
def _attn_leaves(w, layer=0):
    pre = f"l{layer}.attn."
    return {k[len(pre):]: v for k, v in w.items() if k.startswith(pre)}


def _mix(p, u):
    """The op's (q, k, v) from the reference's leaves and input `u`."""
    return ops._cca_mix(
        {"num_heads": H, "num_kv_heads": G, "rope_dims": R,
         "rope_base": float(CFG["rope_parameters"]["hybrid"]["rope_theta"])},
        *(REF._mm(u, p[n + ".weight"], None)
          for n in ("q", "k", "va", "vb")),
        p["conv0_weight"], p["conv0_bias"], p["conv1_weight"],
        p["conv1_bias"], p["temperature"])


def _u(seed=1, t=24):
    return jnp.asarray(np.random.RandomState(seed).randn(2, t, D)
                       .astype("f"))


def test_mixing_op_matches_its_plain_formulation():
    """Values and the gradient of every input (the projections, both
    convolutions with their biases, the temperature) against the
    reference's `cca_qkv`, float32: they differ by the order of sums."""
    p, u = _attn_leaves(_weights()), _u()
    b, t = u.shape[:2]
    q, k, v = _mix(p, u)
    rq, rk, rv = REF.cca_qkv(p, u, CFG)
    _close(q, rq.reshape(b, t, H * HD), rtol=1e-5, atol=1e-5)
    _close(k, rk.reshape(b, t, G * HD), rtol=1e-5, atol=1e-5)
    _close(v, rv.reshape(b, t, G * HD), rtol=1e-6, atol=1e-6)
    # the norms: every query head sqrt(d) long, a key head sqrt(d) times
    # its temperature
    _close(jnp.linalg.norm(q.reshape(b, t, H, HD), axis=-1), HD ** 0.5)
    _close(jnp.linalg.norm(k.reshape(b, t, G, HD), axis=-1),
           HD ** 0.5 * jnp.abs(p["temperature"]) * jnp.ones((b, t, G)))
    cot = [jnp.asarray(np.random.RandomState(i).randn(*a.shape).astype("f"))
           for i, a in enumerate((q, k, v))]

    def through(f):
        def obj(pp, uu):
            outs = f(pp, uu)
            return sum(jnp.sum(o.reshape(c.shape) * c)
                       for o, c in zip(outs, cot))
        return obj

    got = jax.jit(jax.grad(through(_mix), argnums=(0, 1)))(p, u)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(
            through(lambda pp, uu: REF.cca_qkv(pp, uu, CFG)),
            argnums=(0, 1)))(p, u)
    for name in p:
        _close(got[0][name], want[0][name], rtol=1e-4, atol=1e-5)
    _close(got[1], want[1], rtol=1e-4, atol=1e-5)


def test_partial_rotary_leaves_the_second_half_of_a_head_untouched():
    p, u = _attn_leaves(_weights()), _u()
    turned = _mix(p, u)
    plain = ops._cca_mix(
        {"num_heads": H, "num_kv_heads": G, "rope_dims": 0,
         "rope_base": 1e4},
        *(REF._mm(u, p[n + ".weight"], None)
          for n in ("q", "k", "va", "vb")),
        p["conv0_weight"], p["conv0_bias"], p["conv1_weight"],
        p["conv1_bias"], p["temperature"])
    for a, b, heads in zip(turned[:2], plain[:2], (H, G)):
        a = np.asarray(a).reshape(2, -1, heads, HD)
        b = np.asarray(b).reshape(2, -1, heads, HD)
        np.testing.assert_array_equal(a[..., R:], b[..., R:])
        np.testing.assert_array_equal(a[:, 0], b[:, 0])  # position 0: angle 0
        assert np.abs(a[:, 1:, :, :R] - b[:, 1:, :, :R]).max() > 1e-2


# -- the model ---------------------------------------------------------------
def _net(seed=3, hybrid=True, cfg=CFG):
    net = MODEL.build(dict(cfg))
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    weights = _weights(seed, cfg)
    params = MODEL.trainable(net)
    spec = REF.leaves(cfg)
    assert [tuple(p.shape) for p in params] == [tuple(s) for _n, s, _k in spec]
    for p, (name, _s, _k) in zip(params, spec):
        p.set_data(nd.NDArray(jnp.asarray(weights[name])))
    if hybrid:
        net.hybridize()
    return net, MODEL.gluon_loss(net, cfg), params, weights


def _batch(seed=1, seq=64, batch=2):
    xs, ys = REF.make_batches(seed, 1, batch, CFG, {"seq": seq})
    return xs[0], ys[0]


def _as_program(a):
    return nd.NDArray(jnp.asarray(a, jnp.float32))


def test_tokens_after_t_leave_every_output_up_to_t_unchanged():
    """Through the whole model: the convolutions, the value shift, the mean
    and the routers read nothing ahead."""
    net, *_ = _net()
    x, _y = _batch(seq=32)
    x2 = np.array(x)
    x2[:, 20:] = (x2[:, 20:] + 7) % CFG["vocab_size"]
    a = net(_as_program(x)).asnumpy()
    b = net(_as_program(x2)).asnumpy()
    np.testing.assert_array_equal(a[:, :20], b[:, :20])
    assert np.abs(a[:, 20:] - b[:, 20:]).max() > 1e-3


def test_loss_and_every_leafs_gradient_match_the_reference():
    """float32 throughout, the reference at "highest": the two differ by
    the order of sums (the program's attention through the kernel's
    interpreter in tiles, the loss in blocks of the vocabulary), so values
    agree to 2e-4 and gradients to 1e-3 of their size.  The router's
    choices are the same on both sides at this size (a flipped choice
    would show as a gap of order one)."""
    net, loss, params, weights = _net()
    x, y = _batch()
    with autograd.record():
        got = loss(_as_program(x), _as_program(y))
    got.backward()
    with jax.default_matmul_precision("highest"):
        want, grads = jax.jit(jax.value_and_grad(
            functools.partial(REF.loss, cfg=CFG)))(weights, x, y)
        last = jax.jit(functools.partial(REF.logits, cfg=CFG))(weights, x)
    _close(got.asnumpy().mean(), want)
    for p, (name, _s, _k) in zip(params, REF.leaves(CFG)):
        # the loss is a mean over two sequences; backward sums them
        _close(p.grad().asnumpy() / 2, grads[name], rtol=1e-3, atol=2e-6)
    # every leaf moves the loss: the carried state's scales and the
    # temperatures among them
    assert all(np.abs(np.asarray(g)).max() > 0 for g in grads.values())
    _close(net(_as_program(x)).asnumpy(), last, rtol=1e-3, atol=1e-4)


def test_a_layers_scores_read_the_previous_layers_router_state():
    """Layer 1's logits move with layer 0's state, and with nothing else
    held fixed: the same input, another state."""
    w = _weights()
    p = {k[len("l1.router."):]: v for k, v in w.items()
         if k.startswith("l1.router.")}
    x = _u(t=8)
    state = jnp.asarray(np.random.RandomState(5).randn(2, 8, p["w1"].shape[0])
                        .astype("f"))
    args = (p["down_weight"], p["down_bias"], p["state_scale"],
            p["norm_gamma"], p["w1"], p["w2"], p["w3"])
    z1, rho1 = nd.mlp_router_carried(nd.NDArray(x), nd.NDArray(state),
                                     *map(nd.NDArray, args),
                                     eps=CFG["rms_norm_eps"])
    z2, _ = nd.mlp_router_carried(nd.NDArray(x), nd.NDArray(state * 0.5),
                                  *map(nd.NDArray, args),
                                  eps=CFG["rms_norm_eps"])
    assert np.abs(z1.asnumpy() - z2.asnumpy()).max() > 1e-3
    with jax.default_matmul_precision("highest"):
        want_z, want_rho = REF.router(p, x, state, CFG["rms_norm_eps"])
    _close(z1.asnumpy(), want_z, rtol=1e-5, atol=1e-6)
    _close(rho1.asnumpy(), want_rho, rtol=1e-5, atol=1e-6)


# -- the share and the skip ----------------------------------------------------
def _layer_experts(seed=9, tokens=40):
    """(logits, w, gate, up, down) of one expert layer with all the
    router's experts stacked, from the reference's own leaves at a size
    where the router's 8 experts are 2 chips' 4."""
    cfg = dict(CFG, num_experts=CFG["expert_parallel"]["router_outputs"] - 1)
    w = REF.init_weights(seed, cfg)
    rs = np.random.RandomState(seed)
    logits = jnp.asarray(rs.randn(tokens, cfg["num_experts"] + 1)
                         .astype("f") * 2)
    g = jnp.asarray(rs.randn(tokens, D).astype("f"))
    return (logits, g, w["l0.ffn.experts.gate"], w["l0.ffn.experts.up"],
            w["l0.ffn.experts.down"])


def _share(logits, g, gate, up, down, first, held):
    e1 = logits.shape[-1]
    p = {"num_experts": e1, "top_k": 1, "first": first, "held": held,
         "scale": 1.0, "norm_topk": False, "router": "softmax_topk",
         "activation": "silu"}
    sl = slice(first, first + held)
    y, _bias, load = ops._moe_ffn_scored(
        p, g, logits, jnp.zeros(e1), gate[sl], up[sl], down[sl],
        jnp.zeros(held + 3))
    return np.asarray(y), np.asarray(load)


def test_two_chips_shares_add_up_to_the_uncut_layer():
    """Experts 0-3 on one chip, 4-7 on the other: their parts add up to
    what the reference's layer gives with all 8 held.  Attention, the
    router and the residual are what every chip computes alike and are
    counted once (they are not in the expert op).  A token whose choice is
    the skip adds nothing on either chip."""
    logits, g, gate, up, down = _layer_experts()
    held = CFG["num_experts"]
    logits = logits.at[:5, -1].set(50.0)  # five tokens take the skip
    y0, load0 = _share(logits, g, gate, up, down, 0, held)
    y1, load1 = _share(logits, g, gate, up, down, held, held)
    with jax.default_matmul_precision("highest"):
        whole = REF.moe_routed(logits, gate, up, down, g)
    _close(y0 + y1, whole, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(y0[:5], 0.0)
    np.testing.assert_array_equal(y1[:5], 0.0)
    assert np.abs(y0[5:]).max() > 0 and np.abs(y1[5:]).max() > 0
    # each chip: its own rows, the other chip's as absent, the skip apart
    skipped = int((np.argmax(np.asarray(logits), -1) == 2 * held).sum())
    assert skipped >= 5
    for load, other in ((load0, load1), (load1, load0)):
        assert load[held + 1] == skipped
        assert load[held] == other[:held].sum()
        assert load[:held].sum() + load[held] + load[held + 1] == 40


_ROUTINGS = {"even": [6, 6, 6, 6], "one_expert": [30, 0, 0, 0],
             "all_held": [10, 20, 5, 5], "none_held": [0, 0, 0, 0],
             "two_empty": [0, 17, 0, 9]}


@pytest.mark.parametrize("routing", sorted(_ROUTINGS))
def test_steady_buffer_equals_the_ragged_split(routing, monkeypatch):
    """`_steady_rows` gives what `_expert_rows` gives, outputs and every
    gradient, whatever the routing: empty experts, every pair held, none
    held.  Its groups are never empty and cover the whole buffer, so the
    tiles the grouped products visit do not follow the routing."""
    monkeypatch.setattr(ops, "GROUPED_TILE_ROWS", 8)
    held, top_k, T, Dm, Fm = 4, 1, 40, 16, 24
    counts = _ROUTINGS[routing]
    rs = np.random.RandomState(len(routing))
    key = np.concatenate([np.full(c, e) for e, c in enumerate(counts)]
                         + [np.full(T * top_k - sum(counts), held)])
    key = jnp.asarray(rs.permutation(key).astype("int32"))
    h = jnp.asarray(rs.randn(T, Dm).astype("f"))
    w = jnp.asarray(rs.rand(T, top_k).astype("f"))
    mats = [jnp.asarray(rs.randn(held, a, b).astype("f") * 0.2)
            for a, b in ((Dm, Fm), (Dm, Fm), (Fm, Dm))]
    dy = jnp.asarray(rs.randn(T, Dm).astype("f"))
    buffer = ops.steady_buffer(T * top_k, held)
    assert buffer == 48 and buffer % 8 == 0

    groups = []
    grouped = ops._grouped_ffn
    monkeypatch.setattr(ops, "_grouped_ffn", lambda xs, g, *a, **kw: (
        groups.append(np.asarray(g)), grouped(xs, g, *a, **kw))[1])

    def run(fn, **kw):
        out, pull = jax.vjp(lambda h, w, *m: fn(h, key, w, *m, **kw)[0],
                            h, w, *mats)
        return (out,) + pull(dy), fn(h, key, w, *mats, **kw)[1]

    with jax.default_matmul_precision("highest"):
        got, sizes = run(ops._steady_rows, buffer=buffer)
        steady_groups = groups[-1]
        want, want_sizes = run(ops._expert_rows, buffer=T * top_k)
    for a, b in zip(got, want):
        _close(a, b, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(sizes[:held + 1], want_sizes[:held + 1])
    assert int(sizes[-1]) == buffer
    # every group holds its expert's rows and a spare one; the last runs
    # to the end of the buffer
    assert (steady_groups[:-1] == np.asarray(counts[:-1]) + 1).all()
    assert steady_groups.sum() == buffer and steady_groups.min() >= 1
    if sum(counts):
        assert np.abs(np.asarray(got[0])).max() > 0
    else:
        np.testing.assert_array_equal(got[0], 0.0)


def test_the_skip_lands_in_its_own_counter(monkeypatch):
    import weakref
    monkeypatch.setattr(metrics, "_moe_layers", weakref.WeakKeyDictionary())
    ffn = decoder.MoEFeedForward(D, 24, 5, 1, held_experts=2, shared_experts=0,
                                 norm_topk=False, router="softmax_topk",
                                 scored=True, prefix="f_")
    ffn.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    assert ffn.load.shape == (5,)
    logits = np.zeros((2, 6, 5), "f")
    logits[..., 4] = 9.0       # 7 tokens skip
    logits[0, :5] = 0.0
    logits[0, :2, 0] = 9.0     # 2 tokens to held expert 0
    logits[0, 2:5, 3] = 9.0    # 3 tokens to absent expert 3
    before = {w: metrics.MOE_ASSIGNMENTS.get(where=w)
              for w in ("held", "absent", "skipped")}
    ffn(nd.array(np.random.RandomState(0).randn(2, 6, D).astype("f")),
        nd.array(logits))
    # the last slot: the steady buffer's rows (12 pairs, 2 spare rows, one
    # tile)
    np.testing.assert_array_equal(ffn.load.data().asnumpy(),
                                  [2, 0, 3, 7, ops.steady_buffer(12, 2)])
    assert ops.steady_buffer(12, 2) == ops.GROUPED_TILE_ROWS
    metrics.refresh_moe()
    got = {w: metrics.MOE_ASSIGNMENTS.get(where=w) - before[w]
           for w in before}
    assert got == {"held": 2, "absent": 3, "skipped": 7}
    # the load ratio is over the held experts alone
    assert metrics.MOE_LOAD_MAX_OVER_MEAN.get() == 2.0
    assert 'where="skipped"' in metrics.render_prometheus()


# -- the configuration, the cell and its readers --------------------------------
def test_configuration_keeps_the_published_sizes():
    cfg = cellmod.load_json(os.path.join(CDIR, "config.json"))
    catalog = {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 8,
        "num_key_value_heads": 2, "cca_time0": 2, "cca_time1": 2,
        "moe_intermediate_size": 2048, "num_experts_per_tok": 1,
        "router_hidden_size": 256, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "max_position_embeddings": 131072,
        "num_hidden_layers": 40, "num_experts": 16, "vocab_size": 262272}
    differ = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                                "vocab_size": 262272}
    assert cfg["layer_types"] == ["hybrid"] * 40
    assert cfg["tie_word_embeddings"] and cfg["vocab_size"] * 2 == 262272
    assert cfg["expert_parallel"] == {"chips": 2, "first_expert": 0,
                                      "router_outputs": 17}
    entry = next(c for c in cellmod.benchmark()["configs"]
                 if c["name"] == "zaya1-8b")
    assert entry["reduced"] == cfg["reduced"] and \
        entry["source"] == cfg["source"]


def test_reference_leaves_are_the_programs_parameters():
    full = cellmod.Cell(CELL, 1)
    assert sum(int(np.prod(s)) for _n, s, _k in full.spec) == 696_215_304
    fl = full.flops
    assert full.units_per_step() == 4096
    assert fl.layer_windows(full.cfg) == [None] * 4
    assert fl.train_flops_per_unit(full.cfg, full.traffic) == \
        pytest.approx(2.0037e9, rel=1e-4)
    assert fl.head_loss_shape(full.cfg, full.traffic) == (4096, 131136, 2048)


def _analysis(rows):
    return {"rows": {k: {"ms": ms, "instructions": {}}
                     for k, ms in rows.items()}}


def test_readers_return_a_number_or_none(monkeypatch):
    from chipbench import scope_reduce
    readers = {n: cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", n + ".py"), "test_reader_" + n)
        for n in ("cca_device_ms", "head_loss_roofline")}
    cell = cellmod.Cell(CELL, 1)
    peaks = cellmod.load_json(os.path.join(cellmod.HERE, "peaks.json"))[
        "TPU v5 lite"]
    ctx = {"cell": cell, "peaks": peaks}
    fwd, bwd = "jit_mx_cachedop_fwd", "jit_mx_cachedop_bwd"
    rows = {
        (fwd, "fwd", "_contrib_cca_mix", "lmloss0_zayalm0_l0_attn_cca_mix0"):
            1.0,
        (bwd, "bwd", "_contrib_cca_mix", "lmloss0_zayalm0_l0_attn_cca_mix0"):
            2.0,
        (fwd, "fwd", "_contrib_grouped_query_attention",
         "lmloss0_zayalm0_l0_attn_grouped_query_attention0"): 3.0,
        (bwd, "bwd", "FullyConnected", "lmloss0_zayalm0_l0_attn_q_fwd"): 4.0,
        (fwd, "fwd", "_contrib_rms_norm", "lmloss0_zayalm0_l0_n1_rms_norm0"):
            50.0,
        (fwd, "fwd", "_contrib_blocked_cross_entropy",
         "lmloss0_zayalm0_tiedhead0_blocked_cross_entropy0"): 20.0,
        (bwd, "bwd", None, "blocked_cross_entropy"): 40.0,
        ("jit__threefry_split", "recordless", None, "jit__threefry_split"):
            9.0}
    monkeypatch.setattr(scope_reduce, "analyse", lambda c: _analysis(rows))
    assert readers["cca_device_ms"].read(ctx) == 10.0
    least = 6 * 4096 * 131136 * 2048 / peaks["bf16_flops_per_s"]
    assert readers["head_loss_roofline"].read(ctx) == pytest.approx(
        100 * least / 0.060)
    # a program without the blocks (the parent's): nothing, never 0
    monkeypatch.setattr(scope_reduce, "analyse", lambda c: _analysis({
        k: v for k, v in rows.items()
        if "cca" not in k[3] and "blocked" not in k[3]}))
    assert readers["cca_device_ms"].read(ctx) is None
    assert readers["head_loss_roofline"].read(ctx) is None
    monkeypatch.setattr(scope_reduce, "analyse", lambda c: None)
    assert readers["cca_device_ms"].read(ctx) is None
    assert readers["head_loss_roofline"].read(ctx) is None
