"""A loop in the graph (`contrib.foreach`) and the looped LM that needs it
(gluon/model_zoo/decoder.py `LoopedLM`, `LoopedLMLoss`), on the CPU at toy
sizes: the loop against the unrolled graph, eager and hybridized; what a
recorded call keeps of a loop and what its backward program runs; the
model against `chipbench/configs/ouro-2.6b/reference.py` on seeded weights
(loss, every leaf's gradient, three Adam steps); the tied weights'
gradient as the sum over untied copies; the exit distribution; the
sandwich placement leaving the older decoder as it was; the configuration's
published sizes; the counters and the two readers; the cell's rehearsal
with its planted faults.
"""
import collections
import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo import decoder
from mxnet_tpu.observability import metrics

from chipbench import cell as cellmod
from chipbench import run

from test_benchmark_cells import float32_traffic  # noqa: F401  (fixture)
from test_flash_attention import _kernel_eqns

CELL = "ouro2.6b_train_gluon"
CDIR = os.path.join(cellmod.HERE, "configs", "ouro-2.6b")
REF = cellmod.load_module(os.path.join(CDIR, "reference.py"),
                          "test_looped_reference")
CFG = dict(cellmod.load_json(os.path.join(CDIR, "config.json")),
           **cellmod.load_json(os.path.join(CDIR, "rehearsal.json")))
R, LAYERS = CFG["total_ut_steps"], CFG["num_hidden_layers"]


def _close(a, b, rtol=2e-4, atol=2e-5):
    np.testing.assert_allclose(np.asarray(a, "f"), np.asarray(b, "f"),
                               rtol=rtol, atol=atol)


# -- the loop ------------------------------------------------------------------
class _Steps(gluon.HybridBlock):
    """Three steps of h + d2(tanh(d1(h))) * scale, `scale` a value of the
    outer graph; outputs a sum a step.  loop=False writes the steps out."""

    def __init__(self, loop, **kw):
        super().__init__(**kw)
        self._loop = loop
        with self.name_scope():
            self.d1 = nn.Dense(16, flatten=False, in_units=8, prefix="d1_")
            self.d2 = nn.Dense(8, flatten=False, in_units=16, prefix="d2_")

    def hybrid_forward(self, F, x):
        scale = x * 0.5

        def body(_step, states):
            h = states[0]
            h = h + self.d2(F.Activation(self.d1(h), act_type="tanh")) * scale
            return [F.sum(h, axis=-1)], [h]

        if self._loop:
            outs, states = F.contrib.foreach(body, F.arange(0, 3), [x])
            return outs[0], states[0]
        sums, states = [], [x]
        for _ in range(3):
            out, states = body(None, states)
            sums.append(out[0])
        return F.stack(*sums, axis=0), states[0]


def _steps_run(loop, hybrid):
    net = _Steps(loop, prefix="steps_")
    net.initialize(mx.init.Zero())
    for p in net.collect_params().values():
        rs = np.random.RandomState(len(p.name))
        p.set_data(nd.array(rs.randn(*p.shape).astype("f") * 0.3))
    if hybrid:
        net.hybridize()
    x = nd.array(np.random.RandomState(1).randn(2, 5, 8).astype("f"))
    x.attach_grad()
    with autograd.record():
        sums, last = net(x)
        loss = (sums * sums).sum() + last.sum()
    loss.backward()
    return [sums.asnumpy(), last.asnumpy(), x.grad.asnumpy()] + \
        [p.grad().asnumpy() for p in net.collect_params().values()]


@pytest.mark.parametrize("hybrid", [False, True], ids=["eager", "hybrid"])
def test_foreach_equals_the_unrolled_graph(hybrid):
    """Outputs, final state, the data's gradient and the gradient of every
    parameter the body closes over (the sum over the steps)."""
    want = _steps_run(False, False)
    got = _steps_run(True, hybrid)
    assert got[0].shape == (3, 2, 5)
    for g, w in zip(got, want):
        _close(g, w)


def test_foreach_through_an_executor():
    """`sym.contrib.foreach` bound like any symbol: scanned data, a carried
    state, a free variable that every step reads whole."""
    data, init, w = (mx.sym.Variable(n) for n in ("data", "init", "w"))

    def body(x, state):
        new = state * w + x
        return new * 2.0, new

    outs, last = mx.sym.contrib.foreach(body, data, init)
    net = mx.sym.Group([outs, last])
    assert net.list_arguments() == ["data", "init", "w"]
    rs = np.random.RandomState(0)
    vals = {"data": rs.randn(4, 3).astype("f"), "init": rs.randn(3).astype("f"),
            "w": rs.rand(3).astype("f")}
    ex = net.simple_bind(mx.cpu(), grad_req="write",
                         **{k: v.shape for k, v in vals.items()})
    for k, v in vals.items():
        ex.arg_dict[k][:] = v
    got = [o.asnumpy() for o in ex.forward(is_train=True)]
    state, rows = vals["init"], []
    for x in vals["data"]:
        state = state * vals["w"] + x
        rows.append(2 * state)
    _close(got[0], np.stack(rows))
    _close(got[1], state)
    ex.backward([nd.zeros((4, 3)), nd.ones((3,))])
    # d last / d w = sum over the steps the weight was read in
    want = jax.grad(lambda w_: jnp.sum(functools.reduce(
        lambda s, x: s * w_ + x, jnp.asarray(vals["data"]),
        jnp.asarray(vals["init"]))))(jnp.asarray(vals["w"]))
    _close(ex.grad_dict["w"].asnumpy(), want)


def test_foreach_refuses_what_a_body_cannot_carry():
    data, init = mx.sym.Variable("data"), mx.sym.Variable("init")
    with pytest.raises(MXNetError, match="auxiliary state"):
        mx.sym.contrib.foreach(
            lambda x, s: (mx.sym.BatchNorm(x + s, name="bn"), s), data, init)
    with pytest.raises(MXNetError, match="random numbers"):
        mx.sym.contrib.foreach(
            lambda x, s: (mx.sym.Dropout(x + s, p=0.5), s), data, init)
    with pytest.raises(MXNetError, match="structure"):
        mx.sym.contrib.foreach(lambda x, s: (x, [s, s]), data, init)
    outer = data * 2.0
    with pytest.raises(MXNetError, match="outside the body"):
        mx.sym.contrib.foreach(lambda x, s: (outer, s), data, init)
    outs, _ = mx.sym.contrib.foreach(lambda x, s: (x + s, s), data, init)
    with pytest.raises(MXNetError, match="foreach"):
        outs.tojson()


# -- the looped model ----------------------------------------------------------
def _attention(impl):
    return functools.partial(
        decoder.GroupedQueryAttention, CFG["hidden_size"],
        CFG["num_attention_heads"], CFG["num_key_value_heads"],
        CFG["head_dim"], rope=True, rope_base=float(CFG["rope_theta"]),
        attn_type=impl)


def _net(impl="dense", loop_steps=R, hybrid=True, seed=3):
    """(net, loss block, the reference's weights) from one seed."""
    cfg = dict(CFG, total_ut_steps=loop_steps)
    net = decoder.LoopedLM(cfg["vocab_size"], cfg["hidden_size"], LAYERS,
                           loop_steps, _attention(impl),
                           cfg["intermediate_size"],
                           epsilon=cfg["rms_norm_eps"])
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    weights = REF.init_weights(seed, cfg)
    rs = np.random.RandomState(seed)
    # norm scales and the gate's bias away from their initial 1 and 0, so
    # that their gradients are held to the reference too
    weights = {k: (v + 0.2 * rs.randn(*v.shape).astype("f")
                   if k.endswith(("gamma", "bias")) else v)
               for k, v in weights.items()}
    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    spec = REF.leaves(cfg)
    assert [tuple(p.shape) for p in params] == [tuple(s) for _n, s, _k in spec]
    for p, (name, _s, _k) in zip(params, spec):
        p.set_data(nd.NDArray(jnp.asarray(weights[name])))
    loss = decoder.LoopedLMLoss(net, beta=cfg["exit_entropy_weight"])
    if hybrid:
        net.hybridize()
        loss.hybridize()
    return net, loss, params, weights, cfg


def _batch(seed=1, seq=48):
    xs, ys = REF.make_batches(seed, 1, 2, CFG, {"seq": seq})
    return xs[0], ys[0]


def _as_program(a):
    return nd.NDArray(jnp.asarray(a, jnp.float32))


@pytest.mark.parametrize("hybrid", [False, True], ids=["eager", "hybrid"])
def test_loss_and_every_leafs_gradient_match_the_reference(hybrid):
    net, loss, params, weights, cfg = _net(hybrid=hybrid)
    x, y = _batch()
    with autograd.record():
        got = loss(_as_program(x), _as_program(y))
    got.backward()
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(REF.loss)(weights, x, y, cfg)
        last = REF.logits(weights, x, cfg)
    _close(got.asnumpy().mean(), want)
    names = [n for n, _s, _k in REF.leaves(cfg)]
    for p, name in zip(params, names):
        # the loss is a mean over two sequences; backward sums them
        _close(p.grad().asnumpy() / 2, grads[name], rtol=1e-3, atol=2e-6)
    _close(net(_as_program(x)).asnumpy(), last, rtol=1e-3, atol=1e-4)


def test_three_adam_steps_follow_the_reference():
    net, loss, params, weights, cfg = _net()
    adam = cellmod.load_module(
        os.path.join(cellmod.HERE, "optimizers", "adam.py"), "test_looped_adam")
    hp = {"lr": 2e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8, "wd": 0.0}
    trainer = gluon.Trainer(
        net.collect_params(), "adam",
        {"learning_rate": hp["lr"], "beta1": 0.9, "beta2": 0.95,
         "epsilon": 1e-8, "wd": 0.0}, kvstore="tpu_sync",
        update_on_kvstore=False)
    state = adam.init(weights)
    mask = {k: True for k in weights}
    for t in range(3):
        x, y = _batch(seed=10 + t)
        with autograd.record():
            got = loss(_as_program(x), _as_program(y))
        got.backward()
        trainer.step(2)
        with jax.default_matmul_precision("highest"):
            want, grads = jax.value_and_grad(REF.loss)(weights, x, y, cfg)
        _close(got.asnumpy().mean(), want, rtol=1e-4)
        weights, state = adam.update(weights, grads, state, hp,
                                     jnp.float32(t + 1), mask)
    for p, (name, _s, _k) in zip(params, REF.leaves(cfg)):
        moved = np.abs(np.asarray(weights[name])
                       - np.asarray(REF.init_weights(3, cfg)[name])).max()
        _close(p.data().asnumpy(), weights[name], rtol=1e-3,
               atol=max(moved, 1e-6) * 0.05)


def test_one_loop_step_is_the_plain_stack():
    net, _loss, _params, weights, cfg = _net(loop_steps=1)
    x, _y = _batch()
    xs = _as_program(x)
    plain = net.head(net.norm_f(net.blocks(net.tok(xs))))
    _close(net(xs).asnumpy(), plain.asnumpy(), rtol=1e-5, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        _close(net(xs).asnumpy(), REF.logits(weights, x, cfg), rtol=1e-3,
               atol=1e-4)


def test_a_tied_leafs_gradient_is_the_sum_over_untied_copies():
    """The reference with R copies of the stack, one a loop step, all
    starting equal: the sum of the copies' gradients is the tied leaf's,
    in the reference and in the program."""
    net, loss, params, weights, cfg = _net()
    x, y = _batch()

    def untied(copies):
        h = weights["tok.weight"][x]
        ce, lam = [], []
        for stack in copies:  # stack: the layers' and norm_f's leaves
            h = REF.loop_step(stack, h, cfg)
            ce.append(REF._exit_ce(weights["head.weight"], h, y, None))
            lam.append(REF.exit_gate(weights, h))
        p = REF.exit_distribution(jnp.stack(lam))
        obj = jnp.sum(p * jnp.stack(ce), axis=0) \
            + cfg["exit_entropy_weight"] * jnp.sum(p * jnp.log(p), axis=0)
        return jnp.mean(obj)

    stack = {k: v for k, v in weights.items()
             if k.startswith("l") or k == "normf.gamma"}
    with jax.default_matmul_precision("highest"):
        per_copy = jax.grad(untied)([stack] * R)
        tied = jax.grad(REF.loss)(weights, x, y, cfg)
    with autograd.record():
        out = loss(_as_program(x), _as_program(y))
    out.backward()
    got = {name: p.grad().asnumpy() / 2
           for p, (name, _s, _k) in zip(params, REF.leaves(cfg))}
    for name in stack:
        total = sum(np.asarray(c[name]) for c in per_copy)
        assert np.abs(np.asarray(per_copy[0][name])
                      - np.asarray(per_copy[-1][name])).max() > 0
        _close(tied[name], total, rtol=1e-4, atol=1e-7)
        _close(got[name], total, rtol=1e-3, atol=2e-6)


def test_exit_distribution_sums_to_one_and_the_last_step_takes_the_rest():
    rs = np.random.RandomState(4)
    gate = rs.randn(R, 2, 6).astype("f") * 3
    gate[0, 0, 0], gate[1, 0, 1] = 80.0, -80.0  # saturated either way
    mass = nd.zeros((R + 1,))
    p, logp = nd.exit_distribution(nd.array(gate), mass)
    p, logp = p.asnumpy(), logp.asnumpy()
    lam = 1 / (1 + np.exp(-gate.astype("f8")))
    _close(p.sum(axis=0), np.ones((2, 6)), rtol=1e-6)
    _close(p[-1], np.prod(1 - lam[:-1], axis=0), rtol=1e-5, atol=1e-30)
    _close(p[0], lam[0], rtol=1e-6)
    _close(p, REF.exit_distribution(jnp.asarray(lam, jnp.float32)),
           rtol=1e-4, atol=1e-7)
    assert np.isfinite(logp).all() and np.isfinite(p * logp).all()
    # the last step reads no gate of its own
    gate[-1] += 5.0
    again, _ = nd.exit_distribution(nd.array(gate), nd.zeros((R + 1,)))
    np.testing.assert_array_equal(again.asnumpy(), p)
    # the state: p summed over the tokens, then their number
    _close(mass.asnumpy(), np.append(p.reshape(R, -1).sum(axis=1), 12.0),
           rtol=1e-6)


def test_sandwich_off_leaves_the_older_decoder_as_it_was():
    """The accepted configurations build `DecoderBlock` without the
    option: the same parameters in the same order, the same output."""
    attn = functools.partial(decoder.GroupedQueryAttention, 16, 2, 1, 8)
    ffn = functools.partial(decoder.GatedFeedForward, 16, 24)
    plain = decoder.DecoderBlock(attn, ffn, 16, prefix="b_")
    assert list(plain.collect_params()) == [
        "b_n1_gamma", "b_attn_q_weight", "b_attn_k_weight",
        "b_attn_v_weight", "b_attn_proj_weight", "b_n2_gamma",
        "b_ffn_gate_weight", "b_ffn_up_weight", "b_ffn_down_weight"]
    wide = decoder.DecoderBlock(attn, ffn, 16, sandwich=True, prefix="b_")
    assert [n for n in wide.collect_params()
            if n not in plain.collect_params()] == [
        "b_n1post_gamma", "b_n2post_gamma"]
    lm = decoder.DecoderLM(32, 16, 2, attention=[attn], dense_ffn_dim=24,
                           first_k_dense=2)
    assert not [n for n in lm.collect_params() if "post" in n]
    for blk in (plain, wide):
        blk.initialize(mx.init.Normal(0.3))
    x = nd.array(np.random.RandomState(2).randn(2, 5, 16).astype("f"))
    a = x + plain.attn(plain.n1(x))
    _close(plain(x).asnumpy(), (a + plain.ffn(plain.n2(a))).asnumpy(),
           rtol=1e-6, atol=1e-6)
    for p in wide.collect_params().values():
        p.set_data(plain.collect_params()[p.name].data()
                   if p.name in plain.collect_params() else p.data() * 2)
    a = x + wide.n1_post(wide.attn(wide.n1(x)))
    _close(wide(x).asnumpy(),
           (a + wide.n2_post(wide.ffn(wide.n2(a)))).asnumpy(), rtol=1e-6,
           atol=1e-6)
    assert np.abs(wide(x).asnumpy() - plain(x).asnumpy()).max() > 1e-2


def test_looped_lm_has_no_decode_path_yet():
    net, *_ = _net(hybrid=False)
    with pytest.raises(NotImplementedError, match="decode"):
        net.generate(None)


# -- what a recorded call keeps of the loop ------------------------------------
def _count(jaxpr, into=None):
    """Primitive name -> equations, through every sub-jaxpr (a scan's body
    counts once, as the program text holds it) but a kernel's own."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, into)
    return into


@pytest.fixture(scope="module")
def recorded():
    """The hybridized loss over the flash kernels (the interpreter), its
    CachedOp's inputs, and one recorded forward."""
    _net_, loss, _params, _weights, _cfg = _net(impl="flash")
    x, y = _batch(seq=128)
    with autograd.pause():
        loss(_as_program(x), _as_program(y))
    op = loss._cached_op
    names = loss._cached_input_names
    args = dict(zip(names, (jnp.asarray(x, jnp.float32),
                            jnp.asarray(y, jnp.float32))))
    aux = {}
    for name, p in loss._cached_params.items():
        (aux if name in loss._cached_aux else args)[name] = p.data()._data
    key = jax.random.PRNGKey(0)
    outs, new_aux, pull = op._fwd(args, aux, key, True, True)
    return loss, op, args, aux, key, outs, new_aux, pull


def test_backward_program_runs_no_forward_product_of_the_loop(recorded):
    _loss, op, args, aux, key, outs, new_aux, pull = recorded
    fwd = _count(jax.make_jaxpr(
        lambda a, s, k: op._fwd.__wrapped__(a, s, k, True, False))(
            args, aux, key).jaxpr)
    cots = (tuple(jnp.ones_like(o) for o in outs),
            {k: jnp.zeros_like(v) for k, v in new_aux.items()})
    jaxpr = jax.make_jaxpr(op._bwd.__wrapped__)(
        pull, (args, aux, key), cots).jaxpr
    bwd = _count(jaxpr)
    # the program text holds the stack ONCE: seven products a layer and
    # the head's, one forward kernel a layer, not a layer application
    assert fwd["scan"] == 1 and fwd["dot_general"] == 7 * LAYERS + 1, fwd
    assert fwd["pallas_call"] == LAYERS
    assert metrics.LOOP_STACK_COPIES.get() == 1
    assert metrics.LOOP_APPLICATIONS.get() == LAYERS * R
    # dx and dW of every product and no third; two backward kernels a
    # layer and no forward kernel
    assert bwd["dot_general"] == 2 * fwd["dot_general"], (fwd, bwd)
    scopes = collections.Counter(
        str(e.source_info.name_stack).rsplit("/", 1)[-1]
        for e in _kernel_eqns(jaxpr) if e.primitive.name == "pallas_call")
    assert scopes == {"flash_bwd_dkv": LAYERS, "flash_bwd_dq": LAYERS}
    assert metrics.FLASH_BWD.get(path="reference") == 0


def test_kept_residuals_carry_the_loop_axis(recorded):
    from mxnet_tpu.gluon import block as blk
    _loss, _op, args, _aux, _key, _outs, _new_aux, pull = recorded
    nodes = jax.tree_util.tree_leaves(pull, is_leaf=blk._is_input_ref)
    kept = [n for n in nodes if not blk._is_input_ref(n)]
    b, t = args[next(iter(args))].shape
    d, f, h = (CFG["hidden_size"], CFG["intermediate_size"],
               CFG["num_attention_heads"])
    shapes = collections.Counter(tuple(n.shape) for n in kept)
    # a layer's products, stacked over the R steps: q, k, v, the attention
    # output's projection, the feed-forward's down (B, T, D) and its gate
    # and up (B, T, F); the kernel's output and its rows' statistics
    assert shapes[(R, b, t, f)] == 2 * LAYERS
    assert shapes[(R, b, t, d)] >= 5 * LAYERS
    assert shapes[(R, b * h, 1, t)] == LAYERS  # log-sum-exp, float32
    assert shapes[(R, b, t, CFG["vocab_size"])] == 1  # the exits' logits
    big = [n for n in kept if n.size >= b * t * d]
    assert big and all(n.shape[0] == R for n in big), \
        [n.shape for n in big if n.shape[0] != R]
    total = sum(n.nbytes for n in kept)
    assert metrics.CACHEDOP_RESIDUAL_BYTES.get(kind="kept") == total


# -- counters and readers --------------------------------------------------------
def test_exit_mass_rides_the_auxiliary_path_and_is_read_at_export(
        monkeypatch):
    import weakref
    # the gauge is the mean over every looped model alive: this one alone
    monkeypatch.setattr(metrics, "_loop_exits", weakref.WeakKeyDictionary())
    net, loss, *_ = _net()
    assert net.exits.mass.dtype == np.float32
    net.cast("bfloat16")
    assert net.exits.mass.dtype == np.float32  # a bfloat16 counter stalls
    net.cast("float32")
    x, y = _batch()
    for _ in range(2):
        with autograd.record():
            out = loss(_as_program(x), _as_program(y))
        out.backward()
    mass = net.exits.mass.data().asnumpy()
    assert mass[-1] == 2 * x.size and abs(mass[:-1].sum() - mass[-1]) < 1e-2
    text = metrics.render_prometheus()
    got = [metrics.LOOP_EXIT_MASS.get(step=str(t + 1)) for t in range(R)]
    _close(got, mass[:-1] / mass[-1], rtol=1e-5)
    assert abs(sum(got) - 1) < 1e-5
    assert 'mxnet_loop_exit_mass{step="1"}' in text


def test_readers_return_a_number_or_none(monkeypatch):
    readers = {n: cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", n + ".py"), "test_reader_" + n)
        for n in ("loop_stack_copies", "residual_kb_per_token")}
    cell = cellmod.Cell(CELL, 1)
    ctx = {"cell": cell}
    monkeypatch.setattr(metrics, "LOOP_STACK_COPIES", metrics.Gauge(
        "test_loop_stack_copies", registry=metrics.MetricsRegistry()))
    monkeypatch.setattr(metrics, "CACHEDOP_RESIDUAL_BYTES", metrics.Gauge(
        "test_residual_bytes", registry=metrics.MetricsRegistry()))
    assert readers["loop_stack_copies"].read(ctx) is None
    assert readers["residual_kb_per_token"].read(ctx) is None
    metrics.LOOP_STACK_COPIES.set(1)
    metrics.CACHEDOP_RESIDUAL_BYTES.set(4_701_814_784, kind="kept")
    assert readers["loop_stack_copies"].read(ctx) == 1
    assert readers["residual_kb_per_token"].read(ctx) == \
        pytest.approx(4_701_814_784 / 4096 / 1e3)
    # a program from before the gauges: nothing to read, and no error
    monkeypatch.delattr(metrics, "LOOP_STACK_COPIES")
    monkeypatch.delattr(metrics, "CACHEDOP_RESIDUAL_BYTES")
    assert readers["loop_stack_copies"].read(ctx) is None
    assert readers["residual_kb_per_token"].read(ctx) is None


# -- the configuration and its cell ----------------------------------------------
def test_configuration_keeps_the_published_sizes():
    cfg = cellmod.load_json(os.path.join(CDIR, "config.json"))
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 5632,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000, "total_ut_steps": 4,
        "early_exit_threshold": 1, "vocab_size": 49152}
    differ = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differ == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert cfg["num_hidden_layers"] == 4 and not cfg["tie_word_embeddings"]
    entry = next(c for c in cellmod.benchmark()["configs"]
                 if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == cfg["reduced"] and \
        entry["source"] == cfg["source"]


def test_reference_leaves_are_the_programs_parameters():
    small = cellmod.Cell(CELL, 1, rehearsal=True)
    net = small.model.build(small.cfg)
    got = [tuple(p.shape) for p in small.model.trainable(net)]
    assert got == [tuple(s) for _n, s, _k in small.spec]
    full = cellmod.Cell(CELL, 1)
    assert sum(int(np.prod(s)) for _n, s, _k in full.spec) == 406_884_353
    fl = full.flops
    assert full.units_per_step() == 4096
    assert fl.layer_matrix_params(full.cfg) == 51_380_224
    assert fl.layer_windows(full.cfg) == [None] * 16
    assert fl.train_flops_per_unit(full.cfg, full.traffic) == \
        pytest.approx(7.7513e9, rel=1e-4)


def _half_batch(monkeypatch):
    """Half of every batch repeats the other half."""
    real = cellmod.Cell.batches
    monkeypatch.setattr(cellmod.Cell, "batches", lambda self: [
        (jnp.concatenate([x[:1], x[:1]]), jnp.concatenate([y[:1], y[:1]]))
        for x, y in real(self)])


def _state_unchanged(monkeypatch):
    """The update hands back the weights and moments it was given."""
    monkeypatch.setattr(
        mx.optimizer.Adam, "fused_step",
        lambda self, index, weight, grad, state, lr, wd, t: (weight, state))


@pytest.mark.parametrize("plant", [_half_batch, _state_unchanged])
def test_cell_rehearsal_planted_fault_reads_not_correct(
        plant, float32_traffic, monkeypatch):
    plant(monkeypatch)
    res = run.run_cell(CELL, 7, 0.5, False, rehearsal=True)
    assert res["correct"] is False, res["compared"]
    if plant is _state_unchanged:  # reads 1 by the measure
        for number in ("grad_norm_gap", "dparam_norm_gap"):
            assert res["compared"][number]["value"] == pytest.approx(
                1.0, abs=1e-4)
