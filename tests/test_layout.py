"""NHWC internal-layout mode (VERDICT r3 #1a; SURVEY.md §7 NCHW→NHWC).

User-facing semantics are NCHW either way — these tests pin that the
channels-last lowering in ops/nn.py (conv/deconv/pool/BN) is numerically
identical to the channels-first one, forward AND backward, for every
configuration the model zoo uses.  No cell has measured NHWC on the chip
(ROADMAP.md S2); here we prove the flag can be flipped without changing
results.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, layout, nd


@pytest.fixture
def nhwc():
    prev = layout.set_conv_layout("NHWC")
    yield
    layout.set_conv_layout(prev)


def _both_layouts(fn):
    """Run fn() under NCHW then NHWC; return both results."""
    prev = layout.set_conv_layout("NCHW")
    try:
        a = fn()
        layout.set_conv_layout("NHWC")
        b = fn()
    finally:
        layout.set_conv_layout(prev)
    return a, b


def _close(a, b, tol=1e-5):
    np.testing.assert_allclose(a, b, rtol=tol, atol=tol)


rs = np.random.RandomState(7)


@pytest.mark.parametrize("cfg", [
    dict(kernel=(3, 3), stride=(1, 1), pad=(1, 1), num_filter=8),
    dict(kernel=(3, 3), stride=(2, 2), pad=(1, 1), num_filter=8),
    dict(kernel=(1, 1), stride=(1, 1), pad=(0, 0), num_filter=16),
    dict(kernel=(3, 3), stride=(1, 1), pad=(2, 2), dilate=(2, 2),
         num_filter=8),
    dict(kernel=(3, 3), stride=(1, 1), pad=(1, 1), num_filter=8,
         num_group=4),
    dict(kernel=(7, 7), stride=(2, 2), pad=(3, 3), num_filter=8,
         no_bias=True),
])
def test_convolution_layout_equivalence(cfg):
    x = nd.array(rs.normal(size=(2, 8, 14, 14)).astype("f"))
    cin = 8 // cfg.get("num_group", 1)
    w = nd.array(rs.normal(
        size=(cfg["num_filter"], cin) + cfg["kernel"]).astype("f") * 0.1)
    b = nd.array(rs.normal(size=(cfg["num_filter"],)).astype("f"))

    def run():
        args = [x, w] if cfg.get("no_bias") else [x, w, b]
        return nd.Convolution(*args, **cfg).asnumpy()

    a, bb = _both_layouts(run)
    _close(a, bb)


@pytest.mark.parametrize("rank,shape,kernel", [
    (1, (2, 4, 9), (3,)),
    (3, (2, 4, 5, 6, 7), (2, 2, 2)),
])
def test_convolution_layout_equivalence_1d_3d(rank, shape, kernel):
    x = nd.array(rs.normal(size=shape).astype("f"))
    w = nd.array(rs.normal(size=(6, 4) + kernel).astype("f") * 0.1)
    b = nd.array(rs.normal(size=(6,)).astype("f"))

    def run():
        return nd.Convolution(x, w, b, kernel=kernel, num_filter=6).asnumpy()

    a, bb = _both_layouts(run)
    _close(a, bb)


def test_deconvolution_layout_equivalence():
    x = nd.array(rs.normal(size=(2, 6, 7, 7)).astype("f"))
    w = nd.array(rs.normal(size=(6, 4, 4, 4)).astype("f") * 0.1)

    def run():
        return nd.Deconvolution(x, w, kernel=(4, 4), stride=(2, 2),
                                pad=(1, 1), num_filter=4).asnumpy()

    a, b = _both_layouts(run)
    _close(a, b)


@pytest.mark.parametrize("pool_type", ["max", "avg", "sum"])
@pytest.mark.parametrize("convention", ["valid", "full"])
def test_pooling_layout_equivalence(pool_type, convention):
    x = nd.array(rs.normal(size=(2, 5, 11, 11)).astype("f"))

    def run():
        return nd.Pooling(x, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                          pool_type=pool_type,
                          pooling_convention=convention).asnumpy()

    a, b = _both_layouts(run)
    _close(a, b)


@pytest.mark.parametrize("train", [True, False])
def test_batchnorm_layout_equivalence(train):
    x = nd.array(rs.normal(size=(4, 6, 5, 5)).astype("f"))
    gamma = nd.array(rs.uniform(0.5, 1.5, 6).astype("f"))
    beta = nd.array(rs.normal(size=6).astype("f"))
    mm = nd.array(rs.normal(size=6).astype("f"))
    mv = nd.array(rs.uniform(0.5, 1.5, 6).astype("f"))

    def run():
        with autograd.record(train_mode=train):
            out = nd.BatchNorm(x, gamma, beta, mm.copy(), mv.copy(),
                               fix_gamma=False)
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out.asnumpy()

    a, b = _both_layouts(run)
    _close(a, b)


def test_gluon_convnet_forward_backward_layout_equivalence():
    """Full conv→BN→relu→pool→dense net: outputs AND weight grads match
    across layouts (the boundary-transpose-cancellation correctness
    proof for a real chain)."""
    x_np = rs.normal(size=(2, 3, 16, 16)).astype("f")

    def run():
        mx.random.seed(11)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Conv2D(8, 3, padding=1),
                gluon.nn.BatchNorm(),
                gluon.nn.Activation("relu"),
                gluon.nn.MaxPool2D(2),
                gluon.nn.Conv2D(4, 3, padding=1),
                gluon.nn.GlobalAvgPool2D(),
                gluon.nn.Dense(5))
        net.initialize(mx.init.Xavier(rnd_type="gaussian", magnitude=2),
                       force_reinit=True)
        x = nd.array(x_np)
        with autograd.record():
            out = net(x)
            loss = (out * out).sum()
        loss.backward()
        # positional: auto-naming counters differ between the two builds
        grads = [v.grad().asnumpy() for v in
                 net.collect_params().values() if v.grad_req != "null"]
        return out.asnumpy(), grads

    (out_a, g_a), (out_b, g_b) = _both_layouts(run)
    _close(out_a, out_b, tol=1e-4)
    assert len(g_a) == len(g_b) > 0
    for a, b in zip(g_a, g_b):
        _close(a, b, tol=1e-4)


def test_module_resnet_style_fit_layout_equivalence():
    """symbol-API conv net trains identically under both layouts."""
    import mxnet_tpu.symbol as sym

    x_np = rs.normal(size=(4, 3, 12, 12)).astype("f")
    y_np = rs.randint(0, 4, (4,)).astype("f")

    def run():
        data = sym.Variable("data")
        net = sym.Convolution(data, kernel=(3, 3), num_filter=6,
                              pad=(1, 1), name="c1")
        net = sym.BatchNorm(net, name="bn1")
        net = sym.Activation(net, act_type="relu")
        net = sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                          pool_type="max")
        net = sym.FullyConnected(sym.Flatten(net), num_hidden=4)
        net = sym.SoftmaxOutput(net, name="softmax")
        mod = mx.mod.Module(net, label_names=("softmax_label",))
        mod.bind(data_shapes=[("data", x_np.shape)],
                 label_shapes=[("softmax_label", y_np.shape)])
        mod.init_params(mx.init.Xavier(), force_init=True)
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        from mxnet_tpu.io import NDArrayIter
        it = NDArrayIter(x_np, y_np, batch_size=4, label_name="softmax_label")
        batch = next(iter(it))
        for _ in range(3):
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        return [a.asnumpy() for a in mod.get_outputs()]

    mx.random.seed(3)
    outs = {}
    for lay in ("NCHW", "NHWC"):
        prev = layout.set_conv_layout(lay)
        try:
            mx.random.seed(3)
            outs[lay] = run()
        finally:
            layout.set_conv_layout(prev)
    for a, b in zip(outs["NCHW"], outs["NHWC"]):
        _close(a, b, tol=2e-4)


def test_whole_graph_cl_transposes_only_at_edges():
    """VERDICT r4 #1b: the GraphPlan-level channels-last pass must leave
    transposes only at true graph edges (+ one OIHW->HWIO per conv
    weight), not a to_cl/from_cl pair around every spatial op — the
    per-op mode measured SLOWER than NCHW on-chip because XLA does not
    reliably cancel the pairs.  Pins (a) the jaxpr transpose counts,
    (b) forward AND gradient equivalence across all three modes."""
    import os
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.symbol.graph import GraphPlan

    net = vision.resnet18_v1(classes=10, prefix="wgcl_")
    out = net(mx.sym.Variable("data"))
    plan = GraphPlan(out)
    B = 2
    arg_shapes, _, aux_shapes = out.infer_shape(data=(B, 3, 16, 16))
    rs2 = np.random.RandomState(0)
    args = {n: jnp.asarray(rs2.normal(0, 0.05, s).astype("f"))
            for n, s in zip(out.list_arguments(), arg_shapes)
            if n != "data"}
    aux = {n: (jnp.ones if n.endswith(("running_var", "gamma"))
               else jnp.zeros)(s, jnp.float32)
           for n, s in zip(out.list_auxiliary_states(), aux_shapes)}
    x = jnp.asarray(rs2.normal(0, 1, (B, 3, 16, 16)).astype("f"))
    key = jax.random.PRNGKey(0)
    n_convs = sum(1 for s in plan.steps if s.op.name == "Convolution")

    def make_loss(tag):  # fresh fn object per mode (trace caches are
        def loss(a, xx, _tag=tag):  # not keyed on the layout flag)
            d = dict(a)
            d["data"] = xx
            outs, _ = plan.run(d, aux, key, True)
            return jnp.sum(outs[0] ** 2)
        return loss

    res = {}
    prev_wg = os.environ.get("MXNET_TPU_CL_WHOLEGRAPH")
    try:
        for mode, lay, wg in (("nchw", "NCHW", "1"),
                              ("perop", "NHWC", "0"),
                              ("whole", "NHWC", "1")):
            os.environ["MXNET_TPU_CL_WHOLEGRAPH"] = wg
            prev = layout.set_conv_layout(lay)
            try:
                f = make_loss(mode)
                txt = str(jax.make_jaxpr(f)(args, x))
                val, grads = jax.jit(jax.value_and_grad(f))(args, x)
                res[mode] = (txt.count("transpose["), float(val),
                             jax.tree_util.tree_map(np.asarray, grads))
            finally:
                layout.set_conv_layout(prev)
    finally:
        if prev_wg is None:
            os.environ.pop("MXNET_TPU_CL_WHOLEGRAPH", None)
        else:
            os.environ["MXNET_TPU_CL_WHOLEGRAPH"] = prev_wg

    # (a) transpose economy: whole-graph leaves ~n_convs weight
    # transposes + graph-edge conversions; per-op pays a pair per
    # spatial op on top (resnet18: 103 vs 23 measured)
    n_whole, n_perop = res["whole"][0], res["perop"][0]
    assert n_whole <= n_convs + 6, (n_whole, n_convs)
    assert n_perop > n_whole + 2 * n_convs, (n_perop, n_whole)

    # (b) numerics: loss + every grad agree across modes
    for m in ("perop", "whole"):
        np.testing.assert_allclose(res[m][1], res["nchw"][1], rtol=1e-5)
        for k in res["nchw"][2]:
            np.testing.assert_allclose(
                res[m][2][k], res["nchw"][2][k], rtol=1e-4, atol=1e-5,
                err_msg=f"{m}:{k}")


def test_whole_graph_cl_segmented_remat():
    """The sqrt(N)-remat segmented runner shares the layout pass: CL
    values crossing checkpoint boundaries keep their physical layout,
    and outputs still convert back at the graph edge."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.symbol.graph import GraphPlan

    sym = mx.sym.Variable("data")
    net = mx.sym.Convolution(sym, kernel=(3, 3), num_filter=8, pad=(1, 1),
                             name="c0")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Convolution(net, kernel=(3, 3), num_filter=8, pad=(1, 1),
                             name="c1")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="avg")
    plan = GraphPlan(net)
    arg_shapes, _, _ = net.infer_shape(data=(2, 3, 8, 8))
    rs2 = np.random.RandomState(1)
    args = {n: jnp.asarray(rs2.normal(0, 0.1, s).astype("f"))
            for n, s in zip(net.list_arguments(), arg_shapes)}
    key = jax.random.PRNGKey(0)

    def run(segments, tag):
        def f(a, _tag=tag):
            outs, _ = plan.run(a, {}, key, True, segments=segments)
            return outs[0]
        return np.asarray(jax.jit(f)(args))

    ref = run(1, "nchw-1seg")
    prev = layout.set_conv_layout("NHWC")
    try:
        got1 = run(1, "nhwc-1seg")
        got3 = run(3, "nhwc-3seg")
    finally:
        layout.set_conv_layout(prev)
    np.testing.assert_allclose(got1, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got3, ref, rtol=1e-5, atol=1e-6)


def test_whole_graph_cl_mixed_paths_1d():
    """Mixed paths composed: 1D convs (NWC dimension numbers), BN/relu
    riding the CL tag, a channel-axis Concat that STAYS channels-last
    (the pass remaps dim=1 to the minor axis), global pooling, FC —
    identical to NCHW."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.symbol.graph import GraphPlan

    d = mx.sym.Variable("data")
    c = mx.sym.Convolution(d, kernel=(3,), num_filter=6, pad=(1,),
                           name="m1c1")
    c = mx.sym.BatchNorm(c, name="m1bn")
    c = mx.sym.Activation(c, act_type="relu")
    c2 = mx.sym.Convolution(c, kernel=(3,), num_filter=6, pad=(1,),
                            name="m1c2")
    s = mx.sym.Concat(c, c2, dim=1)      # stays CL (dim remapped)
    p = mx.sym.Pooling(s, global_pool=True, pool_type="avg")
    out = mx.sym.FullyConnected(mx.sym.Flatten(p), num_hidden=3)
    plan = GraphPlan(out)
    arg_shapes, _, aux_shapes = out.infer_shape(data=(2, 4, 16))
    rs2 = np.random.RandomState(0)
    args = {n: jnp.asarray(rs2.normal(0, 0.1, sh).astype("f"))
            for n, sh in zip(out.list_arguments(), arg_shapes)
            if n != "data"}
    aux = {n: (jnp.ones if n.endswith(("var", "gamma"))
               else jnp.zeros)(sh, jnp.float32)
           for n, sh in zip(out.list_auxiliary_states(), aux_shapes)}
    x = jnp.asarray(rs2.normal(0, 1, (2, 4, 16)).astype("f"))
    key = jax.random.PRNGKey(0)

    def make(tag):
        def f(a, xx, _t=tag):
            dd = dict(a)
            dd["data"] = xx
            o, _ = plan.run(dd, aux, key, True)
            return o[0]
        return f

    prev = layout.set_conv_layout("NCHW")
    try:
        ref = np.asarray(jax.jit(make("nchw"))(args, x))
        layout.set_conv_layout("NHWC")
        got = np.asarray(jax.jit(make("nhwc"))(args, x))
    finally:
        layout.set_conv_layout(prev)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
