"""Max pooling's own backward (ops/nn.py `_max_pool`): one
`lax.reduce_window(max)` forward and XLA's SelectAndScatter backward, which
sends each window's cotangent whole to its FIRST maximum in window order.

Held against a plain float32 `lax.reduce_window` under `jax.vjp` (the rule
of MXNet's pooling backward and of the ResNet benchmark's reference), in
both layouts, both conventions, one to three spatial dimensions, a kernel of
volume above 64, bfloat16 and an integer input (forward only); and the
benchmark's reader of the layer's device time.  The temporaries of the
stem's pooling compiled for a described v5e are checked in
tests/test_flash_attention.py, the one file that describes the chip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import autograd, layout, nd
from mxnet_tpu.ops.registry import get_op

from chipbench import cell as cellmod
from chipbench import scope_reduce

POOL = get_op("Pooling")


def _params(**kw):
    return dict(POOL.normalize(dict(pool_type="max", **kw)))


def _reference(x, kernel, stride, pad, convention):
    """float32 NC[spatial] reduce_window with the op's padding rule."""
    n = len(kernel)
    lo_hi = []
    for i in range(n):
        hi = pad[i]
        if convention == "full":
            hi += (-(x.shape[2 + i] + 2 * pad[i] - kernel[i])) % stride[i]
        lo_hi.append((pad[i], hi))
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1) + kernel,
                             (1, 1) + stride, ((0, 0), (0, 0)) + tuple(lo_hi))


def _to_cl(a):
    return jnp.moveaxis(a, 1, -1)


CASES = [
    # id, shape, kernel, stride, pad, convention
    ("stem_2d", (2, 8, 16, 16), (3, 3), (2, 2), (1, 1), "valid"),
    ("full_2d", (2, 4, 11, 11), (3, 3), (2, 2), (1, 1), "full"),
    ("valid_1d", (2, 3, 17), (3,), (2,), (1,), "valid"),
    ("full_1d", (2, 3, 16), (4,), (3,), (0,), "full"),
    ("valid_3d", (2, 3, 6, 7, 8), (2, 3, 2), (2, 2, 1), (0, 1, 1), "valid"),
    ("vol_81", (2, 3, 20, 20), (9, 9), (3, 3), (0, 0), "valid"),
]


@pytest.mark.parametrize("where", ["NCHW", "NHWC", "NHWC_graph"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
@pytest.mark.parametrize("name,shape,kernel,stride,pad,convention", CASES,
                         ids=[c[0] for c in CASES])
def test_max_pool_matches_reduce_window(name, shape, kernel, stride, pad,
                                        convention, dtype, where):
    rs = np.random.RandomState(len(shape) + kernel[0])
    if dtype == "int32":
        x = jnp.asarray(rs.randint(-50, 50, shape), jnp.int32)
    else:
        # a ReLU's output, as the stem's pooling reads: windows of tied zeros
        x = jnp.asarray(np.maximum(rs.normal(size=shape), 0), dtype)
    p = _params(kernel=kernel, stride=stride, pad=pad,
                pooling_convention=convention)
    if where == "NHWC_graph":
        # the whole-graph layout pass hands the op channels-last data
        p["__io_layout__"] = "NHWC"
        run = lambda a: jnp.moveaxis(POOL.fn(p, _to_cl(a)), -1, 1)
    else:
        run = lambda a: POOL.fn(p, a)
    prev = layout.set_conv_layout("NHWC" if where == "NHWC" else "NCHW")
    try:
        if dtype == "int32":
            out = run(x)
            assert out.dtype == jnp.int32
            ref = _reference(x.astype(jnp.float32), kernel, stride, pad,
                             convention)
            np.testing.assert_array_equal(np.asarray(out, np.float32),
                                          np.asarray(ref))
            return
        out, vjp = jax.vjp(run, x)
        g = jnp.asarray(rs.normal(size=out.shape), dtype)
        dx, = vjp(g)
    finally:
        layout.set_conv_layout(prev)
    ref, ref_vjp = jax.vjp(
        lambda a: _reference(a, kernel, stride, pad, convention),
        x.astype(jnp.float32))
    dref, = ref_vjp(g.astype(jnp.float32))
    assert out.dtype == x.dtype and dx.dtype == x.dtype
    # the maximum of a window is one of its values: exact in any dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref))
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dref),
                                   rtol=1e-6, atol=1e-6)
    else:
        # up to prod(ceil(k / s)) cotangents meet at one input, summed in
        # bfloat16: its rounding, no more
        np.testing.assert_allclose(np.asarray(dx, np.float32),
                                   np.asarray(dref), rtol=2e-2, atol=2e-2)
    # every window hands its whole cotangent to one input
    np.testing.assert_allclose(float(jnp.sum(dx.astype(jnp.float32))),
                               float(jnp.sum(g.astype(jnp.float32))),
                               rtol=1e-2, atol=1e-2)


def test_a_tied_window_sends_its_whole_gradient_to_its_first_position():
    """A window of equal values: the first position in window order takes
    the cotangent whole, where a patch stack would split it among the tied
    positions (ones the reference never has)."""
    x = nd.array(np.full((1, 1, 4, 4), 2.0, "f"))
    x.attach_grad()
    with autograd.record():
        y = nd.Pooling(x, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                       pool_type="max")
    g = np.array([[[[1.0, 2.0], [3.0, 4.0]]]], "f")
    y.backward(nd.array(g))
    want = np.zeros((1, 1, 4, 4), "f")
    # output (i, j)'s window starts at input (2i - 1, 2j - 1); its first
    # position inside the input is (max(2i - 1, 0), max(2j - 1, 0))
    want[0, 0, 0, 0], want[0, 0, 0, 1] = 1.0, 2.0
    want[0, 0, 1, 0], want[0, 0, 1, 1] = 3.0, 4.0
    np.testing.assert_array_equal(x.grad.asnumpy(), want)
    assert x.grad.asnumpy().sum() == g.sum()


def test_a_large_kernel_trains_through_a_module():
    """A kernel of volume above 64 (SPP-style) now has a gradient: one
    Module step through it moves the convolution before it."""
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             name="c0")
    net = mx.sym.Pooling(net, kernel=(9, 9), stride=(9, 9), pool_type="max",
                         name="spp")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=3, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(0)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 3, 18, 18))],
             label_shapes=[("softmax_label", (2,))])
    mod.init_params(mx.init.Xavier())
    batch = mx.io.DataBatch(
        [nd.array(rs.normal(size=(2, 3, 18, 18)).astype("f"))],
        [nd.array(np.array([0, 2], "f"))])
    mod.forward_backward(batch)
    grad = mod._exec.grad_dict["c0_weight"].asnumpy()
    assert np.isfinite(grad).all() and np.abs(grad).sum() > 0


def test_the_pool_reader_sums_pooling_nodes_of_the_step_programs(
        monkeypatch):
    """`pool_device_ms.images`: every pass of every `Pooling` node in the
    step's own programs; nothing under another operator or another
    program; None, never 0, where no such node is on the path."""
    reader = cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", "pool_device_ms.py"),
        "test_reader_pool_device_ms")
    prog = "jit_mx_executor_fwd_bwd"
    rows = {(prog, "fwd", "Pooling", "resnetv10_pool0_fwd"): 1.25,
            (prog, "bwd", "Pooling", "resnetv10_pool0_fwd"): 1.5,
            (prog, "fwd", "Pooling", "resnetv10_pool1_fwd"): 0.25,
            (prog, "bwd", "Convolution", "resnetv10_conv0_fwd"): 7.0,
            (prog, "unsplit", None, "_unattributed"): 3.0,
            ("jit__threefry_split", "recordless", None,
             "jit__threefry_split"): 9.0}

    def analysis(kept):
        return {"rows": {k: {"ms": ms, "instructions": {}}
                         for k, ms in kept.items()}}

    monkeypatch.setattr(scope_reduce, "analyse", lambda c: analysis(rows))
    assert reader.read({}) == 3.0
    monkeypatch.setattr(scope_reduce, "analyse", lambda c: analysis(
        {k: v for k, v in rows.items() if k[2] != "Pooling"}))
    assert reader.read({}) is None
    monkeypatch.setattr(scope_reduce, "analyse", lambda c: None)
    assert reader.read({}) is None
