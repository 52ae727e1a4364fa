"""The attention kernels inside a loop body, on the chip at the looped
cell's own attention shape: 16 heads of 128 over as many key/value heads
(group 1), 2 x 2,048 tokens, one layer of the published widths applied four
times by `contrib.foreach`.  The kernels' path counters read `kernel` for
the forward's recording call and the backward (traced once a layer: the
loop's body is one piece of program text) and never `reference`; the loop
holds the stack once; loss and gradients agree with the same net over
dense attention.

tests/test_consistency_harness.py runs this file on the CPU at a toy size
through the kernels' interpreter."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.model_zoo import decoder
from mxnet_tpu.observability import metrics


def _loss_and_grads(impl, sizes, weights, x, y, ctx):
    vocab, dim, heads, hd, ffn = sizes
    attn = functools.partial(decoder.GroupedQueryAttention, dim, heads, heads,
                             hd, rope=True, rope_base=1e6, attn_type=impl)
    net = decoder.LoopedLM(vocab, dim, 1, 4, attn, ffn)
    net.initialize(mx.init.Zero(), ctx=ctx)
    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    for p, w in zip(params, weights):
        p.set_data(nd.NDArray(jnp.asarray(w), ctx))
    loss = decoder.LoopedLMLoss(net)
    net.hybridize()
    loss.hybridize()
    with autograd.record():
        out = loss(x, y)
    out.backward()
    return out.asnumpy(), [p.grad().asnumpy() for p in params]


def test_kernels_run_inside_the_loop_body_at_sixteen_heads_of_128():
    on_chip = jax.default_backend() == "tpu"
    ctx = mx.tpu(0) if on_chip else mx.cpu()
    sizes = (4096, 2048, 16, 128, 5632) if on_chip else (256, 64, 4, 16, 160)
    seq = 2048 if on_chip else 128
    vocab, dim = sizes[:2]
    rs = np.random.RandomState(7)
    probe = decoder.LoopedLM(vocab, dim, 1, 4, functools.partial(
        decoder.GroupedQueryAttention, dim, sizes[2], sizes[2], sizes[3]),
        sizes[4])
    weights = [np.ones(p.shape, "f") if "gamma" in p.name else
               rs.randn(*p.shape).astype("f")
               * (1.0 if "tok" in p.name else 0.02)
               for p in probe.collect_params().values()
               if p.grad_req != "null"]
    x, y = (nd.NDArray(jnp.asarray(rs.randint(0, vocab, (2, seq)),
                                   jnp.float32), ctx) for _ in range(2))
    before = {path: metrics.FLASH_BWD.get(path=path)
              for path in ("kernel", "reference")}
    got = _loss_and_grads("flash", sizes, weights, x, y, ctx)
    assert metrics.FLASH_BWD.get(path="kernel") == before["kernel"] + 1
    assert metrics.FLASH_BWD.get(path="reference") == before["reference"]
    assert metrics.LOOP_STACK_COPIES.get() == 1
    assert metrics.LOOP_APPLICATIONS.get() == 4
    want = _loss_and_grads("dense", sizes, weights, x, y, ctx)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-3)
    for g, w in zip(got[1], want[1]):
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, atol=2e-2 * np.abs(w).max() + 1e-9,
                                   rtol=0)
