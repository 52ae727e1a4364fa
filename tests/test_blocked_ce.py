"""The blocked cross-entropy (ops/decoder.py `blocked_cross_entropy`)
against a plain log-softmax and pick: values and both gradients, in
float32 and in bfloat16, at a vocabulary that no block divides, and what
a recorded CachedOp call keeps of it."""
import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.ops import decoder as ops

N, V, D, BLOCK = 48, 1000, 32, 128   # 1000 = 7 x 128 + 104: the last block
                                     # is moved back over 24 taken rows


def _inputs(dtype, seed=0):
    rs = np.random.RandomState(seed)
    h = jnp.asarray(rs.randn(N, D).astype("f"), dtype)
    w = jnp.asarray(rs.randn(V, D).astype("f") * 0.3, dtype)
    # labels in the first block, the last block and the rows they share
    label = np.concatenate([rs.randint(0, V, N - 3), [0, V - 1, 7 * BLOCK - 1]])
    return h, w, jnp.asarray(label, jnp.int32)


def _plain(h, w, label):
    z = jnp.einsum("nd,vd->nv", h.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return -jnp.take_along_axis(jax.nn.log_softmax(z, axis=-1),
                                label[:, None], axis=-1)[:, 0]


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(ops, "CE_BLOCK", BLOCK)


def _blocked(h, w, label, block=BLOCK):
    return ops._blocked_ce(h, w, label, block)


# float32: the two differ by the order of the sums alone.  bfloat16: the
# op multiplies the bfloat16 operands with float32 sums, the plain form
# the same operands in float32, so the logits agree to float32 round-off
# and what is left is the cast of dh and dW to bfloat16 (8 bits)
TOL = {"float32": (1e-5, 1e-6), "bfloat16": (1e-2, 1e-3)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_and_both_gradients_match_log_softmax(dtype):
    h, w, label = _inputs(jnp.dtype(dtype))
    g = jnp.asarray(np.random.RandomState(3).rand(N).astype("f"))
    got, pull = jax.vjp(lambda a, b: _blocked(a, b, label), h, w)
    want, pull_ref = jax.vjp(lambda a, b: _plain(a, b, label), h, w)
    rtol, atol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert got.dtype == jnp.float32
    for a, b in zip(pull(g), pull_ref(g)):
        assert a.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(np.asarray(a, "f"), np.asarray(b, "f"),
                                   rtol=rtol, atol=atol)


def test_one_block_of_the_whole_vocabulary_is_the_same():
    h, w, label = _inputs(jnp.float32, seed=1)
    np.testing.assert_allclose(np.asarray(_blocked(h, w, label, V)),
                               np.asarray(_blocked(h, w, label)), rtol=1e-6)
    dw = jax.grad(lambda b: _blocked(h, b, label).sum())(w)
    dw_one = jax.grad(lambda b: _blocked(h, b, label, V).sum())(w)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_one), rtol=1e-5,
                               atol=1e-7)


def test_no_more_than_one_block_of_logits_in_either_pass():
    """The forward and the backward loop over the blocks; no value of the
    program is as large as N x V."""
    h, w, label = _inputs(jnp.bfloat16)

    def sizes(jaxpr, into):
        for eqn in jaxpr.eqns:
            into.extend(int(np.prod(v.aval.shape)) for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                sizes(sub, into)
        return into

    jaxpr = jax.make_jaxpr(jax.grad(
        lambda a, b: _blocked(a, b, label).sum(), argnums=(0, 1)))(h, w)
    largest = max(sizes(jaxpr.jaxpr, []))
    assert largest == V * D, largest   # dW itself; a block is N x 128


def test_through_the_ndarray_front_end_and_a_recorded_block():
    """`nd.blocked_cross_entropy` eagerly, and inside a hybridized block
    under autograd: gradients for the hidden states and the matrix, none
    for the labels."""
    h, w, label = _inputs(jnp.float32, seed=2)

    class Loss(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.weight = self.params.get("weight", shape=(V, D))

        def hybrid_forward(self, F, x, y, weight):
            return F.blocked_cross_entropy(x, weight, y)

    eager = nd.blocked_cross_entropy(nd.NDArray(h), nd.NDArray(w),
                                     nd.NDArray(label.astype(jnp.float32)))
    np.testing.assert_allclose(eager.asnumpy(), np.asarray(_plain(h, w, label)),
                               rtol=1e-5, atol=1e-5)
    blk = Loss(prefix="l_")
    blk.initialize(ctx=mx.cpu())
    blk.weight.set_data(nd.NDArray(w))
    blk.hybridize()
    x = nd.NDArray(h)
    x.attach_grad()
    with autograd.record():
        out = blk(x, nd.NDArray(label.astype(jnp.float32)))
    out.backward()
    dh, dw = jax.grad(lambda a, b: _plain(a, b, label).sum(),
                      argnums=(0, 1))(h, w)
    np.testing.assert_allclose(x.grad.asnumpy(), np.asarray(dh), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(blk.weight.grad().asnumpy(), np.asarray(dw),
                               rtol=1e-4, atol=1e-6)
    # the backward program walks the vocabulary once: the loop of the
    # backward pass, not the forward's again (the log-sum-exp is kept)
    op = blk._cached_op
    args = {"data0": h, "data1": label.astype(jnp.float32), "l_weight": w}
    key = jax.random.PRNGKey(0)
    outs, new_aux, pull = op._fwd(args, {}, key, True, True)
    bwd = jax.make_jaxpr(op._bwd.__wrapped__)(
        pull, (args, {}, key), ((jnp.ones_like(outs[0]),), {})).jaxpr
    prims = collections.Counter()

    def count(jaxpr):
        for eqn in jaxpr.eqns:
            prims[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                count(sub)

    count(bwd)
    assert prims["while"] + prims["scan"] == 1, prims
    assert prims["dot_general"] == 3, prims
