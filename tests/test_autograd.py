"""Autograd tests (parity model: tests/python/unittest/test_autograd.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.test_utils import assert_almost_equal


def test_basic_backward():
    x = nd.array(np.random.randn(3, 4).astype("f"))
    x.attach_grad()
    with autograd.record():
        y = (x * x + 2 * x).sum()
    y.backward()
    assert_almost_equal(x.grad.asnumpy(), 2 * x.asnumpy() + 2, rtol=1e-5)


def test_chain_and_branches():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        a = x * 2
        b = a + x
        c = (b * b).sum()
    c.backward()
    # d/dx (3x)^2 = 18x
    assert_almost_equal(x.grad.asnumpy(), 18 * x.asnumpy(), rtol=1e-5)


def test_grad_req_add():
    x = nd.array([1.0, 2.0])
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad.asnumpy(), 3 * 2 * x.asnumpy(), rtol=1e-5)


def test_head_grads():
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
    y.backward(nd.array([10.0, 20.0]))
    assert_almost_equal(x.grad.asnumpy(), [30.0, 60.0])


def test_recording_state():
    assert not autograd.is_recording()
    assert not autograd.is_training()
    with autograd.record():
        assert autograd.is_recording()
        assert autograd.is_training()
        with autograd.pause():
            assert not autograd.is_recording()
        assert autograd.is_recording()
    with autograd.record(train_mode=False):
        assert autograd.is_recording() and not autograd.is_training()
    with autograd.train_mode():
        assert autograd.is_training() and not autograd.is_recording()
    with autograd.predict_mode():
        assert not autograd.is_training()


def test_detach():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
        z = y.detach() * x
    z.backward()
    # z = const(4) * x → dz/dx = 4
    assert_almost_equal(x.grad.asnumpy(), [4.0])


def test_retain_graph():
    x = nd.array([3.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    y.backward(retain_graph=True)
    g1 = x.grad.asnumpy().copy()
    y.backward()
    assert_almost_equal(g1, [6.0])


def test_mark_variables():
    x = nd.array([1.0, 2.0])
    g = nd.zeros((2,))
    autograd.mark_variables([x], [g])
    with autograd.record():
        y = (x ** 3).sum()
    y.backward()
    assert_almost_equal(g.asnumpy(), 3 * x.asnumpy() ** 2, rtol=1e-5)


def test_custom_function():
    class Sigmoid(autograd.Function):
        def forward(self, x):
            y = nd.sigmoid(x)
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            (y,) = self.saved_tensors
            return dy * y * (1 - y)

    x = nd.array(np.random.randn(5).astype("f"))
    x.attach_grad()
    fn = Sigmoid()
    with autograd.record():
        y = fn(x)
    y.backward()
    s = 1 / (1 + np.exp(-x.asnumpy()))
    assert_almost_equal(x.grad.asnumpy(), s * (1 - s), rtol=1e-4, atol=1e-5)


def test_grad_function():
    x = nd.array([1.0, 2.0])
    with autograd.record():
        y = (x * x).sum()
    grads = autograd.grad([y], [x])
    assert_almost_equal(grads[0].asnumpy(), 2 * x.asnumpy())


def test_mutation_after_record():
    # gradient uses the value at record time, not after mutation
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * x
    x += 100  # mutate after recording
    y.backward()
    assert_almost_equal(x.grad.asnumpy(), [4.0])


def test_dropout_identity_grad():
    x = nd.ones((10, 10))
    x.attach_grad()
    with autograd.record(train_mode=False):
        y = nd.Dropout(x, p=0.5)
    y.backward()
    assert_almost_equal(x.grad.asnumpy(), np.ones((10, 10)))


def test_tape_key_recycling_stress():
    """Gradients stay correct when many intermediate NDArrays are garbage
    collected mid-record (CPython id reuse must not alias tape keys)."""
    import gc
    x = nd.array(np.ones((4, 4), "f"))
    x.attach_grad()
    with autograd.record():
        acc = x * 1.0
        for i in range(50):
            tmp = acc * 2.0
            acc = tmp * 0.5 + x * 0.0
            del tmp
            if i % 7 == 0:
                gc.collect()
        loss = acc.sum()
    loss.backward()
    assert_almost_equal(x.grad.asnumpy(), np.ones((4, 4), "f"),
                        rtol=1e-5, atol=1e-6)


def test_view_ops_recorded():
    """reshape/transpose/slice participate in the tape."""
    x = nd.array(np.arange(12, dtype="f").reshape(3, 4))
    x.attach_grad()
    with autograd.record():
        y = x.reshape((4, 3)).transpose()
        loss = (y[0:2] * 2).sum() + x[1].sum() + x[:, 0:2].sum()
    loss.backward()
    import jax
    import jax.numpy as jnp

    def f(a):
        yy = jnp.transpose(a.reshape(4, 3))
        return (yy[0:2] * 2).sum() + a[1].sum() + a[:, 0:2].sum()

    g_ref = np.asarray(jax.grad(f)(jnp.asarray(x.asnumpy())))
    assert_almost_equal(x.grad.asnumpy(), g_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("key", [
    (0, 1), (1, slice(0, 2)), (slice(None), 2), (-1, -1, -1), (0,),
    (1, slice(None), 3), (slice(0, 1), 1, slice(1, 3))],
    ids=lambda k: "-".join(str(i) if isinstance(i, int)
                           else f"{i.start}:{i.stop}" for i in k))
def test_int_and_slice_tuple_index_is_recorded(key):
    """x[i, j] and its mixes with unit-step slices reach the tape like
    x[i][j] does: numpy's values and shape, and the gradient of what was
    read (as a raw view it read right and its gradient was silently 0)."""
    x = nd.array(np.arange(24, dtype="f").reshape(2, 3, 4))
    x.attach_grad()
    with autograd.record():
        y = x[key]
        loss = (y * 2).sum()
    loss.backward()
    want = np.zeros((2, 3, 4), "f")
    want[key] = 2
    assert y.shape == x.asnumpy()[key].shape
    assert np.array_equal(y.asnumpy(), x.asnumpy()[key])
    assert np.array_equal(x.grad.asnumpy(), want)


def test_int_tuple_index_out_of_range_raises_under_record():
    x = nd.array(np.zeros((2, 3), "f"))
    with autograd.record(), pytest.raises(IndexError):
        x[0, 3]
