"""Gluon tests (parity model: tests/python/unittest/test_gluon.py,
test_gluon_data.py, test_gluon_rnn.py, test_loss.py in the reference)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn, rnn, loss as gloss
from mxnet_tpu.test_utils import assert_almost_equal


# ---------------------------------------------------------------- blocks

def test_dense_forward():
    net = nn.Dense(8, in_units=4, activation="relu")
    net.initialize()
    x = nd.random.uniform(shape=(2, 4))
    out = net(x)
    assert out.shape == (2, 8)
    assert (out.asnumpy() >= 0).all()


def test_dense_deferred_init():
    net = nn.Dense(5)
    net.initialize()
    x = nd.ones((3, 7))
    out = net(x)
    assert out.shape == (3, 5)
    assert net.weight.shape == (5, 7)


def test_hybridize_consistency():
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"),
                nn.Dense(4))
    net.initialize()
    x = nd.random.uniform(shape=(2, 8))
    eager = net(x).asnumpy()
    net.hybridize()
    compiled = net(x).asnumpy()
    assert_almost_equal(eager, compiled, rtol=1e-5, atol=1e-5)
    # second call hits the cached executable
    compiled2 = net(x).asnumpy()
    assert_almost_equal(eager, compiled2, rtol=1e-5, atol=1e-5)


def test_sequential_nonhybrid():
    net = nn.Sequential()
    net.add(nn.Dense(4), nn.Dense(2))
    net.initialize()
    assert net(nd.ones((1, 3))).shape == (1, 2)


def test_collect_params_select():
    net = nn.HybridSequential(prefix="model_")
    with net.name_scope():
        net.add(nn.Dense(4, prefix="fc1_"), nn.Dense(2, prefix="fc2_"))
    params = net.collect_params()
    assert any("fc1_weight" in k for k in params.keys())
    sel = net.collect_params(".*fc2.*")
    assert all("fc2" in k for k in sel.keys())
    assert len(list(sel.keys())) == 2


def test_save_load_params(tmp_path):
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    x = nd.random.uniform(shape=(2, 3))
    ref = net(x).asnumpy()
    fname = str(tmp_path / "net.params")
    net.save_params(fname)

    net2 = nn.HybridSequential(prefix="net_")
    with net2.name_scope():
        net2.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net2.load_params(fname)
    assert_almost_equal(ref, net2(x).asnumpy())


def test_parameter_grad_req():
    p = gluon.Parameter("w", shape=(3, 3))
    p.initialize()
    p.zero_grad()
    assert p.grad().shape == (3, 3)
    p.grad_req = "null"
    assert p._grad is None


def test_block_cast():
    net = nn.Dense(4, in_units=3)
    net.initialize()
    net.cast("float16")
    assert net.weight.dtype == np.float16


# ------------------------------------------------------------- conv/pool

def test_conv2d_shapes():
    net = nn.Conv2D(8, kernel_size=3, padding=1, in_channels=3)
    net.initialize()
    out = net(nd.random.uniform(shape=(2, 3, 16, 16)))
    assert out.shape == (2, 8, 16, 16)


def test_conv2d_strided():
    net = nn.Conv2D(4, kernel_size=3, strides=2)
    net.initialize()
    out = net(nd.ones((1, 2, 9, 9)))
    assert out.shape == (1, 4, 4, 4)


def test_conv1d_conv3d():
    c1 = nn.Conv1D(4, kernel_size=3)
    c1.initialize()
    assert c1(nd.ones((1, 2, 10))).shape == (1, 4, 8)
    c3 = nn.Conv3D(2, kernel_size=2)
    c3.initialize()
    assert c3(nd.ones((1, 1, 4, 4, 4))).shape == (1, 2, 3, 3, 3)


def test_conv_transpose():
    net = nn.Conv2DTranspose(3, kernel_size=2, strides=2, in_channels=4)
    net.initialize()
    out = net(nd.ones((1, 4, 5, 5)))
    assert out.shape == (1, 3, 10, 10)


def test_pooling():
    x = nd.random.uniform(shape=(1, 2, 8, 8))
    assert nn.MaxPool2D(2)(x).shape == (1, 2, 4, 4)
    assert nn.AvgPool2D(2)(x).shape == (1, 2, 4, 4)
    assert nn.GlobalAvgPool2D()(x).shape == (1, 2, 1, 1)
    assert nn.GlobalMaxPool2D()(x).shape == (1, 2, 1, 1)
    gap = nn.GlobalAvgPool2D()(x).asnumpy()
    assert_almost_equal(gap.reshape(1, 2), x.asnumpy().mean(axis=(2, 3)),
                        rtol=1e-5, atol=1e-5)


def test_batchnorm_train_vs_eval():
    net = nn.BatchNorm(in_channels=4)
    net.initialize()
    x = nd.random.uniform(shape=(8, 4, 3, 3))
    with autograd.record():
        out_train = net(x)
    # training-mode output is normalized per batch
    m = out_train.asnumpy().mean(axis=(0, 2, 3))
    assert np.abs(m).max() < 1e-2
    out_eval = net(x)  # uses running stats
    assert out_eval.shape == x.shape


def test_dropout_modes():
    net = nn.Dropout(0.5)
    x = nd.ones((100, 100))
    # eval mode: identity
    assert_almost_equal(net(x).asnumpy(), x.asnumpy())
    with autograd.record(train_mode=True):
        y = net(x).asnumpy()
    assert (y == 0).mean() > 0.3  # roughly half dropped


def test_embedding_flatten():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    idx = nd.array([[1, 2], [3, 4]])
    assert emb(idx).shape == (2, 2, 4)
    assert nn.Flatten()(nd.ones((2, 3, 4))).shape == (2, 12)


def test_norm_layers():
    x = nd.random.uniform(shape=(2, 3, 4))
    ln = nn.LayerNorm()
    ln.initialize()
    y = ln(x).asnumpy()
    assert_almost_equal(y.mean(axis=-1), np.zeros((2, 3)), atol=1e-5)
    inorm = nn.InstanceNorm()
    inorm.initialize()
    assert inorm(nd.random.uniform(shape=(2, 3, 4, 4))).shape == (2, 3, 4, 4)


def test_lambda_blocks():
    sq = nn.HybridLambda(lambda F, x: x * x)
    assert_almost_equal(sq(nd.array([2.0])).asnumpy(), np.array([4.0]))
    lam = nn.Lambda(lambda x: x + 1)
    assert_almost_equal(lam(nd.array([1.0])).asnumpy(), np.array([2.0]))


# ----------------------------------------------------------------- losses

def test_l2_l1_loss():
    pred = nd.array([[1.0, 2.0], [3.0, 4.0]])
    label = nd.array([[1.5, 2.0], [2.0, 4.0]])
    l2 = gloss.L2Loss()(pred, label).asnumpy()
    assert_almost_equal(l2, np.array([0.0625, 0.25]), rtol=1e-5, atol=1e-6)
    l1 = gloss.L1Loss()(pred, label).asnumpy()
    assert_almost_equal(l1, np.array([0.25, 0.5]), rtol=1e-5, atol=1e-6)


def test_softmax_ce_loss():
    pred = nd.array([[10.0, -10.0], [-10.0, 10.0]])
    label = nd.array([0, 1])
    l = gloss.SoftmaxCrossEntropyLoss()(pred, label).asnumpy()
    assert (l < 1e-4).all()
    # sparse_label=False path
    onehot = nd.array([[1.0, 0.0], [0.0, 1.0]])
    l2 = gloss.SoftmaxCrossEntropyLoss(sparse_label=False)(pred, onehot)
    assert_almost_equal(l, l2.asnumpy(), rtol=1e-4, atol=1e-5)


def test_sigmoid_bce_loss():
    pred = nd.array([[100.0], [-100.0]])
    label = nd.array([[1.0], [0.0]])
    l = gloss.SigmoidBinaryCrossEntropyLoss()(pred, label).asnumpy()
    assert (l < 1e-4).all()


def test_misc_losses_shapes():
    pred = nd.random.uniform(shape=(4, 5))
    label = nd.random.uniform(shape=(4, 5))
    for L in (gloss.HuberLoss(), gloss.HingeLoss(), gloss.SquaredHingeLoss(),
              gloss.LogisticLoss(), gloss.KLDivLoss()):
        out = L(pred, label)
        assert out.shape == (4,), type(L).__name__
    t = gloss.TripletLoss()(pred, label, nd.random.uniform(shape=(4, 5)))
    assert t.shape == (4,)


def test_loss_sample_weight():
    pred = nd.ones((2, 3))
    label = nd.zeros((2, 3))
    w = nd.array([[1.0], [0.0]])
    l = gloss.L2Loss()(pred, label, w).asnumpy()
    assert l[1] == 0 and l[0] > 0


# ------------------------------------------------------------------ rnn

def test_lstm_cell_unroll():
    cell = rnn.LSTMCell(16, input_size=8)
    cell.initialize()
    inputs = nd.random.uniform(shape=(2, 5, 8))  # NTC
    outputs, states = cell.unroll(5, inputs, layout="NTC", merge_outputs=True)
    assert outputs.shape == (2, 5, 16)
    assert len(states) == 2 and states[0].shape == (2, 16)


def test_gru_rnn_cells():
    for cell_t in (rnn.GRUCell, rnn.RNNCell):
        cell = cell_t(8, input_size=4)
        cell.initialize()
        out, st = cell(nd.ones((3, 4)), cell.begin_state(batch_size=3))
        assert out.shape == (3, 8)


def test_sequential_rnn_cell():
    cell = rnn.SequentialRNNCell()
    cell.add(rnn.LSTMCell(8, input_size=4))
    cell.add(rnn.LSTMCell(6, input_size=8))
    cell.initialize()
    outputs, _ = cell.unroll(3, nd.ones((2, 3, 4)), layout="NTC",
                             merge_outputs=True)
    assert outputs.shape == (2, 3, 6)


def test_bidirectional_cell():
    cell = rnn.BidirectionalCell(rnn.LSTMCell(4, input_size=3),
                                 rnn.LSTMCell(4, input_size=3))
    cell.initialize()
    outputs, _ = cell.unroll(5, nd.ones((2, 5, 3)), layout="NTC",
                             merge_outputs=True)
    assert outputs.shape == (2, 5, 8)


def test_residual_dropout_zoneout_cells():
    cell = rnn.ResidualCell(rnn.LSTMCell(4, input_size=4))
    cell.initialize()
    out, _ = cell.unroll(3, nd.ones((2, 3, 4)), layout="NTC",
                         merge_outputs=True)
    assert out.shape == (2, 3, 4)
    dc = rnn.DropoutCell(0.5)
    out, _ = dc.unroll(3, nd.ones((2, 3, 4)), layout="NTC",
                       merge_outputs=True)
    assert out.shape == (2, 3, 4)


def test_lstm_layer():
    layer = rnn.LSTM(16, num_layers=2)
    layer.initialize()
    x = nd.random.uniform(shape=(5, 3, 8))  # TNC default
    out = layer(x)
    assert out.shape == (5, 3, 16)
    # with explicit states
    states = layer.begin_state(batch_size=3)
    out, new_states = layer(x, states)
    assert out.shape == (5, 3, 16)
    assert new_states[0].shape == (2, 3, 16)


def test_rnn_layer_bidirectional():
    layer = rnn.LSTM(8, bidirectional=True)
    layer.initialize()
    out = layer(nd.ones((4, 2, 5)))
    assert out.shape == (4, 2, 16)


def test_rnn_gru_layers():
    for layer_t in (rnn.RNN, rnn.GRU):
        layer = layer_t(8)
        layer.initialize()
        assert layer(nd.ones((4, 2, 5))).shape == (4, 2, 8)


# ------------------------------------------------------------- training

def test_trainer_step_sgd():
    net = nn.Dense(1, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array([[1.0, 2.0]])
    w0 = net.weight.data().asnumpy().copy()
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(1)
    w1 = net.weight.data().asnumpy()
    assert not np.allclose(w0, w1)


def test_trainer_convergence():
    rs = np.random.RandomState(0)
    x = rs.randn(200, 4).astype("f")
    true_w = rs.randn(4, 1).astype("f")
    y = x @ true_w
    net = nn.Dense(1, in_units=4)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.1})
    l2 = gloss.L2Loss()
    for _ in range(60):
        with autograd.record():
            loss = l2(net(nd.array(x)), nd.array(y))
        loss.backward()
        trainer.step(200)
    final = loss.asnumpy().mean()
    assert final < 1e-2, final


def test_trainer_save_load_states(tmp_path):
    net = nn.Dense(2, in_units=2)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "adam")
    with autograd.record():
        loss = net(nd.ones((1, 2))).sum()
    loss.backward()
    trainer.step(1)
    fname = str(tmp_path / "trainer.states")
    trainer.save_states(fname)
    trainer.load_states(fname)


def test_trainer_lr():
    net = nn.Dense(1, in_units=1)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    assert trainer.learning_rate == 0.5
    trainer.set_learning_rate(0.25)
    assert trainer.learning_rate == 0.25


# ----------------------------------------------------------------- data

def test_array_dataset_dataloader():
    x = np.arange(20).reshape(10, 2).astype("f")
    y = np.arange(10).astype("f")
    ds = gluon.data.ArrayDataset(x, y)
    assert len(ds) == 10
    loader = gluon.data.DataLoader(ds, batch_size=3, last_batch="keep")
    batches = list(loader)
    assert len(batches) == 4
    assert batches[0][0].shape == (3, 2)
    assert batches[-1][0].shape == (1, 2)


def test_dataloader_shuffle_discard():
    ds = gluon.data.SimpleDataset(list(range(10)))
    loader = gluon.data.DataLoader(ds, batch_size=3, shuffle=True,
                                   last_batch="discard")
    batches = list(loader)
    assert len(batches) == 3
    seen = sorted(int(v) for b in batches for v in b.asnumpy())
    assert len(seen) == 9


def test_dataset_transform():
    ds = gluon.data.SimpleDataset([1, 2, 3]).transform(lambda x: x * 2)
    assert list(ds) == [2, 4, 6]


def test_samplers():
    s = list(gluon.data.SequentialSampler(5))
    assert s == [0, 1, 2, 3, 4]
    r = list(gluon.data.RandomSampler(5))
    assert sorted(r) == [0, 1, 2, 3, 4]
    b = list(gluon.data.BatchSampler(gluon.data.SequentialSampler(5), 2,
                                     "keep"))
    assert b == [[0, 1], [2, 3], [4]]


def test_record_file_dataset(tmp_path):
    from mxnet_tpu import recordio
    fname = str(tmp_path / "test.rec")
    rec = recordio.MXIndexedRecordIO(str(tmp_path / "test.idx"), fname, "w")
    for i in range(5):
        rec.write_idx(i, bytes([i] * 4))
    rec.close()
    ds = gluon.data.RecordFileDataset(fname)
    assert len(ds) == 5
    assert ds[2] == bytes([2] * 4)


# -------------------------------------------------------------- model zoo

def test_model_zoo_resnet_forward():
    net = gluon.model_zoo.vision.resnet18_v1(classes=10)
    net.initialize()
    out = net(nd.random.uniform(shape=(1, 3, 32, 32)))
    assert out.shape == (1, 10)


def test_model_zoo_constructors():
    zoo = gluon.model_zoo.vision
    for ctor in (zoo.alexnet, zoo.squeezenet1_0, zoo.mobilenet0_25,
                 zoo.vgg11, zoo.densenet121):
        net = ctor(classes=10)
        assert net is not None


def test_symbol_block():
    from mxnet_tpu import sym
    data = sym.Variable("data")
    out = sym.FullyConnected(data, num_hidden=4, name="fc")
    net = gluon.SymbolBlock(out, data)
    net.initialize()
    y = net(nd.ones((2, 3)))
    assert y.shape == (2, 4)


def test_unroll_valid_length():
    """Outputs past valid_length are zero-masked and returned states come
    from each sample's last valid step (SequenceLast parity)."""
    cell = rnn.LSTMCell(4, input_size=3)
    cell.initialize()
    x = nd.random.uniform(shape=(2, 5, 3))
    vl = nd.array([2, 5])
    out, states = cell.unroll(5, x, layout="NTC", merge_outputs=True,
                              valid_length=vl)
    o = out.asnumpy()
    assert (o[0, 2:] == 0).all()       # masked past t=2 for sample 0
    assert (o[0, :2] != 0).any()
    # sample 0's state == state after running only 2 steps
    out2, states2 = cell.unroll(2, nd.array(x.asnumpy()[:, :2]),
                                layout="NTC", merge_outputs=True)
    assert_almost_equal(states[0].asnumpy()[0], states2[0].asnumpy()[0],
                        rtol=1e-5, atol=1e-6)


def test_bidirectional_valid_length():
    cell = rnn.BidirectionalCell(rnn.LSTMCell(4, input_size=3),
                                 rnn.LSTMCell(4, input_size=3))
    cell.initialize()
    x = nd.random.uniform(shape=(2, 5, 3))
    out, _ = cell.unroll(5, x, layout="NTC", merge_outputs=True,
                         valid_length=nd.array([3, 5]))
    o = out.asnumpy()
    assert o.shape == (2, 5, 8)
    assert (o[0, 3:] == 0).all()


# ------------------------------------------------- stale gradients, eager

class _TwoHeads(gluon.Block):
    """An eager net in the shape of example/gluon/actor_critic.py: one
    trunk, two heads, called once per time step inside one record."""

    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.trunk = nn.Dense(8, activation="relu")
            self.policy = nn.Dense(2)
            self.value = nn.Dense(1)

    def forward(self, x):
        h = self.trunk(x)
        return nd.softmax(self.policy(h)), self.value(h)


def test_eager_block_called_twice_in_one_record_steps():
    """A non-hybridized block called several times in one record, its
    outputs picked apart with x[i, j], trains: every parameter took part,
    so Trainer.step finds no stale gradient and moves them all.  (x[i, j]
    was a raw view off the tape: backward reached no parameter and the
    step raised the stale-gradient UserWarning for the first of them.)"""
    net = _TwoHeads()
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    xs = [nd.array(np.random.RandomState(i).rand(1, 4).astype("f"))
          for i in range(2)]
    with autograd.record():
        loss = 0.0
        for t, x in enumerate(xs):
            probs, value = net(x)
            loss = loss - nd.log(probs[0, t]) + (value[0, 0] - 1.0) ** 2
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    loss.backward()
    trainer.step(1)
    for k, p in net.collect_params().items():
        assert p.fresh_grad is False, k          # step cleared the mark
        assert np.abs(p.data().asnumpy() - before[k]).max() > 0, k


def test_eager_block_left_out_of_the_record_still_raises():
    """The twin: a head that took no part in the recorded step is named by
    the stale-gradient guard, and ignore_stale_grad=True skips exactly it."""
    net = _TwoHeads()
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(np.random.RandomState(0).rand(1, 4).astype("f"))
    net(x)                                      # deferred shapes

    def backward_through_policy_only():
        with autograd.record():
            h = net.trunk(x)
            loss = -nd.log(nd.softmax(net.policy(h))[0, 1])
        loss.backward()

    backward_through_policy_only()
    with pytest.raises(UserWarning, match=net.value.weight.name):
        trainer.step(1)
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    backward_through_policy_only()
    trainer.step(1, ignore_stale_grad=True)
    for k, p in net.collect_params().items():
        moved = np.abs(p.data().asnumpy() - before[k]).max() > 0
        assert moved == (not k.startswith(net.value.prefix)), k
