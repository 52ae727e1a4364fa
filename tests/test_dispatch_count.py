"""O(1)-dispatch invariant of the Module.fit hot path (VERDICT r2 #3).

Round 2 found the product path issuing 193 `jax.device_put` calls per
step (per-parameter kvstore pull-backs) — a 18x throughput collapse
invisible on CPU.  The fix (pointer-handoff pull,
fused update, one fused fwd+bwd program) reduced a steady-state step to
a constant number of device dispatches.  This test pins that invariant
on CPU so a regression fails CI before it ever reaches a chip.

Parity model: the reference's segment bulking collapsed per-op engine
pushes into one push per segment (src/executor/graph_executor.cc:1350,
MXNET_EXEC_BULK_EXEC_TRAIN); here the analogous property is "a training
step is a fixed handful of XLA program launches".
"""
import collections

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.io import DataBatch, DataDesc


class _CountingJit:
    """Wraps a jitted callable; counts invocations under a label."""

    def __init__(self, fn, label, counters):
        self._fn = fn
        self._label = label
        self._counters = counters

    def __call__(self, *a, **k):
        self._counters["jit:" + self._label] += 1
        return self._fn(*a, **k)

    def __getattr__(self, name):
        return getattr(self._fn, name)


@pytest.fixture
def counters(monkeypatch):
    c = collections.Counter()
    real_jit = jax.jit

    def counting_jit(fn, *a, **k):
        label = getattr(fn, "__name__", "anon")
        return _CountingJit(real_jit(fn, *a, **k), label, c)

    real_dp = jax.device_put

    def counting_dp(*a, **k):
        c["device_put"] += 1
        return real_dp(*a, **k)

    import mxnet_tpu.ops.registry as reg
    real_apply = reg.apply_op

    def counting_apply(op, params, inputs):
        if not any(isinstance(x, jax.core.Tracer)
                   for x in inputs if x is not None):
            c["eager_op:" + op.name] += 1
        return real_apply(op, params, inputs)

    monkeypatch.setattr(jax, "jit", counting_jit)
    monkeypatch.setattr(jax, "device_put", counting_dp)
    monkeypatch.setattr(reg, "apply_op", counting_apply)
    return c


def _steady_state_counts(counters, n_steps=3, batch=16):
    """Build the product path under counting patches, measure N
    steady-state steps (post-compile), return (per-step Counter,
    per-step observability dispatch_counts delta)."""
    from mxnet_tpu import observability as obs
    rs = np.random.RandomState(0)
    net = sym.Convolution(sym.Variable("data"), kernel=(3, 3), num_filter=8,
                          pad=(1, 1), name="conv0")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, global_pool=True, pool_type="avg")
    net = sym.FullyConnected(sym.Flatten(net), num_hidden=10, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (batch, 3, 8, 8), np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch,), np.float32)])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9,
                                         "multi_precision": True})
    x = mx.nd.array(rs.normal(0, 1, (batch, 3, 8, 8)).astype("f"))
    y = mx.nd.array(rs.randint(0, 10, batch).astype("f"))
    db = DataBatch(data=[x], label=[y], pad=0, index=None)

    # warmup: compile everything (jit creation + first calls)
    for _ in range(2):
        mod.forward_backward(db)
        mod.update()
    float(mod.get_outputs()[0].asnumpy().ravel()[0])  # sync

    counters.clear()
    obs0 = obs.dispatch_counts()
    for _ in range(n_steps):
        mod.forward_backward(db)
        mod.update()
    float(mod.get_outputs()[0].asnumpy().ravel()[0])  # sync (host fetch,
    # not a dispatch)
    obs1 = obs.dispatch_counts()
    per_step = collections.Counter()
    for k, v in counters.items():
        per_step[k] = v / n_steps
    obs_step = {k: (obs1.get(k, 0) - obs0.get(k, 0)) / n_steps
                for k in obs1 if obs1.get(k, 0) != obs0.get(k, 0)}
    return per_step, obs_step


def test_fit_step_dispatch_budget(counters):
    per_step, obs_step = _steady_state_counts(counters)
    # the invariant from round 2's fix, now pinned:
    #   0 device_puts (pointer-handoff kvstore pull)
    assert per_step["device_put"] == 0, per_step
    #   0 eager per-op dispatches (everything rides fused programs)
    eager = {k: v for k, v in per_step.items() if k.startswith("eager_op")}
    assert not eager, per_step
    #   a fixed handful of compiled-program launches per step:
    #   1 fused fwd+bwd (executor) + 1 fused pushpull/update
    compiled = sum(v for k, v in per_step.items() if k.startswith("jit:"))
    assert compiled <= 2.0, per_step
    # the PRODUCT API (mx.observability.dispatch_counts) reports the same
    # tally the monkeypatch counting measured — the test-only invariant
    # is now queryable at runtime
    obs_compiled = sum(v for k, v in obs_step.items()
                       if k.startswith("xla:"))
    assert obs_compiled == compiled, (obs_step, per_step)
    assert obs_step.get("device_put", 0) == per_step["device_put"], obs_step
    assert obs_step.get("total", 0) == compiled, obs_step


def test_full_fit_loop_dispatch_budget(counters):
    """Pin the FULL fit() loop — metric update + epoch callback
    included, the pattern of chipbench/entries/module_fit.py — not just
    forward_backward+update.  Budget per batch in a steady epoch:
    0 device_puts, and a fixed handful of compiled-program launches
    (fused fwd+bwd, fused update, the metric's one on-device NLL
    program, the iterator's device-side batch slice)."""
    import collections as _c

    import jax.numpy as jnp

    from mxnet_tpu.io import NDArrayIter

    rs = np.random.RandomState(0)
    batch, nbatch = 8, 4
    net = sym.Convolution(sym.Variable("data"), kernel=(3, 3), num_filter=4,
                          pad=(1, 1), name="conv0")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, global_pool=True, pool_type="avg")
    net = sym.FullyConnected(sym.Flatten(net), num_hidden=10, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (batch, 3, 8, 8), np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch,), np.float32)])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9,
                                         "multi_precision": True})

    # device-resident data; the iterator slices on the device
    x = mx.nd.array(rs.normal(0, 1, (batch * nbatch, 3, 8, 8)).astype("f"))
    y = mx.nd.array(rs.randint(0, 10, batch * nbatch).astype("f"))
    it = NDArrayIter(x, y, batch_size=batch)

    class LossMetric(mx.metric.EvalMetric):
        """ONE jitted on-device NLL per batch, no
        host fetch inside the timed loop."""

        def __init__(self):
            super().__init__("nll")
            self._device_vals = []
            self._nll = jax.jit(lambda p, l: -jnp.log(
                jnp.take_along_axis(
                    p.astype(jnp.float32),
                    l.astype(jnp.int32)[:, None], axis=1) + 1e-8).mean())

        def update(self, labels_, preds):
            self._device_vals.append(
                self._nll(preds[0]._data, labels_[0]._data))
            self.num_inst += 1

        def get(self):
            return ("nll", 0.0)

    metric = LossMetric()
    snaps = []

    def epoch_end(epoch, sym_=None, arg=None, aux=None):
        snaps.append(_c.Counter(counters))

    mod.fit(it, num_epoch=3, eval_metric=metric,
            epoch_end_callback=epoch_end)

    steady = snaps[-1] - snaps[-2]  # epoch 3 minus epochs 1-2 totals
    per_batch = {k: v / nbatch for k, v in steady.items()}
    assert per_batch.get("device_put", 0) == 0, per_batch
    compiled = sum(v for k, v in per_batch.items() if k.startswith("jit:"))
    eager = sum(v for k, v in per_batch.items() if k.startswith("eager_op"))
    # 1 fused fwd+bwd + 1 fused update + 1 metric nll (measured exactly
    # 3.0; small headroom for iterator slicing variants)
    assert compiled + eager <= 4.0, per_batch


def test_fused_step_fit_loop_dispatch_budget(counters, monkeypatch):
    """MXNET_FUSED_STEP=1 bench pattern: ONE donated train-step program
    + the metric's NLL per batch — 0 device_puts, <= 2 programs."""
    import jax.numpy as jnp

    from mxnet_tpu.io import NDArrayIter

    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    rs = np.random.RandomState(0)
    batch, nbatch = 8, 4
    net = sym.Convolution(sym.Variable("data"), kernel=(3, 3),
                          num_filter=4, pad=(1, 1), name="conv0")
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, global_pool=True, pool_type="avg")
    net = sym.FullyConnected(sym.Flatten(net), num_hidden=10, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (batch, 3, 8, 8), np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch,),
                                    np.float32)])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9,
                                         "multi_precision": True})
    x = mx.nd.array(rs.normal(0, 1, (batch * nbatch, 3, 8, 8)).astype("f"))
    y = mx.nd.array(rs.randint(0, 10, batch * nbatch).astype("f"))
    it = NDArrayIter(x, y, batch_size=batch)

    nll = jax.jit(lambda p, l: -jnp.log(jnp.take_along_axis(
        p.astype(jnp.float32), l.astype(jnp.int32)[:, None],
        axis=1) + 1e-8).mean())

    class LossMetric(mx.metric.EvalMetric):
        def __init__(self):
            super().__init__("nll")
            self._device_vals = []

        def update(self, labels_, preds):
            self._device_vals.append(nll(preds[0]._data,
                                         labels_[0]._data))
            self.num_inst += 1

        def get(self):
            return ("nll", 0.0)

    snaps = []

    def epoch_end(epoch, sym_=None, arg=None, aux=None):
        snaps.append(collections.Counter(counters))

    mod.fit(it, num_epoch=3, eval_metric=LossMetric(),
            epoch_end_callback=epoch_end)
    assert mod.__dict__.get("_fstep") is not None  # path actually taken

    steady = snaps[-1] - snaps[-2]
    per_batch = {k: v / nbatch for k, v in steady.items()}
    assert per_batch.get("device_put", 0) == 0, per_batch
    compiled = sum(v for k, v in per_batch.items()
                   if k.startswith("jit:"))
    eager = sum(v for k, v in per_batch.items()
                if k.startswith("eager_op"))
    # 1 fused train-step + 1 metric nll (+ iterator slice headroom)
    assert compiled + eager <= 3.0, per_batch


def _rsp_model_counts(counters, n_tables, n_steps=3, batch=8):
    """Module with n_tables sparse-grad embeddings training through the
    kvstore rsp path; returns total jit-call count per step."""
    rs = np.random.RandomState(0)
    vocab, dim = 500, 8
    parts = []
    for i in range(n_tables):
        ids = sym.Variable(f"ids{i}")
        emb = sym.Embedding(ids, input_dim=vocab, output_dim=dim,
                            sparse_grad=True, name=f"emb{i}")
        parts.append(sym.sum(emb, axis=1))
    net = parts[0]
    for p in parts[1:]:
        net = net + p
    net = sym.FullyConnected(net, num_hidden=4, name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")

    mod = mx.mod.Module(net, context=mx.cpu(),
                        data_names=[f"ids{i}" for i in range(n_tables)])
    mod.bind(data_shapes=[DataDesc(f"ids{i}", (batch, 6), np.float32)
                          for i in range(n_tables)],
             label_shapes=[DataDesc("softmax_label", (batch,),
                                    np.float32)])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    xs = [mx.nd.array(rs.randint(0, vocab, (batch, 6)).astype("f"))
          for _ in range(n_tables)]
    y = mx.nd.array(rs.randint(0, 4, batch).astype("f"))
    db = DataBatch(data=xs, label=[y], pad=0, index=None)

    for _ in range(2):
        mod.forward_backward(db)
        mod.update()
    float(mod.get_outputs()[0].asnumpy().ravel()[0])

    counters.clear()
    for _ in range(n_steps):
        mod.forward_backward(db)
        mod.update()
    float(mod.get_outputs()[0].asnumpy().ravel()[0])
    return sum(v for k, v in counters.items()
               if k.startswith("jit:")) / n_steps


def test_rsp_step_dispatch_is_key_count_independent(counters):
    """VERDICT r3 #4 done-criterion: the rsp push path runs a constant
    number of compiled programs per step regardless of how many
    row-sparse keys the model has (the pre-batching design paid 2
    programs + a host sync PER KEY)."""
    one = _rsp_model_counts(counters, n_tables=1)
    four = _rsp_model_counts(counters, n_tables=4)
    assert four <= one + 0.01, (one, four)
    assert one <= 6.0, one  # fixed handful, not O(params)


# -- Gluon Trainer fast path (PR 2) -------------------------------------


def _gluon_mlp(depth=9, width=8, nin=16, seed=7):
    """Hybridized dense MLP with 2*(depth+1) parameters."""
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(depth):
            net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    return net


def _gluon_stepper(net, batch=8, nin=16, compression=None):
    """Build one Trainer over `net` and return a step closure (loss) —
    steady-state measurement needs the SAME trainer across warmup and
    the measured window (a fresh trainer re-inits the kvstore)."""
    from mxnet_tpu import autograd, gluon
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (batch, nin)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (batch, 1)).astype("f"))
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore="tpu_sync", update_on_kvstore=False,
                            compression_params=compression)

    def one_step():
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        trainer.step(batch)
        return float(l.asnumpy().ravel()[0])

    return one_step


def _gluon_train(net, n_steps, batch=8, nin=16):
    """Fresh trainer, n_steps of record/backward/step; per-step losses."""
    step = _gluon_stepper(net, batch=batch, nin=nin)
    return [step() for _ in range(n_steps)]


def _gluon_steady_per_step(net, warmup=3, n=3, compression=None):
    """Warm up `warmup` steps, then measure the per-step
    dispatch_counts() delta over `n` more — same trainer throughout."""
    from mxnet_tpu import observability as obs
    step = _gluon_stepper(net, compression=compression)
    for _ in range(warmup):
        step()
    c0 = obs.dispatch_counts()
    for _ in range(n):
        step()
    c1 = obs.dispatch_counts()
    return {k: (c1.get(k, 0) - c0.get(k, 0)) / n
            for k in c1 if c1.get(k, 0) != c0.get(k, 0)}


@pytest.mark.perf_smoke
def test_gluon_trainer_step_dispatch_budget():
    """The PR 2 acceptance invariant, pinned as a CPU perf gate: a dense
    hybridized Gluon step is <= 4 steady-state dispatches REGARDLESS of
    parameter count — 1 fwd + 1 bwd + 1 bucketed allreduce + 1 fused
    update — vs the reference's O(num_params) per-key push/pull loop
    (gluon/trainer.py:191-226) + per-param updater calls."""
    net = _gluon_mlp(depth=9)   # 20 params
    assert len(net.collect_params()) == 20
    per_step = _gluon_steady_per_step(net)
    assert per_step.get("device_put", 0) == 0, per_step
    assert per_step.get("total", 99) <= 4.0, per_step
    from mxnet_tpu.observability import metrics as m
    # step() itself (allreduce + update; fwd/bwd are outside it) is 2
    assert m.TRAINER_STEP_DISPATCHES.get() <= 2.0
    assert m.ALLREDUCE_BUCKETS.get() >= 1.0


@pytest.mark.perf_smoke
def test_gluon_trainer_dispatch_is_param_count_independent():
    """Doubling the parameter count must not change dispatches/step."""
    small = _gluon_steady_per_step(_gluon_mlp(depth=4)).get("total", 0)
    big = _gluon_steady_per_step(_gluon_mlp(depth=9)).get("total", 0)
    assert big <= small + 0.01, (small, big)


@pytest.mark.perf_smoke
def test_gluon_trainer_compressed_step_dispatch_budget():
    """ISSUE 3 acceptance gate: compression_params={'type': '2bit'} on
    a dense hybridized model keeps the fused path — step() stays <= 4
    steady-state dispatches regardless of parameter count (flatten +
    fused quantize/dequantize reduce + update; compression costs
    exactly ONE extra program over the raw path, never O(num_params))
    — and the dist leg ships <= 1/8 of the gradient bytes (measured
    1/16 + padding, reported by KVSTORE_WIRE_BYTES)."""
    from mxnet_tpu.observability import metrics as m
    comp = {"type": "2bit", "threshold": 0.5}
    net = _gluon_mlp(depth=9)   # 20 params
    per_step = _gluon_steady_per_step(net, compression=comp)
    assert per_step.get("device_put", 0) == 0, per_step
    # 1 fwd + 1 bwd + 1 flatten + 1 compressed reduce + 1 fused update
    assert per_step.get("total", 99) <= 5.0, per_step
    assert m.TRAINER_STEP_DISPATCHES.get() <= 4.0
    raw = m.KVSTORE_WIRE_BYTES.get(leg="dist", stage="raw")
    packed = m.KVSTORE_WIRE_BYTES.get(leg="dist", stage="compressed")
    assert raw > 0 and packed * 8 <= raw, (raw, packed)
    # param-count independence holds under compression too
    small = _gluon_steady_per_step(_gluon_mlp(depth=4),
                                   compression=comp).get("total", 0)
    assert small <= per_step.get("total", 0) + 0.01, (small, per_step)


def test_gluon_fused_vs_legacy_agreement(monkeypatch):
    """MXNET_FUSED_TRAINER=0 pins the reference-shaped per-key path; both
    paths must agree numerically (rtol 1e-5) over a 3-step training run —
    losses and final weights."""
    def run(flag):
        monkeypatch.setenv("MXNET_FUSED_TRAINER", flag)
        net = _gluon_mlp(depth=4, seed=11)
        losses = _gluon_train(net, 3)
        weights = [p.data().asnumpy()
                   for p in net.collect_params().values()]
        return losses, weights

    lf, wf = run("1")
    ll, wl = run("0")
    np.testing.assert_allclose(lf, ll, rtol=1e-5)
    for a, b in zip(wf, wl):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_grad_bucketer_round_trip():
    """flatten→unflatten is the identity, across dtype boundaries and
    size-cap splits; views address every element exactly once."""
    from mxnet_tpu.kvstore import GradBucketer
    rs = np.random.RandomState(3)
    arrs = [rs.normal(0, 1, s).astype(d) for s, d in
            [((4, 3), "float32"), ((7,), "float32"), ((2, 2), "float64"),
             ((5,), "float32"), ((1,), "float32"), ((3, 3, 2), "float64")]]
    sig = [(a.shape, str(a.dtype)) for a in arrs]
    # tiny cap: forces multiple buckets even within one dtype run
    bk = GradBucketer(sig, cap_bytes=64)
    import jax.numpy as jnp
    flats = bk.flatten([jnp.asarray(a) for a in arrs])
    # dtype homogeneity per bucket
    for f, bucket in zip(flats, bk.layout):
        for pos in bucket:
            assert str(f.dtype) == sig[pos][1]
    outs = bk.unflatten(flats)
    for a, o in zip(arrs, outs):
        np.testing.assert_array_equal(a, np.asarray(o))
    # views slice to the same values the unflatten materializes
    for k, (b, off, shape) in enumerate(bk.views):
        size = int(np.prod(shape)) if shape else 1
        np.testing.assert_array_equal(
            np.asarray(flats[b][off:off + size]).reshape(shape), arrs[k])


def test_multi_bucket_fused_vs_legacy_agreement(monkeypatch):
    """A tiny MXNET_BUCKET_SIZE_MB forces one bucket per parameter —
    the multi-bucket allreduce path must agree with the legacy per-key
    path exactly like the single-bucket one (regression: buckets being
    mistaken for per-device copies of one key and summed together)."""
    def run(flag):
        monkeypatch.setenv("MXNET_FUSED_TRAINER", flag)
        net = _gluon_mlp(depth=4, seed=13)
        losses = _gluon_train(net, 3)
        weights = [p.data().asnumpy()
                   for p in net.collect_params().values()]
        return losses, weights

    monkeypatch.setenv("MXNET_BUCKET_SIZE_MB", "0.0001")
    lf, wf = run("1")
    ll, wl = run("0")
    np.testing.assert_allclose(lf, ll, rtol=1e-5)
    for a, b in zip(wf, wl):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_bucketed_allreduce_is_storeless():
    """The transient grad buckets must never enter the kvstore's backing
    store — a pinned gradient-size copy per trainer would double
    steady-state HBM for no reader."""
    from mxnet_tpu import autograd, gluon
    net = _gluon_mlp(depth=4)
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (8, 16)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05},
                            kvstore="tpu_sync", update_on_kvstore=False)
    for _ in range(2):
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        trainer.step(8)
    n_params = len(net.collect_params())
    assert len(trainer._kv._store) == n_params, \
        sorted(map(str, trainer._kv._store))


# -- Gluon whole-step compilation (ISSUE 10) ----------------------------


def _wholestep_stepper(net, batch=8, nin=16, compression=None,
                       loss_fn=None):
    """WholeStepCompiler step closure over `net` (same steady-state
    discipline as _gluon_stepper: one trainer/compiler across warmup
    and the measured window)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.wholestep import WholeStepCompiler
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (batch, nin)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (batch, 1)).astype("f"))
    loss_fn = loss_fn or gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore="tpu_sync", update_on_kvstore=False,
                            compression_params=compression)
    st = WholeStepCompiler(net, loss_fn, trainer)
    return st, lambda: st.step(x, y)


def _wholestep_steady_per_step(net, warmup=3, n=3, compression=None,
                               loss_fn=None):
    from mxnet_tpu import observability as obs
    st, step = _wholestep_stepper(net, compression=compression,
                                  loss_fn=loss_fn)
    for _ in range(warmup):
        step()
    c0 = obs.dispatch_counts()
    for _ in range(n):
        step()
    c1 = obs.dispatch_counts()
    return st, {k: (c1.get(k, 0) - c0.get(k, 0)) / n
                for k in c1 if c1.get(k, 0) != c0.get(k, 0)}


@pytest.mark.perf_smoke
def test_wholestep_dispatch_budget(monkeypatch, program_audit):
    """ISSUE 10 acceptance gate: MXNET_WHOLE_STEP=1 runs a dense
    hybridized step as ONE donated XLA program — <= 2 steady-state
    dispatches (measured exactly 1: xla:whole_step), 0 device_puts,
    and the TRAINER_STEP_DISPATCHES gauge keeps telling the truth.
    ISSUE 15 extends the gate: the program-contract auditor must
    confirm on the SAME program that donation really became
    input-output aliasing — 1 dispatch that secretly copies the model
    would pass the count while doubling HBM."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    from mxnet_tpu.observability import metrics as m
    net = _gluon_mlp(depth=9)   # 20 params
    st, per_step = _wholestep_steady_per_step(net)
    assert st.active, st.fallback_reason
    assert per_step.get("device_put", 0) == 0, per_step
    assert per_step.get("total", 99) <= 2.0, per_step
    assert per_step.get("xla:whole_step", 0) >= 1.0, per_step
    assert m.TRAINER_STEP_DISPATCHES.get() <= 2.0
    # every donated leaf (params + optimizer states + aux) must alias:
    # 20 trainable params with momentum state = >= 40 aliased buffers
    aliased = program_audit("whole_step", min_aliased=1)
    from mxnet_tpu.observability import introspect
    rec = introspect.programs()["whole_step"]
    assert len(aliased) >= rec["contracts"]["donated_leaves"] > 0


@pytest.mark.perf_smoke
def test_wholestep_dispatch_is_param_count_independent(monkeypatch):
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    st_s, small = _wholestep_steady_per_step(_gluon_mlp(depth=4))
    st_b, big = _wholestep_steady_per_step(_gluon_mlp(depth=9))
    # both must really be on the whole-step program — the fused
    # fallback is ALSO param-count independent, so without this the
    # comparison passes vacuously with the feature dead
    assert st_s.active, st_s.fallback_reason
    assert st_b.active, st_b.fallback_reason
    assert big.get("total", 0) <= small.get("total", 0) + 0.01, \
        (small, big)


@pytest.mark.perf_smoke
def test_wholestep_compressed_dispatch_budget(monkeypatch):
    """2-bit compression composes with whole-step at ZERO extra
    launches: quantize/dequantize + residual update trace into the
    same single program (vs +1 program on the fused path)."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = _gluon_mlp(depth=9)
    st, per_step = _wholestep_steady_per_step(
        net, compression={"type": "2bit", "threshold": 0.5})
    assert st.active, st.fallback_reason
    assert per_step.get("device_put", 0) == 0, per_step
    assert per_step.get("total", 99) <= 2.0, per_step


@pytest.mark.perf_smoke
def test_wholestep_fallback_dispatch_budget(monkeypatch):
    """An ineligible construct (eager-only loss) must land on the PR 2
    fused path and keep ITS budget: <= 4 steady-state dispatches."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")

    def plain_loss(pred, label):  # .mean(): no Symbol support -> fallback
        return ((pred - label) ** 2).mean(axis=1) / 2

    net = _gluon_mlp(depth=9)
    st, per_step = _wholestep_steady_per_step(net, loss_fn=plain_loss)
    assert not st.active
    assert per_step.get("device_put", 0) == 0, per_step
    assert per_step.get("total", 99) <= 4.0, per_step


def test_explicit_update_on_kvstore_without_store_raises():
    """update_on_kvstore=True with no kvstore must raise, not silently
    train on local updaters (parity: reference Trainer)."""
    from mxnet_tpu import gluon
    net = _gluon_mlp(depth=1)
    net(mx.nd.ones((2, 16)))  # materialize deferred shapes
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            kvstore=None, update_on_kvstore=True)
    with pytest.raises(ValueError, match="update_on_kvstore"):
        trainer._init_kvstore()


def test_trainer_stale_grad_guard():
    """A param untouched by backward raises by default and is skipped
    under ignore_stale_grad=True (parity: gluon/trainer.py:216)."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(5)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4, activation="relu"))
        net.add(nn.Dense(1))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    extra = gluon.Parameter("orphan", shape=(3,))
    extra.initialize(ctx=mx.cpu())
    params = list(net.collect_params().values()) + [extra]
    trainer = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                            kvstore="tpu_sync", update_on_kvstore=False)
    x = mx.nd.ones((2, 4))
    with autograd.record():
        l = net(x).sum()
    l.backward()
    with pytest.raises(UserWarning, match="orphan"):
        trainer.step(2)
    before = extra.data().asnumpy().copy()
    with autograd.record():
        l = net(x).sum()
    l.backward()
    trainer.step(2, ignore_stale_grad=True)  # orphan masked out
    np.testing.assert_array_equal(before, extra.data().asnumpy())
