"""`Module.prepare` launches the next batch's forward-backward and holds its
results (ISSUE 34).  A small symbol with BatchNorm and Dropout, so that
auxiliary states and keys matter; every comparison is bitwise against a hand
loop `forward_backward; update; update_metric` that never calls `prepare`.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.observability import metrics as M

EPOCHS, BATCHES, BATCH = 2, 5, 8
OPT = {"learning_rate": 0.1, "momentum": 0.9}
WRITE_AT = (0, 1)  # (epoch, nbatch) after which the (c) cases write


def _net(dropout=True):
    x = mx.sym.Variable("data")
    x = mx.sym.FullyConnected(x, num_hidden=16, name="fc1")
    x = mx.sym.BatchNorm(x, name="bn1")
    x = mx.sym.Activation(x, act_type="relu")
    if dropout:
        x = mx.sym.Dropout(x, p=0.3)
    x = mx.sym.FullyConnected(x, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _iter():
    rs = np.random.RandomState(0)
    x = rs.normal(0, 1, (BATCHES * BATCH, 8)).astype("f")
    y = rs.randint(0, 4, BATCHES * BATCH).astype("f")
    return mx.io.NDArrayIter(x, y, batch_size=BATCH)


def _fresh(dropout=True):
    mx.random.seed(7)
    return mx.mod.Module(_net(dropout)), _iter()


def _bound(dropout=True):
    mod, it = _fresh(dropout)
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params=OPT)
    return mod, it


def _raw(state):
    if isinstance(state, (tuple, list)):
        return [a for s in state for a in _raw(s)]
    return [] if state is None else [state.asnumpy()]


def _state(mod):
    """Parameters, auxiliary states and optimizer states as numpy."""
    args, auxs = mod.get_params()
    upd = mod._kvstore._updater if mod._update_on_kvstore else mod._updater
    out = {"arg:" + k: v.asnumpy() for k, v in args.items()}
    out.update({"aux:" + k: v.asnumpy() for k, v in auxs.items()})
    for k, st in upd.states.items():
        for i, a in enumerate(_raw(st)):
            out[f"opt:{k}:{i}"] = a
    return out


def _seen(mod):
    """What a caller can read between two steps."""
    out = {"out": mod.get_outputs()[0].asnumpy()}
    args, auxs = mod.get_params()
    out.update({"arg:" + k: v.asnumpy() for k, v in args.items()})
    out.update({"aux:" + k: v.asnumpy() for k, v in auxs.items()})
    out.update({"grad:" + k: v.asnumpy()
                for k, v in mod._exec.grad_dict.items()})
    return out


def _same(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        assert np.array_equal(a[k], b[k]), k


def _hand(after_step=None, dropout=True):
    """The loop that never prepares: (final state, per-step metric values,
    per-step readings)."""
    mod, it = _bound(dropout)
    metric = mx.metric.create("acc")
    values, seen = [], []
    for epoch in range(EPOCHS):
        it.reset()
        metric.reset()
        for nbatch, batch in enumerate(it):
            mod.forward_backward(batch)
            mod.update()
            mod.update_metric(metric, batch.label)
            values.append(metric.get()[1])
            seen.append(_seen(mod))
            if after_step is not None:
                after_step(mod, it, epoch, nbatch)
    return _state(mod), values, seen


def _fit(after_step=None, dropout=True, **kw):
    mod, it = _fresh(dropout)
    values, seen = [], []

    def batch_end(param):
        values.append(param.eval_metric.get()[1])
        seen.append(_seen(mod))
        if after_step is not None:
            after_step(mod, it, param.epoch, param.nbatch)

    mod.fit(it, eval_metric="acc", num_epoch=EPOCHS, optimizer="sgd",
            optimizer_params=OPT, initializer=mx.init.Xavier(),
            batch_end_callback=batch_end, **kw)
    return mod, _state(mod), values, seen


def _held():
    return (M.HELD_LAUNCHES.get(result="taken"),
            M.HELD_LAUNCHES.get(result="dropped"))


@pytest.fixture(autouse=True)
def _counters():
    M.REGISTRY.reset()
    yield
    M.REGISTRY.reset()


# -- (a), (b), (d), (g) --------------------------------------------------------
def fit_equals_the_hand_loop():
    want, values, _ = _hand()
    _mod, got, got_values, _ = _fit()
    _same(want, got)
    assert values == got_values
    assert _held() == (EPOCHS * (BATCHES - 1), 0)


def callback_reads_its_own_step():
    _, _, want = _hand()
    _mod, _, _, got = _fit()
    assert len(want) == len(got) == EPOCHS * BATCHES
    for a, b in zip(want, got):
        _same(a, b)
    assert _held()[0] == EPOCHS * (BATCHES - 1)


def one_launch_a_batch():
    mod, _, _, _ = _fit()
    assert M.XLA_LAUNCHES.get(kind="fwd_bwd") == EPOCHS * BATCHES
    assert _held() == (EPOCHS * (BATCHES - 1), 0)
    assert M.FIT_STEP_DISPATCHES.get() == 2.0
    assert mod._exec._held is None
    assert M.snapshot()["held_launches"] == {
        "taken": EPOCHS * (BATCHES - 1), "dropped": 0}


def supervised_fit_equals_the_hand_loop(monkeypatch, inline):
    """The supervisor's step_fn takes the slot like any caller.  With its
    stall watchdog on, the step runs on a worker thread, whose random
    stream is its own (`mxnet_tpu.random` keeps its key per thread, before
    this change as after): a graph that draws keys is compared with the
    watchdog off, one that draws none with it on."""
    if inline:
        monkeypatch.setenv("MXNET_SUPERVISE_STALL_FACTOR", "0")
    want, values, _ = _hand(dropout=inline)
    _mod, got, got_values, _ = _fit(dropout=inline, supervise=True)
    _same(want, got)
    assert values == got_values
    assert _held() == (EPOCHS * (BATCHES - 1), 0)


# -- (c): a write between prepare and the step drops the slot ------------------
def _set_params(mod, _it):
    args, auxs = mod.get_params()
    mod.set_params({k: v * 0.5 for k, v in args.items()}, auxs)


def _write_argument(mod, _it):
    w = mod._exec.arg_dict["fc1_weight"]
    w[:] = w * 0.5


def _reshape(mod, it):
    mod.reshape(it.provide_data, it.provide_label)


def _drops(write):
    def after_step(mod, it, epoch, nbatch):
        if (epoch, nbatch) == WRITE_AT:
            write(mod, it)

    want, values, _ = _hand(after_step)
    _mod, got, got_values, _ = _fit(after_step)
    _same(want, got)
    assert values == got_values
    assert _held() == (EPOCHS * (BATCHES - 1) - 1, 1)
    # the step that found its slot dropped launched again: one more
    assert M.XLA_LAUNCHES.get(kind="fwd_bwd") == 2 * EPOCHS * BATCHES + 1


# -- (e): where it must not engage ----------------------------------------------
def _with_monitor(_monkeypatch):
    mod, it = _fresh()
    mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=OPT,
            monitor=mx.mon.Monitor(1))


def _with_fused_step(monkeypatch):
    monkeypatch.setenv("MXNET_FUSED_STEP", "1")
    mod, it = _fresh()
    mod.fit(it, num_epoch=1, optimizer="sgd", optimizer_params=OPT)
    assert mod.__dict__.get("_fstep") is not None  # the one-program step ran


def _with_bucketing(_monkeypatch):
    def sym_gen(_key):
        return _net(), ("data",), ("softmax_label",)

    mx.random.seed(7)
    mod = mx.mod.BucketingModule(sym_gen, default_bucket_key=8)
    mod.fit(_iter(), num_epoch=1, optimizer="sgd", optimizer_params=OPT)


def _with_score(_monkeypatch):
    mod, it = _bound()
    it.reset()
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    mod.score(it, "acc")
    mod.prepare(batch)  # the step before was no training step
    assert mod._exec._held is None
    # and a module bound for inference has nothing to launch
    inf = mx.mod.Module(_net())
    inf.bind(it.provide_data, it.provide_label, for_training=False)
    inf.init_params(mx.init.Xavier())
    inf.prepare(batch)
    inf.forward(batch)
    assert inf._exec._held is None


def _never_engages(run, monkeypatch):
    run(monkeypatch)
    assert _held() == (0, 0)


# -- (f): prepare by hand --------------------------------------------------------
def prepare_by_hand():
    def loop(prepare):
        mod, it = _bound()
        for batch in it:
            if prepare:
                mod.prepare(batch)
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
        return mod

    want = _state(loop(False))
    assert _held() == (0, 0)
    mod = loop(True)
    _same(want, _state(mod))
    assert _held() == (BATCHES - 1, 0)  # nothing to launch behind no step
    it = _iter()
    first, other = next(iter(it)), next(iter(it))
    mod.prepare(first)
    kept = mod.get_outputs()[0].asnumpy()
    assert mod._exec._held is not None
    assert np.array_equal(kept, mod.get_outputs()[0].asnumpy())
    mod.forward(other)
    assert mod._exec._held is None
    assert _held() == (BATCHES - 1, 1)


def failed_launch_raises_from_its_step():
    """A launch that fails inside prepare() is the step's failure: it
    raises where the step would have raised, and the step can run again."""
    from mxnet_tpu import faultinject as fi
    from mxnet_tpu.observability import DeviceMemoryError
    mod, it = _bound()
    first, second = next(iter(it)), next(iter(it))
    mod.forward_backward(first)
    mod.update()
    kept = mod.get_outputs()[0].asnumpy()
    with fi.active(fi.FaultPlan().add("memory.oom", "raise", times=1)):
        mod.prepare(second)
    assert np.array_equal(kept, mod.get_outputs()[0].asnumpy())
    with pytest.raises(DeviceMemoryError):
        mod.forward_backward(second)
    assert mod._exec._held is None
    mod.forward_backward(second)
    mod.update()
    assert _held() == (1, 0)


CASES = [
    pytest.param(fit_equals_the_hand_loop, id="a-fit-equals-hand-loop"),
    pytest.param(callback_reads_its_own_step, id="b-callback-reads-step-n"),
    pytest.param(lambda: _drops(_set_params), id="c-set-params-drops"),
    pytest.param(lambda: _drops(_write_argument), id="c-written-argument-drops"),
    pytest.param(lambda: _drops(_reshape), id="c-reshape-drops"),
    pytest.param(one_launch_a_batch, id="d-one-launch-a-batch"),
    pytest.param(prepare_by_hand, id="f-prepare-by-hand"),
    pytest.param(failed_launch_raises_from_its_step,
                 id="h-failed-launch-raises-from-its-step"),
]


@pytest.mark.parametrize("case", CASES)
def test_held_launch(case):
    case()


@pytest.mark.parametrize("inline", [
    pytest.param(True, id="g-supervised-fit-inline-with-dropout"),
    pytest.param(False, id="g-supervised-fit-worker-thread"),
])
def test_held_launch_supervised(monkeypatch, inline):
    supervised_fit_equals_the_hand_loop(monkeypatch, inline)


@pytest.mark.parametrize("run", [
    pytest.param(_with_monitor, id="e-monitor"),
    pytest.param(_with_fused_step, id="e-fused-step"),
    pytest.param(_with_bucketing, id="e-bucketing-module"),
    pytest.param(_with_score, id="e-score-and-inference"),
])
def test_held_launch_never_engages(run, monkeypatch):
    _never_engages(run, monkeypatch)
