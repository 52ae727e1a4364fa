"""MXNET_WHOLE_STEP=1: the whole Gluon training step — fwd + loss +
bwd + bucketed reduce (+2-bit) + fused optimizer — as ONE donated XLA
program (gluon/wholestep.py), with the MXNET_AMP mixed-precision layer
on top.

Contracts pinned here (ISSUE 10):
  * f32 whole-step training is BITWISE identical to the PR 2 fused
    path over 5 steps — losses, weights, and (with compression) the
    error-feedback residuals;
  * bf16/fp16 autocast tracks f32 at documented rtol, including the
    fp16 dynamic loss-scale evolution (growth after
    MXNET_LOSS_SCALE_WINDOW finite steps, x0.5 backoff + skip-step on
    nonfinite gradients);
  * scaler + residual state rides save_states/load_states and the PR 5
    checkpoint manager — kill-resume under MXNET_WHOLE_STEP=1 + fp16
    matches the uninterrupted run;
  * unsupported constructs fall back to the fused path with one
    warning, and a dtype-policy flip recompiles LOUDLY (counter+log),
    never silently reusing a program traced under another precision.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.wholestep import WholeStepCompiler, amp_policy


# documented AMP tolerances (docs/perf_tuning.md): bf16 has an 8-bit
# mantissa, fp16 a 10-bit one + loss-scale rounding; bounds are
# training-noise scale over 6 steps on the toy nets below
BF16_TOL = 0.08
FP16_TOL = 0.05


def _mlp(seed=11, depth=4, width=8):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(depth):
            net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    return net


def _cnn(seed=7):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Conv2D(4, kernel_size=3, padding=1))
        net.add(nn.BatchNorm())
        net.add(nn.Activation("relu"))
        net.add(nn.Flatten())
        net.add(nn.Dense(3))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    return net


def _data(shape=(8, 16), reg=True, seed=0):
    rs = np.random.RandomState(seed)
    x = mx.nd.array(rs.normal(0, 1, shape).astype("f"))
    if reg:
        y = mx.nd.array(rs.normal(0, 1, (shape[0], 1)).astype("f"))
    else:
        y = mx.nd.array(rs.randint(0, 3, (shape[0],)).astype("f"))
    return x, y


def _trainer(net, comp=None, opt="sgd", opt_params=None, **kw):
    return gluon.Trainer(
        net.collect_params(), opt,
        opt_params or {"learning_rate": 0.05, "momentum": 0.9},
        kvstore="tpu_sync", update_on_kvstore=False,
        compression_params=comp, **kw)


def _run(monkeypatch, whole, steps=5, comp=None, net_fn=_mlp, amp=None,
         opt="sgd", opt_params=None):
    """Train `steps` steps through WholeStepCompiler.step (whole-step
    or fallback/fused depending on the env); returns (losses, ordered
    weights, trainer, compiler)."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1" if whole else "0")
    if amp:
        monkeypatch.setenv("MXNET_AMP", amp)
    else:
        monkeypatch.delenv("MXNET_AMP", raising=False)
    net = net_fn()
    reg = net_fn is _mlp
    x, y = _data() if reg else _data((8, 3, 8, 8), reg=False)
    loss_fn = gluon.loss.L2Loss() if reg else \
        gluon.loss.SoftmaxCrossEntropyLoss()
    tr = _trainer(net, comp=comp, opt=opt, opt_params=opt_params)
    st = WholeStepCompiler(net, loss_fn, tr)
    losses = [float(st.step(x, y).asnumpy().mean()) for _ in range(steps)]
    weights = [p.data().asnumpy().astype("f")
               for p in net.collect_params().values()]
    return losses, weights, tr, st


# ---------------------------------------------------------------------------
# numerics: f32 bitwise parity with the fused path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("opt,opt_params", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    ("adam", {"learning_rate": 3e-3}),
])
def test_wholestep_f32_bitwise_matches_fused(monkeypatch, opt, opt_params):
    lw, ww, _, st = _run(monkeypatch, True, opt=opt, opt_params=opt_params)
    assert st.active, st.fallback_reason
    lf, wf, _, _ = _run(monkeypatch, False, opt=opt, opt_params=opt_params)
    np.testing.assert_array_equal(lw, lf)
    for a, b in zip(ww, wf):
        np.testing.assert_array_equal(a, b)


def test_wholestep_bn_adam_bitwise_matches_fused(monkeypatch):
    """Conv + BatchNorm exercises the aux-state leg (running stats ride
    the donated program and are written back).  Every leaf is held, to
    float32's last digits where the whole-step program was bitwise
    before: the default path's backward program now recomputes the
    normalisation from the convolution's output (gluon/block.py), in
    fusions of its own, where the whole-step program computes it once.
    Two leaves have a bound of their own, from the same cause.  Batch
    normalisation takes the convolution's bias away again, so the bias's
    gradient is round-off, whose sign Adam turns into a step of at most
    lr: either path's bias (zero at the start) lies within steps * lr of
    zero, and so of the other's.  The running mean is a moving average
    of batch means that carry the bias, so the paths' means differ by no
    more than their biases do."""
    steps, lr = 5, 3e-3
    lw, ww, trw, st = _run(monkeypatch, True, steps=steps, net_fn=_cnn,
                           opt="adam", opt_params={"learning_rate": lr})
    assert st.active, st.fallback_reason
    lf, wf, _, _ = _run(monkeypatch, False, steps=steps, net_fn=_cnn,
                        opt="adam", opt_params={"learning_rate": lr})
    np.testing.assert_allclose(lw, lf, rtol=2e-6)
    names = [p.name for p in trw._params]
    assert len(names) == len(ww) == len(wf)
    gap = dict(zip(names, (np.abs(a - b).max() for a, b in zip(ww, wf))))
    (bias,) = [n for n in names if n.endswith("conv0_bias")]
    (mean,) = [n for n in names if n.endswith("running_mean")]
    for name, a, b in zip(names, ww, wf):
        if name == bias:
            assert max(np.abs(a).max(), np.abs(b).max(), gap[name]) \
                <= steps * lr * (1 + 1e-5), (name, a, b)
        elif name == mean:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=gap[bias],
                                       err_msg=name)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-7,
                                       err_msg=name)


def test_wholestep_compressed_bitwise_matches_fused(monkeypatch):
    """2-bit compression composes: flat residual trajectory included."""
    comp = {"type": "2bit", "threshold": 0.5}
    lw, ww, trw, st = _run(monkeypatch, True, comp=comp)
    assert st.active, st.fallback_reason
    lf, wf, trf, _ = _run(monkeypatch, False, comp=comp)
    np.testing.assert_array_equal(lw, lf)
    for a, b in zip(ww, wf):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(trw._residuals, trf._residuals):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# mixed precision
# ---------------------------------------------------------------------------
def test_wholestep_bf16_tracks_f32(monkeypatch):
    lw, ww, _, st = _run(monkeypatch, True, steps=6, amp="bf16")
    assert st.active, st.fallback_reason
    lf, wf, _, _ = _run(monkeypatch, True, steps=6)
    np.testing.assert_allclose(lw, lf, rtol=BF16_TOL, atol=BF16_TOL)
    for a, b in zip(ww, wf):
        np.testing.assert_allclose(a, b, rtol=BF16_TOL, atol=BF16_TOL)
    # master weights and optimizer state stayed f32
    assert all(str(a.dtype) == "float32" for a in ww)


def test_wholestep_fp16_tracks_f32_with_scaling(monkeypatch):
    monkeypatch.setenv("MXNET_LOSS_SCALE_INIT", "1024")
    lw, ww, tr, st = _run(monkeypatch, True, steps=6, amp="fp16")
    assert st.active, st.fallback_reason
    assert tr.loss_scale >= 1024.0  # scaling engaged, no spurious backoff
    lf, wf, _, _ = _run(monkeypatch, True, steps=6)
    np.testing.assert_allclose(lw, lf, rtol=FP16_TOL, atol=FP16_TOL)
    for a, b in zip(ww, wf):
        np.testing.assert_allclose(a, b, rtol=FP16_TOL, atol=FP16_TOL)


def test_fp16_scale_growth_backoff_and_skip(monkeypatch):
    """Scale evolution pinned: x2 after MXNET_LOSS_SCALE_WINDOW finite
    steps, x0.5 + skip-step (weights/states untouched) on nonfinite
    gradients, training resumes on the next finite batch."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.setenv("MXNET_AMP", "fp16")
    monkeypatch.setenv("MXNET_LOSS_SCALE_INIT", "1024")
    monkeypatch.setenv("MXNET_LOSS_SCALE_WINDOW", "3")
    net = _mlp()
    x, y = _data()
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    st.step(x, y)  # first call may fall back (deferred shapes)
    for _ in range(4):
        st.step(x, y)
    assert st.active, st.fallback_reason
    # >= window finite whole-step steps passed: scale grew exactly once
    assert tr.loss_scale == 2048.0
    before = [p.data().asnumpy().copy()
              for p in net.collect_params().values()]
    xbad = mx.nd.array(np.full((8, 16), np.inf, dtype="f"))
    st.step(xbad, y)
    after = [p.data().asnumpy() for p in net.collect_params().values()]
    for a, b in zip(before, after):  # skip-step: nothing moved
        np.testing.assert_array_equal(a, b)
    assert tr.loss_scale == 1024.0  # backoff
    st.step(x, y)  # finite again: trains
    trained = [p.data().asnumpy() for p in net.collect_params().values()]
    assert any(not np.array_equal(a, b) for a, b in zip(after, trained))


def test_fp16_skip_step_preserves_bn_running_stats(monkeypatch):
    """A skipped step must hold BatchNorm running mean/var at their
    pre-step values — an overflowing batch's inf activations must not
    poison inference forever."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.setenv("MXNET_AMP", "fp16")
    monkeypatch.setenv("MXNET_LOSS_SCALE_INIT", "1024")
    net = _cnn()
    x, y = _data((8, 3, 8, 8), reg=False)
    net(x)
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    st.step(x, y)
    assert st.active, st.fallback_reason
    aux_before = {n: p.data().asnumpy()
                  for n, p in net.collect_params().items()
                  if "running" in n}
    assert aux_before  # the net really has BN running stats
    xbad = x.copy()
    xbad[0, 0, 0, 0] = float("nan")
    st.step(xbad, y)  # skip-step
    assert tr.loss_scale == 512.0  # the skip really happened
    for n, before in aux_before.items():
        after = net.collect_params()[n].data().asnumpy()
        np.testing.assert_array_equal(before, after)


def test_amp_without_wholestep_warns_once(monkeypatch, caplog):
    """MXNET_AMP with MXNET_WHOLE_STEP unset silently trains f32 — the
    compiler must say so instead of letting the user believe they are
    benchmarking bf16."""
    monkeypatch.delenv("MXNET_WHOLE_STEP", raising=False)
    monkeypatch.setenv("MXNET_AMP", "bf16")
    net = _mlp()
    x, y = _data()
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    import logging
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.gluon.wholestep"):
        st.step(x, y)
        st.step(x, y)
    assert sum("MXNET_WHOLE_STEP is not enabled" in r.message
               for r in caplog.records) == 1


def test_amp_ineligible_model_is_not_permanently_demoted(monkeypatch):
    """MXNET_AMP on a model with non-f32 master weights falls back
    per-step (config-dependent) — unsetting MXNET_AMP must resume the
    1-dispatch whole-step program without rebuilding the compiler."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.delenv("MXNET_AMP", raising=False)
    net = _mlp()
    x, y = _data()
    net(x)
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    st.step(x, y)
    assert st.active
    # simulate a non-f32 master weight (the sig the AMP gate checks)
    st._built["sig"] = ((st._built["sig"][0][0], "float64"),) + \
        tuple(st._built["sig"][1:])
    monkeypatch.setenv("MXNET_AMP", "bf16")
    st.step(x, y)  # falls back this step...
    assert st.fallback_reason is None  # ...but is NOT demoted
    monkeypatch.delenv("MXNET_AMP")
    st.step(x, y)
    assert st.active  # whole-step resumed


def test_fp16_scaler_survives_save_load_states(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.setenv("MXNET_AMP", "fp16")
    monkeypatch.setenv("MXNET_LOSS_SCALE_INIT", "1024")
    monkeypatch.setenv("MXNET_LOSS_SCALE_WINDOW", "3")
    net = _mlp()
    x, y = _data()
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    for _ in range(5):
        st.step(x, y)
    assert tr.loss_scale == 2048.0
    fname = str(tmp_path / "states")
    tr.save_states(fname)

    net2 = _mlp(seed=3)
    tr2 = _trainer(net2)
    with autograd.record():  # materialize shapes so load can adopt
        l = gluon.loss.L2Loss()(net2(x), y)
    l.backward()
    tr2.step(8)
    tr2.load_states(fname)
    assert tr2.loss_scale == 2048.0
    assert tr2._scaler["window"] == 3

    # the reverse: loading a non-fp16 states file must CLEAR a live
    # scaler, not let the old run's scale leak into the next save
    net3 = _mlp(seed=4)
    x3, y3 = _data()
    with autograd.record():
        l3 = gluon.loss.L2Loss()(net3(x3), y3)
    l3.backward()
    tr3 = _trainer(net3)
    tr3.step(8)
    plain = str(tmp_path / "plain_states")
    tr3.save_states(plain)
    tr2.load_states(plain)
    assert tr2._scaler is None and tr2.loss_scale == 1.0


# ---------------------------------------------------------------------------
# checkpoint kill-resume (extends the PR 5 pin to whole-step + fp16)
# ---------------------------------------------------------------------------
def test_wholestep_fp16_kill_resume_matches_uninterrupted(monkeypatch,
                                                          tmp_path):
    from mxnet_tpu import checkpoint as ck
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.setenv("MXNET_AMP", "fp16")
    monkeypatch.setenv("MXNET_LOSS_SCALE_INIT", "1024")
    monkeypatch.setenv("MXNET_LOSS_SCALE_WINDOW", "4")
    x, y = _data()
    xnan = x.copy()
    xnan[0, 0] = float("nan")  # forces a skip-step (scaler backoff)
    loss_fn = gluon.loss.L2Loss()
    comp = {"type": "2bit", "threshold": 0.5}
    # adam: bias correction depends on the APPLIED-step counter t, which
    # lags the schedule counts by one after the skip — the resume must
    # restore t, not re-derive it from the counts
    batches = [x, xnan, x, x, x, x]

    def setup(seed=0):
        net = _mlp(seed=seed)
        tr = _trainer(net, comp=comp, opt="adam",
                      opt_params={"learning_rate": 3e-3})
        return net, tr, WholeStepCompiler(net, loss_fn, tr)

    net, tr, st = setup()
    ref = [float(st.step(b, y).asnumpy().mean()) for b in batches]
    ref_w = [p.data().asnumpy() for p in net.collect_params().values()]
    ref_scale = tr.loss_scale

    net1, tr1, st1 = setup()
    for b in batches[:3]:
        st1.step(b, y)
    mgr = ck.CheckpointManager(str(tmp_path))
    ck.save_trainer(mgr, 3, net1, tr1)
    mgr.wait()
    manifest = ck.read_manifest(str(tmp_path / "step_3"))
    assert manifest["signatures"].get("amp_policy") == "fp16"

    # "new process": fresh objects, different init, restored over
    net2, tr2, _ = setup(seed=1)
    got = ck.restore_or_initialize(ck.CheckpointManager(str(tmp_path)),
                                   net2, tr2,
                                   initializer=mx.init.Xavier())
    assert got == 3
    st2 = WholeStepCompiler(net2, loss_fn, tr2)
    resumed = [float(st2.step(b, y).asnumpy().mean())
               for b in batches[3:]]
    np.testing.assert_allclose(ref[3:], resumed, rtol=1e-5)
    for a, b in zip(ref_w, [p.data().asnumpy()
                            for p in net2.collect_params().values()]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    assert tr2.loss_scale == ref_scale


# ---------------------------------------------------------------------------
# fallback + loud recompile
# ---------------------------------------------------------------------------
def test_env_off_uses_fused_path(monkeypatch):
    monkeypatch.delenv("MXNET_WHOLE_STEP", raising=False)
    net = _mlp()
    x, y = _data()
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    for _ in range(2):
        st.step(x, y)
    assert not st.active  # never built a program


def test_untraceable_loss_falls_back_with_warning(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")

    def plain_loss(pred, label):  # eager-only: no Symbol support
        return ((pred - label) ** 2).mean()

    net = _mlp()
    x, y = _data()
    net(x)  # materialize shapes so the failure is the loss, not deferral
    tr = _trainer(net)
    st = WholeStepCompiler(net, plain_loss, tr)
    import logging
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.gluon.wholestep"):
        l1 = st.step(x, y)
        st.step(x, y)
    assert st.fallback_reason is not None
    assert sum("not whole-step compilable" in r.message
               for r in caplog.records) == 1  # warned exactly once
    assert np.isfinite(l1.asnumpy()).all()  # training still happened


def test_update_on_kvstore_falls_back(monkeypatch):
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = _mlp()
    x, y = _data()
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore="tpu_sync",
                       update_on_kvstore=True)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    st.step(x, y)
    assert not st.active
    assert "update_on_kvstore" in st.fallback_reason


def test_sparse_param_trains_whole_step(monkeypatch):
    """ISSUE 20 flips the old contract: a sparse_grad Embedding no
    longer demotes the whole step to the legacy per-key loop — the
    row-sparse grad + scatter update ride the donated program (the
    deep numerics live in tests/test_embedding.py; this pins the
    eligibility gate itself)."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    mx.random.seed(2)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Embedding(50, 8, sparse_grad=True))
        net.add(nn.Dense(1, flatten=True))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randint(0, 50, (8, 4)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    l0 = st.step(x, y)
    st.step(x, y)
    assert st.active, st.fallback_reason
    assert np.isfinite(l0.asnumpy()).all()


def test_dtype_policy_flip_recompiles_loudly(monkeypatch, caplog):
    """The ISSUE 10 fix: an MXNET_AMP flip mid-run must recompile the
    whole-step program with a warning + counter — never silently reuse
    the f32-traced program."""
    from mxnet_tpu.observability import metrics as m
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.delenv("MXNET_AMP", raising=False)
    net = _mlp()
    x, y = _data()
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    for _ in range(3):
        st.step(x, y)
    assert st.active, st.fallback_reason
    before = m.FUSED_DTYPE_RECOMPILES.get(mode="whole_step")
    import logging
    monkeypatch.setenv("MXNET_AMP", "bf16")
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.optimizer"):
        st.step(x, y)
    assert m.FUSED_DTYPE_RECOMPILES.get(mode="whole_step") == before + 1
    assert any("recompiling" in r.message for r in caplog.records)
    # fp16 folds the loss-scale window into the policy key component —
    # the flip must still be detected (window must not hide in the
    # policy-independent tail lookup_program compares)
    monkeypatch.setenv("MXNET_AMP", "fp16")
    st.step(x, y)
    assert m.FUSED_DTYPE_RECOMPILES.get(mode="whole_step") == before + 2


def test_trace_failure_does_not_double_count_updates(monkeypatch):
    """A failure AFTER the eligibility checks (first jit trace) routes
    the step to the fallback path, which counts the same step again —
    _run must roll its increments back so num_update advances exactly
    once per optical step (lr schedules, Adam bias correction)."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = _mlp()
    x, y = _data()
    net(x)
    tr = _trainer(net, opt="adam", opt_params={"learning_rate": 1e-3})
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    monkeypatch.setattr(st, "_build_fn",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("trace boom")))
    st.step(x, y)
    assert st.fallback_reason is not None  # fell back on the failure
    st.step(x, y)
    assert tr._updaters[0].optimizer.num_update == 2


def test_runtime_failure_after_success_propagates(monkeypatch):
    """Once the program has executed, a runtime failure (e.g. the typed
    OOM re-raised by memory.oom_guard) must PROPAGATE — the failed call
    may have consumed donated buffers, so silently retrying the step
    eagerly could read dead arrays and would hide the error."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = _mlp()
    x, y = _data()
    net(x)
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    st.step(x, y)
    assert st.active

    def boom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    monkeypatch.setattr(tr._updaters[0], "lookup_program", boom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        st.step(x, y)
    assert st.fallback_reason is None  # not demoted to fallback


def test_fallback_resets_sticky_dtype_policy(monkeypatch):
    """An AMP whole-step run followed by a fallback step must not leave
    the bf16 policy stuck on the updater — the fused path's update_all
    runs f32 math and would loudly (and wrongly) recompile."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.setenv("MXNET_AMP", "bf16")
    net = _mlp()
    x, y = _data()
    net(x)  # materialize shapes so step 1 compiles instead of deferring
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    st.step(x, y)
    assert st.active and tr._updaters[0].dtype_policy == "bf16"
    monkeypatch.setenv("MXNET_WHOLE_STEP", "0")
    st.step(x, y)
    assert tr._updaters[0].dtype_policy == "f32"


def test_amp_policy_parsing(monkeypatch):
    for raw, want in [("", "f32"), ("off", "f32"), ("bf16", "bf16"),
                      ("bfloat16", "bf16"), ("fp16", "fp16"),
                      ("float16", "fp16")]:
        monkeypatch.setenv("MXNET_AMP", raw)
        assert amp_policy() == want
    monkeypatch.setenv("MXNET_AMP", "int8")
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="MXNET_AMP"):
        amp_policy()


def test_step_inside_record_raises(monkeypatch):
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = _mlp()
    x, y = _data()
    net(x)
    tr = _trainer(net)
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), tr)
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="record"):
        with autograd.record():
            st.step(x, y)
