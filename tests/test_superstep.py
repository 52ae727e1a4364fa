"""SuperStepCompiler (ISSUE 17): K whole training steps scanned into
ONE donated XLA dispatch (autotune/superstep.py).

Contracts pinned here:
  * f32 supersteps are BITWISE identical to K sequential whole-steps
    over >=2 supersteps — losses, weights, and (with 2-bit
    compression) the error-feedback residual trajectory;
  * the fp16 dynamic loss scaler rides the scan carry: skip-steps
    inside a superstep hold params AND BatchNorm running stats at
    their pre-step values, with the exact scale evolution of the
    sequential path;
  * a K=8 superstep is <=2 dispatches (expect 1) — the acceptance the
    `mxnet_superstep_dispatches` gauge tripwires in production;
  * ineligibility (MXNET_WHOLE_STEP off, HBM headroom refusal) demotes
    to K sequential steps with ONE warning, without permanently
    demoting the compiler; runtime failures after a successful scan
    PROPAGATE (donation);
  * kill-resume and supervisor retry rewind to the last SUPERSTEP
    boundary and bitwise-match the uninterrupted run
    (steps_per_call=K aligns snapshots to superstep edges).
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ck, faultinject as fi
from mxnet_tpu import gluon, resilience as res
from mxnet_tpu.autotune.superstep import SuperStepCompiler
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import supervisor as sup_mod
from mxnet_tpu.gluon.supervisor import TrainingSupervisor
from mxnet_tpu.observability import memory as mem
from mxnet_tpu.observability import metrics as M


@pytest.fixture(autouse=True)
def _fresh(monkeypatch, tmp_path):
    """Whole-step on, no AMP / K / autotune leakage between tests,
    flight dumps in scratch, no stray fault plan."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    monkeypatch.delenv("MXNET_AMP", raising=False)
    monkeypatch.delenv("MXNET_SUPERSTEP_K", raising=False)
    monkeypatch.delenv("MXNET_AUTOTUNE", raising=False)
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "fl"))
    prev = fi.install(None)
    yield
    fi.install(prev)


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


def _trainer(net, comp=None, opt="sgd", opt_params=None):
    return gluon.Trainer(
        net.collect_params(), opt,
        opt_params or {"learning_rate": 0.05, "momentum": 0.9},
        kvstore="tpu_sync", update_on_kvstore=False,
        compression_params=comp)


def _batches(n, shape=(8, 16), reg=True, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = mx.nd.array(rs.normal(0, 1, shape).astype("f"))
        if reg:
            y = mx.nd.array(rs.normal(0, 1, (shape[0], 1)).astype("f"))
        else:
            y = mx.nd.array(rs.randint(0, 3, (shape[0],)).astype("f"))
        out.append((x, y))
    return out


def _weights(net):
    return [p.data().asnumpy() for p in net.collect_params().values()]


def _setup(comp=None, opt="sgd", opt_params=None, net_fn=_mlp, seed=11,
           x=None):
    net = net_fn(seed=seed)
    if x is not None:
        net(x)  # materialize deferred shapes so the FIRST superstep scans
    loss_fn = gluon.loss.L2Loss() if net_fn is _mlp else \
        gluon.loss.SoftmaxCrossEntropyLoss()
    tr = _trainer(net, comp=comp, opt=opt, opt_params=opt_params)
    return net, tr, SuperStepCompiler(net, loss_fn, tr)


# ---------------------------------------------------------------------------
# numerics: f32 supersteps bitwise-match K sequential whole-steps
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("opt,opt_params", [
    ("sgd", {"learning_rate": 0.05}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    ("adam", {"learning_rate": 3e-3}),
])
def test_superstep_f32_bitwise_matches_sequential(opt, opt_params):
    """2 supersteps of K=4 vs 8 sequential whole-steps: losses AND
    weights bitwise, across the optimizer family (plain SGD, stateful
    momentum, Adam's applied-step bias correction riding the carry)."""
    K, groups = 4, 2
    batches = _batches(K * groups)

    net_s, _, st_s = _setup(opt=opt, opt_params=opt_params,
                            x=batches[0][0])
    super_losses = []
    for g in range(groups):
        xs = [b[0] for b in batches[g * K:(g + 1) * K]]
        ys = [b[1] for b in batches[g * K:(g + 1) * K]]
        super_losses.append(st_s.superstep(xs, ys).asnumpy())
        assert st_s.super_active, st_s.fallback_reason  # every group scanned

    net_q, _, st_q = _setup(opt=opt, opt_params=opt_params,
                            x=batches[0][0])
    seq_losses = [st_q.step(x, y).asnumpy() for x, y in batches]

    np.testing.assert_array_equal(
        np.concatenate(super_losses, axis=0), np.stack(seq_losses))
    for a, b in zip(_weights(net_s), _weights(net_q)):
        np.testing.assert_array_equal(a, b)


def test_superstep_compressed_bitwise_matches_sequential():
    """2-bit compression composes with the scan: the error-feedback
    residuals thread through the carry and their trajectory is bitwise
    the sequential one."""
    comp = {"type": "2bit", "threshold": 0.5}
    K = 4
    batches = _batches(K * 2)

    net_s, tr_s, st_s = _setup(comp=comp, x=batches[0][0])
    for g in range(2):
        st_s.superstep([b[0] for b in batches[g * K:(g + 1) * K]],
                       [b[1] for b in batches[g * K:(g + 1) * K]])
        assert st_s.super_active, st_s.fallback_reason

    net_q, tr_q, st_q = _setup(comp=comp, x=batches[0][0])
    for x, y in batches:
        st_q.step(x, y)

    for a, b in zip(_weights(net_s), _weights(net_q)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(tr_s._residuals, tr_q._residuals):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_superstep_stacked_input_matches_list_input():
    """Pre-stacked (K, ...) arrays (what a depth>=K prefetcher stages)
    run the same program as a list of K batches."""
    K = 4
    batches = _batches(K)
    xs = [b[0] for b in batches]
    ys = [b[1] for b in batches]

    net_l, _, st_l = _setup(x=xs[0])
    l_list = st_l.superstep(xs, ys).asnumpy()
    assert st_l.super_active, st_l.fallback_reason

    net_s, _, st_s = _setup(x=xs[0])
    xstk = mx.nd.array(np.stack([x.asnumpy() for x in xs]))
    ystk = mx.nd.array(np.stack([y.asnumpy() for y in ys]))
    l_stk = st_s.superstep(xstk, ystk).asnumpy()
    assert st_s.super_active, st_s.fallback_reason

    np.testing.assert_array_equal(l_list, l_stk)
    for a, b in zip(_weights(net_l), _weights(net_s)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# fp16: the scaler rides the carry, skip-steps hold params/BN aux
# ---------------------------------------------------------------------------
def test_superstep_fp16_skip_step_holds_params_and_bn_aux(monkeypatch):
    """A superstep whose batches ALL overflow must leave params and
    BatchNorm running stats bitwise-untouched, with the scale backed
    off once per skipped step — the K-fused twin of the sequential
    skip-step contract."""
    monkeypatch.setenv("MXNET_AMP", "fp16")
    monkeypatch.setenv("MXNET_LOSS_SCALE_INIT", "1024")
    K = 4
    net, tr, st = _setup(net_fn=_cnn)
    batches = _batches(K, shape=(8, 3, 8, 8), reg=False)
    net(batches[0][0])  # materialize shapes
    st.superstep([b[0] for b in batches], [b[1] for b in batches])
    assert st.super_active, st.fallback_reason
    assert tr.loss_scale == 1024.0

    before_w = _weights(net)
    aux_before = {n: p.data().asnumpy()
                  for n, p in net.collect_params().items()
                  if "running" in n}
    assert aux_before  # the net really has BN running stats
    bad = mx.nd.array(np.full((8, 3, 8, 8), np.inf, dtype="f"))
    st.superstep([bad] * K, [b[1] for b in batches])
    # every step in the superstep skipped: one x0.5 backoff each
    assert tr.loss_scale == 1024.0 / 2 ** K
    for a, b in zip(before_w, _weights(net)):
        np.testing.assert_array_equal(a, b)
    for n, before in aux_before.items():
        np.testing.assert_array_equal(
            before, net.collect_params()[n].data().asnumpy())
    # finite again: training resumes inside the same compiled program
    st.superstep([b[0] for b in batches], [b[1] for b in batches])
    assert any(not np.array_equal(a, b)
               for a, b in zip(before_w, _weights(net)))


def test_superstep_fp16_mixed_batch_matches_sequential(monkeypatch):
    """A superstep containing ONE overflowing batch evolves the scale
    exactly and the params within the documented fp16 tolerance of the
    sequential fp16 whole-step path (the skip-select runs per scan
    iteration; XLA may fuse the low-precision math differently inside
    the scan, so fp16 — unlike f32 — carries no bitwise guarantee)."""
    monkeypatch.setenv("MXNET_AMP", "fp16")
    monkeypatch.setenv("MXNET_LOSS_SCALE_INIT", "1024")
    K = 4
    batches = _batches(K, shape=(8, 3, 8, 8), reg=False)
    bad = batches[0][0].copy()
    bad[0, 0, 0, 0] = float("nan")
    xs = [batches[0][0], bad, batches[2][0], batches[3][0]]
    ys = [b[1] for b in batches]

    net_s, tr_s, st_s = _setup(net_fn=_cnn)
    net_s(xs[0])
    st_s.superstep(list(xs), list(ys))
    assert st_s.super_active, st_s.fallback_reason

    net_q, tr_q, st_q = _setup(net_fn=_cnn)
    net_q(xs[0])
    for x, y in zip(xs, ys):
        st_q.step(x, y)

    assert tr_s.loss_scale == tr_q.loss_scale == 512.0  # one backoff
    for a, b in zip(_weights(net_s), _weights(net_q)):
        np.testing.assert_allclose(a, b, rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# the dispatch acceptance: K=8 superstep in <=2 dispatches (expect 1)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("comp", [None, {"type": "2bit", "threshold": 0.5}])
def test_superstep_k8_dispatch_gate(comp):
    K = 8
    batches = _batches(K)
    xs = [b[0] for b in batches]
    ys = [b[1] for b in batches]
    net, tr, st = _setup(comp=comp, x=xs[0])
    st.superstep(xs, ys)  # compile warm-up
    assert st.super_active, st.fallback_reason
    d0 = M.step_dispatches()
    st.superstep(xs, ys)
    delta = M.step_dispatches() - d0
    # the ISSUE 17 acceptance: 8 steps in <=2 dispatches (expect 1)
    assert delta <= 2, f"K=8 superstep took {delta} dispatches"
    assert M.SUPERSTEP_DISPATCHES.get() == delta
    assert M.TRAINER_STEP_DISPATCHES.get() == delta / K
    if comp is None:
        assert delta == 1


# ---------------------------------------------------------------------------
# K resolution + demotion reasons
# ---------------------------------------------------------------------------
def test_k_resolution_env_beats_ctor_beats_default(monkeypatch):
    net, tr, st = _setup()
    assert st.k == 4  # static default, no env/ctor/decision
    st2 = SuperStepCompiler(net, gluon.loss.L2Loss(), tr, k=2)
    assert st2.k == 2
    monkeypatch.setenv("MXNET_SUPERSTEP_K", "7")
    assert st2.k == 7  # env always wins


def test_wholestep_off_demotes_with_one_warning(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_WHOLE_STEP", "0")
    K = 4
    batches = _batches(K)
    net, tr, st = _setup()
    xs = [b[0] for b in batches]
    ys = [b[1] for b in batches]
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.autotune.superstep"):
        l1 = st.superstep(xs, ys)
        st.superstep(xs, ys)
    assert sum("demoted" in r.message for r in caplog.records) == 1
    assert not st.super_active
    assert l1.shape[0] == K  # losses still come back stacked
    assert np.isfinite(l1.asnumpy()).all()  # training still happened


def test_headroom_refusal_demotes_per_call_only(monkeypatch, caplog):
    """An HBM-ledger refusal for staging K batches demotes THAT call to
    K sequential steps; the scan program stays viable and the next call
    (headroom back) runs scanned."""
    K = 4
    batches = _batches(K)
    xs = [b[0] for b in batches]
    ys = [b[1] for b in batches]
    net, tr, st = _setup(x=xs[0])
    monkeypatch.setattr(mem, "ENABLED", True)
    monkeypatch.setattr(mem, "ensure_headroom", lambda *a, **k: False)
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.autotune.superstep"):
        st.superstep(xs, ys)
    assert any("headroom" in r.message for r in caplog.records)
    assert not st.super_active
    assert st.fallback_reason is None  # NOT permanently demoted
    monkeypatch.setattr(mem, "ensure_headroom", lambda *a, **k: True)
    st.superstep(xs, ys)
    assert st.super_active


def test_runtime_failure_after_success_propagates(monkeypatch):
    """Once a scan program has executed, a runtime failure may have
    consumed donated carry buffers — it must PROPAGATE (the supervisor
    is the retry authority, superstep-granular), never silently retry
    sequentially."""
    K = 4
    batches = _batches(K)
    xs = [b[0] for b in batches]
    ys = [b[1] for b in batches]
    net, tr, st = _setup(x=xs[0])
    st.superstep(xs, ys)
    assert st.super_active

    def boom(*a, **k):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    monkeypatch.setattr(tr._updaters[0], "lookup_program", boom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        st.superstep(xs, ys)
    assert st.fallback_reason is None


# ---------------------------------------------------------------------------
# superstep-boundary recovery: kill-resume + supervisor chaos
# ---------------------------------------------------------------------------
def test_kill_resume_restores_to_superstep_boundary(tmp_path):
    """Checkpoint at a superstep boundary, 'new process' (fresh
    objects, different init), restore, finish — bitwise-identical to
    the uninterrupted run (f32 + 2-bit residuals ride the manifest)."""
    comp = {"type": "2bit", "threshold": 0.5}
    K, groups = 4, 3
    batches = _batches(K * groups)

    def group(g):
        return ([b[0] for b in batches[g * K:(g + 1) * K]],
                [b[1] for b in batches[g * K:(g + 1) * K]])

    net, tr, st = _setup(comp=comp, x=batches[0][0])
    ref_losses = [st.superstep(*group(g)).asnumpy() for g in range(groups)]
    assert st.super_active, st.fallback_reason
    ref_w = _weights(net)

    net1, tr1, st1 = _setup(comp=comp, x=batches[0][0])
    for g in range(2):
        st1.superstep(*group(g))
    mgr = ck.CheckpointManager(str(tmp_path))
    ck.save_trainer(mgr, 2 * K, net1, tr1)
    mgr.wait()

    net2, tr2, _ = _setup(comp=comp, seed=3)
    got = ck.restore_or_initialize(ck.CheckpointManager(str(tmp_path)),
                                   net2, tr2,
                                   initializer=mx.init.Xavier())
    assert got == 2 * K  # resumed at the superstep boundary
    st2 = SuperStepCompiler(net2, gluon.loss.L2Loss(), tr2)
    resumed = st2.superstep(*group(2)).asnumpy()
    np.testing.assert_array_equal(ref_losses[2], resumed)
    for a, b in zip(ref_w, _weights(net2)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.chaos
def test_supervised_superstep_retry_bitwise_matches(monkeypatch):
    """A transient failure mid-run under TrainingSupervisor with
    steps_per_call=K: the snapshot cadence lands on superstep
    boundaries (snapshot_steps=8 -> every 2nd call), the failed
    SUPERSTEP replays whole, and the run bitwise-matches an
    uninterrupted one."""
    monkeypatch.setattr(res, "POST_MORTEM_MIN_S", 0.0)
    sup_mod.enable()
    K, groups = 4, 5
    batches = _batches(K * groups)
    grouped = [([b[0] for b in batches[g * K:(g + 1) * K]],
                [b[1] for b in batches[g * K:(g + 1) * K]])
               for g in range(groups)]

    def run(plan=None):
        net, tr, st = _setup(comp={"type": "2bit", "threshold": 0.5},
                             x=batches[0][0])
        sup = TrainingSupervisor(st.superstep, trainer=tr, params=net,
                                 snapshot_steps=8, steps_per_call=K,
                                 backoff_s=0.001)
        assert sup._snapshot_calls == 2  # superstep-aligned cadence
        losses = []
        ctx = fi.active(plan) if plan is not None else None
        if ctx:
            ctx.__enter__()
        try:
            for xs, ys in grouped:
                losses.append(sup.step(xs, ys).asnumpy())
        finally:
            if ctx:
                ctx.__exit__(None, None, None)
            sup.close()
        assert st.super_active, st.fallback_reason
        return losses, _weights(net)

    ref_losses, ref_w = run()
    plan = (fi.FaultPlan()
            .add("trainer.step", "raise", exc=OSError, times=1, after=2))
    got_losses, got_w = run(plan)
    for a, b in zip(ref_losses, got_losses):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(ref_w, got_w):
        np.testing.assert_array_equal(a, b)
