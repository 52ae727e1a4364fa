"""TrainingSupervisor (ISSUE 12): typed fault classification,
donation-safe snapshot/replay retry, divergence & stall watchdogs,
supervised preemption, and the chaos acceptance run — the training-side
twin of the PR 6 serving resilience suite."""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint as ck, faultinject as fi
from mxnet_tpu import gluon, resilience as res
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.supervisor import TrainingSupervisor
from mxnet_tpu.gluon.wholestep import WholeStepCompiler
from mxnet_tpu.gluon import supervisor as sup_mod
from mxnet_tpu.observability import flight
from mxnet_tpu.observability import metrics as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch, tmp_path):
    """Unlimited post-mortems per test, dumps in scratch, no stray
    fault plan, supervision enabled."""
    monkeypatch.setattr(res, "POST_MORTEM_MIN_S", 0.0)
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "fl"))
    prev = fi.install(None)
    res.reset()
    sup_mod.enable()
    yield
    fi.install(prev)


def _setup(seed=0, compression=False, lr=0.05):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier())
    kw = {}
    if compression:
        kw["compression_params"] = {"type": "2bit", "threshold": 0.5}
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9},
                            kvstore="tpu_sync", update_on_kvstore=False,
                            **kw)
    return net, trainer


_LOSS = None


def _loss_fn():
    global _LOSS
    if _LOSS is None:
        _LOSS = gluon.loss.L2Loss()
    return _LOSS


def _mkstep(net, trainer, bs=8):
    loss = _loss_fn()

    def step(x, y):
        with autograd.record():
            l = loss(net(x), y)
        l.backward()
        trainer.step(bs)
        return l
    return step


def _data(n=8, d=16, seed=0):
    rs = np.random.RandomState(seed)
    return (mx.nd.array(rs.normal(0, 1, (n, d)).astype("f")),
            mx.nd.array(rs.normal(0, 1, (n, 1)).astype("f")))


def _weights(net):
    return [p.data().asnumpy() for p in net.collect_params().values()]


# ---------------------------------------------------------------------------
# fault classification
# ---------------------------------------------------------------------------
def test_classify_classes():
    from mxnet_tpu.observability.memory import (DeviceMemoryError,
                                                HBMBudgetError)
    assert res.classify(OSError("disk")) == res.TRANSIENT
    assert res.classify(TimeoutError("rpc")) == res.TRANSIENT
    assert res.classify(ConnectionError("reset")) == res.TRANSIENT
    assert res.classify(fi.InjectedFault("chaos")) == res.TRANSIENT
    assert res.classify(res.DeviceUnavailableError("gone")) == res.TRANSIENT
    # gRPC status phrases inside arbitrary exception text (the jaxlib
    # XlaRuntimeError shape for a lost device)
    assert res.classify(RuntimeError("UNAVAILABLE: device lost")) \
        == res.TRANSIENT
    assert res.classify(RuntimeError("DEADLINE_EXCEEDED")) == res.TRANSIENT
    assert res.classify(DeviceMemoryError("oom")) == res.OOM
    assert res.classify(HBMBudgetError("budget")) == res.OOM
    assert res.classify(ValueError("shape")) == res.PERMANENT
    assert res.classify(mx.base.MXNetError("user")) == res.PERMANENT
    # damaged data is NOT retryable-by-replay: the skip budget handles it
    assert res.classify(res.DataCorruptionError("bad rec")) == res.PERMANENT


def test_new_sites_registered_and_device_unavailable_default():
    for site in ("trainer.step", "data.batch", "kvstore.allreduce",
                 "kvstore.sparse_allreduce", "device.unavailable"):
        assert site in fi.SITES
    plan = fi.parse_plan("device.unavailable:raise;"
                         "data.batch:raise:DataCorruptionError:2;"
                         "trainer.step:raise:DeviceUnavailableError")
    assert plan.rules("device.unavailable")[0].exc \
        is res.DeviceUnavailableError
    assert plan.rules("data.batch")[0].exc is res.DataCorruptionError
    assert plan.rules("trainer.step")[0].exc is res.DeviceUnavailableError


# ---------------------------------------------------------------------------
# MXNET_SUPERVISE=0: one boolean test
# ---------------------------------------------------------------------------
def test_disabled_is_passthrough():
    net, tr = _setup()
    x, y = _data()
    calls = []
    step = _mkstep(net, tr)

    def spy(*a, **k):
        calls.append(1)
        return step(*a, **k)

    sup = TrainingSupervisor(spy, trainer=tr, params=net)
    snaps = M.SUPERVISOR_SNAPSHOTS.value
    sup_mod.disable()
    try:
        sup.step(x, y)
    finally:
        sup_mod.enable()
    assert calls == [1]
    # no snapshot, no worker thread, no watchdog state
    assert M.SUPERVISOR_SNAPSHOTS.value == snaps
    assert sup._worker is None and sup._snap is None


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------
def test_snapshot_cadence_and_gauge():
    net, tr = _setup()
    x, y = _data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             snapshot_steps=3)
    base = M.SUPERVISOR_SNAPSHOTS.value
    for _ in range(7):
        sup.step(x, y)
    # deferred init skips the step-0 capture; boundaries 1 (first
    # possible), 3, 6 take one each
    assert M.SUPERVISOR_SNAPSHOTS.value == base + 3
    assert M.SUPERVISOR_LAST_SNAPSHOT_STEP.get() == 6
    assert sup.stats()["snapshot_step"] == 6
    assert len(sup._window) <= 3
    sup.close()


# ---------------------------------------------------------------------------
# donation-safe retry
# ---------------------------------------------------------------------------
def test_fused_retry_bitwise_matches_uninterrupted():
    """2 transient trainer.step failures + 1 kvstore.allreduce failure
    over 12 fused steps: restore+replay makes the run BITWISE equal to
    an uninterrupted one (acceptance asks rtol 1e-5 for fused; the
    snapshot/replay design delivers bitwise)."""
    x, y = _data()
    net0, tr0 = _setup(compression=True)
    s0 = _mkstep(net0, tr0)
    ref = [float(s0(x, y).asnumpy().mean()) for _ in range(12)]

    net1, tr1 = _setup(compression=True)
    sup = TrainingSupervisor(_mkstep(net1, tr1), trainer=tr1, params=net1,
                             snapshot_steps=4)
    retries = M.SUPERVISOR_RETRIES.value
    plan = (fi.FaultPlan()
            .add("trainer.step", "raise", exc=OSError, times=1, after=2)
            .add("trainer.step", "raise",
                 exc=res.DeviceUnavailableError, times=1, after=7)
            .add("kvstore.allreduce", "raise", exc=OSError, times=1,
                 after=10))
    with fi.active(plan):
        got = [float(sup.step(x, y).asnumpy().mean()) for _ in range(12)]
    assert plan.stats() == {"trainer.step": 2, "kvstore.allreduce": 1}
    np.testing.assert_array_equal(np.float32(ref), np.float32(got))
    for a, b in zip(_weights(net0), _weights(net1)):
        np.testing.assert_array_equal(a, b)
    assert M.SUPERVISOR_RETRIES.value >= retries + 3
    sup.close()


@pytest.mark.chaos
def test_sparse_allreduce_retry_bitwise_matches_uninterrupted():
    """ISSUE 20 chaos case: a transient raise at the NEW
    kvstore.sparse_allreduce site (fires BEFORE the row-sparse reduce
    touches anything) retries bitwise — per-ROW optimizer state
    (Adam's m/v slots for exactly the touched rows) restores through
    the snapshot window and the replayed step re-reduces the same
    grads."""
    def sparse_setup(seed=0):
        mx.random.seed(seed)
        np.random.seed(seed)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Embedding(40, 8, sparse_grad=True))
            net.add(nn.Flatten())
            net.add(nn.Dense(1))
        net.hybridize()
        net.initialize(mx.init.Xavier())
        trainer = gluon.Trainer(net.collect_params(), "adam",
                                {"learning_rate": 3e-3},
                                kvstore="tpu_sync",
                                update_on_kvstore=False)
        return net, trainer

    rs = np.random.RandomState(3)
    x = mx.nd.array(rs.randint(0, 40, (8, 4)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
    net0, tr0 = sparse_setup()
    s0 = _mkstep(net0, tr0)
    ref = [float(s0(x, y).asnumpy().mean()) for _ in range(10)]

    net1, tr1 = sparse_setup()
    sup = TrainingSupervisor(_mkstep(net1, tr1), trainer=tr1, params=net1,
                             snapshot_steps=4)
    plan = (fi.FaultPlan()
            .add("kvstore.sparse_allreduce", "raise", exc=OSError,
                 times=1, after=6))
    with fi.active(plan):
        got = [float(sup.step(x, y).asnumpy().mean()) for _ in range(10)]
    assert plan.stats() == {"kvstore.sparse_allreduce": 1}
    np.testing.assert_array_equal(np.float32(ref), np.float32(got))
    for a, b in zip(_weights(net0), _weights(net1)):
        np.testing.assert_array_equal(a, b)
    sup.close()


def test_wholestep_retry_bitwise_and_no_permanent_fallback(monkeypatch):
    """A transient failure of the DONATED whole-step program rebuilds
    params/opt-state from the host snapshot and re-executes — bitwise
    equal to the uninterrupted run, and the compiler stays on the
    whole-step path (no permanent fused demotion)."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    x, y = _data()
    loss = _loss_fn()
    net0, tr0 = _setup()
    st0 = WholeStepCompiler(net0, loss, tr0)
    ref = [float(st0.step(x, y).asnumpy().mean()) for _ in range(10)]
    assert st0.active, st0.fallback_reason

    net1, tr1 = _setup()
    st1 = WholeStepCompiler(net1, loss, tr1)
    sup = TrainingSupervisor(st1.step, trainer=tr1, params=net1,
                             snapshot_steps=4)
    plan = (fi.FaultPlan()
            .add("trainer.step", "raise", exc=OSError, times=1, after=3)
            .add("device.unavailable", "raise", times=1, after=7))
    with fi.active(plan):
        got = [float(sup.step(x, y).asnumpy().mean()) for _ in range(10)]
    assert plan.stats() == {"trainer.step": 1, "device.unavailable": 1}
    assert st1.active, st1.fallback_reason
    np.testing.assert_array_equal(np.float32(ref), np.float32(got))
    for a, b in zip(_weights(net0), _weights(net1)):
        np.testing.assert_array_equal(a, b)
    sup.close()


def test_permanent_error_propagates_without_retry():
    net, tr = _setup()
    x, y = _data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net)
    sup.step(x, y)
    retries = M.SUPERVISOR_RETRIES.value
    plan = fi.FaultPlan().add("trainer.step", "raise", exc=fi._EXC_TYPES[
        "MXNetError"], times=1)
    with fi.active(plan):
        with pytest.raises(mx.base.MXNetError):
            sup.step(x, y)
    assert M.SUPERVISOR_RETRIES.value == retries  # no retry burned
    # the failed batch must not linger in the replay window
    n_window = len(sup._window)
    sup.step(x, y)
    assert len(sup._window) == n_window + 1
    sup.close()


def test_retries_exhaust_to_typed_error():
    net, tr = _setup()
    x, y = _data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             retries=2, backoff_s=0.001)
    sup.step(x, y)
    plan = fi.FaultPlan().add("trainer.step", "raise", exc=OSError)
    with fi.active(plan):
        with pytest.raises(res.StepRetriesExhausted) as ei:
            sup.step(x, y)
    assert isinstance(ei.value.__cause__, OSError)
    sup.close()


def test_oom_propagates_typed():
    from mxnet_tpu.observability.memory import DeviceMemoryError
    net, tr = _setup()
    x, y = _data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net)
    sup.step(x, y)
    retries = M.SUPERVISOR_RETRIES.value
    # memory.oom fires inside oom_guard at the fused update chokepoint
    plan = fi.FaultPlan().add("memory.oom", "raise", times=1)
    with fi.active(plan):
        with pytest.raises(DeviceMemoryError):
            sup.step(x, y)
    assert M.SUPERVISOR_RETRIES.value == retries
    sup.close()


# ---------------------------------------------------------------------------
# divergence watchdog
# ---------------------------------------------------------------------------
def _nan_data(n=8, d=16):
    return mx.nd.array(np.full((n, d), np.nan, dtype="f"))


def test_divergence_raises_typed_with_one_post_mortem():
    net, tr = _setup()
    x, y = _data()
    xnan = _nan_data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             diverge_patience=2)
    trips = M.SUPERVISOR_WATCHDOG_TRIPS.get(kind="divergence")
    dumps = M.FLIGHT_DUMPS.get(reason="divergence")
    sup.step(x, y)
    sup.step(xnan, y)  # 1st nonfinite — under patience
    with pytest.raises(res.DivergenceError) as ei:
        sup.step(xnan, y)
    err = ei.value
    assert err.step == 2  # the failing step id rides the typed error
    assert M.SUPERVISOR_WATCHDOG_TRIPS.get(kind="divergence") == trips + 1
    assert M.FLIGHT_DUMPS.get(reason="divergence") == dumps + 1
    # exactly one post-mortem pair on disk, and it names the step
    rep_path = err.report["report_path"]
    assert rep_path and os.path.exists(rep_path)
    rep = json.load(open(rep_path))
    assert rep["reason"] == "divergence" and rep["step"] == 2
    assert err.report["flight_path"] \
        and os.path.exists(err.report["flight_path"])
    sup.close()


def test_divergence_post_mortem_rate_limited(monkeypatch):
    monkeypatch.setattr(res, "POST_MORTEM_MIN_S", 3600.0)
    res.reset()
    net, tr = _setup()
    x, y = _data()
    xnan = _nan_data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             diverge_patience=1, on_diverge="rewind")
    dumps = M.FLIGHT_DUMPS.get(reason="divergence")
    sup.step(x, y)
    sup.step(xnan, y)  # trips + dumps
    sup.step(xnan, y)  # trips again — dump rate-limited away
    assert M.FLIGHT_DUMPS.get(reason="divergence") == dumps + 1
    sup.close()


def test_divergence_rewind_restores_snapshot_state():
    net, tr = _setup()
    x, y = _data()
    xnan = _nan_data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             diverge_patience=1, on_diverge="rewind",
                             snapshot_steps=100)
    rewinds = M.SUPERVISOR_REWINDS.get(reason="divergence")
    sup.step(x, y)   # snapshot lands at the step-1 boundary (post-step-0)
    sup.step(xnan, y)
    assert M.SUPERVISOR_REWINDS.get(reason="divergence") == rewinds + 1
    # weights equal a clean 1-step run (the snapshot state)
    net2, tr2 = _setup()
    _mkstep(net2, tr2)(x, y)
    for a, b in zip(_weights(net), _weights(net2)):
        np.testing.assert_array_equal(a, b)
    # and training continues healthily afterwards
    out = sup.step(x, y)
    assert np.isfinite(out.asnumpy()).all()
    sup.close()


def test_env_on_diverge_validated():
    net, tr = _setup()
    with pytest.raises(mx.base.MXNetError, match="raise|rewind"):
        TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                           on_diverge="explode")


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------
@pytest.mark.chaos
def test_stall_raises_typed_dumps_and_poisons():
    net, tr = _setup()
    x, y = _data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             stall_min_s=0.3, stall_factor=2.0)
    for _ in range(8):  # warm the EWMA past _EWMA_WARMUP
        sup.step(x, y)
    trips = M.SUPERVISOR_WATCHDOG_TRIPS.get(kind="stall")
    dumps = M.FLIGHT_DUMPS.get(reason="stall")
    plan = fi.FaultPlan().add("trainer.step", "delay", delay_s=4.0,
                              times=1)
    t0 = time.perf_counter()
    with fi.active(plan):
        with pytest.raises(res.TrainingStalledError) as ei:
            sup.step(x, y)
    # raised at the deadline, NOT after the 4s injected wedge finished
    assert time.perf_counter() - t0 < 3.0
    err = ei.value
    assert err.step == 8 and err.timeout_s >= 0.3
    assert M.SUPERVISOR_WATCHDOG_TRIPS.get(kind="stall") == trips + 1
    assert M.FLIGHT_DUMPS.get(reason="stall") == dumps + 1
    rep = json.load(open(err.report["report_path"]))
    assert rep["reason"] == "stall" and rep["step"] == 8
    # poisoned: the wedged dispatch may still own the device
    with pytest.raises(res.TrainingStalledError, match="poisoned"):
        sup.step(x, y)
    assert sup.stalled
    time.sleep(4.2)  # let the wedged worker drain before teardown


def test_stall_watchdog_unarmed_before_warmup():
    net, tr = _setup()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             stall_min_s=0.01, stall_factor=1.0)
    # no EWMA yet (own or flight): wait-forever, never a false trip
    assert sup._stall_timeout() is None


# ---------------------------------------------------------------------------
# chaos acceptance: the ISSUE 12 plan over 50 steps
# ---------------------------------------------------------------------------
@pytest.mark.chaos
@pytest.mark.parametrize("whole_step", [False, True])
def test_chaos_acceptance_50_steps(monkeypatch, whole_step):
    """2 transient trainer.step failures + 1 data.batch corruption +
    1 kvstore.allreduce transient over a 50-step supervised f32 run:
    completes and BITWISE-matches (whole-step) / rtol-1e-5-matches
    (fused — bitwise here too) an uninterrupted run, with the data
    pipeline running through the skip-budgeted prefetcher."""
    from mxnet_tpu.gluon.data.prefetcher import AsyncPrefetcher
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1" if whole_step else "0")
    loss = _loss_fn()
    rs = np.random.RandomState(7)
    batches = [(mx.nd.array(rs.normal(0, 1, (8, 16)).astype("f")),
                mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f")))
               for _ in range(50)]

    def run(plan=None, skip_budget=0):
        net, tr = _setup(compression=not whole_step)
        if whole_step:
            step_fn = WholeStepCompiler(net, loss, tr).step
        else:
            step_fn = _mkstep(net, tr)
        sup = TrainingSupervisor(step_fn, trainer=tr, params=net,
                                 snapshot_steps=10)
        it = iter(batches)
        pf = AsyncPrefetcher(lambda: next(it), skip_budget=skip_budget)
        losses = []
        ctx = fi.active(plan) if plan is not None else None
        if ctx:
            ctx.__enter__()
        try:
            while True:
                try:
                    x, y = pf.get()
                except StopIteration:
                    break
                losses.append(float(sup.step(x, y).asnumpy().mean()))
        finally:
            if ctx:
                ctx.__exit__(None, None, None)
            sup.close()
        return losses, _weights(net)

    ref_losses, ref_w = run()

    plan = (fi.FaultPlan()
            .add("trainer.step", "raise", exc=OSError, times=1, after=12)
            .add("trainer.step", "raise",
                 exc=res.DeviceUnavailableError, times=1, after=33)
            .add("data.batch", "raise", exc=res.DataCorruptionError,
                 times=1, after=20)
            .add("kvstore.allreduce", "raise", exc=OSError, times=1,
                 after=40))
    got_losses, got_w = run(plan, skip_budget=2)
    fired = plan.stats()
    assert fired["trainer.step"] == 2 and fired["data.batch"] == 1
    # whole-step inlines the reduce into the donated program, so the
    # kvstore site only fires on the fused path
    assert fired.get("kvstore.allreduce", 0) == (0 if whole_step else 1)
    assert len(got_losses) == 50
    np.testing.assert_array_equal(np.float32(ref_losses),
                                  np.float32(got_losses))
    for a, b in zip(ref_w, got_w):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# supervised preemption + SIGKILL resume
# ---------------------------------------------------------------------------
_KILL_CHILD = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint as ck, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.supervisor import TrainingSupervisor

def setup(seed=0):
    mx.random.seed(seed); np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu")); net.add(nn.Dense(1))
    net.hybridize(); net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {{"learning_rate": 0.05, "momentum": 0.9}},
                       kvstore="tpu_sync", update_on_kvstore=False)
    return net, tr

loss_fn = gluon.loss.L2Loss()
rs = np.random.RandomState(0)
x = mx.nd.array(rs.normal(0, 1, (8, 16)).astype("f"))
y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
net, tr = setup()

def step(x, y):
    with autograd.record():
        l = loss_fn(net(x), y)
    l.backward(); tr.step(8)
    return l

sup = TrainingSupervisor(step, trainer=tr, params=net)
mgr = ck.CheckpointManager(sys.argv[1], async_save=False)
for i in range(10):
    sup.step(x, y)
    ck.save_trainer(mgr, i + 1, net, tr, block=True)
    print("STEP", i + 1, flush=True)
    # no SIGTERM grace, no atexit, no warning: the parent SIGKILLs us
    # somewhere in here
"""


@pytest.mark.chaos
def test_sigkill_mid_run_supervised_resume_matches(tmp_path):
    """Hard kill (SIGKILL — no handler can run, unlike the PR 5 SIGTERM
    pin): whatever checkpoint was committed last is intact (atomic
    layout), and a supervised resume from it matches the uninterrupted
    run at rtol 1e-5."""
    x, y = _data()
    # uninterrupted 10-step reference
    net0, tr0 = _setup()
    s0 = _mkstep(net0, tr0)
    ref_losses = [float(s0(x, y).asnumpy().mean()) for _ in range(10)]

    d = str(tmp_path / "ck")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_CHECKPOINT_FSYNC="0")
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_CHILD.format(repo=REPO), d],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True)
    killed_after = None
    try:
        for line in proc.stdout:
            if line.startswith("STEP"):
                killed_after = int(line.split()[1])
                if killed_after >= 4:
                    proc.send_signal(signal.SIGKILL)  # mid-step, no grace
                    break
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL
    assert killed_after is not None and killed_after >= 4

    # resume: newest committed checkpoint is valid despite the hard kill
    net2, tr2 = _setup(seed=1)  # different init, restored over
    mgr = ck.CheckpointManager(d)
    got = ck.restore_or_initialize(mgr, net2, tr2,
                                   initializer=mx.init.Xavier())
    assert got is not None and got >= 1
    sup = TrainingSupervisor(_mkstep(net2, tr2), trainer=tr2, params=net2)
    resumed = [float(sup.step(x, y).asnumpy().mean())
               for _ in range(10 - got)]
    np.testing.assert_allclose(ref_losses[got:], resumed, rtol=1e-5)
    for a, b in zip(_weights(net0), _weights(net2)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    sup.close()


def test_preemption_state_fn_prefers_snapshot_mid_step(tmp_path,
                                                       monkeypatch):
    """The supervisor-routed preemption hook: a signal landing MID-STEP
    saves the last consistent SNAPSHOT (live device buffers may be
    half-updated or donated at that instant); between steps it saves a
    fresh live pack — both in restore_trainer-compatible packing."""
    import mxnet_tpu.checkpoint.hooks as hooks_mod
    captured = {}

    def fake_install(manager, state_fn, **kw):
        captured["state_fn"] = state_fn
        return lambda: None

    monkeypatch.setattr(hooks_mod, "install_preemption_hook", fake_install)
    net, tr = _setup()
    x, y = _data()
    sup = TrainingSupervisor(_mkstep(net, tr), trainer=tr, params=net,
                             snapshot_steps=2)
    mgr = ck.CheckpointManager(str(tmp_path), async_save=False)
    sup.install_preemption_hook(mgr)
    state_fn = captured["state_fn"]
    for _ in range(5):
        sup.step(x, y)
    snap_step, snap = sup._snap
    from mxnet_tpu.checkpoint.manager import PARAM_PREFIX
    first_param = next(iter(net.collect_params().keys()))
    # mid-step: the snapshot wins (older than live by construction)
    sup._in_step = True
    try:
        step, state = state_fn()
    finally:
        sup._in_step = False
    assert step == snap_step
    snap_arr = dict(snap)
    live_w = net.collect_params()[first_param].data().asnumpy()
    # snapshot keys carry name-scope-stripped names (the save_trainer
    # packing): match the full collect_params name against them
    saved = key = None
    for name, payload in state.items():
        if name.startswith(PARAM_PREFIX) and \
                first_param.endswith(name[len(PARAM_PREFIX):]):
            saved, key = payload, name
    assert saved is not None, list(state)
    np.testing.assert_array_equal(saved, snap_arr[key][1])
    assert not np.array_equal(saved, live_w)  # NOT the live buffers
    # between steps: a fresh live pack at the current step count
    step2, state2 = state_fn()
    assert step2 == 5
    # and the packing restores through restore_trainer
    mgr.save(step, state, block=True)
    net2, tr2 = _setup(seed=1)
    got = ck.restore_trainer(ck.CheckpointManager(str(tmp_path)), net2,
                             trainer=tr2)
    assert got == snap_step
    sup.close()


@pytest.mark.chaos
def test_preemption_sigterm_subprocess_snapshot_and_flight_dump(tmp_path):
    """SIGTERM a supervised run: the emergency checkpoint holds the
    supervisor's last consistent snapshot (the signal lands mid-step)
    AND the flight ring is dumped with reason="preempt" (satellite:
    a SIGTERM'd run leaves a timeline, not just weights)."""
    child = """
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, checkpoint as ck, gluon, faultinject as fi
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.supervisor import TrainingSupervisor

mx.random.seed(0); np.random.seed(0)
net = nn.HybridSequential()
with net.name_scope():
    net.add(nn.Dense(16, activation="relu")); net.add(nn.Dense(1))
net.hybridize(); net.initialize(mx.init.Xavier())
tr = gluon.Trainer(net.collect_params(), "sgd", {{"learning_rate": 0.05}},
                   kvstore="tpu_sync", update_on_kvstore=False)
loss_fn = gluon.loss.L2Loss()
rs = np.random.RandomState(0)
x = mx.nd.array(rs.normal(0, 1, (8, 16)).astype("f"))
y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))

def step(x, y):
    with autograd.record():
        l = loss_fn(net(x), y)
    l.backward(); tr.step(8)
    return l

sup = TrainingSupervisor(step, tr, net, snapshot_steps=2,
                         stall_min_s=120)
mgr = ck.CheckpointManager(sys.argv[1])
sup.install_preemption_hook(mgr)
for i in range(4):
    sup.step(x, y)
print("READY", sup._snap[0], flush=True)
# wedge INSIDE a step (the next boundary re-snapshots first, at count
# 4) so the signal lands mid-step: the hook must save the snapshot —
# SystemExit from the handler's sys.exit must propagate (128+15)
plan = fi.FaultPlan().add("trainer.step", "delay", delay_s=30.0)
fi.install(plan)
sup.step(x, y)
"""
    d = str(tmp_path / "emer")
    fdir = str(tmp_path / "fl")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_CHECKPOINT_FSYNC="0", MXNET_FLIGHT_DIR=fdir)
    proc = subprocess.Popen(
        [sys.executable, "-c", child.format(repo=REPO), d],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True)
    try:
        line = proc.stdout.readline()
        assert "READY" in line, (line, proc.stderr.read())
        snap_step = int(line.split()[1])
        time.sleep(1.0)  # let the child block inside the wedged step
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 128 + signal.SIGTERM, (rc, proc.stderr.read())
    # emergency checkpoint = the supervisor's CURRENT snapshot: the
    # wedged step's boundary (count 4, snapshot_steps=2) re-captured
    # just before the step wedged, superseding the READY-time one
    assert snap_step == 2
    assert ck.all_steps(d) == [4], ck.all_steps(d)
    manifest = ck.read_manifest(
        os.path.join(d, f"step_{max(ck.all_steps(d))}"))
    assert manifest["meta"].get("emergency", "").startswith("signal")
    # and a preempt flight dump exists with the ring inside
    dumps = [f for f in os.listdir(fdir) if f.startswith("flight-")]
    assert dumps, os.listdir(fdir)
    found = False
    for f in dumps:
        trace = json.load(open(os.path.join(fdir, f)))
        if trace.get("metadata", {}).get("reason") == "preempt":
            found = True
    assert found, "no flight dump with reason=preempt"


# ---------------------------------------------------------------------------
# Module.fit(supervise=True)
# ---------------------------------------------------------------------------
def _fit_params(supervise, X, Y, plan=None):
    mx.random.seed(0)
    np.random.seed(0)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(net, data_names=["data"],
                        label_names=["softmax_label"])
    ctx = fi.active(plan) if plan is not None else None
    if ctx:
        ctx.__enter__()
    try:
        mod.fit(mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False),
                num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                supervise=supervise)
    finally:
        if ctx:
            ctx.__exit__(None, None, None)
    return mod.get_params()[0]


def test_module_fit_supervised_matches_and_retries():
    rs = np.random.RandomState(0)
    X = rs.normal(0, 1, (32, 4)).astype("f")
    Y = (rs.rand(32) > 0.5).astype("f")
    ref = _fit_params(False, X, Y)
    # supervised, with one injected transient mid-fit: same result
    plan = fi.FaultPlan().add("trainer.step", "raise", exc=OSError,
                              times=1, after=3)
    got = _fit_params(True, X, Y, plan=plan)
    assert plan.stats() == {"trainer.step": 1}
    for k in ref:
        np.testing.assert_allclose(ref[k].asnumpy(), got[k].asnumpy(),
                                   rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# snapshot surface checks
# ---------------------------------------------------------------------------
def test_no_snapshot_surface_propagates_transients():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise OSError("transient")
        return 0.0

    sup = TrainingSupervisor(flaky)  # no trainer/params/restore_fn
    with pytest.raises(OSError):
        sup.step()
    sup.close()


def test_custom_snapshot_restore_fns():
    state = {"w": np.zeros(4, dtype="f")}
    restored = []

    def step_fn(v):
        if v < 0:
            raise OSError("transient")
        state["w"] = state["w"] + v
        return float(state["w"].sum())

    sup = TrainingSupervisor(
        step_fn,
        snapshot_fn=lambda: {"w": state["w"]},
        restore_fn=lambda s: (restored.append(1),
                              state.__setitem__("w", s["w"]))[0] or None,
        snapshot_steps=2, retries=1, backoff_s=0.001)
    sup.step(1.0)
    sup.step(1.0)
    with pytest.raises(res.StepRetriesExhausted):
        sup.step(-1.0)
    assert restored  # the restore_fn ran
    # state rewound to the last snapshot + replay of the window
    np.testing.assert_array_equal(state["w"], np.full(4, 2.0, dtype="f"))
    sup.close()


def test_supervisor_metrics_in_snapshot():
    snap = M.snapshot()
    assert "supervisor" in snap
    for k in ("snapshots", "retries", "rewinds", "watchdog_trips",
              "prefetch_respawns", "data_records_skipped",
              "last_snapshot_step"):
        assert k in snap["supervisor"], k


def test_first_step_transient_retries_via_capture_at_retry():
    """A transient on the VERY FIRST step: the boundary snapshot was
    skipped (params deferred until the first trace), but the failed
    attempt materialized them before the fault fired — the retry
    captures the restore point then and the run still bitwise-matches
    an uninterrupted one."""
    x, y = _data()
    net0, tr0 = _setup()
    s0 = _mkstep(net0, tr0)
    ref = [float(s0(x, y).asnumpy().mean()) for _ in range(5)]

    net1, tr1 = _setup()
    sup = TrainingSupervisor(_mkstep(net1, tr1), trainer=tr1, params=net1,
                             snapshot_steps=3)
    plan = fi.FaultPlan().add("trainer.step", "raise", exc=OSError,
                              times=1)  # fires at step 0
    with fi.active(plan):
        got = [float(sup.step(x, y).asnumpy().mean()) for _ in range(5)]
    assert plan.stats() == {"trainer.step": 1}
    np.testing.assert_array_equal(np.float32(ref), np.float32(got))
    for a, b in zip(_weights(net0), _weights(net1)):
        np.testing.assert_array_equal(a, b)
    # later failures replay from a window that includes the first batch
    plan2 = fi.FaultPlan().add("trainer.step", "raise", exc=OSError,
                               times=1)
    with fi.active(plan2):
        got2 = float(sup.step(x, y).asnumpy().mean())
    assert got2 == np.float32(float(s0(x, y).asnumpy().mean()))
    sup.close()


def test_wholestep_first_call_plain_oserror_does_not_demote(monkeypatch):
    """propagate-don't-demote holds for EVERY transient class, plain
    OSError on the FIRST call included: the compiler must stay on the
    whole-step path so a recovered supervisor resumes the 1-dispatch
    program (review finding: only UNAVAILABLE-shaped errors were
    exempted from permanent fallback)."""
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    x, y = _data()
    net, tr = _setup()
    st = WholeStepCompiler(net, _loss_fn(), tr)
    plan = fi.FaultPlan().add("trainer.step", "raise", exc=OSError,
                              times=1)  # fires on the very first call
    with fi.active(plan):
        with pytest.raises(OSError):
            st.step(x, y)
    assert st.fallback_reason is None  # NOT demoted
    st.step(x, y)  # recovers onto the whole-step program
    assert st.active, st.fallback_reason


def test_no_snapshot_surface_window_stays_empty():
    """Without a trainer/params/restore_fn there is nothing to replay
    into — the batch window must not grow one reference per step
    forever (review finding)."""
    sup = TrainingSupervisor(lambda v: v)
    for i in range(50):
        sup.step(float(i))
    assert sup._window == []
    sup.close()
