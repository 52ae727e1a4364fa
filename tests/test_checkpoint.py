"""Fault-tolerant checkpointing & auto-resume (ISSUE 5,
mxnet_tpu/checkpoint/): atomic validated layout, async saves, torn-write
and CRC rejection, retention GC, retry-with-backoff, trainer/module/
serving integrations, SIGTERM emergency save."""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ck
from mxnet_tpu.observability import metrics as M

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_fsync(monkeypatch):
    # atomicity (tmp + rename) is what these tests pin; per-file fsync
    # is ~100ms each on this container's FS and adds nothing
    monkeypatch.setenv("MXNET_CHECKPOINT_FSYNC", "0")
    yield


def _state():
    return {
        "w": mx.nd.array(np.arange(12, dtype="f").reshape(3, 4)),
        "h": np.arange(5, dtype=np.float16),
        "flag": np.array([True, False, True]),
        "blob": b"\x00\x01opaque-bytes\xff",
        "meta": {"epoch": 3, "note": "hi"},
    }


def _assert_state_equal(got, want):
    assert sorted(got) == sorted(want)
    for k, v in want.items():
        if isinstance(v, bytes):
            assert got[k] == v, k
        elif hasattr(v, "asnumpy") or isinstance(v, np.ndarray):
            w = v.asnumpy() if hasattr(v, "asnumpy") else v
            assert got[k].dtype == w.dtype, k
            np.testing.assert_array_equal(got[k], w)
        else:
            assert got[k] == v, k


# ---------------------------------------------------------------------------
# core: round trip, async/sync equivalence, eager snapshot
# ---------------------------------------------------------------------------
def test_roundtrip_async_sync_bitwise_equal(tmp_path):
    sync = ck.CheckpointManager(str(tmp_path / "s"), async_save=False)
    asy = ck.CheckpointManager(str(tmp_path / "a"), async_save=True)
    st = _state()
    sync.save(1, st)
    asy.save(1, st)
    assert asy.wait() is None and asy.all_finished()
    s_step, s_state = sync.restore()
    a_step, a_state = asy.restore()
    assert s_step == a_step == 1
    _assert_state_equal(s_state, st)
    _assert_state_equal(a_state, st)
    # the two layouts are byte-identical shard-for-shard
    for fname in sorted(os.listdir(tmp_path / "s" / "step_1")):
        a = (tmp_path / "s" / "step_1" / fname).read_bytes()
        b = (tmp_path / "a" / "step_1" / fname).read_bytes()
        if fname == ck.layout.MANIFEST:
            # manifests differ only in wall time
            ma, mb = json.loads(a), json.loads(b)
            ma.pop("time"), mb.pop("time")
            assert ma == mb
        else:
            assert a == b, fname


def test_save_snapshots_eagerly(tmp_path):
    """Training may mutate (or donate) its buffers the moment save()
    returns — the checkpoint must hold the values at call time."""
    mgr = ck.CheckpointManager(str(tmp_path))
    arr = mx.nd.array(np.ones((64, 64), dtype="f"))
    host = np.ones(8, dtype="f")
    mgr.save(1, {"a": arr, "b": host})
    arr += 1.0  # mutate immediately, before the writer commits
    host += 1.0
    mgr.wait()
    _, state = mgr.restore()
    np.testing.assert_array_equal(state["a"], np.ones((64, 64), dtype="f"))
    np.testing.assert_array_equal(state["b"], np.ones(8, dtype="f"))


def test_restore_empty_and_explicit_missing(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    assert mgr.restore() is None
    assert mgr.latest_step() is None and mgr.all_steps() == []
    with pytest.raises(ck.CheckpointInvalidError):
        mgr.restore(step=7)


# ---------------------------------------------------------------------------
# torn writes / corruption: never loaded
# ---------------------------------------------------------------------------
def _save_steps(mgr, steps):
    for s in steps:
        mgr.save(s, _state())
    mgr.wait()


def test_torn_manifest_falls_back(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    _save_steps(mgr, [1, 2])
    mpath = tmp_path / "step_2" / "manifest.json"
    mpath.write_text(mpath.read_text()[:40])  # truncate: torn write
    before = M.CHECKPOINT_FAILURES.get(stage="restore", reason="invalid")
    assert mgr.all_steps() == [1]  # discovery skips it
    step, state = mgr.restore()
    assert step == 1
    _assert_state_equal(state, _state())
    # the skipped torn checkpoint is COUNTED (acceptance criterion:
    # fall back AND increment a failure counter)
    assert M.CHECKPOINT_FAILURES.get(stage="restore", reason="invalid") \
        == before + 1


def test_missing_shard_falls_back(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    _save_steps(mgr, [1, 2])
    os.remove(tmp_path / "step_2" / "shard_0.npz")
    assert mgr.latest_step() == 1
    step, _ = mgr.restore()
    assert step == 1


def test_crc_mismatch_rejected_loudly(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    _save_steps(mgr, [1, 2])
    shard = tmp_path / "step_2" / "shard_0.npz"
    size = shard.stat().st_size
    # rewrite the shard with bit-flipped array contents but identical
    # layout, so the size check passes and ONLY the CRC can catch it
    with np.load(shard, allow_pickle=False) as z:
        entries = {k: z[k].copy() for k in z.keys()}
    for k, v in entries.items():
        if v.dtype != np.bool_ and v.size:
            entries[k] = v + v.dtype.type(1)
            break
    with open(shard, "wb") as f:
        np.savez(f, **entries)
    assert shard.stat().st_size == size, "corruption must preserve size"
    # explicit step: loud rejection
    before = M.CHECKPOINT_FAILURES.get(stage="restore", reason="invalid")
    with pytest.raises(ck.CheckpointInvalidError, match="CRC mismatch"):
        mgr.restore(step=2)
    # auto mode: falls back to the previous valid step + counts it
    step, state = mgr.restore()
    assert step == 1
    _assert_state_equal(state, _state())
    assert M.CHECKPOINT_FAILURES.get(stage="restore", reason="invalid") \
        >= before + 2


def test_tmp_dirs_invisible_and_gced(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    _save_steps(mgr, [1])
    stale = tmp_path / ".tmp-step_9-999-1"
    stale.mkdir()
    (stale / "shard_0.npz").write_bytes(b"partial")
    (tmp_path / "junkfile").write_text("x")
    (tmp_path / "step_notanum").mkdir()
    assert mgr.all_steps() == [1]
    _save_steps(mgr, [2])  # GC sweeps stale tmp dirs
    assert not stale.exists()
    assert mgr.all_steps() == [1, 2]


# ---------------------------------------------------------------------------
# retention
# ---------------------------------------------------------------------------
def test_retention_max_to_keep_and_period_pinning(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path), max_to_keep=2, keep_period=5)
    _save_steps(mgr, range(1, 11))
    # newest 2 disposable (9, 10 is pinned too) + every multiple of 5
    assert mgr.all_steps() == [5, 8, 9, 10]
    assert mgr.latest_step() == 10


# ---------------------------------------------------------------------------
# retry / fault injection
# ---------------------------------------------------------------------------
def test_retry_with_injected_fault_succeeds(tmp_path):
    attempts = []

    def hook(step, attempt):
        attempts.append(attempt)
        if attempt < 2:
            raise OSError("flaky storage")

    mgr = ck.CheckpointManager(str(tmp_path), async_save=False, retries=3,
                               backoff_s=0.001, fault_hook=hook)
    mgr.save(1, _state())
    assert attempts == [0, 1, 2]
    assert mgr.all_steps() == [1]


def test_retry_exhausts_sync_raises(tmp_path):
    def hook(step, attempt):
        raise OSError("dead storage")

    before = M.CHECKPOINT_FAILURES.get(stage="save", reason="OSError")
    mgr = ck.CheckpointManager(str(tmp_path), async_save=False, retries=1,
                               backoff_s=0.001, fault_hook=hook)
    with pytest.raises(ck.CheckpointError, match="after 2 attempts"):
        mgr.save(1, _state())
    assert mgr.all_steps() == []
    assert M.CHECKPOINT_FAILURES.get(stage="save", reason="OSError") \
        == before + 1


def test_async_nonio_error_surfaces_at_wait(tmp_path):
    """A non-IO bug on the writer thread (here: a fault hook raising
    TypeError, standing in for e.g. an unserializable manifest value)
    must land in wait(), not kill the worker silently."""
    def hook(step, attempt):
        raise TypeError("not an IO problem")

    mgr = ck.CheckpointManager(str(tmp_path), async_save=True,
                               fault_hook=hook)
    mgr.save(1, _state())
    with pytest.raises(ck.CheckpointError, match="not an IO problem"):
        mgr.wait()
    mgr.fault_hook = None
    mgr.save(2, _state())  # worker still alive and usable
    mgr.wait()
    assert mgr.all_steps() == [2]


def test_retry_exhausts_async_surfaces_at_wait(tmp_path):
    def hook(step, attempt):
        raise OSError("dead storage")

    mgr = ck.CheckpointManager(str(tmp_path), async_save=True, retries=0,
                               backoff_s=0.001, fault_hook=hook)
    mgr.save(1, _state())
    with pytest.raises(ck.CheckpointError):
        mgr.wait()
    mgr.fault_hook = None  # storage "recovers"
    mgr.save(2, _state())
    mgr.wait()
    assert mgr.all_steps() == [2]


# ---------------------------------------------------------------------------
# satellites: nd.save dtype round trip, atomic legacy writes
# ---------------------------------------------------------------------------
def test_nd_save_load_bool_and_float16(tmp_path):
    fname = str(tmp_path / "t.params")
    data = {"b": mx.nd.array(np.array([True, False, True])),
            "h": mx.nd.array(np.arange(6, dtype=np.float16).reshape(2, 3)),
            "f": mx.nd.array(np.ones((2, 2), dtype="f"))}
    assert data["b"].dtype == np.bool_
    assert data["h"].dtype == np.float16
    mx.nd.save(fname, data)
    back = mx.nd.load(fname)
    for k, v in data.items():
        assert back[k].dtype == v.dtype, k
        np.testing.assert_array_equal(back[k].asnumpy(), v.asnumpy())
    # list container too
    mx.nd.save(fname, [data["b"], data["h"]])
    lst = mx.nd.load(fname)
    assert lst[0].dtype == np.bool_ and lst[1].dtype == np.float16


def test_save_checkpoint_atomic_on_crash(tmp_path, monkeypatch):
    """A crash mid-save must never corrupt the previous .params file."""
    prefix = str(tmp_path / "model")
    data = mx.sym.Variable("data")
    sym = mx.sym.FullyConnected(data, num_hidden=2, name="fc")
    arg = {"fc_weight": mx.nd.ones((2, 3)), "fc_bias": mx.nd.zeros(2)}
    mx.model.save_checkpoint(prefix, 1, sym, arg, {})
    good = open(f"{prefix}-0001.params", "rb").read()

    import mxnet_tpu.ndarray.ndarray as nd_mod

    def torn_savez(path, **kw):
        with open(str(path) + ".npz", "wb") as f:
            f.write(b"torn!")  # partial garbage lands on the TEMP name
        raise OSError("disk full")

    monkeypatch.setattr(nd_mod._np, "savez", torn_savez)
    with pytest.raises(OSError):
        mx.model.save_checkpoint(prefix, 1, sym, arg, {})
    monkeypatch.undo()
    assert open(f"{prefix}-0001.params", "rb").read() == good
    _, arg2, _ = mx.model.load_checkpoint(prefix, 1)
    np.testing.assert_array_equal(arg2["fc_weight"].asnumpy(),
                                  np.ones((2, 3), dtype="f"))


# ---------------------------------------------------------------------------
# gluon trainer resume (with 2-bit compression residuals active)
# ---------------------------------------------------------------------------
def _gluon_setup(seed=0):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9},
        kvstore="tpu_sync", update_on_kvstore=False,
        compression_params={"type": "2bit", "threshold": 0.5})
    return net, trainer


def _gluon_step(net, trainer, x, y, loss_fn):
    from mxnet_tpu import autograd
    with autograd.record():
        l = loss_fn(net(x), y)
    l.backward()
    trainer.step(x.shape[0])
    return float(l.asnumpy().ravel()[0])


def test_trainer_kill_resume_matches_uninterrupted(tmp_path):
    """save at step 3, fresh net+trainer (different init seed),
    restore, 3 more steps == the uninterrupted 6-step run at rtol 1e-5
    — with the fused trainer and 2-bit compression residuals active."""
    from mxnet_tpu import gluon
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (8, 16)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
    loss_fn = gluon.loss.L2Loss()

    net, tr = _gluon_setup()
    ref_losses = [_gluon_step(net, tr, x, y, loss_fn) for _ in range(6)]
    ref_w = [p.data().asnumpy() for p in net.collect_params().values()]

    net1, tr1 = _gluon_setup()
    for _ in range(3):
        _gluon_step(net1, tr1, x, y, loss_fn)
    mgr = ck.CheckpointManager(str(tmp_path))
    ck.save_trainer(mgr, 3, net1, tr1)
    mgr.wait()
    manifest = ck.read_manifest(str(tmp_path / "step_3"))
    assert "trainer_bucket_sig" in manifest["signatures"]

    # "new process": fresh objects, different init, restored over
    net2, tr2 = _gluon_setup(seed=1)
    got = ck.restore_or_initialize(ck.CheckpointManager(str(tmp_path)),
                                   net2, tr2, initializer=mx.init.Xavier())
    assert got == 3
    resumed = [_gluon_step(net2, tr2, x, y, loss_fn) for _ in range(3)]
    np.testing.assert_allclose(ref_losses[3:], resumed, rtol=1e-5)
    for a, b in zip(ref_w,
                    [p.data().asnumpy()
                     for p in net2.collect_params().values()]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_restore_or_initialize_fresh(tmp_path):
    net, tr = _gluon_setup()
    assert ck.restore_or_initialize(
        ck.CheckpointManager(str(tmp_path / "empty")), net, tr,
        initializer=mx.init.Xavier()) is None
    assert net.collect_params()  # initialized, usable


# ---------------------------------------------------------------------------
# Module.fit(checkpoint_dir=...) resume
# ---------------------------------------------------------------------------
def _fit_symbol():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    act = mx.sym.Activation(fc, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=2, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _fit(num_epoch, X, Y, ckdir=None, period=1):
    mx.random.seed(0)
    np.random.seed(0)
    mod = mx.mod.Module(_fit_symbol(), data_names=["data"],
                        label_names=["softmax_label"])
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False),
            num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            checkpoint_dir=ckdir, checkpoint_period=period)
    return mod.get_params()


def test_module_fit_checkpoint_resume(tmp_path):
    rs = np.random.RandomState(0)
    X = rs.normal(0, 1, (32, 4)).astype("f")
    Y = (rs.rand(32) > 0.5).astype("f")
    ref_arg, ref_aux = _fit(4, X, Y)

    d = str(tmp_path / "ck")
    _fit(2, X, Y, ckdir=d)
    assert ck.all_steps(d) == [1, 2]
    res_arg, _ = _fit(4, X, Y, ckdir=d)  # auto-resumes at epoch 2
    assert ck.all_steps(d) == [1, 2, 3, 4]
    for k in ref_arg:
        np.testing.assert_allclose(ref_arg[k].asnumpy(),
                                   res_arg[k].asnumpy(),
                                   rtol=1e-5, atol=1e-7)
    # momentum state was in the checkpoint
    _, state = ck.CheckpointManager(d).restore()
    assert ck.OPTIMIZER_STATES_KEY in state


# ---------------------------------------------------------------------------
# legacy callback routing (MXNET_CHECKPOINT_DIR)
# ---------------------------------------------------------------------------
def test_do_checkpoint_env_routing(tmp_path, monkeypatch):
    sym = _fit_symbol()
    arg = {"fc1_weight": mx.nd.ones((8, 4))}
    prefix = str(tmp_path / "legacy" / "model")
    os.makedirs(os.path.dirname(prefix))

    # default: legacy prefix files, no manager involved
    monkeypatch.delenv("MXNET_CHECKPOINT_DIR", raising=False)
    mx.callback.do_checkpoint(prefix)(0, sym, arg, {})
    assert os.path.exists(f"{prefix}-0001.params")
    assert os.path.exists(f"{prefix}-symbol.json")

    # env set: atomic manager checkpoints instead
    d = str(tmp_path / "managed")
    monkeypatch.setenv("MXNET_CHECKPOINT_DIR", d)
    mx.callback.do_checkpoint(prefix)(1, sym, arg, {})
    ck.env_manager().wait()
    assert ck.all_steps(d) == [2]
    _, state = ck.CheckpointManager(d).restore()
    np.testing.assert_array_equal(state["arg:fc1_weight"],
                                  np.ones((8, 4), dtype="f"))
    assert ck.SYMBOL_KEY in state
    assert not os.path.exists(f"{prefix}-0002.params")


# ---------------------------------------------------------------------------
# serving hot reload
# ---------------------------------------------------------------------------
def test_serving_hot_reload(tmp_path):
    from mxnet_tpu import serving
    data = mx.sym.Variable("data")
    out = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    rs = np.random.RandomState(0)
    w0 = rs.normal(0, 1, (3, 4)).astype("f")
    b0 = np.zeros(3, "f")
    pred = serving.BucketedPredictor(out, {"fc_weight": w0, "fc_bias": b0},
                                     {"data": (8, 4)})
    x = rs.normal(0, 1, (2, 4)).astype("f")
    np.testing.assert_allclose(pred.predict(x)[0], x @ w0.T, rtol=1e-5)
    assert pred.loaded_step is None

    mgr = ck.CheckpointManager(str(tmp_path))
    w1 = w0 * 2.0
    mgr.save(7, {"arg:fc_weight": w1, "arg:fc_bias": b0,
                 "optimizer:states": b"ignored"})
    mgr.wait()
    n_compiled = pred.num_compiled
    assert pred.hot_reload(str(tmp_path)) == 7
    assert pred.loaded_step == 7
    np.testing.assert_allclose(pred.predict(x)[0], x @ w1.T, rtol=1e-5)
    assert pred.num_compiled == n_compiled  # swap, not recompile

    # a checkpoint missing a served param: loud error, NO partial swap
    mgr.save(8, {"arg:fc_weight": w1})
    mgr.wait()
    with pytest.raises(mx.MXNetError, match="lacks served"):
        pred.hot_reload(str(tmp_path))
    np.testing.assert_allclose(pred.predict(x)[0], x @ w1.T, rtol=1e-5)


# ---------------------------------------------------------------------------
# preemption hook (SIGTERM in a real subprocess)
# ---------------------------------------------------------------------------
_CHILD = """
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import checkpoint as ck

mgr = ck.CheckpointManager(sys.argv[1])
step_box = {{"step": 41}}
def state_fn():
    step_box["step"] += 1
    return step_box["step"], {{"w": np.full(4, 7.0, dtype="f"),
                               "blob": b"emergency"}}
ck.install_preemption_hook(mgr, state_fn)
print("READY", flush=True)
while True:
    time.sleep(0.1)
"""


def test_preemption_hook_saves_on_sigterm(tmp_path):
    d = str(tmp_path / "emer")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_CHECKPOINT_FSYNC="0")
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD.format(repo=REPO), d],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env, text=True)
    try:
        line = proc.stdout.readline()
        assert "READY" in line, (line, proc.stderr.read())
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 128 + signal.SIGTERM, (rc, proc.stderr.read())
    assert ck.all_steps(d) == [42]
    _, state = ck.CheckpointManager(d).restore()
    np.testing.assert_array_equal(state["w"], np.full(4, 7.0, dtype="f"))
    assert state["blob"] == b"emergency"
    manifest = ck.read_manifest(os.path.join(d, "step_42"))
    assert manifest["meta"]["emergency"].startswith("signal")


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_checkpoint_metrics_in_snapshot(tmp_path):
    saves = M.CHECKPOINT_SAVE_SECONDS.count
    mgr = ck.CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(11, _state())
    mgr.restore()
    snap = M.snapshot()
    assert "checkpoint" in snap
    sec = snap["checkpoint"]
    for k in ("last_step", "saves", "save_ms_mean", "save_blocked_ms_mean",
              "restores", "restore_ms_mean", "bytes_written", "failures"):
        assert k in sec, sec
    assert sec["last_step"] == 11.0
    assert sec["saves"] == saves + 1
    assert sec["bytes_written"] > 0
    json.dumps(snap)


# ---------------------------------------------------------------------------
# restore exhaustion diagnostics (ISSUE 12 satellite): when EVERY
# candidate is invalid, say which steps were scanned and why each was
# rejected — never a bare "no valid checkpoint", never a silent fresh
# start over a directory full of damaged runs
# ---------------------------------------------------------------------------
def _corrupt_crc(step_dir):
    shard = os.path.join(step_dir, "shard_0.npz")
    with np.load(shard, allow_pickle=False) as z:
        entries = {k: z[k].copy() for k in z.keys()}
    for k, v in entries.items():
        if v.dtype != np.bool_ and v.size:
            entries[k] = v + v.dtype.type(1)
            break
    with open(shard, "wb") as f:
        np.savez(f, **entries)


def test_restore_exhaustion_lists_every_candidate_and_reason(tmp_path):
    mgr = ck.CheckpointManager(str(tmp_path))
    _save_steps(mgr, [1, 2, 3])
    # three distinct damage classes across the three candidates
    (tmp_path / "step_3" / "manifest.json").write_text(
        (tmp_path / "step_3" / "manifest.json").read_text()[:40])  # torn
    os.remove(tmp_path / "step_2" / "shard_0.npz")                 # torn
    _corrupt_crc(str(tmp_path / "step_1"))                         # crc
    with pytest.raises(ck.CheckpointError) as ei:
        mgr.restore()
    msg = str(ei.value)
    for frag in ("scanned 3 candidate", "step 3", "step 2", "step 1",
                 "[manifest]", "[torn]", "[crc]"):
        assert frag in msg, (frag, msg)


def test_restore_empty_dir_still_returns_none(tmp_path):
    # the fresh-start contract restore_or_initialize keys on is ONLY
    # for directories with no step_N candidates at all
    mgr = ck.CheckpointManager(str(tmp_path))
    assert mgr.restore() is None


def test_restore_or_initialize_raises_on_all_invalid(tmp_path):
    """A directory full of damaged checkpoints must NOT silently
    initialize fresh — that would quietly discard the run."""
    mgr = ck.CheckpointManager(str(tmp_path))
    _save_steps(mgr, [5])
    _corrupt_crc(str(tmp_path / "step_5"))
    net, tr = _gluon_setup()
    with pytest.raises(ck.CheckpointError, match="step 5"):
        ck.restore_or_initialize(mgr, net, tr,
                                 initializer=mx.init.Xavier())


def test_invalid_error_kinds():
    from mxnet_tpu.checkpoint.layout import CheckpointInvalidError
    assert CheckpointInvalidError("x").kind == "invalid"
    assert CheckpointInvalidError("x", kind="crc").kind == "crc"


def test_preemption_hook_dumps_flight_ring(tmp_path, monkeypatch):
    """Satellite: the emergency save leaves a TIMELINE (flight dump,
    reason="preempt") alongside the weights — in-process drill of what
    the SIGTERM subprocess test pins end-to-end."""
    from mxnet_tpu.checkpoint.hooks import _PreemptionHook
    from mxnet_tpu.observability import flight
    from mxnet_tpu.observability import metrics as MM
    fdir = tmp_path / "fl"
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(fdir))
    mgr = ck.CheckpointManager(str(tmp_path / "ck"), async_save=False)
    hook = _PreemptionHook(mgr, lambda: (7, {"w": np.ones(4, "f")}),
                           signals=(), exit_on_signal=False)
    dumps = MM.FLIGHT_DUMPS.get(reason="preempt")
    hook._save_once("signal 15")
    assert ck.all_steps(str(tmp_path / "ck")) == [7]
    assert MM.FLIGHT_DUMPS.get(reason="preempt") == dumps + 1
    files = list(fdir.glob("flight-*.json"))
    assert files
    import json as _json
    assert any(_json.load(open(f)).get("metadata", {}).get("reason")
               == "preempt" for f in files)
    # already-fired hook never dumps twice
    hook._save_once("atexit")
    assert MM.FLIGHT_DUMPS.get(reason="preempt") == dumps + 1
