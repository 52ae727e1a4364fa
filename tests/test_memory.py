"""HBM ledger (ISSUE 9): device-memory attribution, per-phase memory
timeline, budget watchdog, OOM post-mortem.

Acceptance pinned here:
  * >=90% of tracked live device bytes carry a tag under the
    gluon-trainer and serving workloads (untagged <= 10%);
  * an injected ``memory.oom`` at a dispatch chokepoint produces
    exactly ONE rate-limited post-mortem dump (ledger report + flight
    ring, atomic writes) and re-raises typed;
  * ``MXNET_MEMORY_LEDGER=0`` leaves the hot paths at one boolean test
    (nothing registers, in-process and at import);
  * the <=4-dispatch fused-trainer perf_smoke gate holds with the
    ledger ON;
  * tagged live bytes return to baseline after Trainer teardown,
    ``BucketedPredictor``/``MicroBatcher`` close, prefetcher
    exhaustion, ``CheckpointManager`` drain, AND (ISSUE 14) a full
    predictor evict -> readmit -> close cycle (the weakref registry
    doubles as a leak detector).
"""
import gc
import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject as fi
from mxnet_tpu import serving, sym
from mxnet_tpu.observability import flight, memory, metrics as m, timeline

pytestmark = pytest.mark.memory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_ledger():
    """Each test gets an enabled, empty ledger and the default knobs
    back afterwards."""
    budget0, min_s0 = memory.BUDGET_MB, memory.OOM_DUMP_MIN_S
    memory.enable()
    memory.reset()
    memory.configure(budget_mb=0.0, oom_dump_min_s=min_s0)
    yield
    memory.enable()
    memory.reset()
    memory.BUDGET_MB = budget0
    memory.OOM_DUMP_MIN_S = min_s0


def _collect():
    """Drop reference cycles so weakref death callbacks run NOW."""
    gc.collect()


# -- scopes + registration ---------------------------------------------------

def test_memory_scope_nesting_and_thread_locality():
    assert memory.current_tag() is None
    with memory.memory_scope("param"):
        assert memory.current_tag() == "param"
        with memory.memory_scope("grad"):
            assert memory.current_tag() == "grad"
        assert memory.current_tag() == "param"
    assert memory.current_tag() is None
    import threading
    seen = []
    with memory.memory_scope("param"):
        t = threading.Thread(target=lambda: seen.append(
            memory.current_tag()))
        t.start()
        t.join()
    assert seen == [None]  # scopes never leak across threads


def test_memory_scope_rejects_reserved_tags():
    for bad in ("", "_untagged", None, 7):
        with pytest.raises(mx.MXNetError):
            with memory.memory_scope(bad):
                pass


def test_ndarray_creation_registers_under_scope():
    with memory.memory_scope("data"):
        a = mx.nd.zeros((32, 32))
    b = mx.nd.zeros((16, 16))  # no scope -> untagged
    tags = memory.live_by_tag()
    assert tags["data"] == 32 * 32 * 4
    assert tags[memory.UNTAGGED] == 16 * 16 * 4
    s = memory.snapshot_summary()
    assert s["untagged_bytes"] == 16 * 16 * 4
    assert 0 < s["attribution_pct"] < 100
    del a, b


def test_reregistration_retags_instead_of_double_counting():
    """The executor re-prepares the SAME committed mesh arrays every
    forward (jax.device_put returns the identical object once the
    buffer is committed) and the parameter load path retags _untagged
    wrappers to param — re-registering a live object must MOVE its
    bytes, not add a duplicate entry per step."""
    import jax.numpy as jnp
    buf = jnp.zeros(256, jnp.float32)
    for _ in range(5):  # the per-step executor pattern
        memory.register(buf, tag="executor")
    assert memory.live_by_tag()["executor"] == 256 * 4  # once, not 5x
    # retag: the load-path parameter pattern (_untagged -> param)
    memory.register(buf, tag="param")
    tags = memory.live_by_tag()
    assert tags.get("executor") is None
    assert tags["param"] == 256 * 4
    # the single surviving entry still dies clean
    del buf
    _collect()
    assert memory.live_by_tag().get("param") is None


def test_loaded_parameter_retagged_to_param(tmp_path):
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4, in_units=8, prefix="d_")
    net.initialize(ctx=mx.cpu())
    p = str(tmp_path / "w.params")
    net.collect_params().save(p)
    net2 = nn.Dense(4, in_units=8, prefix="d_")
    memory.reset()
    net2.collect_params().load(p, ctx=mx.cpu())
    tags = memory.live_by_tag()
    assert tags.get("param", 0) > 0, tags
    # the loaded wrappers must not linger under _untagged
    assert tags.get(memory.UNTAGGED, 0) < tags["param"], tags


def test_first_oom_dump_never_rate_limited(tmp_path, monkeypatch):
    """A 0.0 'last dump' sentinel compared against time.monotonic()
    would swallow the FIRST post-mortem whenever uptime < the rate
    window — exactly the dump the feature exists to produce."""
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    memory.configure(oom_dump_min_s=60.0)
    monkeypatch.setattr(memory.time, "monotonic", lambda: 3.0)
    with pytest.raises(mx.observability.DeviceMemoryError):
        with memory.oom_guard("executor"):
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
    assert memory.last_oom()["rate_limited"] is False
    assert memory.wait_oom_dump() is not None


def test_death_callback_returns_bytes_to_baseline():
    with memory.memory_scope("data"):
        a = mx.nd.zeros((64, 64))
    assert memory.live_by_tag().get("data") == 64 * 64 * 4
    del a
    _collect()
    assert memory.live_by_tag().get("data") is None
    # peak survives the death — that's the point of a peak
    assert memory.snapshot_summary()["peak_by_tag"]["data"] == 64 * 64 * 4


def test_register_raw_and_host_buffers():
    import jax.numpy as jnp
    r = memory.register(jnp.zeros(128, jnp.float32),
                        tag="compression_residual")
    h = memory.register_host(np.zeros(64, np.float32),
                             tag="checkpoint_host")
    assert memory.live_by_tag()["compression_residual"] == 128 * 4
    assert memory.live_by_tag(space="host")["checkpoint_host"] == 64 * 4
    rep = memory.report()
    assert rep["host"]["tags"]["checkpoint_host"]["live_bytes"] == 64 * 4
    del r, h


def test_raw_state_writeback_keeps_attribution():
    """A fused step replaces raw (non-NDArray) optimizer states with
    fresh arrays — the replacement must re-register or optimizer_state
    attribution drifts to zero after step 1 while the bytes stay live
    on device (NDArray states keep their wrapper registration via
    _set_data, raw states cannot)."""
    import jax.numpy as jnp
    from mxnet_tpu.optimizer import FusedUpdater, SGD
    old = jnp.zeros(256, jnp.float32)
    memory.register(old, tag="optimizer_state")
    assert memory.live_by_tag()["optimizer_state"] == 256 * 4
    upd = FusedUpdater(SGD(learning_rate=0.1))
    new = upd._state_writeback(old, old + 1.0)
    del old
    _collect()
    assert memory.live_by_tag().get("optimizer_state", 0) == 256 * 4, \
        memory.live_by_tag()
    del new, upd


def test_report_dedupes_shared_buffers_and_lists_top():
    with memory.memory_scope("param"):
        a = mx.nd.zeros((128, 2))
    b = a.detach()  # second wrapper, same device buffer
    rep = memory.report(top=5)
    # counters double-count wrappers; the report audit must not
    assert rep["device"]["tags"]["param"]["live_bytes"] == 128 * 2 * 4
    top = [t for t in rep["top"] if t["tag"] == "param"]
    assert len(top) == 1 and top[0]["shape"] == (128, 2)
    assert top[0]["dtype"] == "float32"
    del a, b


def test_disabled_ledger_registers_nothing_in_process():
    memory.disable()
    a = mx.nd.zeros((32, 32))
    with memory.memory_scope("data"):
        b = mx.nd.zeros((8, 8))
    assert memory.tracked_bytes() == 0
    assert memory.live_by_tag() == {}
    s = memory.snapshot_summary()
    assert s["enabled"] is False and s["tracked_bytes"] == 0
    del a, b


def test_env_off_subprocess():
    """MXNET_MEMORY_LEDGER=0 at import: every hook is one boolean test
    and nothing ever registers — across NDArray creation, gluon
    parameter init, and an oom_guard pass-through."""
    code = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu.observability import memory\n"
        "assert memory.ENABLED is False\n"
        "a = mx.nd.zeros((64, 64))\n"
        "from mxnet_tpu.gluon import nn\n"
        "net = nn.Dense(4, in_units=4)\n"
        "net.initialize()\n"
        "with memory.oom_guard('x'):\n"
        "    pass\n"
        "assert memory.tracked_bytes() == 0\n"
        "assert memory.live_by_tag() == {}\n"
        "print('OK')\n")
    env = dict(os.environ, MXNET_MEMORY_LEDGER="0", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-500:], out.stderr[-2000:])


# -- gluon attribution + leak gate -------------------------------------------

def _train_mlp(steps=3, depth=4, width=16, compression=None):
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    rs = np.random.RandomState(0)
    with memory.memory_scope("data"):
        x = mx.nd.array(rs.normal(0, 1, (8, 16)).astype("f"))
        y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
    loss_fn = gluon.loss.L2Loss()
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(depth):
            net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore="tpu_sync", update_on_kvstore=False,
                            compression_params=compression)
    l = None
    for _ in range(steps):
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        trainer.step(8)
    l.asnumpy()
    return net, trainer, (x, y)


def test_gluon_trainer_attribution_at_least_90pct():
    """The acceptance pin: under the trainer workload every owner is
    tagged — params, grads, optimizer state, grad buckets, kvstore
    store copies, data — and the untagged remainder stays <= 10%."""
    net, trainer, data = _train_mlp(steps=3)
    _collect()
    s = memory.snapshot_summary()
    assert s["attribution_pct"] >= 90.0, s
    for tag in ("param", "grad", "optimizer_state", "data", "kvstore"):
        assert s["tags"].get(tag, 0) > 0, (tag, s["tags"])
    assert s["peak_by_tag"].get("grad_bucket", 0) > 0, s["peak_by_tag"]
    rep = memory.report()
    assert rep["device"]["attribution_pct"] >= 90.0
    assert rep["device"]["untagged_bytes"] <= 0.1 * max(
        1, rep["device"]["total_bytes"])


def test_compressed_trainer_tags_residuals():
    net, trainer, data = _train_mlp(
        steps=3, compression={"type": "2bit", "threshold": 0.5})
    tags = memory.live_by_tag()
    assert tags.get("compression_residual", 0) > 0, tags
    del net, trainer, data


def test_trainer_teardown_leak_gate():
    """Dropping the model + trainer returns EVERY tagged count to its
    baseline — the weakref registry doubles as a leak detector."""
    net, trainer, data = _train_mlp(steps=2)
    assert memory.live_by_tag().get("optimizer_state", 0) > 0
    del net, trainer, data
    _collect()
    _collect()  # param<->grad autograd cycles need a second pass
    left = {t: v for t, v in memory.live_by_tag().items()
            if t != memory.UNTAGGED}
    assert left == {}, f"leaked tagged bytes after teardown: {left}"


@pytest.mark.perf_smoke
def test_dispatch_budget_holds_with_ledger_on():
    """The PR 2 <=4-dispatch invariant with the ledger ENABLED (the
    acceptance's perf guard: attribution must not cost dispatches)."""
    assert memory.ENABLED
    from mxnet_tpu import autograd, gluon, observability as obs
    from mxnet_tpu.gluon import nn
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (8, 16)).astype("f"))
    y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
    loss_fn = gluon.loss.L2Loss()
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(9):
            net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(1))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore="tpu_sync", update_on_kvstore=False)

    def step():
        with autograd.record():
            l = loss_fn(net(x), y)
        l.backward()
        trainer.step(8)
        return float(l.asnumpy().ravel()[0])

    for _ in range(3):
        step()
    c0 = obs.dispatch_counts()
    for _ in range(3):
        step()
    c1 = obs.dispatch_counts()
    per_step = (c1["total"] - c0["total"]) / 3
    assert per_step <= 4.0, (per_step, c0, c1)
    assert c1.get("device_put", 0) == c0.get("device_put", 0)


# -- per-phase memory timeline ------------------------------------------------

def test_trainer_phases_carry_mem_deltas_and_counter_track():
    flight.enable()
    flight.reset()
    _train_mlp(steps=2)
    recs = [r for _, r in flight.records() if r[0] == "mx.trainer.step"]
    assert recs, "no trainer_step phases recorded"
    labeled = [r for r in recs if r[6] and "mem_live_bytes" in r[6]]
    assert labeled, "trainer_step records carry no ledger samples"
    assert all(isinstance(r[6]["mem_delta_bytes"], int) for r in labeled)
    # the Chrome trace grows an hbm_live_bytes counter track
    trace = timeline.build_trace(flight.records())
    counters = [e for e in trace["traceEvents"]
                if e.get("ph") == "C" and e["name"] == "hbm_live_bytes"]
    assert counters and all(e["args"]["bytes"] >= 0 for e in counters)


def test_phase_mem_sampling_skipped_when_ledger_off():
    flight.enable()
    flight.reset()
    memory.disable()
    with flight.phase_span("mx.trainer.step", cat="step", mem=True):
        pass
    (seg, rec), = flight.records()
    assert rec[6] is None  # no labels fabricated when the ledger is off


# -- budget watchdog ----------------------------------------------------------

def test_budget_warns_at_90pct_and_raises_past_100(caplog):
    memory.configure(budget_mb=1.0)  # 1 MB budget
    import logging
    with caplog.at_level(logging.WARNING,
                         logger="mxnet_tpu.observability.memory"):
        a = mx.nd.zeros((240 * 1024,), dtype="float32")  # 0.94 MB
    assert any("90%" in r.message for r in caplog.records)
    with pytest.raises(mx.observability.HBMBudgetError,
                       match="attribution"):
        b = mx.nd.zeros((64 * 1024,), dtype="float32")  # crosses 1 MB
    del a


def test_budget_off_by_default():
    assert memory.BUDGET_MB == 0.0
    big = mx.nd.zeros((1024, 1024))  # 4 MB, no budget -> no raise
    del big


# -- OOM post-mortem ----------------------------------------------------------

def test_is_oom_matches_resource_exhausted_and_site():
    assert memory.is_oom(RuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert memory.is_oom(fi.InjectedFault("injected fault at memory.oom"))
    assert not memory.is_oom(ValueError("shape mismatch"))


def test_oom_guard_passthrough_non_oom():
    with pytest.raises(ValueError):
        with memory.oom_guard("executor"):
            raise ValueError("not an oom")
    assert memory.last_oom() == {}


def _serve_one(pred):
    return pred.predict(data=np.zeros((2, 8), "f"))


def _mlp_predictor(max_batch=8):
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=16,
                             name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(max_batch, 8))
    params = {}
    for n, s in zip(net.list_arguments(), arg_shapes):
        if n == "data" or n.endswith("_label"):
            continue
        params["arg:" + n] = mx.nd.array(rs.normal(0, 0.1, s).astype("f"))
    return serving.BucketedPredictor(net, params,
                                     {"data": (max_batch, 8)})


@pytest.mark.chaos
def test_injected_oom_produces_exactly_one_dump_and_retypes(tmp_path,
                                                            monkeypatch):
    """The acceptance pin: memory.oom at the serving dispatch
    chokepoint -> catch -> ONE rate-limited post-mortem dump (ledger
    report + flight ring, both atomic under MXNET_FLIGHT_DIR) -> typed
    DeviceMemoryError to the caller."""
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    memory.configure(oom_dump_min_s=60.0)  # window >> test duration
    flight.enable()
    flight.reset()
    pred = _mlp_predictor()
    _serve_one(pred)  # warm: compile outside the fault window
    plan = fi.FaultPlan().add("memory.oom", "raise", times=2)
    with fi.active(plan):
        with pytest.raises(mx.observability.DeviceMemoryError,
                           match="serving.dispatch"):
            _serve_one(pred)
        path = memory.wait_oom_dump()
        assert path and os.path.exists(path)
        fpath = memory.last_oom().get("flight_path")
        # second OOM inside the rate window: typed re-raise still, but
        # NO second dump — and the window-opening dump's paths survive
        # on last_oom()/wait_oom_dump() (consumers keep a pointer to
        # the on-disk post-mortem of the same failure episode)
        with pytest.raises(mx.observability.DeviceMemoryError):
            _serve_one(pred)
        assert memory.last_oom()["rate_limited"] is True
        assert memory.last_oom().get("report_path") == path
        assert memory.last_oom().get("flight_path") == fpath
        assert memory.wait_oom_dump() == path
    assert memory.oom_dumps() == 1
    dumps = [n for n in os.listdir(tmp_path)
             if n.startswith("oom") and n.endswith(".json")]
    assert len(dumps) == 1, dumps
    payload = json.load(open(path))
    assert payload["oom"]["site"] == "serving.dispatch"
    assert "serve_weights" in payload["report"]["device"]["tags"]
    # the flight ring rode along (Perfetto-loadable, reason="oom")
    assert fpath and os.path.exists(fpath)
    trace = json.load(open(fpath))
    assert trace["metadata"]["reason"] == "oom"
    assert m.REGISTRY.get("mxnet_flight_dumps_total").get(reason="oom") \
        >= 1
    # no torn files: everything under the dir is complete JSON
    for n in dumps:
        json.load(open(os.path.join(tmp_path, n)))


@pytest.mark.chaos
def test_injected_oom_at_executor_chokepoint(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path))
    memory.configure(oom_dump_min_s=0.0)
    x = sym.Variable("x")
    net = sym.FullyConnected(x, num_hidden=4, name="fc")
    ex = net.simple_bind(mx.cpu(), x=(2, 8))
    ex.forward(is_train=True)
    ex.backward()
    plan = fi.FaultPlan().add("memory.oom", "raise", times=1)
    with fi.active(plan):
        with pytest.raises(mx.observability.DeviceMemoryError,
                           match="executor"):
            ex.forward_backward(x=np.zeros((2, 8), "f"))
    assert memory.wait_oom_dump() is not None


def test_oom_guard_never_double_dumps_nested():
    """An inner guard's typed DeviceMemoryError passes through outer
    guards untouched (one OOM = one post-mortem, however deep the
    chokepoint nesting)."""
    memory.configure(oom_dump_min_s=0.0)
    calls = []
    orig = memory._post_mortem
    memory._post_mortem = lambda s, e: calls.append(s) or orig(s, e)
    try:
        with pytest.raises(mx.observability.DeviceMemoryError):
            with memory.oom_guard("outer"):
                with memory.oom_guard("inner"):
                    raise RuntimeError("RESOURCE_EXHAUSTED: synthetic")
    finally:
        memory._post_mortem = orig
    assert calls == ["inner"]
    memory.wait_oom_dump()


# -- executor memory_analysis (satellite 1) -----------------------------------

def _stub_stats(peak=None):
    s = types.SimpleNamespace(
        temp_size_in_bytes=100, argument_size_in_bytes=200,
        output_size_in_bytes=50, alias_size_in_bytes=8,
        generated_code_size_in_bytes=4096)
    if peak is not None:
        s.peak_memory_in_bytes = peak
    return s


def test_compiled_stats_dict_both_jax_paths():
    """Regression for the satellite: one structured shape across jax
    versions — real peak on >=0.5-style stats, estimated (and flagged)
    on the older CompiledMemoryStats, {} when the backend reports
    nothing."""
    new = memory.compiled_stats_dict(_stub_stats(peak=999))
    assert new["peak_bytes"] == 999 and new["peak_estimated"] is False
    old = memory.compiled_stats_dict(_stub_stats())
    assert old["peak_bytes"] == 100 + 200 + 50 + 8
    assert old["peak_estimated"] is True
    for k in ("temp_bytes", "argument_bytes", "output_bytes",
              "alias_bytes", "generated_code_bytes", "peak_bytes"):
        assert k in new and k in old
    assert memory.compiled_stats_dict(None) == {}


def test_executor_memory_analysis_structured_and_registered():
    x = sym.Variable("x")
    net = sym.FullyConnected(x, num_hidden=4, name="fc")
    ex = net.simple_bind(mx.cpu(), x=(2, 8))
    out = ex.memory_analysis(train=True)
    if not out:
        pytest.skip("backend reports no memory analysis (older PJRT)")
    assert out["argument_bytes"] > 0
    assert out["peak_bytes"] >= out["output_bytes"]
    assert isinstance(out["peak_estimated"], bool)
    # registered under the ledger's executor tag
    assert memory.compiled_stats()["executor"] == out
    assert memory.report()["compiled"]["executor"] == out


# -- serving: per-bucket compiled stats + readyz ------------------------------

def test_serving_bucket_hbm_gauge_and_memory_stats():
    pred = _mlp_predictor()
    pred.warmup()
    ms = pred.memory_stats()
    if not ms["buckets"]:
        pytest.skip("backend reports no memory analysis")
    assert len(ms["buckets"]) == pred.num_compiled
    for label, st in ms["buckets"].items():
        assert st["peak_bytes"] > 0
        assert m.SERVE_BUCKET_HBM_BYTES.get(bucket=label) == \
            st["peak_bytes"]
    assert ms["weights_bytes"] > 0
    assert ms["peak_bytes_max"] == max(
        v["peak_bytes"] for v in ms["buckets"].values())
    # the ledger's compiled table carries the bucket entries too
    assert any(k.startswith("serve_bucket:")
               for k in memory.compiled_stats())


def test_memory_stats_weights_bytes_is_per_instance():
    """Two models in one process: each predictor's weights_bytes is
    ITS OWN footprint (what evicting it frees), not the process-wide
    serve_weights tag summed over every predictor."""
    a = _mlp_predictor()
    b = _mlp_predictor()
    wa = a.memory_stats()["weights_bytes"]
    wb = b.memory_stats()["weights_bytes"]
    assert wa > 0 and wb > 0
    both = memory.live_by_tag().get("serve_weights", 0)
    assert wa < both and wb < both, (wa, wb, both)
    del a, b


def test_serving_attribution_and_close_leak_gate():
    pred = _mlp_predictor()
    batcher = serving.MicroBatcher(pred, max_wait_ms=0)
    batcher.predict(data=np.zeros((2, 8), "f"))
    _collect()
    s = memory.snapshot_summary()
    assert s["tags"].get("serve_weights", 0) > 0
    assert s["attribution_pct"] >= 90.0, s
    batcher.close()
    del batcher, pred
    _collect()
    assert memory.live_by_tag().get("serve_weights") is None, \
        memory.live_by_tag()


def test_evict_readmit_cycle_returns_bytes_to_baseline():
    """ISSUE 14 leak gate: evict() returns every tagged DEVICE byte
    (weights + bucket placeholders) while the host payload stays put;
    readmit()+warmup restores the exact device footprint; close()
    returns everything — device AND host — to baseline."""
    pred = _mlp_predictor()
    pred.warmup()
    _collect()
    dev_full = memory.live_by_tag().get("serve_weights", 0)
    host_full = memory.live_by_tag("host").get("serve_host_params", 0)
    assert dev_full > 0 and host_full > 0
    freed_est = pred.evict()
    assert freed_est > 0
    _collect()
    assert memory.live_by_tag().get("serve_weights") is None, \
        memory.live_by_tag()
    # the readmission source is untouched
    assert memory.live_by_tag("host").get(
        "serve_host_params", 0) == host_full
    pred.readmit()
    pred.warmup()
    _collect()
    # exact parity: same weights, same placeholders, same tags
    assert memory.live_by_tag().get("serve_weights", 0) == dev_full
    pred.close()
    pred.close()  # idempotent
    del pred
    _collect()
    assert memory.live_by_tag().get("serve_weights") is None
    assert memory.live_by_tag("host").get("serve_host_params") is None


def test_bucket_evict_drops_placeholders_and_gauge():
    """Per-bucket eviction returns the bucket's tagged placeholder
    bytes and removes its SERVE_BUCKET_HBM_BYTES child; the weights
    stay resident."""
    from mxnet_tpu.serving.buckets import bucket_label
    pred = _mlp_predictor()
    pred.warmup()
    _collect()
    w0 = memory.live_by_tag().get("serve_weights", 0)
    keys = sorted(pred._compiled)
    key = keys[0]
    ph = sum(memory.nbytes_of(a) for a in pred._extra[key].values())
    pred.evict_bucket(key)
    _collect()
    assert memory.live_by_tag().get("serve_weights", 0) == w0 - ph
    assert pred.resident and key not in pred._compiled
    assert m.SERVE_BUCKET_HBM_BYTES.get(bucket=bucket_label(key)) == 0.0
    # stats entry survives as the readmission cost estimate
    if key in pred._mem_stats:
        assert not pred.memory_stats()["buckets"][
            bucket_label(key)]["resident"]


def test_readyz_reports_bucket_hbm_and_budget_check():
    pred = _mlp_predictor()
    srv = serving.ResilientServer(pred, watchdog_interval_s=60.0)
    try:
        srv.warmup()
        rz = srv.readyz()
        if "bucket_hbm_peak_bytes" in rz["detail"]:
            assert rz["detail"]["bucket_hbm_peak_bytes"] > 0
            assert rz["detail"]["serve_weights_bytes"] > 0
        assert "hbm_budget" not in rz["checks"]  # budget off -> no check
        memory.configure(budget_mb=1e-6)  # absurdly small budget
        rz = srv.readyz()
        assert rz["checks"]["hbm_budget"] is False
        assert rz["ready"] is False
        assert rz["detail"]["hbm_tracked_bytes"] > 0
        memory.configure(budget_mb=0.0)
        assert srv.readyz()["ready"] is True
    finally:
        srv.close()


# -- prefetcher + checkpoint leak gates ---------------------------------------

def test_prefetcher_tags_and_exhaustion_leak_gate():
    from mxnet_tpu.gluon.data.prefetcher import prefetch_to_device
    batches = [np.ones((4, 8), "f") for _ in range(3)]
    it = prefetch_to_device(iter(batches), depth=2)
    out = list(it)
    assert len(out) == 3
    # worker-thread h2d staging carried the prefetch tag
    assert memory.snapshot_summary()["peak_by_tag"].get("prefetch", 0) > 0
    it.close()
    del out, it
    _collect()
    assert memory.live_by_tag().get("prefetch") is None, \
        memory.live_by_tag()


def test_checkpoint_host_twin_and_drain_leak_gate(tmp_path):
    from mxnet_tpu.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    state = {"w": mx.nd.ones((256, 16))}
    mgr.save(0, state)
    # the queued snapshot pins host RAM — attributed while in flight
    # (sync-mode managers may already have drained; peak still shows)
    mgr.wait()
    peak = memory.snapshot_summary()
    assert peak["host_tags"].get("checkpoint_host", 0) >= 0
    with memory._lock:
        host_peak = dict(memory._peak).get(("host", "checkpoint_host"), 0)
    assert host_peak == 256 * 16 * 4
    mgr.close()
    _collect()
    assert memory.live_by_tag(space="host").get("checkpoint_host") \
        is None, memory.live_by_tag(space="host")


# -- snapshot schema + gauges -------------------------------------------------

def test_snapshot_memory_block_schema():
    with memory.memory_scope("data"):
        a = mx.nd.zeros((8, 8))
    s = mx.observability.snapshot()["memory"]
    for k in ("enabled", "tracked_bytes", "tags", "host_tags",
              "untagged_bytes", "attribution_pct", "peak_by_tag",
              "budget_mb", "oom"):
        assert k in s, k
    assert s["enabled"] is True
    assert s["tags"]["data"] == 8 * 8 * 4
    # export refreshed the labeled gauge
    assert m.MEMORY_LEDGER_BYTES.get(tag="data", space="device") == \
        8 * 8 * 4
    del a


def test_render_prometheus_refreshes_gauge_without_snapshot():
    """The documented scrape wiring calls render_prometheus() alone —
    the ledger gauge must be fresh without an interleaved snapshot()."""
    with memory.memory_scope("data"):
        a = mx.nd.zeros((8, 8))
    text = mx.observability.render_prometheus()
    assert 'mxnet_memory_ledger_bytes{space="device",tag="data"} ' \
        + repr(float(8 * 8 * 4)) in text
    del a
    _collect()
    text = mx.observability.render_prometheus()
    assert 'tag="data"' not in text, "dead tag lingered on the scrape path"


def test_snapshot_gauge_drops_dead_tags():
    with memory.memory_scope("data"):
        a = mx.nd.zeros((8, 8))
    mx.observability.snapshot()
    del a
    _collect()
    mx.observability.snapshot()
    assert m.MEMORY_LEDGER_BYTES.get(tag="data", space="device") == 0.0


# -- graft-lint memory-hygiene rule (satellite 3) -----------------------------

_BAD_SRC = """
import jax
def naked(x, dev):
    return jax.device_put(x, dev)
"""

_OK_SRC = """
import jax
from mxnet_tpu.observability.memory import memory_scope
def wrapped_ndarray(x, dev, ctx):
    return NDArray(jax.device_put(x, dev), ctx)
def scoped(x, dev):
    with memory_scope("data"):
        return jax.device_put(x, dev)
def helper(x, dev, _mem):
    arr = jax.device_put(x, dev)
    return _mem.register(arr, tag="serve_weights")
def rebind(nd_arr, x, dev):
    nd_arr._set_data(jax.device_put(x, dev))
def suppressed(x, dev):
    return jax.device_put(x, dev)  # graft-lint: disable=memory-hygiene
"""


def _run_rule(src, tmp_path, name):
    from mxnet_tpu import analysis
    p = tmp_path / name
    p.write_text(src)
    return analysis.run(checkers=["memory-hygiene"], paths=[str(p)],
                        baseline=None)


def test_memory_hygiene_flags_naked_device_put(tmp_path):
    finds = _run_rule(_BAD_SRC, tmp_path, "bad.py")
    assert len(finds) == 1 and "memory_scope" in finds[0].message


def test_memory_hygiene_accepts_registered_idioms(tmp_path):
    assert _run_rule(_OK_SRC, tmp_path, "ok.py") == []


def test_memory_hygiene_unrelated_register_does_not_whitelist(tmp_path):
    """Only a LEDGER register call whitelists the enclosing function —
    atexit.register / base.Registry.register must not open a hole for
    naked device_puts sharing the function."""
    src = """
import atexit, jax
def stage(x, dev, cleanup, registry):
    atexit.register(cleanup)
    registry.register(cleanup)
    return jax.device_put(x, dev)
"""
    finds = _run_rule(src, tmp_path, "hole.py")
    assert len(finds) == 1, [str(f) for f in finds]


def test_memory_hygiene_zero_findings_in_package():
    """Ship clean: every device_put in mxnet_tpu/ is scope-wrapped,
    ledger-registered, NDArray-routed, or justified-suppressed."""
    from mxnet_tpu import analysis
    finds = analysis.run(checkers=["memory-hygiene"],
                         paths=["mxnet_tpu"])
    assert finds == [], [str(f) for f in finds]
