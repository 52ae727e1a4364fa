"""Multi-model serving under an HBM budget (mxnet_tpu.serving.registry).

The ISSUE 14 acceptance invariants this file pins:

  * N=4 models under a budget that fits only 2 serve a mixed-tenant
    flood with bounded p99, ZERO unhandled RESOURCE_EXHAUSTED/OOM
    (every failure is a typed ladder error), goodput >= 0.9 of
    admitted, and eviction churn visible in the metrics + ledger;
  * readmission after eviction is restart-free: with the persistent
    compile cache warm, a readmitted model's bucket rebuilds add ZERO
    new SERVE_COMPILES, and its outputs are bitwise identical to
    pre-eviction (the host payload preserves the exact weights);
  * the degradation ladder is typed — full -> buckets_evicted ->
    weights_evicted -> ModelUnavailable(retry_after_s) — never a raw
    RESOURCE_EXHAUSTED;
  * an evict -> readmit -> close cycle returns every tagged ledger
    byte (serve_weights device-side, serve_host_params host-side) to
    baseline.
"""
import gc
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faultinject as fi
from mxnet_tpu import serving, sym
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import memory
from mxnet_tpu.observability import metrics as m
from mxnet_tpu.serving import (ModelRegistry, ModelUnavailable,
                               Overloaded, DeadlineExceeded)

pytestmark = pytest.mark.registry

NIN = 8


def _mlp_symbol(pfx, nhid=16, nout=4):
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=nhid,
                             name=pfx + "fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=nout, name=pfx + "fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _params(net, seed, **input_shapes):
    rs = np.random.RandomState(seed)
    arg_shapes, _, _ = net.infer_shape(**input_shapes)
    out = {}
    for n, s in zip(net.list_arguments(), arg_shapes):
        if n in input_shapes or n.endswith("_label"):
            continue
        out["arg:" + n] = np.asarray(rs.normal(0, 0.1, s), "f")
    return out


def _register(reg, name, seed=0, max_batch=4, warmup=True, **kw):
    net = _mlp_symbol(name)
    params = _params(net, seed, data=(max_batch, NIN))
    return reg.register(name, net, params, {"data": (max_batch, NIN)},
                        tenants=[name + "-t"], warmup=warmup,
                        server_kwargs={"watchdog_interval_s": 60.0}, **kw)


def _x(rows=2, seed=1):
    return np.asarray(np.random.RandomState(seed).normal(
        0, 1, (rows, NIN)), "f")


def _weights_bytes(reg, name):
    return reg._entry(name).predictor.memory_stats()["weights_bytes"]


def _collect():
    gc.collect()
    memory.tracked_bytes()  # drain the death-callback queue


# -- registration / routing ---------------------------------------------------

def test_register_route_and_predict():
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha", seed=0)
        _register(reg, "beta", seed=1)
        a = reg.predict(tenant="alpha-t", data=_x())
        b = reg.predict(model="beta", data=_x())
        # different weights -> different outputs: routing is real
        assert a[0].shape == b[0].shape == (2, 4)
        assert not np.allclose(a[0], b[0])
        reg.bind("vip", "alpha")
        a2 = reg.predict(tenant="vip", data=_x())
        np.testing.assert_array_equal(a[0], a2[0])
        with pytest.raises(mx.MXNetError, match="no model routed"):
            reg.predict(tenant="unbound", data=_x())
        with pytest.raises(mx.MXNetError, match="unknown model"):
            reg.predict(model="gamma", data=_x())


def test_registry_bounds_and_duplicate():
    with ModelRegistry(budget_mb=0.0, max_models=1) as reg:
        _register(reg, "only")
        with pytest.raises(mx.MXNetError, match="already registered"):
            _register(reg, "only")
        with pytest.raises(mx.MXNetError, match="registry full"):
            _register(reg, "overflow")


def test_evict_policy_validated():
    with pytest.raises(mx.MXNetError, match="evict_policy"):
        ModelRegistry(evict_policy="fifo")


# -- the degradation ladder ---------------------------------------------------

def test_manual_evict_readmit_round_trip_bitwise():
    """weights_evicted -> readmit serves the EXACT pre-eviction
    weights (host payload fidelity), rebuilding buckets lazily."""
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        before = reg.predict(model="alpha", data=_x())
        e = reg._entry("alpha")
        assert reg.degradation("alpha") == "full"
        freed = e.predictor.evict()
        assert freed > 0 and not e.predictor.resident
        assert reg.degradation("alpha") == "weights_evicted"
        # the ladder never surfaces an untyped error: direct predictor
        # use while evicted is typed too
        with pytest.raises(serving.ModelEvictedError):
            e.predictor.predict(data=_x())
        after = reg.predict(model="alpha", data=_x())  # readmits
        assert e.predictor.resident
        np.testing.assert_array_equal(before[0], after[0])
        assert m.SERVE_READMITS.get(kind="model") >= 1


def test_bucket_eviction_is_phase_one_and_lru_ordered():
    """A small deficit is satisfied by evicting the LEAST recently
    used cold bucket — alpha's, warmed first — and no model loses its
    weights (phase 2 never runs)."""
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")   # alpha's buckets carry the oldest
        _register(reg, "beta")    # precompile stamps
        ev0 = m.SERVE_EVICTIONS.value
        reg._make_room(1.0, exclude=None, why="test")
        assert m.SERVE_EVICTIONS.get(kind="bucket", model="alpha") >= 1
        assert m.SERVE_EVICTIONS.get(kind="bucket", model="beta") == 0.0
        assert m.SERVE_EVICTIONS.value > ev0
        # phase 2 never ran: both models keep their weights
        assert reg._entry("alpha").predictor.resident
        assert reg._entry("beta").predictor.resident
        assert reg.degradation("alpha") == "buckets_evicted"


def test_budget_pressure_evicts_lru_model():
    """Admitting a model past the budget evicts the least recently
    used idle model's weights (kind=model), keeping the process under
    budget instead of OOMing.  Models are unwarmed so the budget game
    is purely the weights ledger — deterministic whether or not this
    backend reports CompiledMemoryStats."""
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha", warmup=False)
        _register(reg, "beta", warmup=False)
        reg._entry("alpha").last_used -= 100.0  # alpha is coldest
        wb = reg._entry("alpha").predictor.host_payload_bytes()
        _collect()
        # arm a budget with ~half a model of headroom: the next model
        # cannot fit without evicting one
        reg.budget_bytes = memory.tracked_bytes() + 0.5 * wb
        _register(reg, "gamma", seed=2, warmup=False)
        assert m.SERVE_EVICTIONS.get(kind="model", model="alpha") >= 1
        assert reg.degradation("alpha") == "weights_evicted"
        assert reg._entry("gamma").predictor.resident
        # the gauge tracks residency
        assert m.SERVE_RESIDENT_MODELS.get() == 2.0
        # and the LRU victim readmits on its next request, evicting in
        # turn — churn, not starvation
        out = reg.predict(model="alpha", data=_x())
        assert out[0].shape == (2, 4)
        assert reg._entry("alpha").predictor.resident


def test_unavailable_is_typed_with_retry_after():
    """When nothing can be evicted (policy=none), the over-budget
    model degrades to a typed ModelUnavailable at submit — never an
    admission, never a RESOURCE_EXHAUSTED."""
    with ModelRegistry(budget_mb=0.0, evict_policy="none") as reg:
        _register(reg, "alpha")
        reg._entry("alpha").predictor.evict()
        reg.budget_bytes = max(memory.tracked_bytes(), 1.0)  # no room
        adm0 = m.SERVE_ADMITTED.value
        with pytest.raises(ModelUnavailable) as ei:
            reg.predict(model="alpha", data=_x())
        assert ei.value.retry_after_s > 0
        assert ei.value.model == "alpha"
        assert m.SERVE_ADMITTED.value == adm0  # rejected BEFORE admission


def test_pinned_and_busy_models_are_never_victims():
    with fi.active(fi.FaultPlan().add("serving.dispatch", "delay",
                                      delay_s=0.08)):
        with ModelRegistry(budget_mb=0.0) as reg:
            _register(reg, "pinned", pinned=True)
            _register(reg, "busy")
            _register(reg, "cold")
            # make "busy" owe work, leave "cold" idle
            fut = reg.submit(model="busy", data=_x())
            reg._make_room(float(2 ** 40), exclude=None, why="test")
            assert reg._entry("pinned").predictor.resident
            assert reg._entry("busy").predictor.resident
            assert not reg._entry("cold").predictor.resident
            fut.result(timeout=30)


def test_over_budget_registration_admits_weights_evicted():
    """A model that cannot fit even after eviction still registers —
    at the weights_evicted rung, ready to readmit when capacity
    frees — instead of failing registration."""
    with ModelRegistry(budget_mb=0.0, evict_policy="none") as reg:
        _register(reg, "alpha")
        reg.budget_bytes = max(memory.tracked_bytes(), 1.0)
        _register(reg, "beta", seed=1)
        assert reg.degradation("beta") == "weights_evicted"
        # capacity frees: the first request readmits it
        reg.budget_bytes = 0.0
        reg.evict_policy = "lru"
        out = reg.predict(model="beta", data=_x())
        assert out[0].shape == (2, 4)


# -- restart-free readmission -------------------------------------------------

@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """JAX's persistent cache at a scratch directory for one test; the
    process-global jax config is put back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from mxnet_tpu import base
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    assert base.enable_compile_cache() == str(tmp_path / "cc")
    assert base.compile_cache_active()
    yield
    jax.config.update("jax_compilation_cache_dir", saved)
    compilation_cache.reset_cache()


def test_readmit_zero_new_serve_compiles_when_cache_warm(compile_cache):
    """With JAX_COMPILATION_CACHE_DIR set, rebuilding an evicted
    model's buckets is a persistent-cache hit: SERVE_COMPILES must not
    move (readmissions are counted separately) — the restart-free
    churn contract."""
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        before = reg.predict(model="alpha", data=_x())
        e = reg._entry("alpha")
        n_buckets = e.predictor.num_compiled
        assert n_buckets > 0
        e.predictor.evict()
        compiles0 = m.SERVE_COMPILES.value
        rm0 = m.SERVE_READMITS.get(kind="model")
        rb0 = m.SERVE_READMITS.get(kind="bucket")
        after = reg.predict(model="alpha", data=_x())
        np.testing.assert_array_equal(before[0], after[0])
        assert m.SERVE_COMPILES.value == compiles0, \
            "warm-cache readmission must add ZERO SERVE_COMPILES"
        assert m.SERVE_READMITS.get(kind="model") == rm0 + 1
        assert m.SERVE_READMITS.get(kind="bucket") >= rb0 + 1
        # lazily rebuilt: only the routed bucket came back so far
        assert 1 <= e.predictor.num_compiled <= n_buckets


# -- chaos: injected eviction faults + OOM second chance ----------------------

def test_faultinject_evict_raise_skips_victim_keeps_it_resident():
    """A raise rule at serving.evict models a failed eviction: the
    victim stays FULLY resident and the budgeter moves on (typed
    degradation downstream, never an InjectedFault escape)."""
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        _register(reg, "beta")
        plan = fi.FaultPlan().add("serving.evict", "raise")
        with fi.active(plan):
            freed = reg._make_room(float(2 ** 40), exclude=None,
                                   why="test")
        assert plan.stats()["serving.evict"] > 0
        assert freed == 0.0
        assert reg._entry("alpha").predictor.resident
        assert reg._entry("beta").predictor.resident
        # with the plan gone the same pressure evicts normally
        reg._make_room(float(2 ** 40), exclude=None, why="test")
        assert not reg._entry("alpha").predictor.resident


def test_oom_second_chance_evicts_and_retries():
    """An injected memory.oom at the dispatch chokepoint triggers ONE
    arbiter eviction pass + dispatch retry: the request SUCCEEDS, the
    colder model got evicted, and no DeviceMemoryError reaches the
    caller — an OOM became a policy decision."""
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "hot")
        _register(reg, "cold")
        reg.predict(model="cold", data=_x())
        time.sleep(0.01)
        reg.predict(model="hot", data=_x())  # hot is most recent
        plan = fi.FaultPlan().add("memory.oom", "raise", times=1)
        with fi.active(plan):
            out = reg.predict(model="hot", data=_x())
        assert out[0].shape == (2, 4)
        assert plan.stats()["memory.oom"] == 1
        assert not reg._entry("cold").predictor.resident
        assert m.SERVE_EVICTIONS.get(kind="model", model="cold") >= 1


def test_make_room_reclaims_decode_kv_before_buckets_or_weights():
    """Ladder phase 0 (ISSUE 19): decode KV pages are the CHEAPEST
    victims — a deficit the live engines can absorb never touches
    bucket executables or model weights, and the evicted sequence
    failed typed with a retry-after instead of hanging."""
    from mxnet_tpu.serving import DecodeEngine, SequenceEvicted, ToyLM
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        _register(reg, "beta")
        with DecodeEngine(ToyLM(vocab=16, dim=8, window=4), slots=2,
                          page_tokens=4, max_pages=2,
                          warmup=False) as eng:
            fut = eng.submit([1, 2], 4)
            eng.step()
            kv = eng.stats()["kv_bytes"]
            assert kv > 0
            freed = reg._make_room(float(kv) / 2, exclude=None,
                                   why="test-phase0")
            assert freed > 0
            assert eng.stats()["kv_bytes"] < kv
            # the cheaper rungs were enough: nothing hotter was touched
            assert reg._entry("alpha").predictor.resident
            assert reg._entry("beta").predictor.resident
            with pytest.raises(SequenceEvicted) as ei:
                fut.result(timeout=10)
            assert ei.value.retry_after_s > 0


@pytest.mark.chaos
def test_chaos_four_models_budget_for_two_mixed_tenant_flood():
    """THE acceptance drill: 4 models, a budget sized for ~2, a
    mixed-tenant threaded flood with serving.evict delays and one
    injected memory.oom.  Pins: zero DeviceMemoryError/InjectedFault/
    ModelEvictedError escapes (only ladder-typed failures), goodput
    >= 0.9 of admitted, bounded p99, eviction churn > 0, and ledger
    parity after close.

    ISSUE 19 extends the drill with a GENERATIVE tenant: a continuous-
    batching DecodeEngine shares the same budget (its KV pages are the
    arbiter's phase-0 victims, its weights ride `serve_weights`), its
    sequences count in the same goodput, and every generative failure
    mode is typed too (`SequenceEvicted` rides `Overloaded`)."""
    from mxnet_tpu.serving import DecodeEngine, ToyLM
    # what an earlier test of the process left for the collector is not
    # this drill's baseline (a whole run read 8 bytes here and 0 after)
    _collect()
    dev0 = memory.live_by_tag().get("serve_weights", 0)
    host0 = memory.live_by_tag("host").get("serve_host_params", 0)
    kv0 = memory.live_by_tag().get("serve_kv_pages", 0)
    names = ["m0", "m1", "m2", "m3"]
    reg = ModelRegistry(budget_mb=0.0)
    eng = None
    try:
        for i, n in enumerate(names):
            _register(reg, n, seed=i)
        # the generative tenant's engine shares the process budget:
        # created pre-budget so its weights count as resident state
        eng = DecodeEngine(ToyLM(vocab=16, dim=8, window=4), slots=4,
                           page_tokens=4, max_pages=4, warmup=False,
                           name="gen")
        lock = threading.Lock()

        def tenant_load(results, tenant, model, rounds):
            for i in range(rounds):
                t0 = time.perf_counter()
                try:
                    fut = reg.submit(model=model, tenant=tenant,
                                     data=_x(rows=2, seed=i))
                    with lock:
                        results["admitted"] += 1
                    fut.result(timeout=60)
                    with lock:
                        results["served"] += 1
                        results["lat"].append(time.perf_counter() - t0)
                except (ModelUnavailable, Overloaded,
                        DeadlineExceeded):
                    pass  # typed ladder/backpressure: the design
                except Exception as e:  # noqa: BLE001 — the invariant
                    with lock:
                        results["errors"].append(e)

        def gen_load(results, tenant, rounds):
            """The generative tenant: sequences through the decode
            engine, same goodput ledger, same typed-or-bust rule."""
            for i in range(rounds):
                t0 = time.perf_counter()
                try:
                    fut = eng.submit([i % 8 + 1, i % 4 + 1], 4,
                                     tenant=tenant)
                except Overloaded:
                    continue            # typed shed: never admitted
                with lock:
                    results["admitted"] += 1
                try:
                    while not fut.done():
                        eng.step()
                    fut.result(timeout=60)
                    with lock:
                        results["served"] += 1
                        results["lat"].append(time.perf_counter() - t0)
                except (Overloaded, DeadlineExceeded):
                    pass  # SequenceEvicted rides Overloaded: typed
                except Exception as e:  # noqa: BLE001 — the invariant
                    with lock:
                        results["errors"].append(e)

        def flood():
            """Mixed tenants, traffic shifting across all 4 models, and
            the two generative tenants: eight client threads."""
            results = {"lat": [], "errors": [], "served": 0, "admitted": 0}
            threads = []
            for tenant, model in [("acme", "m0"), ("acme", "m2"),
                                  ("beta", "m1"), ("beta", "m3"),
                                  ("gamma", "m2"), ("gamma", "m0")]:
                threads.append(threading.Thread(
                    target=tenant_load, args=(results, tenant, model, 10)))
            for tenant in ("gen-a", "gen-b"):
                threads.append(threading.Thread(
                    target=gen_load, args=(results, tenant, 6)))
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive(), "flood worker hung"
            return results

        # the base is the SAME flood with the budget off and everything
        # resident: the same eight threads on the same machine under the
        # same load, so the bound below compares what the budget costs and
        # not how busy the container is (a serial base read 0.007 s; the
        # flood beside six busy test workers then read 3.9 s against the
        # old floor of 2 s)
        flood()                # first use compiles: not a latency
        base = flood()
        assert base["errors"] == [] and base["served"] == base["admitted"]
        p99_base = float(np.percentile(base["lat"], 99))
        wb = _weights_bytes(reg, "m0")
        # budget: everything currently resident + ~0.6 models of slack
        # -> keeping all four resident is impossible, ~2 fit as the
        # flood shifts traffic between pairs
        for n in names[2:]:
            reg._entry(n).predictor.evict()
        _collect()
        reg.budget_bytes = memory.tracked_bytes() + 0.6 * wb

        plan = (fi.FaultPlan()
                .add("serving.evict", "delay", delay_s=0.002)
                # early: beside a busy machine most of the flood is shed
                # at submit and as few as three batches are dispatched
                .add("memory.oom", "raise", times=1, after=1))
        with fi.active(plan):
            # the k=2 budget forces continuous evict/readmit churn
            results = flood()
        assert plan.stats().get("memory.oom", 0) == 1

        # 1. zero unhandled OOM/RESOURCE_EXHAUSTED/untyped escapes
        assert results["errors"] == [], results["errors"]
        # 2. goodput over admitted
        assert results["admitted"] > 0
        goodput = results["served"] / results["admitted"]
        assert goodput >= 0.9, (goodput, results)
        # 3. bounded p99, against the same flood without the budget
        p99 = float(np.percentile(results["lat"], 99))
        assert p99 <= max(10 * p99_base, 2.0), (p99, p99_base)
        # 4. eviction churn happened and is visible
        snap = obs.snapshot()["serving"]
        assert sum(snap["evictions"].values()) > 0, snap["evictions"]
        assert snap["readmissions"] > 0
        assert snap["resident_models"] >= 1
        # 6. the generative tenant actually decoded under the budget
        gen = eng.stats()
        assert gen["completed"] > 0, gen
        assert gen["completed"] + gen["evicted"] + gen["expired"] \
            == gen["admitted"], gen
    finally:
        if eng is not None:
            eng.close()
        reg.close()
    del reg
    # the injected OOM's post-mortem dump thread derefs ledger entries
    # while it serializes — wait it out before reading the ledger
    memory.wait_oom_dump(timeout=30)
    _collect()
    # 5. ledger parity after full churn + teardown — engine weights
    # ride serve_weights and its pages serve_kv_pages, so the closed
    # engine must be invisible here too
    assert memory.live_by_tag().get("serve_weights", 0) == dev0
    assert memory.live_by_tag("host").get("serve_host_params", 0) == host0
    assert memory.live_by_tag().get("serve_kv_pages", 0) == kv0


# -- observability ------------------------------------------------------------

def test_snapshot_serving_schema_has_registry_block():
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        reg._entry("alpha").predictor.evict()
        reg.predict(model="alpha", data=_x())  # readmit
        snap = obs.snapshot()["serving"]
        for k in ("evictions", "readmissions", "resident_models",
                  "model_hbm_bytes"):
            assert k in snap, sorted(snap)
        assert snap["readmissions"] >= 1
        assert snap["model_hbm_bytes"].get("alpha", 0) > 0
        assert snap["resident_models"] == 1.0


def test_registry_readyz_per_model_detail_and_budget_block():
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        _register(reg, "beta")
        reg._entry("beta").predictor.evict()
        rz = reg.readyz()
        assert rz["ready"] is True  # evicted != unready: readmits on demand
        assert rz["models"]["alpha"]["degradation"] == "full"
        assert rz["models"]["beta"]["degradation"] == "weights_evicted"
        for k in ("budget_bytes", "tracked_bytes", "reserved_bytes",
                  "headroom_bytes", "evict_policy"):
            assert k in rz["budget"]
        # the per-model ResilientServer carries the degradation rung in
        # its own readyz detail (the extra_ready hook)
        srv_rz = reg._entry("beta").server.readyz()
        assert srv_rz["detail"]["degradation"] == "weights_evicted"
        assert srv_rz["detail"]["model"] == "beta"


def test_flight_timeline_records_evict_and_readmit_phases():
    from mxnet_tpu.observability import flight
    if not flight.ENABLED:
        pytest.skip("flight recorder disabled")
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        reg._make_room(float(2 ** 40), exclude=None, why="test")
        reg.predict(model="alpha", data=_x())  # readmit
        summary = flight.summary()
        assert "serve_evict" in summary, sorted(summary)
        assert "serve_readmit" in summary, sorted(summary)


def test_memory_arbitration_hook_roundtrip():
    """memory.ensure_headroom is the generic chokepoint: with the
    registry's arbiter installed, ANY subsystem asking for headroom
    triggers LRU eviction; with none installed it just answers."""
    assert memory.ensure_headroom(2 ** 40) is True  # budget off
    calls = []
    prev = memory.set_budget_arbiter(
        lambda deficit, why: calls.append((deficit, why)))
    try:
        ok = memory.ensure_headroom(2 ** 40, why="unit",
                                    budget=float(1))
        assert ok is False and calls and calls[0][1] == "unit"
    finally:
        memory.set_budget_arbiter(prev)
    with ModelRegistry(budget_mb=0.0) as reg:
        _register(reg, "alpha")
        assert not memory.ensure_headroom(
            2 ** 40, why="external", budget=float(1))
        # the registry's LRU evictor answered the call
        assert not reg._entry("alpha").predictor.resident
