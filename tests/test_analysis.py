"""graft-lint: repo-specific static analysis + runtime concurrency
sanitizer (mxnet_tpu.analysis, ISSUE 7).

Three layers:
  1. unit fixtures — a known-bad snippet per rule proves every checker
     FIRES, and every suppression form (inline comment, baseline)
     works;
  2. the tier-1 gate — the full mxnet_tpu/ sweep must report ZERO
     non-baselined findings (the `make lint-graft` twin), inside a
     30s budget;
  3. the sanitizer — lock-order cycles and non-reentrant re-entry are
     detected typed, no_sync regions raise on device→host syncs, and
     the real PR 5-class hazard (SIGTERM emergency save re-entering
     CheckpointManager._lock) is pinned: the sanitizer catches the
     plain-Condition shape, the shipped RLock-backed condition passes.
"""
import os
import textwrap
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu.analysis import sanitizer as san
from mxnet_tpu.analysis.core import (Baseline, DEFAULT_BASELINE, REPO_ROOT,
                                     run_detailed)
from mxnet_tpu.observability import metrics as m

ALL_RULES = analysis.ALL_RULES


# -- helpers -----------------------------------------------------------------

def _lint(tmp_path, source, rules=None, baseline=None):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(source))
    return analysis.run(rules, [str(p)], baseline)


@pytest.fixture
def sanitizer():
    """Enable the sanitizer for one test; locks created inside are
    tracked.  State is reset both sides so tests stay independent."""
    san.reset()
    san.enable()
    yield san
    san.disable()
    san.reset()


# known-bad snippets, one per rule ------------------------------------------
BAD_THREAD_SAFETY = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._run)

        def _run(self):
            self.count = self.count + 1   # worker write, no lock

        def bump(self):
            self.count = 99               # caller write, no lock
"""

BAD_REENTRY = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._t = threading.Thread(target=self._run)

        def _run(self):
            with self._lock:
                self.flush()

        def flush(self):
            with self._lock:
                pass
"""

BAD_HOST_SYNC = """
    from mxnet_tpu import analysis

    @analysis.hot_path
    def step(grad):
        return grad.asnumpy()
"""

BAD_HOST_SYNC_TRANSITIVE = """
    from mxnet_tpu import analysis

    def _leaf(x):
        return float(x.sum())

    @analysis.hot_path
    def step(x):
        return _leaf(x)
"""

BAD_HOST_SYNC_JIT = """
    import jax

    def _impl(x):
        x.block_until_ready()
        return x

    run = jax.jit(_impl)
"""

BAD_ATOMIC_WRITE = """
    import json

    def save(path, obj):
        with open(path, "w") as f:
            json.dump(obj, f)
"""

GOOD_ATOMIC_IDIOM = """
    import os

    def save(path, data):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(data)
        os.replace(tmp, path)
"""

BAD_ENV_SYNC = """
    import os

    def knob():
        return os.environ.get("MXNET_TOTALLY_UNDOCUMENTED_KNOB", "0")
"""

BAD_METRICS = """
    def record(counter, tenant):
        counter.SERVE_SHED.inc(tenant=f"tenant-{tenant}")
"""

BAD_MEMORY = """
    import jax
    def stage(x, dev):
        return jax.device_put(x, dev)
"""

# ISSUE 15: the jit/program-boundary tier -------------------------------------
BAD_USE_AFTER_DONATE = """
    import jax

    def step(params, grads):
        fn = jax.jit(update, donate_argnums=(0,))
        new = fn(params, grads)
        loss = params["w"].sum()      # read of a donated value
        return new, loss
"""

BAD_DONATE_LOOP = """
    import jax

    def train(params, batches):
        fn = jax.jit(update, donate_argnums=(0,))
        for b in batches:
            out = fn(params, b)       # iter 2 passes a dead buffer
        return out
"""

GOOD_DONATE_REBIND = """
    import jax

    def train(params, batches):
        fn = jax.jit(update, donate_argnums=(0,))
        for b in batches:
            params = fn(params, b)    # rebind kills the taint
        return params
"""

GOOD_DONATE_RESTORE = """
    import jax

    def retry(self, params, grads):
        fn = jax.jit(update, donate_argnums=(0,))
        try:
            out = fn(params, grads)
        except Exception:
            self._restore_snapshot()   # restore idiom revives state
            out = fn(params, grads)
        return out
"""

BAD_DONATE_FACTORY = """
    import jax

    class C:
        def _build_fn(self):
            return jax.jit(update, donate_argnums=(1,))

        def run(self, upd, key, a, b):
            fn = upd.lookup_program(key, lambda: self._build_fn())
            fn(a, b)
            return b.shape            # b went through a donated slot
"""

BAD_RETRACE = """
    import jax

    def per_call(x):
        return jax.jit(lambda v: v + 1)(x)
"""

BAD_RETRACE_SHARD_MAP = """
    from jax import shard_map

    def sharded(mesh, spec, x):
        fn = shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
        return fn(x)
"""

GOOD_RETRACE_SHARD_MAP = """
    import functools
    import jax
    from jax import shard_map

    @functools.lru_cache(maxsize=8)
    def sharded_fn(mesh, spec):
        return jax.jit(shard_map(body, mesh=mesh, in_specs=spec,  # graft-lint: disable=retrace-hazard
                                 out_specs=spec))
"""

BAD_RETRACE_LOOP = """
    import jax

    def in_loop(xs):
        out = []
        for x in xs:
            f = jax.jit(step)
            out.append(f(x))
        return out
"""

BAD_RETRACE_KEY = """
    def lookup(self, wvals):
        key = ("update", [str(w.dtype) for w in wvals], id(self))
        return self.lookup_program(key, build)
"""

GOOD_RETRACE_KEY = """
    def lookup(self, wvals):
        key = ("update", tuple(str(w.dtype) for w in wvals), self._uid)
        return self.lookup_program(key, build)
"""

BAD_GATE = """
    from mxnet_tpu.base import getenv

    ENABLED = getenv("MXNET_FIXTURE_GATE", True)

    def hook(x):
        y = compute(x)                # work before the kill switch
        if not ENABLED:
            return x
        return y
"""

BAD_GATE_REREAD = """
    from mxnet_tpu.base import getenv

    ENABLED = getenv("MXNET_FIXTURE_GATE", True)

    def hook(x):
        if not getenv("MXNET_FIXTURE_GATE", True):   # per-call parse
            return x
        return compute(x)
"""

GOOD_GATE = """
    from mxnet_tpu.base import getenv

    ENABLED = getenv("MXNET_FIXTURE_GATE", True)

    def hook(x):
        if not ENABLED:
            return x
        return compute(x)
"""

# -- each rule fires on its known-bad fixture --------------------------------

def test_thread_safety_fires(tmp_path):
    got = _lint(tmp_path, BAD_THREAD_SAFETY, ["thread-safety"])
    assert len(got) == 1, got
    assert "self.count" in got[0].message
    assert got[0].rule == "thread-safety"


def test_thread_safety_guarded_is_clean(tmp_path):
    guarded = BAD_THREAD_SAFETY.replace(
        "            self.count = self.count + 1   # worker write, no lock",
        "            with self._lock:\n"
        "                self.count = self.count + 1").replace(
        "            self.count = 99               # caller write, no lock",
        "            with self._lock:\n"
        "                self.count = 99")
    assert _lint(tmp_path, guarded, ["thread-safety"]) == []


def test_thread_safety_reentry_fires(tmp_path):
    got = _lint(tmp_path, BAD_REENTRY, ["thread-safety"])
    assert len(got) == 1, got
    assert "re-acquired" in got[0].message
    # RLock version is legal
    ok = BAD_REENTRY.replace("threading.Lock()", "threading.RLock()")
    assert _lint(tmp_path, ok, ["thread-safety"]) == []
    # a BARE Condition() is RLock-backed (threading's documented
    # default) — re-entry through it is legal, not a finding
    cond = BAD_REENTRY.replace("threading.Lock()",
                               "threading.Condition()")
    assert _lint(tmp_path, cond, ["thread-safety"]) == []
    # ...but an explicitly plain-Lock-backed condition is the hazard
    plain = BAD_REENTRY.replace(
        "threading.Lock()", "threading.Condition(threading.Lock())")
    assert len(_lint(tmp_path, plain, ["thread-safety"])) == 1


def test_host_sync_fires(tmp_path):
    got = _lint(tmp_path, BAD_HOST_SYNC, ["host-sync"])
    assert len(got) == 1 and ".asnumpy()" in got[0].message


def test_host_sync_transitive_fires(tmp_path):
    got = _lint(tmp_path, BAD_HOST_SYNC_TRANSITIVE, ["host-sync"])
    assert len(got) == 1, got
    assert "via" in got[0].message and "step" in got[0].message


def test_host_sync_jit_entry_fires(tmp_path):
    got = _lint(tmp_path, BAD_HOST_SYNC_JIT, ["host-sync"])
    assert len(got) == 1 and "block_until_ready" in got[0].message


def test_host_sync_ignores_host_math(tmp_path):
    src = """
        import numpy as np
        from mxnet_tpu import analysis

        @analysis.hot_path
        def step(x, shape):
            n = int(np.prod(shape))
            m = int(x.shape[0])
            return n + m
    """
    assert _lint(tmp_path, src, ["host-sync"]) == []


def test_atomic_write_fires(tmp_path):
    got = _lint(tmp_path, BAD_ATOMIC_WRITE, ["atomic-write"])
    assert len(got) == 2  # the open() and the json.dump
    assert all(f.rule == "atomic-write" for f in got)


def test_atomic_write_idiom_passes(tmp_path):
    assert _lint(tmp_path, GOOD_ATOMIC_IDIOM, ["atomic-write"]) == []
    via_helper = GOOD_ATOMIC_IDIOM.replace(
        "        tmp = path + \".tmp\"\n"
        "        with open(tmp, \"w\") as f:\n"
        "            f.write(data)\n"
        "        os.replace(tmp, path)",
        "        from mxnet_tpu.base import atomic_write\n"
        "        atomic_write(path, data)")
    assert _lint(tmp_path, via_helper, ["atomic-write"]) == []


def test_atomic_write_ignores_reads_and_membufs(tmp_path):
    src = """
        import io
        import json
        import numpy as np

        def load(path):
            with open(path) as f:
                return json.load(f)

        def encode(arr):
            b = io.BytesIO()
            np.save(b, arr)
            return b.getvalue()
    """
    assert _lint(tmp_path, src, ["atomic-write"]) == []


def test_env_sync_fires(tmp_path):
    got = _lint(tmp_path, BAD_ENV_SYNC, ["env-sync"])
    undoc = [f for f in got if "MXNET_TOTALLY_UNDOCUMENTED_KNOB"
             in f.message]
    assert len(undoc) == 1 and "not documented" in undoc[0].message


def test_metrics_hygiene_fires(tmp_path):
    got = _lint(tmp_path, BAD_METRICS, ["metrics-hygiene"])
    assert len(got) == 1 and "f-string" in got[0].message
    # a bounded variable is the allowed idiom
    ok = BAD_METRICS.replace('f"tenant-{tenant}"', "tenant")
    assert _lint(tmp_path, ok, ["metrics-hygiene"]) == []


# -- ISSUE 15: use-after-donate ----------------------------------------------

def test_use_after_donate_fires(tmp_path):
    got = _lint(tmp_path, BAD_USE_AFTER_DONATE, ["use-after-donate"])
    assert len(got) == 1, got
    assert "'params'" in got[0].message
    assert "donated" in got[0].message


def test_use_after_donate_loop_carried(tmp_path):
    """The loop-carried shape: iteration 2 passes the buffer iteration
    1 donated — only a second pass over the loop body sees it."""
    got = _lint(tmp_path, BAD_DONATE_LOOP, ["use-after-donate"])
    assert len(got) == 1, got


def test_use_after_donate_rebind_and_restore_are_kills(tmp_path):
    assert _lint(tmp_path, GOOD_DONATE_REBIND,
                 ["use-after-donate"]) == []
    assert _lint(tmp_path, GOOD_DONATE_RESTORE,
                 ["use-after-donate"]) == []


GOOD_DONATE_SCATTER_RESTORE = """
    import jax

    def train(table, ids, rows):
        fn = jax.jit(update, donate_argnums=(0,))
        fn(table, rows)
        table = table.at[ids].set(rows)   # scatter-restore rebind
        return table
"""

BAD_DONATE_SCATTER_OTHER_TARGET = """
    import jax

    def train(table, ids, rows):
        fn = jax.jit(update, donate_argnums=(0,))
        fn(table, rows)
        fresh = table.at[ids].set(rows)   # no rebind: stale read
        return fresh
"""


def test_use_after_donate_scatter_restore_idiom(tmp_path):
    """ISSUE 20: ``x = x.at[ids].set(...)`` rebinds the donated name to
    the functional scatter result in the same statement — the aliasing
    flow of the whole-step embedding update, not a stale use.
    Scattering into a DIFFERENT name keeps the flagged read."""
    assert _lint(tmp_path, GOOD_DONATE_SCATTER_RESTORE,
                 ["use-after-donate"]) == []
    got = _lint(tmp_path, BAD_DONATE_SCATTER_OTHER_TARGET,
                ["use-after-donate"])
    assert len(got) == 1, got
    assert "'table'" in got[0].message


def test_use_after_donate_through_factory_and_cache(tmp_path):
    """The repo idiom: donation declared in a _build_fn factory,
    resolved through upd.lookup_program(key, lambda: ...)."""
    got = _lint(tmp_path, BAD_DONATE_FACTORY, ["use-after-donate"])
    assert len(got) == 1, got
    assert "'b'" in got[0].message


# -- ISSUE 15: retrace-hazard -------------------------------------------------

def test_retrace_hazard_jit_then_call(tmp_path):
    got = _lint(tmp_path, BAD_RETRACE, ["retrace-hazard"])
    assert any("EVERY call recompiles" in f.message for f in got), got


def test_retrace_hazard_jit_in_loop(tmp_path):
    got = _lint(tmp_path, BAD_RETRACE_LOOP, ["retrace-hazard"])
    assert any("inside a loop" in f.message for f in got), got


def test_retrace_hazard_bare_shard_map(tmp_path):
    """A shard_map that no jax.jit wraps is the shape the sequence-parallel
    decode had; jitted and cached it is clean (the jit itself, outside the
    chokepoints, carries its suppression)."""
    got = _lint(tmp_path, BAD_RETRACE_SHARD_MAP, ["retrace-hazard"])
    assert len(got) == 1 and "bare shard_map" in got[0].message, got
    assert _lint(tmp_path, GOOD_RETRACE_SHARD_MAP,
                 ["retrace-hazard"]) == []


def test_retrace_hazard_unstable_cache_key(tmp_path):
    got = _lint(tmp_path, BAD_RETRACE_KEY, ["retrace-hazard"])
    msgs = " | ".join(f.message for f in got)
    assert "unhashable" in msgs and "id(...)" in msgs, got
    # tuple()-coerced comprehensions + counter uids are the blessed
    # idiom (exactly what update_all / wholestep do)
    assert _lint(tmp_path, GOOD_RETRACE_KEY, ["retrace-hazard"]) == []


def test_retrace_hazard_key_resolution_is_scoped(tmp_path):
    """An unrelated local named `key` in ANOTHER function must not
    shadow a blessed cache key (the review-caught false positive:
    file-global name resolution flagged legal code)."""
    src = GOOD_RETRACE_KEY + """
    def other():
        key = [1, 2, 3]     # never a cache key — different scope
        return key
"""
    assert _lint(tmp_path, src, ["retrace-hazard"]) == []


def test_retrace_hazard_blessed_chokepoints_pass():
    """The real compile chokepoints (wholestep, FusedUpdater, serving)
    construct jit programs and must stay clean — the rule is about
    UNblessed sites."""
    got = analysis.run(["retrace-hazard"],
                       [os.path.join(REPO_ROOT, "mxnet_tpu")], None)
    assert got == [], got


# -- ISSUE 15: gate-hygiene ---------------------------------------------------

def test_gate_hygiene_buried_guard_fires(tmp_path):
    got = _lint(tmp_path, BAD_GATE, ["gate-hygiene"])
    assert len(got) == 1 and "buried" in got[0].message


def test_gate_hygiene_per_call_reread_fires(tmp_path):
    got = _lint(tmp_path, BAD_GATE_REREAD, ["gate-hygiene"])
    assert len(got) == 1 and "re-read" in got[0].message


def test_gate_hygiene_guard_first_is_clean(tmp_path):
    assert _lint(tmp_path, GOOD_GATE, ["gate-hygiene"]) == []


def test_gate_hygiene_module_level_read_is_clean(tmp_path):
    """The gate DEFINITION itself (module-level getenv) must not count
    as a re-read."""
    src = GOOD_GATE + """
    RAISE = getenv("MXNET_FIXTURE_GATE_RAISE", True)
"""
    assert _lint(tmp_path, src, ["gate-hygiene"]) == []


def test_new_rule_inline_suppression(tmp_path):
    """Both suppression styles work on the new tier too."""
    src = BAD_USE_AFTER_DONATE.replace(
        'loss = params["w"].sum()      # read of a donated value',
        'loss = params["w"].sum()  # graft-lint: disable=use-after-donate')
    assert _lint(tmp_path, src, ["use-after-donate"]) == []
    src2 = BAD_RETRACE.replace(
        "        return jax.jit(lambda v: v + 1)(x)",
        "        # graft-lint: disable=retrace-hazard\n"
        "        return jax.jit(lambda v: v + 1)(x)")
    assert _lint(tmp_path, src2, ["retrace-hazard"]) == []


# -- suppression forms -------------------------------------------------------

def test_inline_suppression_same_line(tmp_path):
    src = BAD_ATOMIC_WRITE.replace(
        'with open(path, "w") as f:',
        'with open(path, "w") as f:  # graft-lint: disable=atomic-write')
    got = _lint(tmp_path, src, ["atomic-write"])
    # the comment covers its own line AND the next (json.dump is two
    # lines down -> still flagged)
    assert len(got) == 1 and "json.dump" in got[0].message


def test_inline_suppression_line_above(tmp_path):
    src = BAD_ATOMIC_WRITE.replace(
        '        with open(path, "w") as f:',
        '        # graft-lint: disable=atomic-write\n'
        '        with open(path, "w") as f:')
    got = _lint(tmp_path, src, ["atomic-write"])
    assert len(got) == 1 and "json.dump" in got[0].message


def test_inline_suppression_rule_list(tmp_path):
    src = BAD_HOST_SYNC.replace(
        "return grad.asnumpy()",
        "return grad.asnumpy()  # graft-lint: disable=host-sync,atomic-write")
    assert _lint(tmp_path, src, ["host-sync"]) == []


def test_suppression_wrong_rule_does_not_hide(tmp_path):
    src = BAD_HOST_SYNC.replace(
        "return grad.asnumpy()",
        "return grad.asnumpy()  # graft-lint: disable=atomic-write")
    assert len(_lint(tmp_path, src, ["host-sync"])) == 1


def test_baseline_suppresses_and_requires_justification(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(textwrap.dedent(BAD_ATOMIC_WRITE))
    active, baselined, _ = run_detailed(["atomic-write"], [str(p)], None)
    assert len(active) == 2
    bl = tmp_path / "baseline.json"
    bl.write_text('{"findings": [{"rule": "atomic-write", '
                  f'"path": "{active[0].path}", "symbol": "save", '
                  '"justification": "test fixture"}]}')
    active2, baselined2, _ = run_detailed(
        ["atomic-write"], [str(p)], str(bl))
    assert active2 == [] and len(baselined2) == 2
    with pytest.raises(ValueError, match="justification"):
        Baseline([{"rule": "x", "path": "y", "symbol": "z"}])


def test_checked_in_baseline_policy():
    """atomic-write and env-sync ship with a near-empty baseline: those
    findings are FIXED, not grandfathered (ISSUE 7 satellite)."""
    bl = Baseline.load(DEFAULT_BASELINE)
    per_rule = bl.rules_present()
    assert per_rule.get("atomic-write", 0) == 0
    assert per_rule.get("env-sync", 0) == 0
    for e in bl.entries:
        assert e["justification"]


# -- the tier-1 gate ---------------------------------------------------------

@pytest.mark.analysis
def test_full_codebase_sweep_clean_and_fast():
    """`make lint-graft` in-process: zero non-baselined findings over
    mxnet_tpu/ at HEAD, inside the 30s budget (so the gate can't
    silently outgrow tier-1)."""
    t0 = time.perf_counter()
    active, _, _ = run_detailed(None, ["mxnet_tpu"], DEFAULT_BASELINE)
    dt = time.perf_counter() - t0
    assert active == [], "\n".join(str(f) for f in active)
    assert dt < 30.0, f"sweep took {dt:.1f}s"


@pytest.mark.analysis
def test_cli_exits_nonzero_on_seeded_violations(tmp_path):
    """One seeded violation per rule -> `python -m mxnet_tpu.analysis`
    exits 1 and names every rule (the acceptance-criteria contract for
    make lint-graft, minus the subprocess import cost x5)."""
    from mxnet_tpu.analysis.__main__ import main
    seeds = {"thread-safety": BAD_THREAD_SAFETY,
             "host-sync": BAD_HOST_SYNC,
             "atomic-write": BAD_ATOMIC_WRITE,
             "env-sync": BAD_ENV_SYNC,
             "metrics-hygiene": BAD_METRICS,
             "memory-hygiene": BAD_MEMORY,
             "use-after-donate": BAD_USE_AFTER_DONATE,
             "retrace-hazard": BAD_RETRACE,
             "gate-hygiene": BAD_GATE}
    assert set(seeds) == set(ALL_RULES)
    for i, (rule, src) in enumerate(seeds.items()):
        p = tmp_path / f"seed_{i}.py"
        p.write_text(textwrap.dedent(src))
        rc = main(["--rules", rule, str(p)])
        assert rc == 1, f"rule {rule} did not gate"
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    assert main([str(clean)]) == 0


# -- sanitizer: lock-order graph ---------------------------------------------

def test_factories_plain_when_disabled():
    assert san.ENABLED is False  # MXNET_SANITIZE defaults off
    assert type(san.make_lock("t")) is type(threading.Lock())
    assert isinstance(san.make_condition("t"), threading.Condition)


def test_lock_order_cycle_detected(sanitizer):
    a = san.make_lock("test.A")
    b = san.make_lock("test.B")
    with a:
        with b:
            pass          # establishes A -> B
    with pytest.raises(san.LockOrderError, match="cycle"):
        with b:
            with a:       # B -> A closes the cycle
                pass
    kinds = [v["kind"] for v in san.violations()]
    assert "cycle" in kinds
    assert ("test.A", "test.B") in san.lock_graph()


def test_consistent_order_is_clean(sanitizer):
    a = san.make_lock("test2.A")
    b = san.make_lock("test2.B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert san.violations() == []


def test_nonreentrant_reentry_detected(sanitizer):
    l = san.make_lock("test.reentry")
    with pytest.raises(san.LockOrderError, match="re-acquired"):
        with l:
            with l:
                pass
    assert [v["kind"] for v in san.violations()] == ["reentry"]


def test_rlock_reentry_is_legal(sanitizer):
    l = san.make_rlock("test.rlock")
    with l:
        with l:
            pass
    assert san.violations() == []


def test_tracked_condition_wait_notify(sanitizer):
    cv = san.make_condition("test.cv", reentrant=True)
    hits = []

    def waiter():
        with cv:
            while not hits:
                cv.wait(timeout=5)
            hits.append("woken")

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cv:
        hits.append("signal")
        cv.notify_all()
    t.join(timeout=5)
    assert not t.is_alive() and hits == ["signal", "woken"]
    assert san.violations() == []


def test_violation_metrics_and_snapshot(sanitizer):
    base = m.ANALYSIS_LOCK_VIOLATIONS.value
    a, b = san.make_lock("m.A"), san.make_lock("m.B")
    with a:
        with b:
            pass
    try:
        with b:
            with a:
                pass
    except san.LockOrderError:
        pass
    assert m.ANALYSIS_LOCK_VIOLATIONS.value == base + 1
    snap = m.snapshot()["analysis"]
    assert snap["enabled"] is True
    assert snap["cycles"] >= 1
    assert snap["lock_edges"] >= 1


# -- sanitizer: no_sync regions ----------------------------------------------

def test_no_sync_raises_on_asnumpy(sanitizer):
    x = mx.nd.array(np.ones((2, 2), np.float32))
    with pytest.raises(san.SyncViolation, match="asnumpy"):
        with analysis.no_sync("test-region"):
            x.asnumpy()
    assert [v["kind"] for v in san.violations()] == ["sync"]
    # outside the region syncs are fine even with the sanitizer on
    assert x.asnumpy().shape == (2, 2)


def test_no_sync_covers_engine_waits(sanitizer):
    x = mx.nd.array(np.ones((2,), np.float32))
    with pytest.raises(san.SyncViolation):
        with analysis.no_sync():
            x.wait_to_read()


def test_no_sync_nested_labels(sanitizer):
    """Exiting an inner region restores the OUTER region's label, so a
    later violation is attributed to the region actually in force."""
    x = mx.nd.array(np.ones((2,), np.float32))
    with analysis.no_sync("outer"):
        with analysis.no_sync("inner"):
            pass
        with pytest.raises(san.SyncViolation, match="'outer'"):
            x.asnumpy()


def test_no_sync_noop_when_disabled():
    assert san.ENABLED is False
    x = mx.nd.array(np.ones((2,), np.float32))
    with analysis.no_sync():
        assert x.asnumpy().sum() == 2.0   # no raise: region unarmed


# -- the PR 5-class regression: SIGTERM re-entry into CheckpointManager ------

def _mgr_state():
    return {"w": np.arange(8, dtype=np.float32)}


def test_checkpoint_lock_is_signal_reentrant(tmp_path, sanitizer):
    """The shipped fix: CheckpointManager._lock is an RLock-backed
    condition, so an emergency save that re-enters a _lock critical
    section on the SAME thread (exactly what a SIGTERM handler does
    when the signal lands mid-save/wait) completes instead of
    deadlocking.  Run under the sanitizer: zero violations."""
    from mxnet_tpu import checkpoint
    mgr = checkpoint.CheckpointManager(str(tmp_path), async_save=True)
    done = []

    def emergency_while_lock_held():
        # simulate the handler firing between bytecodes of a _lock
        # critical section: the outer frame holds _lock, the "handler"
        # runs the full synchronous-save path on the same thread
        with mgr._lock:
            mgr.save(7, _mgr_state(), block=True,
                     meta={"emergency": "test"})
            mgr.wait(timeout=30)
        done.append(True)

    t = threading.Thread(target=emergency_while_lock_held, daemon=True)
    t.start()
    t.join(timeout=20)
    assert done, "emergency save deadlocked while holding _lock " \
                 "(the pre-fix plain-Condition behavior)"
    assert mgr.latest_step() == 7
    assert [v for v in san.violations()
            if v["kind"] in ("reentry", "cycle")] == []
    mgr.close()


def test_sanitizer_catches_plain_condition_hazard(tmp_path, sanitizer):
    """Pin #1 on the hazard: with the pre-fix lock shape (a
    NON-reentrant condition), the same handler path is a guaranteed
    same-thread deadlock — the sanitizer raises typed instead of
    hanging the SIGTERM grace window."""
    from mxnet_tpu import checkpoint
    mgr = checkpoint.CheckpointManager(str(tmp_path), async_save=True)
    mgr._lock = san.make_condition("test.ckpt.plain", reentrant=False)
    with pytest.raises(san.LockOrderError, match="re-acquired"):
        with mgr._lock:
            mgr._raise_pending_error()   # handler path re-enters _lock
    assert "reentry" in [v["kind"] for v in san.violations()]


def test_sanitizer_catches_seq_abba_hazard(tmp_path, sanitizer):
    """Pin #2: the cross-thread half of the hazard.  Pre-fix,
    _next_seq() took _lock while the writer held _write_lock
    (write→queue), while the SIGTERM emergency save acquires
    _write_lock with _lock possibly held on the main thread
    (queue→write) — an ABBA deadlock between the handler and an
    in-flight background write.  Reconstructing the old _next_seq
    shape must trip the lock-order cycle detector; the shipped
    lock-free counter (and the drill test above) stays cycle-free."""
    from mxnet_tpu import checkpoint
    mgr = checkpoint.CheckpointManager(str(tmp_path), async_save=True)

    def old_next_seq():
        with mgr._lock:          # the pre-fix implementation
            return 1

    # writer-thread shape: seq allocation under the held write lock
    with mgr._write_lock:
        old_next_seq()           # edge: write -> queue
    # handler shape: emergency save while the signal interrupted a
    # _lock critical section
    with pytest.raises(san.LockOrderError, match="cycle"):
        with mgr._lock:
            with mgr._write_lock:   # edge: queue -> write = cycle
                pass
    assert "cycle" in [v["kind"] for v in san.violations()]


def test_emergency_save_with_inflight_async_write(tmp_path, sanitizer):
    """End-to-end on the fixed code: a SIGTERM-style emergency save
    (inside a _lock critical section) completes while the background
    writer has queued work — the exact interleaving the pre-fix shape
    could deadlock — and the sanitizer observes zero cycles."""
    from mxnet_tpu import checkpoint
    slow = {"calls": 0}

    def slow_writes(step, attempt):
        slow["calls"] += 1
        time.sleep(0.05)         # keep the writer busy in _write_lock

    mgr = checkpoint.CheckpointManager(str(tmp_path), async_save=True,
                                       fault_hook=slow_writes)
    for step in range(3):
        mgr.save(step, _mgr_state())
    done = []

    def handler():
        with mgr._lock:          # signal landed inside a _lock section
            mgr.save(99, _mgr_state(), block=True,
                     meta={"emergency": "sigterm"})
        mgr.wait(timeout=30)
        done.append(True)

    t = threading.Thread(target=handler, daemon=True)
    t.start()
    t.join(timeout=30)
    assert done, "emergency save deadlocked against the background writer"
    assert mgr.latest_step() == 99
    assert [v for v in san.violations() if v["kind"] == "cycle"] == []
    mgr.close()


# -- donated-buffer poisoning (the ISSUE 15 runtime twin) --------------------

def test_poison_donated_raises_typed_and_set_data_clears(sanitizer):
    x = mx.nd.array(np.ones((2, 2), np.float32))
    n = san.poison_donated("test_dispatch", x)
    assert n == 1
    with pytest.raises(analysis.DonatedBufferError, match="test_dispatch"):
        x.asnumpy()
    with pytest.raises(analysis.DonatedBufferError):
        _ = x.shape
    # repr stays safe for logs/debuggers
    assert "donated buffer" in repr(x._data)
    # the restore path (_set_data) revives the wrapper — exactly where
    # the real buffer would revive
    import jax.numpy as jnp
    x._set_data(jnp.zeros((2, 2), jnp.float32))
    assert x.asnumpy().sum() == 0.0
    assert any(v["kind"] == "donated" for v in san.violations())
    assert san.state()["donated_poisoned"] >= 1


def test_poison_donated_recurses_and_skips_raw(sanitizer):
    a = mx.nd.array(np.ones((2,), np.float32))
    b = mx.nd.array(np.ones((2,), np.float32))
    import jax.numpy as jnp
    raw = jnp.ones((2,))
    n = san.poison_donated("s", [a, (b, None)], raw, {"k": raw})
    assert n == 2  # only the NDArray wrappers carry the sentinel


def test_poison_donated_noop_when_disabled():
    assert san.ENABLED is False
    x = mx.nd.array(np.ones((2,), np.float32))
    assert san.poison_donated("s", x) == 0
    assert x.asnumpy().sum() == 2.0


def test_poison_mapping_in_place(sanitizer):
    import jax.numpy as jnp
    padded = {"data": jnp.ones((4, 3))}
    assert san.poison_mapping("serve_dispatch", padded) == 1
    with pytest.raises(analysis.DonatedBufferError, match="serve_dispatch"):
        _ = padded["data"].shape


def test_wholestep_failed_dispatch_poisons_and_restore_revives(
        tmp_path, monkeypatch, sanitizer):
    """End-to-end drill of the PR 12 incident class: a whole-step
    dispatch fails mid-execution AFTER donation — under MXNET_SANITIZE
    the param wrappers raise typed DonatedBufferError (instead of
    jax's opaque deleted-array RuntimeError), and a
    TrainingSupervisor-style snapshot restore revives them."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.wholestep import WholeStepCompiler
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), trainer)
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (4, 6)).astype(np.float32))
    y = mx.nd.array(rs.normal(0, 1, (4, 4)).astype(np.float32))
    for _ in range(2):  # step 1 may fall back while shapes materialize
        st.step(x, y)
    assert st.active, st.fallback_reason
    # host snapshot BEFORE the failure (what a supervisor keeps)
    params = {n: p.data().asnumpy()
              for n, p in net.collect_params().items()}

    # make the NEXT dispatch fail as if XLA died mid-execution: wrap
    # every cached program to raise an execution-typed error
    upd = trainer._updaters[0]
    for key, fn in list(upd._fn_cache.items()):
        def boom(*a, _fn=fn, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        upd._fn_cache[key] = boom
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        st.step(x, y)
    # donated wrappers are poisoned: the first touch is typed and
    # names the dispatch site
    with pytest.raises(analysis.DonatedBufferError, match="whole_step"):
        for p in net.collect_params().values():
            p.data().asnumpy()
    # snapshot restore (the supervisor path: _load_init from host
    # copies) clears the poison
    for n, p in net.collect_params().items():
        p._load_init(mx.nd.array(params[n]), p.list_ctx())
    for p in net.collect_params().values():
        assert np.isfinite(p.data().asnumpy()).all()


def test_supervisor_retry_revives_poisoned_buffers(tmp_path, monkeypatch,
                                                   sanitizer):
    """The PR 12 donation-safe-retry path, re-drilled under the
    sanitizer twin: a transient device loss DURING the donated
    whole-step dispatch poisons the wrappers; the TrainingSupervisor's
    snapshot-restore-replay retry revives every one of them and the
    retried step completes — proving restore and poison clear at
    exactly the same points."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.supervisor import TrainingSupervisor
    from mxnet_tpu.gluon.wholestep import WholeStepCompiler
    from mxnet_tpu.resilience import DeviceUnavailableError
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), trainer)
    sup = TrainingSupervisor(st.step, trainer=trainer, params=net,
                             retries=2, backoff_s=0.0, stall_factor=0,
                             snapshot_steps=1)
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (4, 6)).astype(np.float32))
    y = mx.nd.array(rs.normal(0, 1, (4, 4)).astype(np.float32))
    for _ in range(2):
        sup.step(x, y)
    assert st.active, st.fallback_reason
    # next dispatch dies mid-execution (transient class) exactly once
    upd = trainer._updaters[0]
    fired = {"n": 0}
    for key, fn in list(upd._fn_cache.items()):
        def flaky(*a, _fn=fn, **k):
            if fired["n"] == 0:
                fired["n"] += 1
                raise DeviceUnavailableError("injected device loss")
            return _fn(*a, **k)
        upd._fn_cache[key] = flaky
    loss = sup.step(x, y)   # retried through snapshot restore + replay
    assert fired["n"] == 1
    assert np.isfinite(loss.asnumpy()).all()
    # the poison event was recorded, and nothing is left poisoned
    assert any(v["kind"] == "donated" for v in san.violations())
    for p in net.collect_params().values():
        assert np.isfinite(p.data().asnumpy()).all()
    sup.close()


def test_audited_paths_stay_use_after_donate_clean():
    """The ISSUE 15 satellite audit, pinned: the supervisor
    snapshot/restore path and the serving evict/readmit/device_put
    path carry no use-after-donate findings (serving never donates
    weights — only the per-request padded batch — and the supervisor
    rebuilds from host copies; if either changes, this fails before
    the opaque deleted-array error ships)."""
    got = analysis.run(
        ["use-after-donate"],
        [os.path.join(REPO_ROOT, "mxnet_tpu", "gluon", "supervisor.py"),
         os.path.join(REPO_ROOT, "mxnet_tpu", "gluon", "wholestep.py"),
         os.path.join(REPO_ROOT, "mxnet_tpu", "serving"),
         os.path.join(REPO_ROOT, "mxnet_tpu", "optimizer.py")], None)
    assert got == [], got


# -- sanitized serving drill (the chaos-subset acceptance) -------------------

def _tiny_predictor():
    from mxnet_tpu import serving, sym
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4,
                             name="fc")
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = net.infer_shape(data=(4, 3))
    params = {"arg:" + n: mx.nd.array(rs.normal(0, 0.1, s).astype("f"))
              for n, s in zip(net.list_arguments(), arg_shapes)
              if n != "data"}
    return serving.BucketedPredictor(net, params, {"data": (4, 3)})


@pytest.mark.chaos
def test_threaded_subsystems_zero_lock_cycles(tmp_path, sanitizer):
    """ISSUE 7 acceptance: the threaded serving + checkpoint subsystems,
    exercised together under MXNET_SANITIZE semantics, report ZERO
    lock-order cycles (any cycle raises inside a worker and fails the
    drill typed)."""
    from mxnet_tpu import checkpoint, serving
    pred = _tiny_predictor()
    x = np.ones((1, 3), np.float32)
    with serving.MicroBatcher(pred, max_wait_ms=1.0) as mb:
        # the subsystems really did get tracked locks (created while
        # the sanitizer fixture was enabled)
        assert isinstance(mb._pending_lock, san._TrackedLock)
        outs = [mb.submit(data=x) for _ in range(16)]
        for f in outs:
            f.result(timeout=30)
    srv = serving.ResilientServer(pred, max_wait_ms=1.0)
    try:
        srv.warmup()
        futs = [srv.submit(tenant=f"t{i % 3}", data=x)
                for i in range(24)]
        for f in futs:
            f.result(timeout=30)
        srv.readyz()
    finally:
        srv.close()
    mgr = checkpoint.CheckpointManager(str(tmp_path), async_save=True)
    for step in range(3):
        mgr.save(step, _mgr_state())
    mgr.wait()
    mgr.close()
    cycles = [v for v in san.violations() if v["kind"] == "cycle"]
    reentry = [v for v in san.violations() if v["kind"] == "reentry"]
    assert cycles == [] and reentry == [], san.violations()
    # an empty order graph is the EXPECTED healthy outcome: these
    # subsystems never nest their tracked locks (nesting is where
    # order edges — and deadlock potential — come from)
