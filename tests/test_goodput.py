"""Goodput accounting + crash-durable run journal + SLO burn (ISSUE 16).

The acceptance invariants this file pins:

  * a 50-step supervised chaos run (injected transient step faults,
    data-wait stalls, one blocking checkpoint save) attributes >= 95%
    of its wall-clock — ``retry_replay``, ``data_wait`` and
    ``checkpoint_block`` all nonzero, ``unattributed`` the honesty row;
  * the journal survives SIGKILL (durable entries fsync'd, torn tails
    tolerated) and a restarted process resumes the SAME run id — the
    offline reporter renders the dead run from disk alone;
  * ``MXNET_GOODPUT=0`` / unset ``MXNET_RUN_DIR`` reduce every hook to
    one boolean test, pinned both in-process and at import in a
    subprocess;
  * ``snapshot()["goodput"]`` carries the schema dashboards consume;
  * a declared serve-p99 SLO breach flips ``readyz()``'s ``slo_burn``
    check and counts ``mxnet_slo_burn_total{slo=...}``;
  * the graft-lint metrics-hygiene rule rejects dynamically built
    ``journal.emit`` / ``goodput.attribute`` names.
"""
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis, checkpoint as ck, faultinject as fi
from mxnet_tpu.gluon.supervisor import TrainingSupervisor
from mxnet_tpu.observability import flight, goodput, journal
from mxnet_tpu.observability import metrics as M
from mxnet_tpu.observability import report as rpt
from mxnet_tpu.serving import ResilientServer
from mxnet_tpu import serving, sym

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_goodput():
    """Each test sees a zeroed ledger, default SLO config, an enabled
    goodput gate, and a DISABLED journal (tests that want one point it
    at their tmp_path)."""
    was = goodput.ENABLED
    slo = (goodput.SLO_GOODPUT_PCT, goodput.SLO_SERVE_P99_MS,
           goodput.SLO_BURN_MIN_S, goodput.SLO_MIN_SAMPLES,
           goodput.SLO_MIN_RUN_S)
    goodput.enable()
    goodput.reset()
    journal.configure(run_dir="")
    M.enable()
    M.REGISTRY.reset()
    yield
    goodput.reset()
    goodput.configure(slo_goodput_pct=slo[0], slo_serve_p99_ms=slo[1],
                      slo_burn_min_s=slo[2], slo_min_samples=slo[3],
                      slo_min_run_s=slo[4])
    (goodput.enable if was else goodput.disable)()
    journal.configure(run_dir="")
    M.REGISTRY.reset()


# -- ledger unit behavior ----------------------------------------------------

def test_span_classification_and_report():
    goodput.start()
    goodput.observe_span("mx.trainer.step", 2.0)
    goodput.observe_span("prefetch_wait", 0.5)
    goodput.observe_span("checkpoint_block", 0.25)
    goodput.observe_span("not_a_unit_of_work", 9.0)  # ignored
    rep = goodput.report()
    assert rep["enabled"] is True
    assert rep["classes"]["compute"] == {"seconds": 2.0, "events": 1}
    assert rep["classes"]["data_wait"]["seconds"] == 0.5
    assert rep["classes"]["checkpoint_block"]["seconds"] == 0.25
    assert "not_a_unit_of_work" not in rep["classes"]
    assert rep["attributed_s"] == pytest.approx(2.75)
    # the instrumented burst outran the coarse wall clock: clamped, so
    # goodput% stays a fraction of ATTRIBUTED time, never > 100
    assert rep["wall_s"] >= rep["attributed_s"]
    assert 0.0 < rep["goodput_pct"] <= 100.0
    assert goodput.ratio() == pytest.approx(rep["goodput_pct"] / 100.0)


def test_unknown_reason_folds_into_unattributed():
    goodput.start()
    goodput.attribute("definitely_not_a_class", 1.0)
    rep = goodput.report()
    assert "definitely_not_a_class" not in rep["classes"]
    assert rep["classes"]["unattributed"]["seconds"] == 1.0


def test_replay_scope_suppresses_double_counted_compute():
    goodput.start()
    with goodput.replay_scope("retry_replay"):
        # replayed steps re-run real math; their spans must NOT book
        # as goodput — the scope owns this wall-clock
        goodput.observe_span("mx.trainer.step", 5.0)
        goodput.observe_span("prefetch_wait", 0.125)
        time.sleep(0.01)
    rep = goodput.report()
    assert "compute" not in rep["classes"]
    assert rep["classes"]["data_wait"]["seconds"] == 0.125  # not compute
    assert rep["classes"]["retry_replay"]["seconds"] >= 0.01
    # scope closed: compute books again
    goodput.observe_span("mx.trainer.step", 1.0)
    assert goodput.report()["classes"]["compute"]["seconds"] == 1.0


def test_badput_metrics_exported():
    goodput.attribute("data_wait", 1.25)
    goodput.attribute("stall", 0.5)
    assert M.BADPUT_SECONDS.get(reason="data_wait") == pytest.approx(1.25)
    assert M.BADPUT_SECONDS.get(reason="stall") == pytest.approx(0.5)
    text = mx.observability.render_prometheus()
    assert "mxnet_goodput_ratio" in text
    assert 'mxnet_badput_seconds_total{reason="data_wait"}' in text


def test_snapshot_goodput_schema():
    goodput.start()
    goodput.observe_span("mx.trainer.step", 1.0)
    g = mx.observability.snapshot()["goodput"]
    assert g["enabled"] is True
    for key in ("classes", "events", "wall_s", "attributed_s",
                "unattributed_s", "goodput_pct", "unattributed_pct",
                "slo", "run_id", "journal_path"):
        assert key in g, key
    assert g["run_id"] is None  # journal off in this test
    assert g["classes"]["compute"]["seconds"] == 1.0


# -- gates (the PR 1 one-boolean contract) -----------------------------------

def test_disabled_ledger_is_inert():
    goodput.disable()
    goodput.start()
    goodput.observe_span("mx.trainer.step", 1.0)
    goodput.attribute("stall", 1.0)
    goodput.note_event("recompile")
    goodput.serve_latency_sample(1e6)
    with goodput.replay_scope("rewind"):
        pass
    assert goodput.report() == {"enabled": False}
    assert goodput.ratio() == 0.0
    assert goodput.badput_totals() == {}
    assert goodput.slo_armed() is False
    assert goodput.slo_burning() is False
    goodput.enable()
    assert goodput.report()["classes"] == {}  # nothing leaked through


def test_disabled_journal_is_inert(tmp_path):
    assert journal.ENABLED is False
    assert journal.emit("milestone", step=1) is None
    assert journal.run_id() is None
    assert journal.path() is None
    journal.note_dump("/nope.json", "manual")
    journal.maybe_milestone(1, source="test")
    assert list(tmp_path.iterdir()) == []


def test_gates_hold_at_import_in_subprocess():
    """MXNET_GOODPUT=0 + unset MXNET_RUN_DIR at IMPORT: both gates are
    plain False module globals and the hooks are no-ops."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_GOODPUT="0")
    env.pop("MXNET_RUN_DIR", None)
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        from mxnet_tpu.observability import goodput, journal
        assert goodput.ENABLED is False
        assert journal.ENABLED is False
        goodput.observe_span("mx.trainer.step", 1.0)
        assert goodput.report() == {{"enabled": False}}
        assert journal.emit("milestone", step=1) is None
        print("GATES-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "GATES-OK" in out.stdout


# -- journal durability + continuity -----------------------------------------

def test_journal_run_id_continuity_in_process(tmp_path):
    d = str(tmp_path / "run")
    journal.configure(run_dir=d)
    rid1 = journal.run_id()
    assert rid1 and rid1.startswith("run-")
    journal.emit("checkpoint_save", step=3, durable=True, bytes=10)
    journal.configure(run_dir=d)  # "restart": close + reopen
    rid2 = journal.run_id()
    assert rid2 == rid1
    entries = rpt.load_journal(d)
    starts = [e for e in entries if e["event"] == "process_start"]
    assert len(starts) == 2
    assert starts[0]["resumed"] is False and starts[1]["resumed"] is True
    assert {e["run"] for e in entries} == {rid1}


def test_journal_rotation_keeps_run_id(tmp_path, monkeypatch):
    d = str(tmp_path / "run")
    monkeypatch.setattr(journal, "MAX_BYTES", 600)
    journal.configure(run_dir=d)
    rid = journal.run_id()
    for i in range(40):
        journal.emit("milestone", step=i, source="test")
    assert os.path.exists(os.path.join(d, "journal.1.jsonl"))
    entries = rpt.load_journal(d)
    assert {e["run"] for e in entries} == {rid}
    # each segment is self-describing: the fresh one re-records a header
    assert any(e["event"] == "rotated" for e in entries)
    assert journal.run_id() == rid


def test_journal_tolerates_torn_tail(tmp_path):
    d = str(tmp_path / "run")
    journal.configure(run_dir=d)
    rid = journal.run_id()
    journal.emit("checkpoint_save", step=5, durable=True)
    journal.reset()
    with open(os.path.join(d, journal.FILE_NAME), "a") as f:
        f.write('{"event": "milest')  # SIGKILL mid-write
    journal.configure(run_dir=d)
    assert journal.run_id() == rid  # resumed through the torn tail
    events = [e["event"] for e in rpt.load_journal(d)]
    assert "checkpoint_save" in events and "milest" not in str(events)


def test_milestones_embed_goodput_and_respect_cadence(tmp_path,
                                                      monkeypatch):
    journal.configure(run_dir=str(tmp_path / "run"))
    monkeypatch.setattr(journal, "MILESTONE_EVERY", 10)
    goodput.start()
    goodput.observe_span("mx.trainer.step", 2.0)
    for step in range(25):
        journal.maybe_milestone(step, source="trainer")
    entries = [e for e in rpt.load_journal(str(tmp_path / "run"))
               if e["event"] == "milestone"]
    assert [e["step"] for e in entries] == [0, 10, 20]
    assert entries[-1]["goodput_pct"] > 0
    assert entries[-1]["classes"]["compute"]["seconds"] == 2.0


def test_flight_dump_cross_references_journal(tmp_path, monkeypatch):
    run_dir = str(tmp_path / "run")
    journal.configure(run_dir=run_dir)
    monkeypatch.setenv("MXNET_FLIGHT_DIR", str(tmp_path / "dumps"))
    with flight.phase_span("mx.trainer.step", cat="step", step=1):
        time.sleep(0.001)
    dump_path = flight.dump(reason="manual")
    assert dump_path
    import json
    with open(dump_path) as f:
        meta = json.load(f)["metadata"]
    assert meta["run_id"] == journal.run_id()
    assert meta["journal_path"] == journal.path()
    dumps = [e for e in rpt.load_journal(run_dir)
             if e["event"] == "flight_dump"]
    assert dumps and dumps[-1]["dump_path"] == dump_path


# -- the chaos acceptance run ------------------------------------------------

@pytest.mark.chaos
def test_chaos_run_attributes_95_percent(tmp_path):
    """50 supervised steps with two injected transient step faults,
    injected data corruption during the prefetch wait, and one blocking
    checkpoint save: every badput class involved is nonzero and the
    unattributed slack stays <= 5% of wall-clock, or twice what the same
    steps without faults leave unattributed under the same load."""
    run_dir = str(tmp_path / "run")
    journal.configure(run_dir=run_dir)
    state = {"w": 0.0}

    def snapshot_fn():
        return {"w": np.float32(state["w"])}

    def restore_fn(snap):
        state["w"] = float(np.asarray(snap["w"]))

    def step_fn(v):
        with flight.phase_span("mx.trainer.step", cat="step"):
            fi.fire("trainer.step")
            time.sleep(0.005)
            state["w"] += v
        return state["w"]

    sup = TrainingSupervisor(step_fn, snapshot_fn=snapshot_fn,
                             restore_fn=restore_fn, snapshot_steps=5,
                             retries=2, backoff_s=0.0, stall_factor=0.0)
    mgr = ck.CheckpointManager(str(tmp_path / "ckpt"))

    def run(plan, save_at):
        goodput.reset()
        goodput.start()
        with fi.active(plan):
            for i in range(50):
                with flight.phase_span("prefetch_wait", cat="data"):
                    try:
                        fi.fire("data.batch")
                    except OSError:
                        pass  # corrupt batch: refetch (stay in the wait)
                    time.sleep(0.001)
                sup.step(1.0)
                if i == save_at:
                    mgr.save(30, {"w": np.full(4, state["w"], "f")},
                             block=True)
        return goodput.report()

    # the base is the SAME fifty steps with no fault and no save, on the
    # same machine under the same load: what it leaves unattributed is the
    # loop's own slack (a thread handoff a step; beside eleven busy
    # processes it once passed 5% of this 0.4 s run by itself), and the
    # bound below holds the chaos run to that, not to an idle machine
    base = run(fi.FaultPlan(), save_at=None)
    # occurrence windows count replay re-executions too, so the two
    # step-fault rules are spaced far enough apart that neither fires
    # inside the other's replay
    plan = (fi.FaultPlan()
            .add("trainer.step", "raise", exc=OSError, times=1, after=12)
            .add("trainer.step", "raise", exc=OSError, times=1, after=33)
            .add("data.batch", "raise", exc=OSError, times=2, after=5))
    rep = run(plan, save_at=30)
    sup.close()
    mgr.close()

    assert plan.stats()["trainer.step"] == 2
    cls = rep["classes"]
    assert cls["compute"]["seconds"] > 0.2
    # 50 successes + 2 truncated spans from the failed attempts; the
    # replayed step is SUPPRESSED (it would make this 53)
    assert cls["compute"]["events"] == 52
    assert cls["data_wait"]["seconds"] > 0
    assert cls["retry_replay"]["seconds"] > 0
    assert cls["retry_replay"]["events"] == 2
    assert cls["checkpoint_block"]["seconds"] > 0
    assert rep["unattributed_pct"] <= max(
        5.0, 2.0 * base["unattributed_pct"]), (rep, base)
    assert rep["goodput_pct"] > 50.0, rep

    # the run is reconstructible from the journal alone
    s = rpt.summarize_run(run_dir)
    assert s["event_counts"]["supervisor_retry"] == 2
    assert s["event_counts"]["checkpoint_save"] == 1
    assert s["goodput"] is not None
    text = rpt.render(s)
    assert s["run_id"] in text and "supervisor_retry" in text


_KILL_CHILD = """
import os, sys, time
sys.path.insert(0, {repo!r})
from mxnet_tpu.observability import journal
journal.emit("checkpoint_save", step=7, durable=True, bytes=123,
             seconds=0.01)
journal.emit("milestone", step=7, source="trainer")
print("RID", journal.run_id(), flush=True)
while True:
    time.sleep(0.1)
"""

_RESUME_CHILD = """
import os, sys
sys.path.insert(0, {repo!r})
from mxnet_tpu.observability import journal
journal.emit("run_resumed", step=7, durable=True, source="test")
print("RID", journal.run_id(), flush=True)
"""


@pytest.mark.chaos
def test_journal_survives_sigkill_and_resumes_run_id(tmp_path):
    """SIGKILL the process mid-run: the durable entries are on disk,
    the reporter renders the dead run, and a restarted process keeps
    the same run id."""
    d = str(tmp_path / "run")
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_RUN_DIR=d)
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILL_CHILD.format(repo=REPO)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True)
    try:
        line = proc.stdout.readline()
        assert line.startswith("RID run-"), (line, proc.stderr.read())
        rid = line.split()[1]
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        proc.kill()

    events = [e["event"] for e in rpt.load_journal(d)]
    assert "process_start" in events
    assert "checkpoint_save" in events  # durable: fsync'd before RID
    s = rpt.summarize_run(d)
    assert s["run_id"] == rid and s["incarnations"] == 1
    assert rid in rpt.render(s)

    out = subprocess.run(
        [sys.executable, "-c", _RESUME_CHILD.format(repo=REPO)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().split()[-1] == rid  # SAME run id
    s2 = rpt.summarize_run(d)
    assert s2["incarnations"] == 2 and s2["resumes"] == 1
    assert s2["downtime_s"] >= 0.0


# -- the offline reporter ----------------------------------------------------

def _fake_run(d, goodput_pct, retries):
    journal.configure(run_dir=d)
    journal.emit("checkpoint_save", step=10, durable=True, bytes=100,
                 seconds=0.01)
    journal.emit("checkpoint_save", step=20, durable=True, bytes=100,
                 seconds=0.01)
    for _ in range(retries):
        journal.emit("supervisor_retry", step=15, attempt=1,
                     error="OSError")
    journal.emit("milestone", step=20, source="trainer",
                 goodput_pct=goodput_pct,
                 classes={"compute": {"seconds": 9.0, "events": 20}})
    journal.reset()


def test_reporter_summary_render_and_diff(tmp_path, capsys):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    _fake_run(a, 91.0, retries=2)
    _fake_run(b, 97.5, retries=0)
    s = rpt.summarize_run(a)
    assert s["goodput"]["goodput_pct"] == 91.0
    assert s["checkpoint"]["saves"] == 2
    assert s["checkpoint"]["cadence_steps"] == 10
    assert s["last_step"] == 20
    assert rpt.main([a]) == 0
    assert "goodput: 91.0%" in capsys.readouterr().out
    assert rpt.main([a, "--diff", b]) == 0
    out = capsys.readouterr().out
    assert "91.0" in out and "97.5" in out
    assert rpt.main([str(tmp_path)]) == 0  # parent dir: newest run wins
    capsys.readouterr()
    assert rpt.main([str(tmp_path / "nope")]) == 2


# -- SLO burn monitors -------------------------------------------------------

def test_serve_p99_slo_burn_counts_journals_and_clears(tmp_path):
    journal.configure(run_dir=str(tmp_path / "run"))
    goodput.configure(slo_serve_p99_ms=5.0, slo_burn_min_s=0.0,
                      slo_min_samples=5)
    assert goodput.slo_armed() is True
    for _ in range(10):
        goodput.serve_latency_sample(50.0)
    assert goodput.slo_burning() is True
    assert M.SLO_BURN.get(slo="serve_p99") >= 1
    st = goodput.slo_state()["serve_p99"]
    assert st["burning"] is True and st["target_ms"] == 5.0
    burns = [e for e in rpt.load_journal(str(tmp_path / "run"))
             if e["event"] == "slo_burn"]
    assert burns and burns[0]["slo"] == "serve_p99"
    # a healthy window clears the flag — readyz reflects the live
    # window, not history (flush the whole deque with fast samples)
    for _ in range(goodput.SLO_WINDOW):
        goodput.serve_latency_sample(0.1)
    assert goodput.slo_burning() is False


def test_goodput_slo_burn():
    goodput.configure(slo_goodput_pct=99.9, slo_burn_min_s=0.0,
                      slo_min_run_s=0.0)
    goodput.start()
    goodput.attribute("stall", 1.0)  # 0% goodput
    assert goodput.slo_burning() is True
    assert M.SLO_BURN.get(slo="goodput") >= 1


def test_slo_burn_rate_limited():
    goodput.configure(slo_serve_p99_ms=5.0, slo_burn_min_s=3600.0,
                      slo_min_samples=5)
    for _ in range(50):
        goodput.serve_latency_sample(50.0)
    assert goodput.slo_burning() is True
    assert M.SLO_BURN.get(slo="serve_p99") == 1  # warned once, still burning


def test_readyz_gains_slo_burn_check_and_flips():
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=4,
                             name="fc")
    pred = serving.BucketedPredictor(net, {}, {"data": (8, 3)}).warmup()
    with ResilientServer(pred) as srv:
        # no SLO declared: the check is absent (operator opt-in)
        assert "slo_burn" not in srv.readyz()["checks"]
        goodput.configure(slo_serve_p99_ms=5.0, slo_burn_min_s=0.0,
                          slo_min_samples=5)
        for _ in range(10):
            goodput.serve_latency_sample(50.0)
        rz = srv.readyz()
        assert rz["checks"]["slo_burn"] is False
        assert rz["ready"] is False and "slo_burn" in rz["reasons"]
        assert rz["detail"]["slo"]["serve_p99"]["burning"] is True
        for _ in range(goodput.SLO_WINDOW):
            goodput.serve_latency_sample(0.1)
        rz = srv.readyz()
        assert rz["checks"]["slo_burn"] is True


# -- the lint rule (satellite 3) ---------------------------------------------

BAD_DYNAMIC_EVENT = """
from mxnet_tpu.observability import goodput, journal

def record(kind: str, dt: float):
    journal.emit(f"fault-{kind}", step=1)
    goodput.attribute("cls_" + kind, dt)
"""

GOOD_LITERAL_EVENT = """
from mxnet_tpu.observability import goodput, journal

def record(kind: str, dt: float):
    journal.emit("fault", step=1, kind=kind)
    goodput.attribute("stall", dt)
"""


def _lint(tmp_path, source, rules):
    p = tmp_path / "snippet.py"
    p.write_text(textwrap.dedent(source))
    return analysis.run(rules, [str(p)], None)


def test_metrics_hygiene_flags_dynamic_journal_and_goodput_names(
        tmp_path):
    got = _lint(tmp_path, BAD_DYNAMIC_EVENT, ["metrics-hygiene"])
    assert len(got) == 2, got
    msgs = " | ".join(f.message for f in got)
    assert "journal" in msgs and "goodput" in msgs
    assert _lint(tmp_path, GOOD_LITERAL_EVENT, ["metrics-hygiene"]) == []
