"""Compiled-program contract auditor (mxnet_tpu.analysis.program_audit,
ISSUE 15).

Four contracts, each verified on a synthetic known-bad HLO fixture AND
(where cheap) on a real compiled program:

  1. donation → input-output aliasing, on the REAL whole-step program;
  2. AMP cast coverage (pass/fail fixtures + the real bf16 program);
  3. host-callback detection (a real ``jax.pure_callback`` program);
  4. collective-count mismatch.

Plus the audit lifecycle: contracts without HLO are skipped (strict
mode fails them), the CLI self-audit probe is clean and restores the
program registry, and the sweep+audit pair stays inside the <60s
acceptance budget.
"""
import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu.analysis import program_audit as pa
from mxnet_tpu.observability import introspect


# -- synthetic HLO fixtures ---------------------------------------------------
_HEADER_ALIAS_2 = (
    'HloModule jit_f, is_scheduled=true, input_output_alias={ {0}: '
    '(0, {}, may-alias), {1}: (3, {}, may-alias) }, '
    'entry_computation_layout={(f32[4]{0})->f32[4]{0}}\n')
_HEADER_NO_ALIAS = (
    'HloModule jit_f, is_scheduled=true, '
    'entry_computation_layout={(f32[4]{0})->f32[4]{0}}\n')

_BODY_BF16 = """\
ENTRY %main (p0: bf16[8,16]) -> bf16[8,16] {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %dot.1 = bf16[8,16]{1,0} dot(bf16[8,16]{1,0} %p0, bf16[16,16]{1,0} %c), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %dot.2 = bf16[8,16]{1,0} dot(bf16[8,16]{1,0} %dot.1, bf16[16,16]{1,0} %c), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
_BODY_F32_LEAK = """\
ENTRY %main (p0: bf16[8,16]) -> f32[8,16] {
  %p0 = bf16[8,16]{1,0} parameter(0)
  %dot.1 = bf16[8,16]{1,0} dot(bf16[8,16]{1,0} %p0, bf16[16,16]{1,0} %c), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %dot.2 = f32[8,16]{1,0} dot(f32[8,16]{1,0} %cvt, f32[16,16]{1,0} %c2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
_BODY_CALLBACK = """\
ENTRY %main (p0: f32[2,2]) -> f32[2,2] {
  %p0 = f32[2,2]{1,0} parameter(0)
  %custom-call.5 = (f32[2,2]{1,0}) custom-call(s64[] %c, f32[2,2]{1,0} %p0), custom_call_target="xla_python_cpu_callback"
  ROOT %gte = f32[2,2]{1,0} get-tuple-element((f32[2,2]{1,0}) %custom-call.5), index=0
}
"""
_BODY_COLLECTIVE = """\
ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %p0), replica_groups={}, to_apply=%sum
  ROOT %all-reduce.2 = f32[4]{0} all-reduce(f32[4]{0} %all-reduce.1), replica_groups={}, to_apply=%sum
}
"""


def _rec(hlo, **contracts):
    return {"name": "fixture", "hlo": hlo, "contracts": contracts}


# -- alias-table parsing ------------------------------------------------------

@pytest.mark.program_audit
def test_alias_table_parses_nested_braces():
    """The header nests braces ({0} output indices, {} param
    sub-indices) — the parser must count EVERY entry, not clip at the
    first inner close brace (the bug the first implementation had)."""
    assert pa.parse_alias_table(_HEADER_ALIAS_2 + _BODY_BF16) == [0, 3]
    assert pa.parse_alias_table(_HEADER_NO_ALIAS + _BODY_BF16) == []


@pytest.mark.program_audit
def test_donation_aliasing_fixture_pass_fail():
    good = _rec(_HEADER_ALIAS_2 + _BODY_BF16, donated_leaves=2,
                donate_argnums=(0,))
    assert pa.audit_program(good) == []
    bad = _rec(_HEADER_NO_ALIAS + _BODY_BF16, donated_leaves=2,
               donate_argnums=(0,))
    issues = pa.audit_program(bad)
    assert len(issues) == 1 and issues[0]["check"] == "donation-aliasing"
    assert "degraded to copy" in issues[0]["detail"]


@pytest.mark.program_audit
def test_donation_aliasing_real_jit_program():
    """End-to-end on a real compiled artifact: a donated jit program's
    HLO header carries exactly the aliases the donation asked for."""
    fn = jax.jit(lambda a, b: (a + b, b * 2), donate_argnums=(0,))
    import jax.numpy as jnp
    txt = fn.lower(jnp.ones((4, 4)), jnp.ones((4, 4))).compile().as_text()
    assert pa.parse_alias_table(txt) == [0]


# -- AMP cast coverage --------------------------------------------------------

@pytest.mark.program_audit
def test_amp_coverage_fixtures():
    ok = _rec(_HEADER_NO_ALIAS + _BODY_BF16, amp="bf16")
    assert pa.audit_program(ok) == []
    leak = _rec(_HEADER_NO_ALIAS + _BODY_F32_LEAK, amp="bf16")
    issues = pa.audit_program(leak)
    assert len(issues) == 1 and issues[0]["check"] == "amp-cast-coverage"
    assert "cast leak" in issues[0]["detail"]
    # declared allowance tolerates known-f32 ops
    waived = _rec(_HEADER_NO_ALIAS + _BODY_F32_LEAK, amp="bf16",
                  amp_f32_allowed=1)
    assert pa.audit_program(waived) == []
    cov = pa.amp_cast_coverage(_BODY_F32_LEAK, "bf16")
    assert cov == {"lp": 1, "f32": 1, "coverage": 0.5}


# -- host callbacks -----------------------------------------------------------

@pytest.mark.program_audit
def test_host_callback_fixture_and_real_program():
    clean = _rec(_HEADER_NO_ALIAS + _BODY_BF16, host_callbacks=0)
    assert pa.audit_program(clean) == []
    cb = _rec(_HEADER_NO_ALIAS + _BODY_CALLBACK, host_callbacks=0)
    issues = pa.audit_program(cb)
    assert len(issues) == 1 and issues[0]["check"] == "host-callbacks"
    # a real pure_callback program lowers to the cpu-callback
    # custom-call the detector matches
    import jax.numpy as jnp

    def host(x):
        return np.asarray(x) * 2

    def f(x):
        y = jax.pure_callback(host, jax.ShapeDtypeStruct(x.shape, x.dtype),
                              x)
        return y + 1

    txt = jax.jit(f).lower(jnp.ones((2, 2))).compile().as_text()
    assert pa.count_host_callbacks(txt) >= 1


# -- collective count ---------------------------------------------------------

@pytest.mark.program_audit
def test_collective_count_mismatch():
    match = _rec(_HEADER_NO_ALIAS + _BODY_COLLECTIVE, collectives=2)
    assert pa.audit_program(match) == []
    surprise = _rec(_HEADER_NO_ALIAS + _BODY_COLLECTIVE, collectives=0)
    issues = pa.audit_program(surprise)
    assert len(issues) == 1 and issues[0]["check"] == "collective-count"
    missing = _rec(_HEADER_NO_ALIAS + _BODY_BF16, collectives=3)
    issues = pa.audit_program(missing)
    assert len(issues) == 1 and "plan says 3" in issues[0]["detail"]


# -- lifecycle ----------------------------------------------------------------

@pytest.mark.program_audit
def test_contract_without_hlo_skips_unless_strict():
    rec = {"name": "p", "hlo": None,
           "contracts": {"donated_leaves": 1}}
    issues = pa.audit_program(rec)
    assert len(issues) == 1 and issues[0]["check"] == "hlo-missing" \
        and issues[0]["skipped"]
    lax = pa.audit_programs({"p": rec})
    assert lax["ok"] and lax["skipped"] == ["p"] and lax["checked"] == 0
    strict = pa.audit_programs({"p": rec}, strict=True)
    assert not strict["ok"] and strict["issues"]


@pytest.mark.program_audit
def test_programs_without_contracts_are_ignored():
    rec = {"name": "q", "hlo": _HEADER_NO_ALIAS + _BODY_CALLBACK,
           "contracts": None}
    rep = pa.audit_programs({"q": rec})
    assert rep["ok"] and rep["checked"] == 0 and rep["skipped"] == []


# -- the real whole-step program ----------------------------------------------

def _tiny_wholestep(monkeypatch, steps=3, amp=None):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.wholestep import WholeStepCompiler
    monkeypatch.setenv("MXNET_WHOLE_STEP", "1")
    if amp:
        monkeypatch.setenv("MXNET_AMP", amp)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(8))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9})
    st = WholeStepCompiler(net, gluon.loss.L2Loss(), trainer)
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (4, 8)).astype(np.float32))
    y = mx.nd.array(rs.normal(0, 1, (4, 8)).astype(np.float32))
    for _ in range(steps):
        st.step(x, y)
    return st


@pytest.mark.program_audit
@pytest.mark.introspect
def test_whole_step_donation_aliasing_real(monkeypatch, program_audit):
    """The acceptance pin: on the real whole-step program, EVERY
    donated leaf (params + momentum states + any aux) shows up in the
    lowered program's input_output_alias table."""
    introspect.reset()
    st = _tiny_wholestep(monkeypatch)
    assert st.active, st.fallback_reason
    rec = introspect.programs()["whole_step"]
    leaves = rec["contracts"]["donated_leaves"]
    assert leaves >= 8  # 4 params + 4 momentum states
    aliased = program_audit("whole_step", min_aliased=leaves)
    assert len(aliased) >= leaves
    report = pa.audit_programs(strict=False)
    assert report["ok"], report["issues"]
    assert report["checked"] >= 1


@pytest.mark.program_audit
@pytest.mark.introspect
def test_whole_step_amp_bf16_cast_coverage_real(monkeypatch,
                                                program_audit):
    """MXNET_AMP=bf16: the captured whole-step HLO must contain zero
    f32 dot/conv ops — autocast covered forward AND backward matmuls."""
    introspect.reset()
    st = _tiny_wholestep(monkeypatch, amp="bf16")
    assert st.active, st.fallback_reason
    rec = introspect.programs()["whole_step"]
    assert rec["contracts"]["amp"] == "bf16"
    program_audit("whole_step")
    cov = pa.amp_cast_coverage(rec["hlo"], "bf16")
    assert cov["f32"] == 0 and cov["lp"] >= 2, cov


@pytest.mark.program_audit
@pytest.mark.introspect
def test_program_audit_fixture_does_not_depend_on_earlier_tests(
        monkeypatch, request):
    """Whatever an earlier test of the process left in introspect's knobs
    (here: capture off, a 123-byte cap, as tests/test_introspect.py used
    to leave), the fixture audits the whole program of the test that
    asked for it, and puts the knobs back as it found them."""
    monkeypatch.setattr(introspect, "HLO", False)
    monkeypatch.setattr(introspect, "HLO_CAP_BYTES", 123)
    check = request.getfixturevalue("program_audit")
    introspect.reset()
    st = _tiny_wholestep(monkeypatch)
    assert st.active, st.fallback_reason
    rec = introspect.programs()["whole_step"]
    assert len(rec["hlo"]) > 123 and not rec["hlo_truncated"]
    assert len(check("whole_step")) >= rec["contracts"]["donated_leaves"]


@pytest.mark.program_audit
def test_truncated_hlo_is_skipped_not_misread():
    """A program cut at the size cap cannot be audited: one skipped
    ``hlo-truncated`` issue (a failure under strict), never a verdict
    read from the stub."""
    rec = {"name": "cut", "hlo": _HEADER_NO_ALIAS[:40],
           "hlo_truncated": True,
           "contracts": {"donated_leaves": 4, "host_callbacks": 0}}
    (issue,) = pa.audit_program(rec)
    assert issue["check"] == "hlo-truncated" and issue["skipped"]
    lax_ = pa.audit_programs({"cut": rec})
    assert lax_["ok"] and lax_["skipped"] == ["cut"]
    strict = pa.audit_programs({"cut": rec}, strict=True)
    assert not strict["ok"] and strict["issues"] == [issue]


# -- CLI self-audit -----------------------------------------------------------

@pytest.mark.program_audit
def test_self_audit_clean_and_restores_registry():
    """The --audit-programs probe: builds its own whole-step program,
    audits strict, reports clean — and leaves the host process's
    program registry exactly as it found it."""
    introspect.reset()
    introspect.note_program("marker_prog")
    before = sorted(introspect.programs())
    report = pa.self_audit()
    assert report["ok"], report["issues"]
    assert report["checked"] >= 1
    assert "whole_step" in report["programs"]
    assert sorted(introspect.programs()) == before


@pytest.mark.program_audit
@pytest.mark.analysis
def test_cli_audit_mode_exits_zero():
    """`python -m mxnet_tpu.analysis --audit-only` in-process: the
    lint-graft acceptance leg, minus the subprocess import cost.  Also
    the <60s budget half that rides the audit (the sweep half lives in
    test_analysis.py)."""
    import time
    from mxnet_tpu.analysis.__main__ import main
    t0 = time.perf_counter()
    assert main(["--audit-only"]) == 0
    assert time.perf_counter() - t0 < 30.0
