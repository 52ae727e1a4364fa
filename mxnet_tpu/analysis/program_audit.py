"""Compiled-program contract auditor (ISSUE 15).

PR 10 collapsed training into ONE donated XLA program; PR 13 taught the
repo to capture each compiled program's cost/memory/HLO through
``note_program``.  This module closes the loop the TPU-MLIR line argues
for (arxiv 2210.15016): verify the LOWERED artifact against the
contract the call site declared, instead of trusting that the compiler
did what the python-side flags asked.  The four contracts, each born
from a real incident class:

  * **donation → aliasing** — ``donate_argnums`` is a *request*; only
    the HLO header's ``input_output_alias`` table proves the buffers
    really alias (a donation that silently degraded to copy doubles
    the model's HBM footprint — the PR 14 transient-copy class, and
    the premise of every donation-safety rule in checkers.py);
  * **AMP cast coverage** — an ``MXNET_AMP=bf16|fp16`` program must
    contain no f32 ``dot``/``convolution`` (a cast leak silently trains
    full-precision while reporting AMP — no error, wrong perf);
  * **host callbacks** — a whole-step program must contain ZERO
    ``xla_python_*_callback`` custom-calls / infeed / outfeed: one host
    callback turns the 1-dispatch step into a blocking host round trip
    per step;
  * **collective count / plan** — a replicated program must contain
    the bucketer's exact count (0 on the single-process inline reduce;
    a surprise collective means the program is waiting on a mesh
    nobody set up); a GSPMD-sharded program (ISSUE 18) instead
    declares ``mesh_axes`` + ``collective_plan`` and every sized mesh
    axis must carry at least the planned number of XLA-inserted
    collectives — verified by each collective's replica-group span —
    with donation STILL aliased under sharding.

Contracts are declared at the compile chokepoints
(``note_program(..., contracts={...})`` — wholestep, FusedUpdater) and
verified here from the opt-in captured HLO text
(``MXNET_INTROSPECT_HLO=1`` / ``introspect.configure(hlo=True)``: the
text is read when ``introspect.programs()`` is first asked for it, so the
flag must be on by then and the program's jitted function alive).  Programs without a contract are
skipped, programs with a contract but no HLO are reported as
``skipped`` (or fail under ``strict=True`` — the CI self-audit mode).

Surfaces: ``analysis.audit_programs()``, the
``python -m mxnet_tpu.analysis --audit-programs`` CLI leg (runs a tiny
whole-step workload so the audit has a real program to chew on — wired
into ``make lint-graft``), and the ``program_audit`` pytest fixture
(tests/conftest.py) that lets dispatch-count tests pin aliasing on the
same program their 1-dispatch gate measures.
"""
from __future__ import annotations

import re
import time
from typing import Dict, List, Optional

__all__ = ["audit_programs", "audit_program", "parse_alias_table",
           "count_host_callbacks", "count_collectives",
           "collective_groups", "amp_cast_coverage", "self_audit"]

# the HLO module header carries the alias table:
#   input_output_alias={ {0}: (0, {}, may-alias), {1}: (3, {}, ...) }
# NESTED braces ({0} output indices, {} param sub-indices) rule out a
# regex over the table — the extent is found by brace counting
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}:\s*\((\d+)")

# instruction shape shared with introspect's flops parser
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(.+?)\s+"
                       r"([\w\-]+)\(")

_CALLBACK_TARGETS = ("xla_python_cpu_callback", "xla_python_gpu_callback",
                     "xla_ffi_python_cpu_callback",
                     "xla_ffi_python_gpu_callback", "tf_host_callback")
_HOST_OPS = frozenset({"infeed", "outfeed", "send", "recv"})

_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-reduce-start", "all-gather", "all-gather-start",
    "reduce-scatter", "all-to-all", "collective-permute",
    "collective-permute-start",
})


def parse_alias_table(hlo: str) -> List[int]:
    """Parameter numbers that alias an output, from the module header.
    The header is line 1 of ``as_text()`` so HLO truncation
    (HLO_CAP_BYTES) never loses it."""
    head = hlo.split("\n", 1)[0]
    marker = "input_output_alias={"
    idx = head.find(marker)
    if idx < 0:
        return []
    start = idx + len(marker)
    depth, i = 1, start
    while i < len(head) and depth:
        if head[i] == "{":
            depth += 1
        elif head[i] == "}":
            depth -= 1
        i += 1
    return [int(g) for g in _ALIAS_ENTRY_RE.findall(head[start:i - 1])]


def _instructions(hlo: str):
    for line in hlo.splitlines():
        m = _INSTR_RE.match(line)
        if m is not None:
            yield line, m.group(1), m.group(2)


def count_host_callbacks(hlo: str) -> int:
    n = 0
    for line, _t, op in _instructions(hlo):
        if op == "custom-call" and \
                any(t in line for t in _CALLBACK_TARGETS):
            n += 1
        elif op in _HOST_OPS:
            n += 1
    return n


def count_collectives(hlo: str) -> int:
    return sum(1 for _l, _t, op in _instructions(hlo)
               if op in _COLLECTIVE_OPS)


# iota-form replica groups: `replica_groups=[G,S]<=[...]` — shape is
# [num_groups, group_size], so the span is the SECOND dimension
_RG_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def collective_groups(hlo: str) -> List[Optional[int]]:
    """One entry per collective instruction: the replica-group SPAN
    (participants per group), or None when the attribute is absent or
    empty — both mean every device participates.  Handles the explicit
    form ``replica_groups={{0,2},{1,3}}`` (span = first subgroup's
    element count; GSPMD emits equal-sized groups) and the iota form
    ``replica_groups=[G,S]<=[...]`` (span = S)."""
    out: List[Optional[int]] = []
    for line, _t, op in _instructions(hlo):
        if op not in _COLLECTIVE_OPS:
            continue
        m = _RG_IOTA_RE.search(line)
        if m is not None:
            out.append(int(m.group(2)))
            continue
        marker = "replica_groups={"
        idx = line.find(marker)
        if idx < 0:
            out.append(None)
            continue
        start = idx + len(marker)
        depth, i = 1, start
        while i < len(line) and depth:
            if line[i] == "{":
                depth += 1
            elif line[i] == "}":
                depth -= 1
            i += 1
        body = line[start:i - 1].strip()
        if not body:
            out.append(None)
            continue
        first = body.lstrip("{").split("}", 1)[0]
        ids = [s for s in first.split(",") if s.strip()]
        out.append(len(ids) if ids else None)
    return out


# computation header: `%fused_computation.3 (p: f32[4]) -> bf16[4] {`
# or `ENTRY %main.90 (...) -> (...) {`
_COMP_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%([\w.\-]+)\s*\(.*->.*\{")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")


def amp_cast_coverage(hlo: str, lp: str) -> dict:
    """{"lp": n, "f32": n, "coverage": 0..1} over dot/convolution
    instructions.  ``lp`` is the declared low-precision dtype
    ("bf16"/"fp16" -> HLO "bf16"/"f16").

    A dot/conv counts as CAST-COVERED when its result type is the lp
    dtype (the TPU shape: the MXU really runs low-precision), or when
    an operand carries the lp rounding — defined with an lp type, by a
    ``convert`` touching lp, or by a fusion whose called computation
    contains lp values.  The fusion hop matters on CPU: XLA legalizes
    a bf16 dot as convert(f32→bf16→f32) fusions feeding an f32 dot, so
    the OPTIMIZED text shows f32 dots whose numerics are nonetheless
    bf16-rounded — the contract holds; only a dot with NO lp anywhere
    upstream of its line is a genuine cast leak."""
    want = {"bf16": "bf16", "fp16": "f16"}[lp]
    # computation name -> does its body mention the lp dtype at all
    comp_has_lp: Dict[str, bool] = {}
    cur: Optional[str] = None
    # instruction name -> its defining line (all computations pooled:
    # instruction names are module-unique in HLO text)
    def_line: Dict[str, str] = {}
    for line in hlo.splitlines():
        m = _COMP_HEADER_RE.match(line)
        if m is not None:
            cur = m.group(1)
            comp_has_lp.setdefault(cur, False)
        if cur is not None and f"{want}[" in line:
            comp_has_lp[cur] = True
        dm = _DEF_RE.match(line)
        if dm is not None:
            def_line[dm.group(1)] = line

    def covered(line: str, opcode: str, type_str: str) -> bool:
        if type_str.lstrip().startswith(want):
            return True
        seg = line.split(opcode + "(", 1)
        if len(seg) < 2:
            return False
        body = seg[1].split(" metadata=")[0]
        for op_name in _OPERAND_NAME_RE.findall(body):
            dl = def_line.get(op_name)
            if dl is None:
                continue
            if f"{want}[" in dl:
                return True
            cm = _CALLS_RE.search(dl)
            if cm is not None and comp_has_lp.get(cm.group(1)):
                return True
        return False

    n_lp = n_f32 = 0
    for line, type_str, op in _instructions(hlo):
        if op not in ("dot", "convolution"):
            continue
        if covered(line, op, type_str):
            n_lp += 1
        else:
            n_f32 += 1
    total = n_lp + n_f32
    return {"lp": n_lp, "f32": n_f32,
            "coverage": (n_lp / total) if total else 1.0}


def audit_program(rec: dict) -> List[dict]:
    """Verify one ``introspect.programs()`` record against its declared
    contracts.  Returns issue dicts ``{program, check, ok, detail}`` —
    one per failed check (empty = clean).  A record without contracts
    yields nothing; a contract without captured HLO, or with HLO cut at
    the size cap, yields one ``hlo-missing`` / ``hlo-truncated`` issue
    marked ``skipped=True`` so callers can decide strictness."""
    contracts = rec.get("contracts")
    if not contracts:
        return []
    name = rec.get("name", "?")
    hlo = rec.get("hlo")
    if not hlo:
        return [{"program": name, "check": "hlo-missing", "ok": False,
                 "skipped": True,
                 "detail": "contract declared but no HLO captured — "
                           "set MXNET_INTROSPECT_HLO=1 (or "
                           "introspect.configure(hlo=True)) before the "
                           "records are read"}]
    if rec.get("hlo_truncated"):
        # half a program proves nothing: its alias table, casts and
        # collectives may all lie past the cut, and reading the stub
        # reports donation "degraded to copy" of a program that is fine
        return [{"program": name, "check": "hlo-truncated", "ok": False,
                 "skipped": True,
                 "detail": "captured HLO was cut at the size cap — raise "
                           "introspect.configure(hlo_cap_bytes=...) "
                           "before the records are read"}]
    issues: List[dict] = []

    leaves = contracts.get("donated_leaves")
    if leaves is not None:
        aliased = parse_alias_table(hlo)
        if leaves > 0 and len(aliased) < leaves:
            issues.append({
                "program": name, "check": "donation-aliasing",
                "ok": False,
                "detail": f"{leaves} leaves were donated "
                          f"(donate_argnums="
                          f"{contracts.get('donate_argnums')}) but only "
                          f"{len(aliased)} parameter(s) alias an output "
                          f"in the lowered program — the difference is "
                          f"a silent extra copy of those buffers "
                          f"(donation degraded to copy)"})

    amp = contracts.get("amp")
    if amp in ("bf16", "fp16"):
        cov = amp_cast_coverage(hlo, amp)
        allowed = contracts.get("amp_f32_allowed", 0)
        if cov["f32"] > allowed:
            issues.append({
                "program": name, "check": "amp-cast-coverage",
                "ok": False,
                "detail": f"MXNET_AMP={amp} program contains "
                          f"{cov['f32']} f32 dot/conv op(s) "
                          f"(coverage {cov['coverage']:.2%}, allowed "
                          f"f32 count {allowed}) — a cast leak trains "
                          f"full precision while reporting AMP"})

    want_cb = contracts.get("host_callbacks")
    if want_cb is not None:
        got = count_host_callbacks(hlo)
        if got != want_cb:
            issues.append({
                "program": name, "check": "host-callbacks", "ok": False,
                "detail": f"{got} host callback op(s) in the lowered "
                          f"program, contract says {want_cb} — each one "
                          f"is a blocking host round trip inside the "
                          f"compiled step"})

    want_coll = contracts.get("collectives")
    if want_coll is not None:
        got = count_collectives(hlo)
        if got != want_coll:
            issues.append({
                "program": name, "check": "collective-count",
                "ok": False,
                "detail": f"{got} collective op(s) in the lowered "
                          f"program, the bucketer's plan says "
                          f"{want_coll} — the program's communication "
                          f"does not match what was planned"})

    plan = contracts.get("collective_plan")
    if plan:
        # the sharded-program contract: each sized mesh axis must carry
        # at least the planned number of GSPMD collectives.  A
        # collective is credited to an axis when its replica-group span
        # equals the axis size, or when it spans the whole mesh (a
        # fused cross-axis reduce serves every axis it covers); an
        # absent/empty replica_groups spans everything too.
        axes = contracts.get("mesh_axes") or {}
        spans = collective_groups(hlo)
        total = 1
        for v in axes.values():
            total *= int(v)
        for axis, want_min in sorted(plan.items()):
            asize = int(axes.get(axis, 0))
            got = sum(1 for s in spans
                      if s is None or s == asize
                      or (total > 1 and s == total))
            if got < int(want_min):
                issues.append({
                    "program": name, "check": "collective-plan",
                    "ok": False,
                    "detail": f"mesh axis {axis!r} (size {asize}) "
                              f"carries {got} collective(s) in the "
                              f"lowered program, the GSPMD plan "
                              f"requires >= {want_min} — XLA did not "
                              f"insert the cross-shard communication "
                              f"this axis needs (spans seen: "
                              f"{sorted({x for x in spans if x}) or '[]'}"
                              f", {len(spans)} total)"})
    return issues


def audit_programs(programs: Optional[Dict[str, dict]] = None,
                   strict: bool = False) -> dict:
    """Audit every captured program with a declared contract.

    Returns ``{"checked": n, "skipped": [names], "issues": [...],
    "ok": bool, "seconds": s}``.  ``skipped`` are contracts that could
    not be verified (no HLO captured); under ``strict=True`` they count
    as failures — the CI self-audit runs strict because IT controls HLO
    capture."""
    t0 = time.perf_counter()
    if programs is None:
        from ..observability import introspect as _introspect
        programs = _introspect.programs()
    issues: List[dict] = []
    skipped: List[str] = []
    checked = 0
    for name, rec in sorted(programs.items()):
        if not rec.get("contracts"):
            continue
        rec = dict(rec, name=rec.get("name", name))
        out = audit_program(rec)
        if any(i.get("skipped") for i in out):
            skipped.append(name)
            if strict:
                issues.extend(out)
            continue
        checked += 1
        issues.extend(out)
    return {"checked": checked, "skipped": skipped, "issues": issues,
            "ok": not issues,
            "seconds": round(time.perf_counter() - t0, 3)}


# -- the CLI self-audit workload ----------------------------------------------
def self_audit(steps: int = 2, amp: Optional[str] = None) -> dict:
    """Build a tiny whole-step training program WITH HLO capture and
    audit it — the ``--audit-programs`` CLI leg.  Runs entirely
    in-process on whatever backend ``jax`` resolves (the Makefile pins
    cpu); restores every knob it touches.  Returns the ``audit_programs(strict=True)`` report plus
    ``{"programs": [names audited]}``."""
    import os
    import numpy as _np

    from ..observability import introspect as _introspect

    env_prev = {k: os.environ.get(k)
                for k in ("MXNET_WHOLE_STEP", "MXNET_AMP")}
    os.environ["MXNET_WHOLE_STEP"] = "1"
    if amp:
        os.environ["MXNET_AMP"] = amp
    else:
        os.environ.pop("MXNET_AMP", None)
    hlo_prev = _introspect.HLO
    enabled_prev = _introspect.ENABLED
    # the probe notes its program under the canonical "whole_step" name
    # — snapshot the registry so a host process's own captured programs
    # (a live trainer's) come back untouched
    with _introspect._lock:
        saved_programs = {k: dict(v)
                          for k, v in _introspect._programs.items()}
    _introspect.enable()
    _introspect.configure(hlo=True)
    try:
        from .. import gluon, nd
        from ..gluon.wholestep import WholeStepCompiler

        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"),
                gluon.nn.Dense(8))
        net.initialize()
        loss_fn = gluon.loss.L2Loss()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.01, "momentum": 0.9})
        stepper = WholeStepCompiler(net, loss_fn, trainer)
        rs = _np.random.RandomState(0)
        x = nd.array(rs.normal(0, 1, (4, 8)).astype(_np.float32))
        y = nd.array(rs.normal(0, 1, (4, 8)).astype(_np.float32))
        for _ in range(max(1, steps)):
            stepper.step(x, y)
        if not stepper.active:
            return {"checked": 0, "skipped": [], "ok": False,
                    "seconds": 0.0, "programs": [],
                    "issues": [{"program": "whole_step",
                                "check": "build", "ok": False,
                                "detail": "whole-step probe fell back: "
                                          f"{stepper.fallback_reason}"}]}
        progs = {k: v for k, v in _introspect.programs().items()
                 if v.get("contracts")}
        report = audit_programs(progs, strict=True)
        report["programs"] = sorted(progs)
        return report
    finally:
        _introspect.configure(hlo=hlo_prev)
        if not enabled_prev:
            _introspect.disable()
        with _introspect._lock:
            _introspect._programs.clear()
            _introspect._programs.update(saved_programs)
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
