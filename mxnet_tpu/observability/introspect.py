"""Program introspection: per-layer cost attribution, MFU/roofline
telemetry, and a persisted perf-regression sentinel (ISSUE 13).

PR 10 collapsed training to ONE donated XLA dispatch — which made the
flight recorder blind *inside* the step: ``whole_step`` is a single
opaque span, and nothing could say which layer or pipeline stage the
time or FLOPs go to.  This module is the program-level half of the
observability story (TVM's measured cost models, arxiv 1802.04799;
TF's per-op attribution + utilization telemetry, arxiv 1605.08695):

  * **program registry** — ``note_program(name, compiled=...)`` /
    ``note_jit(name, fn, *args)`` capture each compiled program's
    ``cost_analysis()`` (analytical flops, bytes accessed), its
    ``CompiledMemoryStats`` (via ``memory.compiled_stats_dict`` — ONE
    uniform shape across jax versions), and what it takes to read its
    optimized HLO text LATER (the ``Lowered`` of a jit-called program,
    tied to the life of the jitted function): the text is rendered at
    the first read, never at the program's first call.  Wired at every
    compile chokepoint: Executor
    (fwd/fwd_bwd + ``memory_analysis``), ``CachedOp`` (gluon fwd/bwd),
    ``FusedUpdater.update_all``, ``WholeStepCompiler``, and the serving
    bucket precompile.  Surfaces: ``snapshot()["programs"]``,
    ``introspect.report()``.
  * **per-layer attribution** — ``symbol.graph.GraphPlan.run`` wraps
    every step in ``jax.named_scope(<node name>)`` (and the fused
    optimizer/allreduce math in literal scopes), so HLO instruction
    metadata carries layer names through forward AND backward
    (``jvp(dense0_fwd)`` / ``transpose(jvp(dense0_fwd))``).
    ``per_layer()`` parses the captured HLO with a small per-opcode
    flops model (dot/conv exact from shapes, elementwise ≈ 1/elem) and
    groups by innermost known scope — the per-layer flops table for
    the one-dispatch whole-step program; handed the measured seconds
    of a device trace by instruction it reports ``device_ms`` per layer.
  * **device-time names** — ``op_scopes(jit_name)`` maps every
    instruction of a compiled program (entry, ``while`` bodies, called
    computations) to ``{node, op_type, pass, scope}``: the graph node
    and its registered operator (``layer_scope(name, op_type=...)``),
    ``fwd`` / ``bwd`` / ``recompute`` / ``update``, the raw path.  A
    profiler trace names device time by instruction and launch
    (``jit_mx_cachedop_bwd``); this is the join
    (``chipbench/scope_reduce.py`` reads it).
  * **MFU / roofline** — analytical flops-per-step ÷ the flight
    recorder's warmed step-time EWMA → ``mxnet_mfu``,
    ``mxnet_step_flops_per_s``, ``mxnet_step_bytes_per_s``, and
    ``mxnet_step_arithmetic_intensity`` gauges (computed at export
    only), plus an ``mxnet_flops_per_s`` counter track in the Perfetto
    export.  Peak flops come from the one peaks table in
    ``mxnet_tpu/chip.py`` (keyed by ``device_kind``; an unknown TPU
    raises); ``MXNET_PEAK_FLOPS`` overrides.  On a CPU there is no
    peak, so flops/s and bytes/s are reported and MFU is not.
  * **perf-regression sentinel** — per (model signature, platform)
    baselines of {step-time p50, dispatches/step, flops, HBM peak}
    persist under ``MXNET_PERF_BASELINE_DIR`` (unset: the sentinel is
    disarmed).  At runtime the warmed EWMA is
    compared against the stored p50; drift past ``REGRESSION_FACTOR``
    fires ONE loud warning + ``mxnet_perf_regressions_total``
    increment (rate-limited) and flips the ``perf_regression``
    ``readyz()`` check until the regression clears or
    ``refresh_baseline()`` records the intentional change.  These
    persisted measurements are the substrate the ROADMAP's
    profile-guided autotuning tier will search over.

Overhead contract (the ``MXNET_METRICS_ENABLED`` discipline):
``MXNET_INTROSPECT=0`` reduces every hook — named scopes, program
notes, sentinel ticks — to ONE module-global boolean test.  Enabled,
the steady-state per-step cost is one counter increment (captures are
once-per-program retraces at build time, never per step); an untraced
step path calls neither ``compile()`` nor ``as_text()``.  Records carry
HLO text only under ``MXNET_INTROSPECT_HLO=1`` (size-capped; dumps go
through ``base.atomic_write`` + ``base.unique_path`` like flight
dumps), rendered when ``programs()`` is first read: a jit-called
program's ``lower().compile()`` then finds the executable the step
already runs (in-process, else the persistent compile cache).
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import re
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax

from ..base import (MXNetError, atomic_write, flight_dir, getenv,
                    unique_path)
from ..analysis import sanitizer as _san

log = logging.getLogger(__name__)

__all__ = ["ENABLED", "enabled", "enable", "disable", "layer_scope",
           "known_scopes", "note_program", "note_jit", "programs",
           "op_scopes", "program_sources", "per_layer", "attributed_pct", "step_flops", "mfu",
           "peak_flops", "phase_flops_map", "dump_hlo", "report",
           "snapshot_summary", "sentinel_tick", "refresh_baseline",
           "baseline_dir", "baseline_path", "sentinel_armed",
           "regression_active", "sentinel_state", "reset", "configure"]

# -- the fast-path switch ----------------------------------------------------
# Hooks across symbol/executor/gluon/optimizer/serving read this module
# global directly: `if introspect.ENABLED: ...`.
ENABLED: bool = getenv("MXNET_INTROSPECT", True)
#: opt-in optimized-HLO text in the program records (per_layer()'s and
#: the audit's input), rendered at the first read of programs().
#: Default OFF for steady state: the text of a large program is tens of
#: MB and its first read may compile.
HLO: bool = getenv("MXNET_INTROSPECT_HLO", False)
#: size cap on captured HLO text per program (truncated past it — the
#: flops parser still sees the leading instructions; configure() tunes)
HLO_CAP_BYTES: int = 8 << 20
#: sentinel check cadence, in sentinel_tick() calls per phase
SENTINEL_EVERY: int = 25
#: regression trigger: warmed EWMA > factor x persisted baseline p50
REGRESSION_FACTOR: float = 1.5
#: minimum seconds between PERF_REGRESSION firings per phase (tests 0)
REGRESSION_MIN_S: float = 300.0

#: the per-layer row every instruction lands in when no known scope is
#: found in its metadata (glue ops outside any named block)
UNATTRIBUTED = "_unattributed"

#: training-step phase -> program name the MFU/sentinel math pairs it
#: with (the fused path's step splits across three programs)
PHASE_PROGRAM = {"whole_step": "whole_step", "mx.trainer.step": "fused_update",
                 "superstep": "superstep"}
#: programs whose flops sum to one FUSED-path training step (CachedOp
#: bwd recomputes the forward inside its fused vjp program)
FUSED_STEP_PROGRAMS = ("gluon:fwd", "gluon:bwd", "fused_update")
#: phases whose flight span covers the WHOLE training step — only these
#: may serve as the denominator for step-flops rates.  The fused path's
#: "mx.trainer.step" span times Trainer.step alone (allreduce+update; the
#: user's fwd/bwd run outside it), so dividing full-step flops by it
#: would overstate MFU severalfold — fused-path MFU needs an explicit
#: step_time_s.
#: "superstep" qualifies too: its span covers K whole steps and its
#: noted program's cost_analysis flops are K x one step, so the
#: flops/time quotient stays a true device rate.
FULL_STEP_PHASES = frozenset({"whole_step", "superstep"})

#: scopes opened straight by ``jax.named_scope`` around a kernel call
#: (ops/flash_attention.py: the kernels' trace events are named after
#: them), known here so that op_scopes() can fall back on them
KERNEL_SCOPES = ("flash_attention_bwd", "flash_bwd_dkv", "flash_bwd_dq")

_lock = _san.make_lock("introspect.programs")
_programs: Dict[str, dict] = {}
#: every compiled program that can still name its own instructions,
#: oldest first; a program leaves with its jitted function
_sources: List["_Source"] = []
#: registry name -> the newest source noted under it (programs() reads
#: its text under MXNET_INTROSPECT_HLO=1)
_latest: Dict[str, "_Source"] = {}
#: every name ever passed through layer_scope() — the known scopes
#: per_layer() and op_scopes() match HLO metadata components against —
#: with the registered operator of a graph node (None for a literal
#: scope such as "optimizer").  Bounded by the graphs traced in-process
#: (one entry per distinct node name), the same boundedness contract as
#: flight phase names.
_scopes: Dict[str, Optional[str]] = {}


def enabled() -> bool:
    return ENABLED


def enable() -> None:
    global ENABLED
    ENABLED = True


def disable() -> None:
    global ENABLED
    ENABLED = False


# -- named scopes ------------------------------------------------------------
@contextlib.contextmanager
def layer_scope(name: str, op_type: Optional[str] = None):
    """Wrap a traced region in ``jax.named_scope(name)`` and register
    ``name`` as a known layer scope.  ``GraphPlan.run`` calls this per
    graph step with the node name and the node's registered operator
    (so HLO metadata carries layer names through fwd AND the vjp, and
    op_scopes() can say which operator a node is), the fused optimizer
    math and the program's own glue with literal scopes
    (``"optimizer"``, ``"allreduce"``; no ``op_type``).  Names must
    come from a bounded set (graph node names / literals) — the
    metrics-hygiene graft-lint rule rejects call-site string building.
    One boolean test when introspection is off."""
    if not ENABLED:
        yield
        return
    if op_type is not None or name not in _scopes:
        _scopes[name] = op_type
    try:
        ctx = jax.named_scope(name)
    except Exception:  # noqa: BLE001 — a bad name must never kill a trace
        yield
        return
    with ctx:
        yield


def known_scopes() -> frozenset:
    # list() snapshots the keys in one GIL-atomic C call: a trace on
    # another thread may be registering scopes concurrently
    return frozenset(list(_scopes))


def _scope_types() -> Dict[str, Optional[str]]:
    """{scope: registered operator of a graph node | None for a literal},
    the kernels' own scopes among the literals."""
    types: Dict[str, Optional[str]] = dict.fromkeys(KERNEL_SCOPES)
    types.update(list(_scopes.items()))
    return types


# -- program capture ---------------------------------------------------------
def _cost_of(compiled, lowered) -> dict:
    """Normalize jax's cost_analysis() across versions/stages: compiled
    returns a list-of-dicts on some versions, lowered a plain dict.
    Uniform output: {"flops": float, "bytes": float} (keys present only
    when the backend reports them)."""
    src = compiled if compiled is not None else lowered
    if src is None:
        return {}
    try:
        ca = src.cost_analysis()
    except Exception:  # noqa: BLE001 — stats are best-effort
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    if ca.get("flops") is not None:
        out["flops"] = float(ca["flops"])
    if ca.get("bytes accessed") is not None:
        out["bytes"] = float(ca["bytes accessed"])
    return out


def _memory_of(compiled) -> dict:
    if compiled is None:
        return {}
    from . import memory as _memory
    try:
        return _memory.compiled_stats_dict(compiled.memory_analysis())
    except Exception:  # noqa: BLE001
        return {}


def _cap(txt) -> Tuple[Optional[str], bool]:
    if not isinstance(txt, str) or not txt:
        return None, False
    if len(txt) > HLO_CAP_BYTES:
        return txt[:HLO_CAP_BYTES], True
    return txt, False


class _Source:
    """What ONE compiled program keeps so that its optimized HLO can be
    read later: the ``Lowered`` of a jit-called program (its
    ``compile()`` finds the executable the step already runs: jax keeps
    it on the lowering it cached, else the persistent compile cache has
    it), or a weak reference to a ``Compiled`` the caller holds.
    Nothing is compiled or rendered until ``text()`` is asked, and the
    instruction records parsed from it (``ops``) replace the text."""

    __slots__ = ("name", "jit_name", "lowered", "compiled", "ops",
                 "text_bytes", "read_s", "__weakref__")

    def __init__(self, name, jit_name, lowered=None, compiled=None):
        self.name, self.jit_name = name, jit_name
        self.lowered = lowered
        self.compiled = weakref.ref(compiled) if compiled is not None \
            else None
        self.ops: Optional[Dict[str, dict]] = None
        self.text_bytes = 0
        self.read_s = 0.0

    def text(self) -> Optional[str]:
        t0 = time.perf_counter()
        src = self.compiled() if self.compiled is not None else None
        try:
            if src is None and self.lowered is not None:
                src = self.lowered.compile()
            txt = src.as_text() if src is not None else None
        except Exception as e:  # noqa: BLE001 — introspection never raises
            log.debug("introspect: reading the HLO of %s failed: %s",
                      self.name, e)
            return None
        if not isinstance(txt, str) or not txt:
            return None
        self.text_bytes = len(txt)
        self.read_s += time.perf_counter() - t0
        return txt


def _drop_source(src: "_Source") -> None:
    with _lock:
        if src in _sources:
            _sources.remove(src)
        for name, latest in list(_latest.items()):
            if latest is src:
                del _latest[name]


def note_program(name: str, compiled=None, lowered=None, label=None,
                 signature=None, memory_stats=None,
                 contracts=None, jit_name=None, owner=None) -> dict:
    """File one compiled program's stats under ``name`` — THE shared
    surface every compile chokepoint routes through (Executor bind /
    memory_analysis, CachedOp, FusedUpdater, WholeStepCompiler, serving
    bucket precompile).

    ``name`` must be a bounded literal; a varying-but-bounded qualifier
    (the serving bucket label) goes in ``label`` and is joined as
    ``name:label`` here, mirroring the flight recorder's bucket_label
    discipline.  ``memory_stats`` short-circuits the CompiledMemoryStats
    read for callers that already hold the uniform dict.  Captured
    memory stats are also filed into the HBM ledger's compiled table
    (``memory.report()["compiled"]``) so that surface keeps one source.

    ``contracts`` (ISSUE 15) declares what the LOWERED artifact must
    look like — ``{"donate_argnums": ..., "donated_leaves": n,
    "amp": policy, "host_callbacks": 0, "collectives": 0}`` — which
    ``analysis.audit_programs()`` verifies against the captured HLO
    (donation really became input-output aliasing, AMP left no f32
    dots, no host callbacks, collective count matches the bucketer's
    plan).

    ``jit_name`` is the name a device trace shows for the program's
    launches (``jit_`` + the jitted function's name) and files the
    program for ``op_scopes()``; ``owner`` (the jitted function) ties
    what is kept to its life, so a program that is freed keeps no
    executable loaded through this registry.  Returns the record
    (``{}`` when introspection is off)."""
    if not ENABLED:
        return {}
    from . import goodput as _goodput
    if _goodput.ENABLED:
        # training compiles happen inside jax where their duration is
        # invisible here — count the event (serving precompile, which
        # owns its compile call, attributes measured seconds)
        _goodput.note_event("recompile")
    full = name if label is None else f"{name}:{label}"
    cost = _cost_of(compiled, lowered)
    mem = memory_stats if memory_stats is not None else _memory_of(compiled)
    if mem:
        from . import memory as _memory
        _memory.note_compiled(full, mem)
    # a Compiled in hand has paid its compile: with the flag on its
    # text is read now (the caller may drop it); a Lowered is kept and
    # compiled at the first read
    src = None
    hlo, truncated = None, False
    if compiled is not None or lowered is not None:
        src = _Source(full, jit_name, lowered=lowered, compiled=compiled)
        if HLO and compiled is not None:
            hlo, truncated = _cap(src.text())
        # a source leaves with the jitted function, or with the Compiled
        # it points at weakly: the registry does not grow with programs
        # that are gone
        owner = owner if owner is not None else compiled
        if owner is not None:
            try:
                weakref.finalize(owner, _drop_source, src)
            except TypeError:  # not weak-referenceable: kept for good
                pass
    with _lock:
        if src is not None:
            _sources.append(src)
            _latest[full] = src
        prev = _programs.get(full)
        rec = {
            "name": full,
            "flops": cost.get("flops"),
            "bytes": cost.get("bytes"),
            "memory": dict(mem) if mem else {},
            "signature": signature if signature is not None
            else (prev or {}).get("signature"),
            # a new program under the name: the old text goes with it
            "hlo": hlo if src is not None else (prev or {}).get("hlo"),
            "hlo_truncated": truncated if src is not None
            else bool((prev or {}).get("hlo_truncated")),
            "contracts": dict(contracts) if contracts is not None
            else (prev or {}).get("contracts"),
            "captures": ((prev or {}).get("captures") or 0) + 1,
        }
        _programs[full] = rec
        return dict(rec)


def note_jit(name: str, fn, *args, label=None, signature=None,
             contracts=None, **kwargs) -> dict:
    """Capture a jit-called program via ``fn.lower(*args)`` — a retrace
    served by jax's own lowering cache, NO XLA compile.  The ``Lowered``
    is kept for as long as ``fn`` lives (host memory only: jax's cache
    holds the same lowering) under the name its launches carry in a
    device trace, ``jit_`` + ``fn.__name__``.  Call sites guard to once
    per program/cache key; a capture failure is logged and swallowed —
    introspection must never break the step it observes."""
    if not ENABLED:
        return {}
    try:
        lowered = fn.lower(*args, **kwargs)
    except Exception as e:  # noqa: BLE001
        log.debug("introspect: lowering %s for capture failed: %s", name, e)
        return {}
    fn_name = getattr(fn, "__name__", None)
    return note_program(name, lowered=lowered, label=label,
                        signature=signature, contracts=contracts,
                        jit_name="jit_" + fn_name if fn_name else None,
                        owner=fn)


def programs() -> Dict[str, dict]:
    """The program records.  Under ``MXNET_INTROSPECT_HLO=1`` a record
    whose text has not been read yet reads it here (size-capped), the
    first time it is asked for."""
    if HLO:
        with _lock:
            unread = [(k, _latest[k]) for k, v in _programs.items()
                      if v.get("hlo") is None and k in _latest]
        for full, src in unread:
            hlo, truncated = _cap(src.text())
            with _lock:
                rec = _programs.get(full)
                if hlo is not None and rec is not None \
                        and _latest.get(full) is src:
                    rec["hlo"], rec["hlo_truncated"] = hlo, truncated
    with _lock:
        return {k: dict(v) for k, v in _programs.items()}


def program_sources(sizes: bool = False) -> List[dict]:
    """What the registry holds to read programs' text from, and what
    reading has cost so far: ``[{name, jit_name, kept, text_bytes,
    read_s, instructions}]``, oldest first (the builder's report).
    ``sizes`` adds ``lowered_bytes``, the length of each kept
    lowering's StableHLO text (it renders every one: seconds for a
    large program), the measure of the host memory the registry pins
    beside jax's own lowering cache, which holds the same modules."""
    with _lock:
        held = list(_sources)
    out = [{"name": s.name, "jit_name": s.jit_name,
            "kept": "lowered" if s.lowered is not None else "compiled",
            "text_bytes": s.text_bytes, "read_s": s.read_s,
            "instructions": len(s.ops) if s.ops is not None else None}
           for s in held]
    if sizes:
        for row, s in zip(out, held):
            row["lowered_bytes"] = len(s.lowered.as_text()) \
                if s.lowered is not None else None
    return out


def dump_hlo(name: str, directory: Optional[str] = None) -> str:
    """Write one program's captured HLO text to disk (atomic,
    collision-free timestamped filename — the flight-dump policy).
    Default directory: ``MXNET_FLIGHT_DIR``."""
    rec = programs().get(name)
    if rec is None or not rec.get("hlo"):
        raise MXNetError(
            f"no HLO captured for program {name!r} — set "
            f"MXNET_INTROSPECT_HLO=1 before the program is noted "
            f"(captured: {sorted(programs())})")
    d = directory or flight_dir()
    os.makedirs(d, exist_ok=True)
    safe = re.sub(r"[^\w.-]", "-", name)
    path = unique_path(d, f"hlo-{safe}", ".txt")
    atomic_write(path, rec["hlo"])
    return path


# -- per-layer flops attribution ---------------------------------------------
# Opcodes that move/route data but compute nothing (match XLA's own
# HloCostAnalysis, which costs these 0 flops)
_ZERO_FLOP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "copy", "copy-start", "copy-done", "reshape",
    "broadcast", "transpose", "slice", "concatenate", "iota", "pad",
    "dynamic-slice", "dynamic-update-slice", "fusion", "call", "while",
    "conditional", "custom-call", "get-dimension-size", "after-all",
    "rng-bit-generator", "rng", "partition-id", "replica-id", "gather",
    "convert", "reverse", "domain", "infeed", "outfeed", "send", "recv",
    "send-done", "recv-done", "all-gather", "optimization-barrier",
})

#: an instruction line: (ROOT?, name, result type, opcode)
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+"
                       r"([\w\-]+)\(")
_DIMS_RE = re.compile(r"\[([0-9,]*)\]")
_META_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]+)"')
_WRAP_RE = re.compile(r"^[\w\-]+\((.*)\)$")


def _prod_dims(spec: str) -> int:
    n = 1
    for d in spec.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return n


def _all_dims_prod(type_str: str) -> int:
    """Sum of element counts over every array shape in a (possibly
    tuple) HLO result type."""
    total = 0
    for m in _DIMS_RE.finditer(type_str):
        total += _prod_dims(m.group(1))
    return total if total else 1


def _operand_dims(line: str, opcode: str) -> List[List[int]]:
    seg = line.split(opcode + "(", 1)
    if len(seg) < 2:
        return []
    out = []
    for m in _DIMS_RE.finditer(seg[1].split(" metadata=")[0]):
        out.append([int(d) for d in m.group(1).split(",") if d.strip()])
    return out


def _instr_flops(line: str, type_str: str, opcode: str) -> float:
    """Per-instruction flops model: dot/conv exact from shapes (2 flops
    per MAC, XLA's convention), reduce ≈ input elements, everything
    else ≈ 1 flop per output element.  Conservative where it cannot
    parse — the attribution acceptance runs against this model's own
    total, and dots/convs dominate real training programs."""
    out_elems = _all_dims_prod(type_str)
    if opcode == "dot":
        ops = _operand_dims(line, opcode)
        m = re.search(r"lhs_contracting_dims=\{([0-9,]+)\}", line)
        if ops and m:
            lhs = ops[0]
            contracted = 1
            for i in m.group(1).split(","):
                i = int(i)
                if i < len(lhs):
                    contracted *= lhs[i]
            return 2.0 * out_elems * contracted
        return 2.0 * out_elems
    if opcode == "convolution":
        window = 1
        m = re.search(r"window=\{[^}]*size=([0-9x]+)", line)
        if m:
            for d in m.group(1).split("x"):
                window *= int(d)
        kin = 1
        m = re.search(r"dim_labels=(\S+)", line)
        ops = _operand_dims(line, opcode)
        if m and len(ops) >= 2 and "_" in m.group(1):
            klabels = m.group(1).split("_", 1)[1].split("->", 1)[0]
            pos = klabels.find("i")
            if 0 <= pos < len(ops[1]):
                kin = ops[1][pos]
        return 2.0 * out_elems * window * kin
    if opcode in ("reduce", "reduce-window", "scatter", "select-and-scatter",
                  "sort", "all-reduce"):
        ops = _operand_dims(line, opcode)
        if ops and ops[0]:
            n = 1
            for d in ops[0]:
                n *= d
            return float(n)
        return float(out_elems)
    return float(out_elems)


def _scope_of(op_name: str, known: frozenset) -> Optional[str]:
    """Innermost known layer scope in an HLO op_name path.  Components
    arrive decorated by the tracing machinery — ``jvp(dense0_fwd)``,
    ``transpose(jvp(dense0_fwd))``, ``rematted_computation(...)`` — so
    each is unwrapped to its innermost token before the known-set
    test."""
    best = None
    for comp in op_name.split("/"):
        t = _unwrap(comp)
        if t in known:
            best = t
    return best


def _layer_of(scope: str) -> str:
    """Scope name -> layer row: graph node names carry an op-derived
    ``_fwd`` suffix (``hybridsequential0_dense0_fwd``) that per-layer
    grouping strips; literal scopes (``optimizer``) pass through."""
    return scope[:-4] if scope.endswith("_fwd") else scope


def parse_hlo_flops(text: str,
                    known: Optional[frozenset] = None) -> Dict[str, float]:
    """Parse optimized HLO text into ``{layer: flops}`` (instructions
    inside fusion computations carry their own metadata, so fused ops
    still attribute; the ``fusion``/``call`` container instructions
    themselves cost 0).  Instructions without a known scope land under
    ``_unattributed``."""
    known = known if known is not None else known_scopes()
    out: Dict[str, float] = {}
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        type_str, opcode = m.group(3), m.group(4)
        if opcode in _ZERO_FLOP_OPS:
            continue
        flops = _instr_flops(line, type_str, opcode)
        if flops <= 0:
            continue
        meta = _META_RE.search(line)
        scope = _scope_of(meta.group(1), known) if meta else None
        layer = _layer_of(scope) if scope else UNATTRIBUTED
        out[layer] = out.get(layer, 0.0) + flops
    return out


# -- device-time names: instruction -> node, operator, pass -------------------
_HEAD_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_CALLEE_RE = re.compile(r"\b(calls|to_apply)=%?([\w.\-]+)")
#: the optimizer's literal scope: what runs under it is the update pass
UPDATE_SCOPE = "optimizer"


def _lines(text: str):
    """The lines of a text that may be hundreds of MB, one at a time."""
    for m in re.finditer(r"[^\n]+", text):
        yield m.group(0)


def _unwrap(component: str) -> str:
    """``transpose(jvp(dense0_fwd))`` -> ``dense0_fwd``."""
    while True:
        m = _WRAP_RE.match(component)
        if m is None:
            return component
        component = m.group(1)


def scope_record(op_name: Optional[str], opcode: str,
                 types: Dict[str, Optional[str]], by: str = "self") -> dict:
    """One instruction's ``{node, op_type, pass, scope, opcode, by}`` from
    the ``op_name`` path of its metadata.  ``node``: the innermost graph
    node of the path, else its innermost literal scope, else
    ``UNATTRIBUTED``.  ``pass``: ``update`` under the optimizer's scope,
    ``recompute`` under a checkpoint's ``rematted_computation``, ``bwd``
    under a ``transpose(``, else ``fwd``; None where the path says
    nothing (no scope and neither marker) or is not the instruction's
    to speak for (``by == "inner"``: a convolution XLA rewrote loses its
    path and keeps, fused into it, the forward activation it reads: the
    node names the place, the pass is the reader's to settle).  ``by`` says whose path it
    is: the instruction's own (``self``), or for a fusion that XLA left
    without one its root's (``root``) or the commonest of its fused
    instructions' (``inner``)."""
    node = literal = None
    parts = [_unwrap(c) for c in op_name.split("/")] if op_name else ()
    for t in parts:
        if t in types:
            if types[t] is None:
                literal = t
            else:
                node = t
    if UPDATE_SCOPE in parts:
        pass_ = "update"
    elif op_name and "rematted_computation" in op_name:
        pass_ = "recompute"
    elif op_name and "transpose(" in op_name:
        pass_ = "bwd"
    else:
        pass_ = "fwd" if (node or literal) else None
    if by == "inner":
        pass_ = None
    return {"node": node or literal or UNATTRIBUTED,
            "op_type": types[node] if node else literal,
            "pass": pass_, "scope": op_name, "opcode": opcode,
            "by": by if op_name else None}


def parse_op_scopes(text: str,
                    types: Optional[Dict[str, Optional[str]]] = None
                    ) -> Dict[str, dict]:
    """``{instruction name: record}`` for every instruction of every
    computation of an optimized HLO module that can show in a device
    trace: the entry, ``while`` bodies and conditions, branches, called
    computations.  A fused computation gives only its fusion instruction
    (named by the fusion's own ``op_name``; XLA leaves some without one,
    a convolution it rewrote, a multi-output root: those take their
    root's, else the commonest path of the instructions fused into
    them), a reduction's ``to_apply`` region nothing.  What is left
    without a path (copies, layout changes, broadcasts XLA made) is named
    after the instruction that reads it, else the one it reads.
    Instructions of one path and opcode share one record; the text is
    walked twice line by line and never split, so no size cap applies."""
    types = types if types is not None else _scope_types()
    inner = set()     # computations no trace event is named after
    for line in _lines(text):
        if "calls=" in line or "to_apply=" in line:
            m = _INSTR_RE.match(line)
            if m is None or m.group(4) == "call":
                continue
            inner.update(c for kind, c in _CALLEE_RE.findall(line)
                         if kind == "to_apply" or m.group(4) == "fusion")
    out: Dict[str, dict] = {}
    shared: Dict[tuple, dict] = {}
    paths: Dict[str, str] = {}      # one string a distinct path
    fused: Dict[str, list] = {}     # fused computation -> [root's, tally]
    flows: Dict[str, tuple] = {}    # pathless instruction -> (reads, readers)
    comp = None
    for line in _lines(text):
        m = _INSTR_RE.match(line)
        if m is None:
            h = _HEAD_RE.match(line)
            if h is not None:
                comp = h.group(1)
            continue
        is_root, name, opcode = m.group(1), m.group(2), m.group(4)
        meta = _META_RE.search(line)
        path = meta.group(1) if meta else None
        if path is not None:
            path = paths.setdefault(path, path)
        if comp in inner:
            if path and "/" in path:
                seen = fused.setdefault(comp, [None, {}])
                if is_root:
                    seen[0] = path
                seen[1][path] = seen[1].get(path, 0) + 1
            continue
        by = "self"
        if path is None and opcode == "fusion":
            c = _CALLEE_RE.search(line)
            seen = fused.pop(c.group(2), None) if c else None
            if seen is not None:
                path, by = (seen[0], "root") if seen[0] else \
                    (max(seen[1], key=seen[1].get), "inner")
        key = (path, opcode, by)
        rec = shared.get(key)
        if rec is None:
            rec = shared[key] = scope_record(path, opcode, types, by)
        out[name] = rec
        # what XLA made itself (a layout change, a copy, a broadcast of
        # zeros) carries no path: remember who reads it and what it reads
        reads = _OPERAND_RE.findall(line[m.end():].split(")", 1)[0])
        for o in reads:
            if o in flows:
                flows[o][1].append(name)
        if rec["node"] == UNATTRIBUTED and opcode not in _NO_WORK_OPS:
            flows[name] = (reads, [])
    _name_by_neighbours(out, flows, shared)
    return out


_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_NO_WORK_OPS = frozenset({"parameter", "constant"})


def _name_by_neighbours(out: Dict[str, dict], flows: Dict[str, tuple],
                        shared: Dict[tuple, dict]) -> None:
    """Give an instruction without a path the names of the instruction
    that reads it (``by: "user"``: a layout change is made for its
    reader), else of the first one it reads (``"operand"``).  Readers
    come later in the text, so walking backwards names a chain of such
    instructions from its named end; a second walk forwards serves what
    only its operands can name."""
    def take(name, neighbours, by):
        for n in neighbours:
            rec = out.get(n)
            if rec is not None and rec["node"] != UNATTRIBUTED:
                key = (id(rec), out[name]["opcode"], by)
                new = shared.get(key)
                if new is None:
                    new = shared[key] = dict(rec, by=by,
                                             opcode=out[name]["opcode"])
                out[name] = new
                return True
        return False

    order = list(flows)
    for name in reversed(order):
        take(name, flows[name][1], "user")
    for name in order:
        if out[name]["node"] == UNATTRIBUTED:
            take(name, flows[name][0], "operand")


def op_scopes(jit_name: str) -> Optional[List[Dict[str, dict]]]:
    """Name the instructions of the compiled programs whose launches a
    device trace shows as ``jit_name`` (``jit_mx_cachedop_bwd``): one
    ``{instruction name: {node, op_type, pass, scope, opcode}}`` a
    program, oldest first (two CachedOps share a name and no
    instruction set: a reader matches a launch to the map that knows
    all of its instructions).  The first call reads the program's
    optimized HLO — ``compile()`` of the kept lowering: the executable
    in use, else a read of the persistent cache — parses it and keeps
    the records in its place; never on an untraced step path.  None
    with introspection off, or where no live program has that name."""
    if not ENABLED:
        return None
    with _lock:
        held = [s for s in _sources if s.jit_name == jit_name]
    maps = []
    for src in held:
        if src.ops is None:
            text = src.text()
            if text is None:
                continue
            t0 = time.perf_counter()
            src.ops = parse_op_scopes(text)
            src.read_s += time.perf_counter() - t0
        maps.append(src.ops)
    return maps or None


def per_layer(program: str = "whole_step", top: Optional[int] = None,
              measured: Optional[Dict[str, float]] = None) -> List[dict]:
    """The per-layer cost table for a captured program: ``[{layer,
    flops, pct, device_ms}]`` sorted by flops (the ``_unattributed``
    remainder is a row, never hidden).  ``flops`` is the parser's
    estimate; ``device_ms`` is MEASURED: hand in the seconds a device
    trace gives by instruction name (``{"fusion.58": 0.0021}``: leaf
    operations of this program's launches; chipbench/scope_reduce.py
    makes them) and each lands on the layer ``op_scopes`` names for it;
    None without ``measured``.  Requires HLO text in the record
    (``MXNET_INTROSPECT_HLO=1``: read when the record is first asked
    for)."""
    rec = programs().get(program)
    if rec is None:
        raise MXNetError(
            f"program {program!r} has not been captured "
            f"(captured: {sorted(programs())})")
    if not rec.get("hlo"):
        raise MXNetError(
            f"no HLO text captured for {program!r}: set "
            f"MXNET_INTROSPECT_HLO=1 (or configure(hlo=True)) — the "
            f"text is opt-in because a large program's is tens of MB "
            f"and its first read may compile")
    by_layer = parse_hlo_flops(rec["hlo"])
    total = sum(by_layer.values()) or 1.0
    ms: Dict[str, float] = {}
    if measured is not None:
        names = parse_op_scopes(rec["hlo"])
        for instr, seconds in measured.items():
            node = names.get(instr, {}).get("node", UNATTRIBUTED)
            layer = _layer_of(node)
            ms[layer] = ms.get(layer, 0.0) + seconds * 1e3
            by_layer.setdefault(layer, 0.0)
    rows = [{"layer": k, "flops": v,
             "pct": round(100.0 * v / total, 2),
             "device_ms": round(ms.get(k, 0.0), 4)
             if measured is not None else None}
            for k, v in sorted(by_layer.items(), key=lambda kv: -kv[1])]
    return rows[:top] if top else rows


def attributed_pct(program: str = "whole_step") -> float:
    """Fraction (pct) of the parsed program flops attributed to NAMED
    blocks — the ISSUE 13 >=90% acceptance number."""
    rows = per_layer(program)
    return round(sum(r["pct"] for r in rows
                     if r["layer"] != UNATTRIBUTED), 2)


# -- MFU / roofline ----------------------------------------------------------
def peak_flops() -> Tuple[Optional[float], str]:
    """(peak flops/s, source): the MXNET_PEAK_FLOPS override, else the
    chip.py peaks table for this TPU's ``device_kind`` (an unknown kind
    raises), else ``(None, "none:<platform>")`` — a host CPU has no
    published peak and MFU against a made-up one would mean nothing."""
    override = float(getenv("MXNET_PEAK_FLOPS", 0.0))
    if override > 0:
        return override, "MXNET_PEAK_FLOPS"
    dev = jax.local_devices()[0]
    if dev.platform != "tpu":
        return None, f"none:{dev.platform}"
    from .. import chip as _chip
    return _chip.peaks(dev.device_kind).bf16_flops, f"chip:{dev.device_kind}"


def step_flops() -> Tuple[Optional[float], Optional[float], Optional[str]]:
    """(flops, bytes, phase) for one training step, from the noted
    programs: the whole-step program when captured, else the sum of the
    fused path's three programs (CachedOp's bwd recomputes the forward
    inside its fused vjp, so the sum is what actually executes)."""
    progs = programs()
    rec = progs.get("whole_step")
    if rec is not None and rec.get("flops"):
        return rec["flops"], rec.get("bytes"), "whole_step"
    parts = [progs[n] for n in FUSED_STEP_PROGRAMS if n in progs]
    if parts and any(p.get("flops") for p in parts):
        return (sum(p.get("flops") or 0.0 for p in parts),
                sum(p.get("bytes") or 0.0 for p in parts) or None,
                "mx.trainer.step")
    return None, None, None


def mfu(step_time_s: Optional[float] = None, flops: Optional[float] = None,
        bytes_per_step: Optional[float] = None,
        peak: Optional[float] = None) -> dict:
    """MFU + roofline telemetry: analytical flops/step ÷ measured step
    time ÷ platform peak.  Every input is overridable; defaults come
    from the noted programs + the flight recorder's warmed EWMA.  Returns ``{}`` when
    either the flops or the step time is not yet measurable; where the
    platform has no peak (CPU) the ``mfu``/``peak_flops`` keys are
    absent and the achieved rates remain."""
    phase = None
    if flops is None:
        flops, b, phase = step_flops()
        if bytes_per_step is None:
            bytes_per_step = b
    if flops is None or flops <= 0:
        return {}
    if step_time_s is None and phase in FULL_STEP_PHASES:
        from . import flight as _flight
        step_time_s = _flight.watch_ewma(phase)
    if not step_time_s or step_time_s <= 0:
        return {}
    pk, src = (peak, "caller") if peak else peak_flops()
    fps = flops / step_time_s
    out = {
        "flops_per_step": flops,
        "step_time_ms": round(step_time_s * 1e3, 4),
        "flops_per_s": fps,
        "peak_source": src,
    }
    if pk:
        out["peak_flops"] = pk
        out["mfu"] = round(fps / pk, 6)
        out["mfu_pct"] = round(100.0 * fps / pk, 4)
    if bytes_per_step:
        out["bytes_per_step"] = bytes_per_step
        out["bytes_per_s"] = bytes_per_step / step_time_s
        out["arithmetic_intensity"] = round(flops / bytes_per_step, 4)
    try:
        from ..parallel.mesh import current_mesh, mesh_signature
        m = current_mesh()
        if m is not None:
            # the sharded-run attribution: total program flops split by
            # each mesh axis's size — the per-shard share along that
            # axis (metrics.py exports these as per-axis gauge children)
            out["mesh"] = mesh_signature(m)
            out["mesh_axes"] = {a: int(m.shape[a]) for a in m.axis_names}
            out["per_axis_flops_per_s"] = {
                a: fps / int(m.shape[a]) for a in m.axis_names}
    except Exception:  # noqa: BLE001 — telemetry must never fail a pull
        pass
    return out


def phase_flops_map() -> Dict[str, float]:
    """{flight phase name: analytical flops/step} for the phases whose
    spans cover a whole training step — the feed for the Perfetto
    ``mxnet_flops_per_s`` counter track (timeline.chrome_events).
    Restricted to FULL_STEP_PHASES: emitting the fused path's
    fwd+bwd+update flops over the "mx.trainer.step" span (which times only
    allreduce+update) would render impossible flops/s."""
    flops, _b, phase = step_flops()
    return {phase: flops} if phase in FULL_STEP_PHASES and flops else {}


# -- perf-regression sentinel ------------------------------------------------
_BASELINE_SCHEMA = 1
_BASELINE_KEYS = ("step_time_p50_ms", "dispatches_per_step",
                  "flops_per_step", "hbm_peak_bytes")
_sent_counts: Dict[str, int] = {}
_sentinel: Dict[str, dict] = {}


def baseline_dir() -> Optional[str]:
    """Where baselines persist: ``MXNET_PERF_BASELINE_DIR``.  Unset,
    the sentinel is disarmed — it never arms itself off the compile
    cache, which entry points always have."""
    return os.environ.get("MXNET_PERF_BASELINE_DIR") or None


def _platform() -> str:
    try:
        return jax.default_backend()
    except Exception:  # noqa: BLE001
        return "unknown"


def _signature_of(phase: str) -> str:
    rec = programs().get(PHASE_PROGRAM.get(phase, phase))
    sig = (rec or {}).get("signature")
    return sig or "unsigned"


def baseline_path(phase: str) -> Optional[str]:
    d = baseline_dir()
    if d is None:
        return None
    return os.path.join(
        d, f"{phase}-{_signature_of(phase)}-{_platform()}.json")


def _current_measurements(phase: str) -> Optional[dict]:
    from . import flight as _flight
    from . import metrics as _metrics
    ewma = _flight.watch_ewma(phase)
    if ewma is None:
        return None
    rec = programs().get(PHASE_PROGRAM.get(phase, phase))
    hbm = 0
    try:
        from . import memory as _memory
        if _memory.ENABLED:
            _dev, _host, peaks = _memory._live_split()
            hbm = int(sum(v for (sp, _t), v in peaks.items()
                          if sp == "device"))
    except Exception:  # noqa: BLE001
        pass
    return {
        "schema": _BASELINE_SCHEMA,
        "phase": phase,
        "platform": _platform(),
        "signature": _signature_of(phase),
        # the persisted "p50" is the warmed EWMA — the same robust
        # location estimate the runtime comparison reads, so write and
        # compare can never disagree on methodology
        "step_time_p50_ms": round(ewma * 1e3, 4),
        # the superstep phase gates on its own gauge: scanned = 1 per
        # K-step superstep, ~K after a silent demotion — which is the
        # regression this baseline exists to catch
        "dispatches_per_step": float(
            _metrics.SUPERSTEP_DISPATCHES.get() if phase == "superstep"
            else _metrics.TRAINER_STEP_DISPATCHES.get()),
        "flops_per_step": (rec or {}).get("flops"),
        "hbm_peak_bytes": hbm,
        "written_at": time.time(),
    }


def _sentinel_entry(phase: str) -> dict:
    ent = _sentinel.get(phase)
    if ent is None:
        ent = _sentinel[phase] = {
            "baseline": None, "loaded": False, "corrupt": False,
            "active": False, "kind": None, "fired_at": None,
            "pending": False, "path": None, "wrote": False,
            "sig": None,
        }
    return ent


def _load_baseline(phase: str, ent: dict) -> None:
    ent["loaded"] = True
    path = baseline_path(phase)
    ent["path"] = path
    if path is None or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict) or \
                data.get("schema") != _BASELINE_SCHEMA or \
                not isinstance(data.get("step_time_p50_ms"), (int, float)) \
                or data["step_time_p50_ms"] <= 0:
            raise ValueError("missing/invalid required fields")
    except Exception as e:  # noqa: BLE001 — reject loudly, never crash
        ent["corrupt"] = True
        log.warning(
            "perf-regression sentinel: baseline %s is corrupt (%s) — "
            "REJECTED; the sentinel stays disarmed for this phase until "
            "introspect.refresh_baseline(%r) rewrites it", path, e, phase)
        return
    ent["baseline"] = data


def _write_baseline(phase: str, cur: dict, ent: dict) -> None:
    path = baseline_path(phase)
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        atomic_write(path, json.dumps(cur, indent=1, sort_keys=True))
        ent["baseline"] = cur
        ent["path"] = path
        ent["wrote"] = True
        log.info("perf-regression sentinel: wrote baseline %s "
                 "(p50 %.3f ms)", path, cur["step_time_p50_ms"])
    except OSError as e:
        log.warning("perf-regression sentinel: baseline write to %s "
                    "failed: %s", path, e)


def sentinel_tick(phase: str) -> None:
    """Per-step hook at the training chokepoints (Trainer.step /
    WholeStepCompiler._dispatch).  One boolean + one counter increment
    per step; the real check runs every SENTINEL_EVERY steps once the
    phase's EWMA has warmed."""
    if not ENABLED:
        return
    n = _sent_counts.get(phase, 0) + 1
    _sent_counts[phase] = n
    if n % SENTINEL_EVERY:
        return
    try:
        _sentinel_check(phase)
    except Exception as e:  # noqa: BLE001 — never break the step
        log.debug("perf sentinel check failed: %s", e)


def _sentinel_check(phase: str) -> None:
    if baseline_dir() is None:
        return
    cur = _current_measurements(phase)
    if cur is None:
        return  # EWMA not warmed yet
    ent = _sentinel_entry(phase)
    sig = _signature_of(phase)
    if ent["loaded"] and ent.get("sig") != sig:
        # the program's signature moved mid-run (a legitimate batch or
        # config change re-noted it): the cached baseline belongs to
        # the OLD workload — re-resolve against the new signature's
        # file instead of firing a false regression
        prev = _sentinel[phase] = dict(ent, loaded=False, baseline=None,
                                       corrupt=False, active=False,
                                       kind=None, pending=False)
        ent = prev
    if not ent["loaded"]:
        ent["sig"] = sig
        _load_baseline(phase, ent)
    if ent["baseline"] is None:
        if not ent["corrupt"]:
            _write_baseline(phase, cur, ent)
        return
    base = ent["baseline"]
    kind = None
    if cur["step_time_p50_ms"] > REGRESSION_FACTOR * \
            base["step_time_p50_ms"]:
        kind = "step_time"
    elif base.get("dispatches_per_step") and \
            cur["dispatches_per_step"] > base["dispatches_per_step"] + 0.5:
        kind = "dispatches"
    ent["current"] = cur
    if kind is None:
        ent["active"] = False
        ent["kind"] = None
        ent["pending"] = False
        return
    if ent["active"] and not ent.get("pending"):
        return  # still the same regression episode — fired already
    ent["active"] = True
    ent["kind"] = kind
    now = time.monotonic()
    if ent["fired_at"] is not None and \
            now - ent["fired_at"] < REGRESSION_MIN_S:
        # inside the rate window: DEFER the fire, never drop it — an
        # episode that begins here and persists must still warn and
        # count on the first check after the window elapses (readyz
        # flips immediately either way via ent["active"])
        ent["pending"] = True
        return
    ent["pending"] = False
    ent["fired_at"] = now
    log.warning(
        "PERF REGRESSION (%s) on %s: step-time p50 %.3f ms vs baseline "
        "%.3f ms (factor %.1f), dispatches/step %.1f vs %.1f — baseline "
        "%s; if this change is intentional, refresh it with "
        "mx.observability.introspect.refresh_baseline(%r)",
        kind, phase, cur["step_time_p50_ms"], base["step_time_p50_ms"],
        REGRESSION_FACTOR, cur["dispatches_per_step"],
        base.get("dispatches_per_step", 0.0), ent["path"], phase)
    from . import metrics as _metrics
    if _metrics.ENABLED:
        # kind/phase are bounded literal sets (step_time|dispatches x
        # whole_step|mx.trainer.step)
        _metrics.PERF_REGRESSIONS.inc(kind=kind, phase=phase)
    from . import journal as _journal
    if _journal.ENABLED:
        _journal.emit("perf_regression", durable=True, kind=kind,
                      phase=phase,
                      current_p50_ms=cur["step_time_p50_ms"],
                      baseline_p50_ms=base["step_time_p50_ms"])


def refresh_baseline(phase: str = "whole_step") -> Optional[dict]:
    """Rewrite the persisted baseline from CURRENT warmed measurements
    — the intentional-change lifecycle step (a deliberate model/config
    change that moves step time must not page forever).  Clears any
    active regression for the phase.  Returns the written baseline
    (None when the EWMA has not warmed or no baseline dir is set)."""
    if not ENABLED or baseline_dir() is None:
        return None
    cur = _current_measurements(phase)
    if cur is None:
        return None
    ent = _sentinel_entry(phase)
    ent["loaded"] = True
    ent["sig"] = _signature_of(phase)
    ent["corrupt"] = False
    ent["active"] = False
    ent["kind"] = None
    ent["pending"] = False
    _write_baseline(phase, cur, ent)
    return dict(cur)


def sentinel_armed() -> bool:
    """True once any phase has a loaded baseline to compare against.
    list() snapshots against a supervised worker thread's sentinel_tick
    inserting a phase entry mid-iteration (the readyz watchdog calls
    this from the server thread)."""
    return any(e.get("baseline") is not None
               for e in list(_sentinel.values()))


def regression_active() -> bool:
    return any(e.get("active") for e in list(_sentinel.values()))


def sentinel_state() -> dict:
    """snapshot()-able sentinel block: per-phase baseline/current/
    active state + the resolved baseline directory.  Iterates a
    GIL-atomic list() snapshot — a training thread may be inserting a
    phase entry while a readyz/scrape thread renders this."""
    phases = {}
    for phase, e in sorted(list(_sentinel.items())):
        base, cur = e.get("baseline"), e.get("current")
        phases[phase] = {
            "baseline": dict(base) if base else None,
            "current": dict(cur) if cur else None,
            "active": bool(e.get("active")),
            "kind": e.get("kind"),
            "corrupt": bool(e.get("corrupt")),
            "path": e.get("path"),
        }
    return {"dir": baseline_dir(), "armed": sentinel_armed(),
            "regression_active": regression_active(), "phases": phases}


# -- surfaces ----------------------------------------------------------------
def snapshot_summary() -> dict:
    """The compact block ``observability.snapshot()["programs"]``
    carries: per-program flops/bytes/peak + MFU + sentinel state."""
    progs = {}
    for name, rec in sorted(programs().items()):
        progs[name] = {
            "flops": rec.get("flops"),
            "bytes": rec.get("bytes"),
            "peak_bytes": (rec.get("memory") or {}).get("peak_bytes"),
            "signature": rec.get("signature"),
            "hlo_captured": bool(rec.get("hlo")),
            "captures": rec.get("captures", 0),
        }
    return {"enabled": ENABLED, "hlo": HLO, "programs": progs,
            "mfu": mfu(), "sentinel": sentinel_state(),
            "known_scopes": len(_scopes)}


def report() -> dict:
    """The operator's one-stop view: full program records (HLO elided
    to a length), per-layer tables where HLO was captured, MFU, and
    sentinel state."""
    out = {"enabled": ENABLED, "hlo": HLO, "mfu": mfu(),
           "sentinel": sentinel_state(), "programs": {}, "per_layer": {}}
    for name, rec in sorted(programs().items()):
        r = dict(rec)
        hlo = r.pop("hlo", None)
        r["hlo_bytes"] = len(hlo) if hlo else 0
        out["programs"][name] = r
        if hlo:
            try:
                out["per_layer"][name] = per_layer(name)
            except MXNetError:
                pass
    return out


# -- lifecycle ---------------------------------------------------------------
def reset() -> None:
    """Drop every program record, known scope, and sentinel state
    (tests).  On-disk baselines are untouched — delete the file or
    refresh_baseline() to change them."""
    with _lock:
        _programs.clear()
        del _sources[:]
        _latest.clear()
    _scopes.clear()
    _sent_counts.clear()
    _sentinel.clear()


def configure(hlo: Optional[bool] = None,
              hlo_cap_bytes: Optional[int] = None,
              sentinel_every: Optional[int] = None,
              regression_factor: Optional[float] = None,
              regression_min_s: Optional[float] = None) -> None:
    """Tune knobs at runtime.  Every parameter follows the same rule:
    None leaves the current value UNCHANGED (a call tuning only the
    sentinel cadence must not silently reset HLO capture from the
    env — env values are read once at import)."""
    global HLO, HLO_CAP_BYTES, SENTINEL_EVERY, REGRESSION_FACTOR, \
        REGRESSION_MIN_S
    if hlo is not None:
        HLO = bool(hlo)
    if hlo_cap_bytes is not None:
        HLO_CAP_BYTES = max(1, int(hlo_cap_bytes))
    if sentinel_every is not None:
        SENTINEL_EVERY = max(1, int(sentinel_every))
    if regression_factor is not None:
        REGRESSION_FACTOR = float(regression_factor)
    if regression_min_s is not None:
        REGRESSION_MIN_S = float(regression_min_s)
