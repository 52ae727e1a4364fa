"""From the device's events and the program's own names to device time by
program, pass, operator type and graph node.  Shared by the readers
fwd_device_ms, bwd_device_ms and scoped_device_share; `report` is the
builder's longer view for PERF.md (chipbench/scope_report.py prints it).

The trace names device time by instruction (`XLA Ops`: `%fusion.58 = ...`)
and by launch (`XLA Modules`: `jit_mx_cachedop_bwd(<id>)`).  The program
names its instructions: `mxnet_tpu.observability.introspect.op_scopes(
"jit_mx_cachedop_bwd")` gives, per compiled program of that name, `{
instruction: {node, op_type, pass, scope, opcode, by}}` from the `op_name`
metadata of its optimized HLO (the graph node's `jax.named_scope`, the
node's registered operator, `fwd` / `bwd` / `recompute` / `update`, and
whose path it was: the instruction's own, or a neighbour's where XLA left
it none).  This file is the join.  Input is `ctx["reduced"]["events"]`, names and times only.

- **Rows tile the busy time.**  On one device's `XLA Ops` line an event
  that holds other events in time is a container (`while`, `conditional`,
  `call`): its body's operations are events of their own.  Every event is
  counted for its SELF time, its length less the events directly inside
  it, so a `while` counts only what its body does not cover (the loop's
  own bookkeeping) and the body's operations count once.  The self times
  sum to the union of the line's intervals, which is the busy time of
  `trace_reduce.reduce`.  An event that holds nothing is a leaf and its self
  time its length.
- **Launch.**  An operation belongs to the `XLA Modules` event of its own
  plane that holds its start (one plane, one clock: no offset enters), hence
  to a program (`jit_mx_cachedop_bwd`).  Operations under no launch are
  filed under `NO_LAUNCH`.
- **Record.**  Two programs may hold a `fusion.5`, and two CachedOps share
  a program name: a launch is matched to the map of its program's name that
  knows the most of the launch's instruction names.  A program without a
  map (`jit__threefry_split`, eager operators) is `RECORDLESS`: its time
  lands under its program name.
- **Pass.**  The record's; an instruction without one takes the pass of a
  program whose records hold one pass only (`jit_mx_cachedop_fwd`: fwd;
  `jit_mx_cachedop_bwd`: bwd, `recompute` being part of the backward;
  `jit_mx_fused_update`: update), and is `UNSPLIT` in a program that holds
  several (`jit_mx_executor_fwd_bwd`).  So fwd + bwd + recompute + update
  + unsplit + recordless is the busy time.
- **A step.**  Sums are averaged over the devices that ran anything and
  divided by the steps the loop completed in the window.
"""
import bisect
import re
import time

from chipbench import trace_reduce
from chipbench.span_reduce import program_of

#: the step's own programs: the share of their time that carries a name is
#: the instrument's health
STEP_PROGRAMS = "jit_mx_"
UNSPLIT = "unsplit"
RECORDLESS = "recordless"
NO_LAUNCH = "no_launch"
UNATTRIBUTED = "_unattributed"
_OPCODE_RE = re.compile(r"\s([\w\-]+)\(")


def opcode_of(event_name):
    """`%fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop` -> `fusion`."""
    tail = event_name.split(" = ", 1)
    m = _OPCODE_RE.search(tail[1]) if len(tail) == 2 else None
    return m.group(1) if m else trace_reduce.op_short_name(
        event_name).split(".", 1)[0]


def self_times(ops):
    """[(name, start, self_ns, is_leaf)] of one `XLA Ops` line, by start:
    each event's length less the events directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    covered = [0.0] * len(ops)
    stack = []
    for i in order:
        _n, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            covered[stack[-1]] += min(e, ops[stack[-1]][2]) - s
        stack.append(i)
    return [(ops[i][0], ops[i][1],
             max(0.0, ops[i][2] - ops[i][1] - covered[i]), covered[i] == 0)
            for i in order]


def launches_of(modules):
    """A function from an instant to the index of the launch that holds
    it, or None."""
    starts = [m[1] for m in modules]

    def at(t):
        k = bisect.bisect_right(starts, t) - 1
        return k if k >= 0 and t < modules[k][2] else None
    return at


def _op_scopes():
    """The program's `introspect.op_scopes`, or None where the program
    predates it."""
    try:
        from mxnet_tpu.observability import introspect
    except ImportError:
        return None
    return getattr(introspect, "op_scopes", None)


class Records:
    """The instruction maps of the programs a trace names, read once a
    program, and the match of a launch to one of them."""

    def __init__(self, op_scopes):
        self._op_scopes = op_scopes
        self._maps = {}
        self._matched = {}
        self._only_pass = {}
        self.seconds = 0.0

    def maps(self, program):
        if program not in self._maps:
            t0 = time.perf_counter()
            self._maps[program] = self._op_scopes(program) or []
            self.seconds += time.perf_counter() - t0
        return self._maps[program]

    def match(self, program, names):
        """The map of `program` that knows the most of `names`; None where
        the program has none."""
        maps = self.maps(program)
        if not maps:
            return None
        key = (program, names)
        if key not in self._matched:
            self._matched[key] = max(
                maps, key=lambda m: sum(1 for n in names if n in m))
        return self._matched[key]

    def only_pass(self, found):
        """The pass an instruction without one takes in the program of
        map `found`: the one pass its records hold (the recomputed forward
        is the backward's), `UNSPLIT` where they hold several."""
        if id(found) not in self._only_pass:
            held = {"bwd" if r["pass"] == "recompute" else r["pass"]
                    for r in found.values()} - {None}
            self._only_pass[id(found)] = \
                held.pop() if len(held) == 1 else UNSPLIT
        return self._only_pass[id(found)]


def device_rows(dev, records):
    """One device's {(program, pass, op_type, node): {"ns", "instructions":
    {name: ns}}}, its {(program, opcode): ns} of what no node names, and
    what the tiling is checked by (with the time by whose path named it:
    the instruction's own, its fusion's root or fused instructions, its
    reader or operand: the record's `by`)."""
    at = launches_of(dev["modules"])
    by_launch = {}
    for name, start, self_ns, leaf in self_times(dev["ops"]):
        by_launch.setdefault(at(start), []).append(
            (trace_reduce.op_short_name(name), name, self_ns, leaf))
    rows, unnamed = {}, {}
    tally = {"busy_ns": 0.0, "leaf_ns": 0.0, "events": 0, "unknown": 0,
             "named_by": {}}
    for k, evs in by_launch.items():
        program = NO_LAUNCH if k is None else program_of(dev["modules"][k][0])
        names = frozenset(short for short, _n, _ns, _l in evs)
        found = None if k is None else records.match(program, names)
        for short, full, ns, leaf in evs:
            rec = found.get(short) if found is not None else None
            tally["busy_ns"] += ns
            tally["events"] += 1
            if leaf:
                tally["leaf_ns"] += ns
            if found is None:
                key = (program, RECORDLESS, None, program)
            else:
                if rec is None:
                    tally["unknown"] += 1
                    rec = {"node": UNATTRIBUTED, "op_type": None,
                           "pass": None, "opcode": opcode_of(full)}
                pass_ = rec["pass"] or records.only_pass(found)
                key = (program, pass_, rec["op_type"], rec["node"])
                by = rec.get("by") or "nothing"
                tally["named_by"][by] = tally["named_by"].get(by, 0.0) + ns
                if rec["node"] == UNATTRIBUTED:
                    uk = (program, rec["opcode"])
                    unnamed[uk] = unnamed.get(uk, 0.0) + ns
            row = rows.setdefault(key, {"ns": 0.0, "instructions": {}})
            row["ns"] += ns
            row["instructions"][short] = \
                row["instructions"].get(short, 0.0) + ns
    return rows, unnamed, tally


def analyse(ctx):
    """Everything the readers share, made once a run (kept on
    `ctx["reduced"]`); None where there is no trace, no step, the program
    has no `op_scopes`, or no step program of the trace has a record
    (`MXNET_INTROSPECT=0`)."""
    red = ctx.get("reduced")
    steps = ctx["window"]["attempted"]
    if not red or not steps:
        return None
    if "scope_analysis" in red:
        return red["scope_analysis"]
    red["scope_analysis"] = None
    op_scopes = _op_scopes()
    if op_scopes is None:
        return None
    t0 = time.perf_counter()
    records = Records(op_scopes)
    per_device = [device_rows(dev, records)
                  for _plane, dev in sorted(red["events"]["devices"].items())
                  if dev["ops"]]
    if not per_device or not any(
            key[1] != RECORDLESS and key[0].startswith(STEP_PROGRAMS)
            for rows, _u, _t in per_device for key in rows):
        return None
    scale = 1e-6 / len(per_device) / steps      # ns summed -> ms a step
    rows, unnamed = {}, {}
    for dev_rows, dev_unnamed, _t in per_device:
        for key, row in dev_rows.items():
            out = rows.setdefault(key, {"ms": 0.0, "instructions": {}})
            out["ms"] += row["ns"] * scale
            for name, ns in row["instructions"].items():
                out["instructions"][name] = \
                    out["instructions"].get(name, 0.0) + ns * scale
        for key, ns in dev_unnamed.items():
            unnamed[key] = unnamed.get(key, 0.0) + ns * scale
    tally = {k: sum(t[k] for _r, _u, t in per_device)
             for k in ("busy_ns", "leaf_ns", "events", "unknown")}
    named_by = {}
    for _r, _u, t in per_device:
        for by, ns in t["named_by"].items():
            named_by[by] = named_by.get(by, 0.0) + ns * scale
    red["scope_analysis"] = {
        "steps": steps, "devices": len(per_device), "rows": rows,
        "unnamed_by_opcode": unnamed,
        "busy_ms": tally["busy_ns"] * scale,
        "leaf_ms": tally["leaf_ns"] * scale,
        "busy_s": tally["busy_ns"] / 1e9 / len(per_device),
        "leaf_s": tally["leaf_ns"] / 1e9 / len(per_device),
        "events": tally["events"], "unknown_instructions": tally["unknown"],
        "named_by": named_by,
        "op_scopes_s": records.seconds,
        "analyse_s": time.perf_counter() - t0}
    return red["scope_analysis"]


def pass_ms(an, passes):
    """Milliseconds a step under `passes` in the step's own programs."""
    return sum(row["ms"] for (program, pass_, _t, _n), row in an["rows"].items()
               if pass_ in passes and program.startswith(STEP_PROGRAMS))


def scoped_share(an):
    """Percent of the step's own programs' time whose record names a graph
    node or a literal scope."""
    own = {k: row["ms"] for k, row in an["rows"].items()
           if k[0].startswith(STEP_PROGRAMS)}
    total = sum(own.values())
    if not total:
        return None
    named = sum(ms for (_p, pass_, _t, node), ms in own.items()
                if pass_ != RECORDLESS and node != UNATTRIBUTED)
    return 100.0 * named / total


def _sum_by(rows, key_of):
    out = {}
    for key, row in rows.items():
        k = key_of(key)
        out[k] = out.get(k, 0.0) + row["ms"]
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def report(ctx, top=20):
    """The builder's view of one traced run (PERF.md, section 5): device
    milliseconds a step by pass, by program, by operator type and pass,
    the `top` costliest nodes with their instruction count and largest
    instruction, and what no node names by program and HLO opcode."""
    an = analyse(ctx)
    if an is None:
        return None
    rows = an["rows"]
    by_pass = _sum_by(rows, lambda k: k[1])
    by_type = {}
    for (_p, pass_, op_type, node), row in rows.items():
        t = op_type or (node if pass_ == RECORDLESS else UNATTRIBUTED)
        cell = by_type.setdefault(t, {})
        cell[pass_] = cell.get(pass_, 0.0) + row["ms"]
    by_type = dict(sorted(by_type.items(),
                          key=lambda kv: -sum(kv[1].values())))
    nodes = {}
    for (_p, pass_, op_type, node), row in rows.items():
        if pass_ == RECORDLESS or node == UNATTRIBUTED:
            continue
        n = nodes.setdefault(node, {"op_type": op_type, "ms": 0.0,
                                    "by_pass": {}, "instructions": {}})
        n["ms"] += row["ms"]
        n["by_pass"][pass_] = n["by_pass"].get(pass_, 0.0) + row["ms"]
        for name, ms in row["instructions"].items():
            n["instructions"][name] = n["instructions"].get(name, 0.0) + ms
    costliest = []
    for node, n in sorted(nodes.items(), key=lambda kv: -kv[1]["ms"])[:top]:
        largest = max(n["instructions"].items(), key=lambda kv: kv[1])
        costliest.append({"node": node, "op_type": n["op_type"],
                          "ms": n["ms"], "by_pass": n["by_pass"],
                          "instructions": len(n["instructions"]),
                          "largest": list(largest)})
    unnamed = {}
    for (program, opcode), ms in an["unnamed_by_opcode"].items():
        unnamed.setdefault(program, {})[opcode] = ms
    unnamed = {p: dict(sorted(v.items(), key=lambda kv: -kv[1]))
               for p, v in unnamed.items()}
    return {
        "steps": an["steps"], "devices": an["devices"],
        "busy_ms_a_step": an["busy_ms"], "leaf_ms_a_step": an["leaf_ms"],
        "busy_s": an["busy_s"], "leaf_s": an["leaf_s"],
        "trace_busy_s": ctx["reduced"]["busy_s"],
        "events": an["events"],
        "unknown_instructions": an["unknown_instructions"],
        "ms_a_step_by_whose_path": dict(sorted(
            an["named_by"].items(), key=lambda kv: -kv[1])),
        "ms_a_step_by_pass": by_pass,
        "ms_a_step_by_program": _sum_by(rows, lambda k: k[0]),
        "ms_a_step_by_program_and_pass": {
            f"{p}:{q}": ms for (p, q), ms in _sum_by(
                rows, lambda k: (k[0], k[1])).items()},
        "ms_a_step_by_op_type_and_pass": by_type,
        "nodes_named": len(nodes),
        "costliest_nodes": costliest,
        "unnamed_ms_a_step_by_program_and_opcode": unnamed,
        "scoped_device_share": scoped_share(an),
        "op_scopes_s": an["op_scopes_s"], "analyse_s": an["analyse_s"]}
