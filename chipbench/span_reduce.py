"""From the program's own spans (`mx.*`, mxnet_tpu/observability/tracing.py)
and the device's events, both in the profiler's one trace, to what the host
did while the chip idled.  Shared by the readers idle_host_work_share,
host_ms_per_step and update_device_ms; `report` is the builder's longer
view for PERF.md (chipbench/span_report.py prints it).

Input is what `trace_reduce.load` returns (`ctx["reduced"]["events"]`):
host events as `(name, start_ns, end_ns)` of every python thread merged,
and per device plane its `modules` (one event a program launch) and `ops`.
Names and times only.

- **Window.**  The harness's `chipbench_window` span; only `mx.` spans
  that start inside it count.
- **Innermost span.**  At an instant, the open `mx.` span that started
  last (the shortest among equals).  On one thread spans nest, so this is
  nesting by containment; a span's *self* intervals are those where it is
  the innermost, and the self intervals of all spans tile the union of the
  spans: nothing is counted twice.
- **Clock offset.**  The device plane's clock is not the host plane's.
  A launch cannot start on the device before the host span that issued it
  opened, so the k-th launch of a program is held against the k-th span
  of the kind that launches it (`LAUNCHED_UNDER`), and the device plane is
  shifted later by the least amount that puts every such launch at or
  after its span's start: `max(0, max_k(span_start_k - launch_start_k))`.
  A device that is never idle starts its launches long after they were
  issued and gives no bound: the offset then reads 0.
- **Idle intervals.**  The complement, within the window, of the union of
  the (shifted) `XLA Ops` intervals of the first device that ran anything:
  one host drives all devices, as in `trace_reduce.reduce`.
"""
from chipbench import trace_reduce

SPAN_PREFIX = "mx."
SYNC_SPAN = "mx.sync.read"
#: compiled program (`XLA Modules` event `jit_<name>(<hash>)`) -> the span
#: whose body issues its launch
LAUNCHED_UNDER = {
    "jit_mx_executor_fwd": "mx.executor.launch",
    "jit_mx_executor_fwd_bwd": "mx.executor.launch",
    "jit_mx_module_fused_step": "mx.executor.launch",
    "jit_mx_cachedop_fwd": "mx.cachedop.forward",
    "jit_mx_cachedop_bwd": "mx.cachedop.backward",
    "jit_mx_fused_update": "mx.optimizer.update_all",
    "jit_mx_sparse_update": "mx.optimizer.update_all",
}
#: the optimizer's own programs (the update fused into a whole-step
#: program has no device time of its own to read)
UPDATE_PROGRAMS = ("jit_mx_fused_update", "jit_mx_sparse_update")
OUTSIDE = "outside_spans"


def program_of(module_event_name):
    """`jit_mx_fused_update(123)` -> `jit_mx_fused_update`."""
    return module_event_name.split("(", 1)[0]


def window_spans(trace):
    """(lo, hi, [(name, start, end)] of the `mx.` spans that start inside
    the window, clipped to it, by start)."""
    lo, hi = trace_reduce.window_of(trace)
    out = [(n, s, min(e, hi)) for n, s, e in trace["host"]
           if n.startswith(SPAN_PREFIX) and lo <= s < hi]
    out.sort(key=lambda ev: (ev[1], -ev[2]))
    return lo, hi, out


def innermost_segments(spans):
    """[(start, end, name)]: the innermost open span over time, adjacent
    segments of one name merged; instants under no span are left out."""
    points = sorted({t for _n, s, e in spans for t in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    out, active, nxt = [], [], 0
    for a, b in zip(points, points[1:]):
        while nxt < len(order) and spans[order[nxt]][1] <= a:
            active.append(order[nxt])
            nxt += 1
        active = [i for i in active if spans[i][2] > a]
        if not active:
            continue
        # started last; the shorter of two that started together
        top = max(active, key=lambda i: (spans[i][1], -spans[i][2]))
        name = spans[top][0]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return [tuple(seg) for seg in out]


def self_seconds(segments):
    """{span name: seconds in which it was the innermost}."""
    out = {}
    for s, e, name in segments:
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def first_device(trace):
    """Events of the first device plane that ran anything, or None."""
    for _plane, dev in sorted(trace["devices"].items()):
        if dev["modules"] or dev["ops"]:
            return dev
    return None


def clock_offset_ns(spans, modules):
    """(shift to add to device times, launches it rests on).  (None, 0)
    where the trace holds no `jit_mx_*` launch; a kind whose launches and
    spans differ in number (a launch outside the window) is left out, and
    with every kind left out the shift reads 0 on 0 launches."""
    by_span, launches = {}, {}
    for name, s, _e in spans:
        by_span.setdefault(name, []).append(s)
    for name, s, _e in modules:
        kind = LAUNCHED_UNDER.get(program_of(name))
        if kind is not None:
            launches.setdefault(kind, []).append(s)
    if not launches:
        return None, 0
    worst, paired = 0.0, 0
    for kind, starts in launches.items():
        opened = by_span.get(kind, [])
        if len(opened) != len(starts):
            continue
        for span_start, launch_start in zip(sorted(opened), sorted(starts)):
            worst = max(worst, span_start - launch_start)
            paired += 1
    return worst, paired


def idle_intervals(dev, lo, hi, shift):
    """The window minus the union of the device's shifted operations."""
    source = dev["ops"] or dev["modules"]
    busy = trace_reduce.union([(max(s + shift, lo), min(e + shift, hi))
                               for _n, s, e in source])
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def overlap_by_name(intervals, segments):
    """{segment name: ns of `intervals` under it, OUTSIDE: ns under none}.
    Both lists are sorted and disjoint within themselves."""
    out, j = {}, 0
    covered = 0.0
    for s, e in intervals:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            ov = min(e, segments[k][1]) - max(s, segments[k][0])
            if ov > 0:
                name = segments[k][2]
                out[name] = out.get(name, 0.0) + ov
                covered += ov
            k += 1
    total = sum(e - s for s, e in intervals)
    out[OUTSIDE] = total - covered
    return out


def analyse(trace):
    """Everything the readers share, or None where the trace holds no
    `mx.` span in the window or no `jit_mx_*` launch to set the clocks by
    (the program predates the spans, or ran another path)."""
    dev = first_device(trace)
    if dev is None:
        return None
    lo, hi, spans = window_spans(trace)
    if not spans:
        return None
    shift, paired = clock_offset_ns(spans, dev["modules"])
    if shift is None:
        return None
    segments = innermost_segments(spans)
    idle = idle_intervals(dev, lo, hi, shift)
    return {"window_ns": hi - lo, "spans": spans, "segments": segments,
            "self_s": self_seconds(segments), "offset_ns": shift,
            "offset_launches": paired, "idle": idle,
            "idle_ns_by_span": overlap_by_name(idle, segments)}


def idle_shares(an):
    """Percent of the window the device idled, split three ways: while the
    host worked under a program span, while it was blocked in
    `mx.sync.read` (launch and read latency), outside every span (the
    user's loop).  The three add up to the device's idle share."""
    by = an["idle_ns_by_span"]
    work = sum(v for k, v in by.items() if k not in (SYNC_SPAN, OUTSIDE))
    scale = 100.0 / an["window_ns"]
    return {"host_work": work * scale,
            "sync_read": by.get(SYNC_SPAN, 0.0) * scale,
            "outside": by[OUTSIDE] * scale}


def update_device_seconds(trace):
    """(device seconds in the optimizer's own programs, launches), averaged
    over the devices that ran one."""
    sums, counts = [], []
    for _plane, dev in sorted(trace["devices"].items()):
        evs = [(e - s) / 1e9 for n, s, e in dev["modules"]
               if program_of(n) in UPDATE_PROGRAMS]
        if evs:
            sums.append(sum(evs))
            counts.append(len(evs))
    if not sums:
        return None, 0
    return sum(sums) / len(sums), sum(counts) / len(counts)


def report(ctx):
    """The builder's view of one traced run (PERF.md, section 5): per step,
    idle and self milliseconds by innermost span, the clock offset, the
    device operations over a millisecond a step."""
    red = ctx["reduced"]
    steps = max(ctx["window"]["attempted"], 1)
    an = analyse(red["events"])
    out = {"steps": steps, "window_s": red["window_s"],
           "busy_s": red["busy_s"],
           "device_idle_share": 100.0 * (1 - red["busy_s"] / red["window_s"]),
           "ops_over_1ms_a_step": [
               [k, 1e3 * v / steps] for k, v in sorted(
                   red["op_seconds"].items(), key=lambda kv: -kv[1])
               if 1e3 * v / steps >= 1.0]}
    if an is None:
        return out
    out.update({
        "clock_offset_ms": an["offset_ns"] / 1e6,
        "clock_offset_launches": an["offset_launches"],
        "idle_shares": idle_shares(an),
        "idle_ms_a_step_by_innermost_span": {
            k: v / 1e6 / steps for k, v in sorted(
                an["idle_ns_by_span"].items(), key=lambda kv: -kv[1])},
        "self_ms_a_step_by_span": {
            k: 1e3 * v / steps for k, v in sorted(
                an["self_s"].items(), key=lambda kv: -kv[1])},
        "spans_a_step": len(an["spans"]) / steps})
    return out
