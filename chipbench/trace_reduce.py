"""From the profiler's trace (`*.xplane.pb`) to what the per-layer metrics
read: the device's busy union, the program launches, per-operation sums
and the idle gaps with what the host was doing in them.

What a TPU trace holds (looked at by hand, PR 24, jax 0.9.0 / libtpu
0.0.34): one plane `/device:TPU:<i>` per chip with the lines `XLA Modules`
(one event per launch of a compiled program, named `jit_<name>(<hash>)`),
`XLA Ops` (one event per HLO operation as it ran, named by its HLO text
`%fusion.3 = bf16[...] fusion(...)`), `Async XLA Ops` (copies in flight,
which overlap the operations and are not counted as busy) and `Steps`;
and a plane `/host:CPU` with one line per thread, `TraceAnnotation` spans
on the line of the thread that opened them.  Times are nanoseconds; the
device plane's clock ran some 0.6 ms ahead of the host plane's in the trace
recorded under tests/ (the first launch starts before the host call that
made it).
"""
import glob
import os

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "chipbench_window"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def load(path, host_line_prefixes=("python",)):
    """{"devices": {plane: {"modules": [...], "ops": [...]}}, "host":
    [...]}; every event a (name, start_ns, end_ns) tuple, sorted."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            rec = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OPS_LINE: "ops"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    rec[key].append((e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)))
                rec[key].sort(key=lambda ev: ev[1])
            devices[plane.name] = rec
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if not line.name.startswith(tuple(host_line_prefixes)):
                    continue
                for e in line.events:
                    host.append((e.name, float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)))
    host.sort(key=lambda ev: ev[1])
    return {"devices": devices, "host": host}


def union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(trace):
    """(start, end) of the traced window on the host's clock: the harness's
    own `chipbench_window` span where the trace has it, else from the first
    program launch to the end of the last."""
    for name, s, e in trace["host"]:
        if name == WINDOW_SPAN:
            return s, e
    starts = [m[1] for d in trace["devices"].values() for m in d["modules"]]
    ends = [m[2] for d in trace["devices"].values() for m in d["modules"]]
    if not starts:
        raise ValueError("the trace holds no program launch on a device")
    return min(starts), max(ends)


def op_short_name(name):
    """`%fusion.3 = bf16[...] fusion(...)` -> `fusion.3`."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def label_gap(host, s, e):
    """The host span that overlaps [s, e] most; the shortest among equals
    (the innermost).  `host_idle` where none does."""
    best, best_key = "host_idle", (0.0, 0.0)
    for name, hs, he in host:
        if name == WINDOW_SPAN:
            continue
        if hs >= e:
            break
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        key = (ov, -(he - hs))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(trace, top_ops=10, top_gaps=5, labelled_gaps=200):
    """The numbers the metric readers take.  Seconds throughout; busy and
    launches are averaged over the devices that ran anything.

    The profiler is started just before the window and stopped once the
    device has run everything the window queued, so every device event of
    the trace is the window's work: none is clipped.  (The device plane's
    clock runs some 0.6 ms ahead of the host plane's in the recorded trace
    under tests/, so clipping device events to a host span would lose the
    first launches.)  The window's length is the host span's; an idle gap
    is labelled by the host span that overlaps it most, which the offset
    can only blur for gaps of a millisecond or less.  Only the
    `labelled_gaps` longest gaps are labelled (a loop of hundreds of small
    launches a step leaves tens of thousands of gaps a few microseconds
    long); the rest is summed under `shorter_gaps`."""
    lo, hi = window_of(trace)
    window_s = (hi - lo) / 1e9
    busy, launches, op_sums, gaps = [], [], {}, []
    modules = {}
    for _plane, dev in sorted(trace["devices"].items()):
        if not dev["modules"] and not dev["ops"]:
            continue
        source = dev["ops"] or dev["modules"]
        merged = union([(s, e) for _n, s, e in source])
        busy_s = sum(e - s for s, e in merged) / 1e9
        busy.append(busy_s)
        launches.append(len(dev["modules"]))
        for name, _s, _e in dev["modules"]:
            short = name.split("(", 1)[0]
            modules[short] = modules.get(short, 0) + 1
        for name, s, e in dev["ops"]:
            short = op_short_name(name)
            op_sums[short] = op_sums.get(short, 0.0) + (e - s) / 1e9
        if not gaps:  # gaps of the first device that ran: one host drives all
            for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
                gaps.append((e0, s1))
            hull_s = (merged[-1][1] - merged[0][0]) / 1e9
            edges_s = max(0.0, window_s - hull_s)
    if not busy:
        raise ValueError("no operation ran on a device in the traced window")
    n = len(busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [[label_gap(trace["host"], gs, ge), (ge - gs) / 1e9]
                for gs, ge in gaps[:labelled_gaps]]
    by_label = {"window_edges": edges_s} if edges_s else {}
    for lab, secs in labelled:
        by_label[lab] = by_label.get(lab, 0.0) + secs
    if len(gaps) > labelled_gaps:
        by_label["shorter_gaps"] = sum(
            ge - gs for gs, ge in gaps[labelled_gaps:]) / 1e9
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n,
        "devices": n,
        "launches": sum(launches) / n,
        "modules": modules,
        "op_seconds": op_sums,
        "device_ops": [[k, v] for k, v in sorted(
            op_sums.items(), key=lambda kv: -kv[1])[:top_ops]],
        "idle_gaps": labelled[:top_gaps],
        "idle_by_host_span": by_label,
        "events": trace,
    }
