"""One run of one cell of the benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: finds the cell's files by the names in BENCHMARK.json
(chipbench/cell.py), asserts the chips (no CPU fallback), builds the cell
through the program's normal API, drives its first steps and warms every
shape (set-up), measures for `--seconds`, reads the peak memory, frees the
program, lets the cell's check (chipbench/checks/<check>.py, named by the
traffic file) compare what set-up took with the plain reference and prints
ONE last line of JSON: `correct`, `attempted`, `failed`, `metrics`,
`device`, with `--trace 1` `breakdown`, and last `compared`, every number
that decided `correct` beside its limit (also the last lines of stderr).

`--trace 0` reports the cell's end-to-end metrics.  `--trace 1` traces a
window of at most `TRACE_SECONDS` with the profiler and reports the cell's
per-layer metrics, read from that trace by chipbench/metrics/<reader>.py.

`--rehearsal` debugs the harness on the CPU at the tiny sizes of the
configuration's rehearsal.json: the line says `"rehearsal": true` and holds
no metric: a CPU run gives no device number.
"""
import time
T0 = time.perf_counter()  # process start, to all intents: set-up counts from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import cell as cellmod  # noqa: E402
from chipbench import trace_reduce  # noqa: E402

TRACE_SECONDS = 3.0
WARM_STEPS = 1  # beyond the steps that the check drives in set-up


class NoChip(RuntimeError):
    pass


class CompileCounter:
    """Counts programs compiled, or loaded from the persistent cache, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if "backend_compile" in event or "cache_retrieval" in event:
            self.count += 1


def percentile(values, q):
    """Linear-interpolated percentile (numpy's default)."""
    s = sorted(values)
    if not s:
        return float("nan")
    k = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def window_numbers(start, end, steps, compiled_at, units_per_step):
    """attempted, failed, rate and step-time tail of a window from `start`
    to `end` (when the device had run all of it): `steps` are (completion
    time, loss or exception), `compiled_at` the compile count seen at each
    completion (first entry: at the window's start)."""
    attempted = len(steps)
    failed = 0
    for i, (_t, loss) in enumerate(steps):
        bad = isinstance(loss, Exception) or not math.isfinite(loss)
        if bad or compiled_at[i + 1] != compiled_at[i]:
            failed += 1
    times = [start] + [t for t, _l in steps]
    gaps = [(b - a) * 1e3 for a, b in zip(times, times[1:])]
    elapsed = end - start
    done = attempted - failed
    longest = max(range(len(gaps)), key=gaps.__getitem__) if gaps else None
    return {
        "attempted": attempted, "failed": failed, "completed": done,
        "elapsed_s": elapsed,
        "units_per_s": done * units_per_step / elapsed if steps else 0.0,
        "step_ms_p90": percentile(gaps, 90),
        "step_ms_p50": percentile(gaps, 50),
        # a stall shows here with its place in the window
        "step_ms_max": gaps[longest] if gaps else float("nan"),
        "step_ms_max_at": longest,
        "after_last_read_ms": (end - times[-1]) * 1e3,
    }


def memory_peak(device):
    """Peak bytes held on a chip: the allocator's peak of live arrays plus
    the peak reserved for the compiled programs' temporaries.  On the TPU
    the two are counted apart: `peak_bytes_in_use` holds the arrays (weights,
    optimizer state, batches, a step's outputs) and `peak_bytes_reserved`
    what the running programs take for their temporaries, which for a
    training step are the activations kept for the backward pass.  Read on
    the chip, the Module cell's 9.26 GB reserved are the 9.29 GB of
    `temp_size_in_bytes` that `memory_analysis()` gives for its step program
    compiled ahead of time for the v5e (PERF.md, section 2).  While a step
    runs both are held at once, so the peak of the chip is their sum."""
    st = device.memory_stats() or {}
    return int(st.get("peak_bytes_in_use", 0)) + \
        int(st.get("peak_bytes_reserved", 0))


def read_metrics(cell, kind, ctx):
    """{name: {"value", "unit"}} of the cell's metrics of `kind`; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics[kind]:
        if kind == "end_to_end":
            value = ctx["end_to_end"].get(m["name"])
        else:
            reader = m["name"].split(".", 1)[0]
            mod = cellmod.load_module(
                os.path.join(cellmod.HERE, "metrics", reader + ".py"),
                "chipbench_metric_" + reader)
            value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(name, seed, seconds, trace, rehearsal=False, t0=None):
    """Everything of a run but the argument parsing and the printing."""
    t0 = time.perf_counter() if t0 is None else t0
    import jax
    devs = jax.devices()
    cell = cellmod.Cell(name, seed, rehearsal)
    if not rehearsal:
        if devs[0].platform != "tpu" or len(devs) < cell.chips:
            raise NoChip(f"{name} needs {cell.chips} TPU chip(s); JAX sees "
                         f"{len(devs)} x {devs[0].platform!r}")
        peaks_row = cellmod.peaks(devs[0].device_kind)
    else:
        peaks_row = None
    import mxnet_tpu as mx
    # JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    mx.base.enable_compile_cache(default_to_checkout=True)
    compiles = CompileCounter()
    used = devs[:cell.chips]

    entry = cell.entry_mod.Entry(cell)
    entry.build()
    taken = cell.check.before(cell, entry)
    entry.drive(steps=WARM_STEPS)
    entry.wait()
    setup_s = time.perf_counter() - t0

    length = min(seconds, TRACE_SECONDS) if trace else seconds
    compiled_at = [compiles.count]
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # user spans only (the entries' and the program's TraceAnnotations):
        # at level 2 the runtime's own host events made the Gluon ResNet
        # cell's trace, 238 launches a step, take four minutes to write and
        # read (chip run, PR 24)
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            steps = entry.drive(
                seconds=length,
                clock=lambda _s: compiled_at.append(compiles.count))
            # the window ends when the device has run all of its steps (the
            # loop's last read returns before the last update has run);
            # wait() launches nothing, so the trace holds the window's work
            entry.wait()
            end = time.perf_counter()
    finally:
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            t_stop = time.perf_counter() - t_stop
    while len(compiled_at) < len(steps) + 1:
        compiled_at.append(compiles.count)
    win = window_numbers(start, end, steps, compiled_at,
                         cell.units_per_step())

    peak = max(memory_peak(d) for d in used)
    stats = max((d.memory_stats() or {} for d in used),
                key=lambda st: st.get("peak_bytes_in_use", 0))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}

    unit = cell.flops.unit(cell.cfg, cell.traffic)
    ctx = {"cell": cell, "peaks": peaks_row, "window": win,
           "end_to_end": {unit + "_per_s": win["units_per_s"],
                          "step_ms_p90": win["step_ms_p90"],
                          "setup_s": setup_s}}
    breakdown = launches = idle_by = None
    if trace:
        t_read = time.perf_counter()
        try:
            reduced = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(tdir)))
        except ValueError:
            if not rehearsal:  # a CPU's trace has no device plane
                raise
            reduced = None
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        t_read = time.perf_counter() - t_read
    if trace and reduced is not None:
        ctx["reduced"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        launches = reduced["modules"]
        idle_by = reduced["idle_by_host_span"]
    metrics = {} if rehearsal else read_metrics(
        cell, "per_layer" if trace else "end_to_end", ctx)

    # the program's state goes before the reference comes
    entry.free()
    del entry
    ctx.pop("reduced", None)
    gc.collect()
    t_check = time.perf_counter()
    ok, compared, detail = cell.check.after(cell, taken)
    t_check = time.perf_counter() - t_check
    if not cell.limits:
        ok = False  # a cell without limits has not been proven
    result = {"correct": bool(ok and win["completed"] > 0),
              "attempted": win["attempted"], "failed": win["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if rehearsal:
        result["rehearsal"] = True
    result["run"] = {"workload": name, "seed": cell.seed,
                     "seconds": seconds, "trace": int(bool(trace)),
                     "window_s": win["elapsed_s"],
                     "steps_completed": win["completed"],
                     "step_ms_p50": win["step_ms_p50"],
                     "step_ms_max": win["step_ms_max"],
                     "step_ms_max_at": win["step_ms_max_at"],
                     "after_last_read_ms": win["after_last_read_ms"],
                     "setup_s": setup_s, "check_s": t_check,
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                     "peak_bytes_reserved": stats.get("peak_bytes_reserved"),
                     **detail}
    if trace:
        result["run"]["launches_by_program"] = launches
        result["run"]["idle_s_by_host_span"] = idle_by
        result["run"]["trace_stop_s"] = t_stop
        result["run"]["trace_read_s"] = t_read
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU; prints no metric")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), args.rehearsal, t0=T0)
    except NoChip as exc:
        sys.stderr.write(f"chipbench: {exc}; there is no CPU fallback "
                         "(--rehearsal debugs the harness on the CPU)\n")
        return 2
    sys.stdout.flush()
    for name, rec in result["compared"].items():
        sys.stderr.write(f"compared {name} value {rec['value']!r} "
                         f"limit {rec['limit']!r}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
