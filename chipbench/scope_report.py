"""The builder's view of where one traced run's device time went, for
PERF.md section 5:

    python3 chipbench/scope_report.py --workload <cell> --seed <n> \
        [--seconds 30] [--out chiprun_out/<file>.json]

One `--trace 1` run of the cell through `chipbench/run.py`'s own
`run_cell`, with `scope_reduce.report` taken from what the readers are
given: device milliseconds a step by pass, by program, by operator type
and pass, the twenty costliest graph nodes (instruction count, largest
instruction), what no node names by HLO opcode; and what the join cost:
seconds in `op_scopes` (compile or cache read, text, parse), programs
loaded or compiled while the readers ran, bytes of HLO text parsed, the
lowered modules' size, the allocator's reading before and after.  Prints
the run's result line and then the report; the benchmark itself never runs
this file.
"""
import argparse
import json
import os
import resource
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run, scope_reduce  # noqa: E402


def _allocator():
    import jax
    st = jax.devices()[0].memory_stats() or {}
    return {k: st.get(k) for k in ("bytes_in_use", "bytes_reserved",
                                   "peak_bytes_in_use", "bytes_limit")}


def _rss_bytes():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def cost_of_join(seen, before):
    """What reading the names added to the run."""
    from mxnet_tpu.observability import introspect
    sources = introspect.program_sources(sizes=True) \
        if hasattr(introspect, "program_sources") else []
    return {"readers_s": seen["readers_s"],
            "programs_loaded_by_readers": seen["loads"],
            "hlo_text_bytes": sum(s["text_bytes"] for s in sources),
            "lowered_bytes": sum(s["lowered_bytes"] or 0 for s in sources),
            "instructions_named": sum(s["instructions"] or 0
                                      for s in sources),
            "sources": sources,
            "rss_peak_bytes_before": before["rss"],
            "rss_peak_bytes_after": _rss_bytes(),
            "allocator_before": before["allocator"],
            "allocator_after": _allocator()}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seen = {}
    read_metrics = run.read_metrics

    def read_and_keep(cell, kind, ctx):
        if "reduced" not in ctx:
            return read_metrics(cell, kind, ctx)
        import jax.monitoring
        loads = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, _s, **_kw: loads.append(event)
            if "backend_compile" in event or "cache_retrieval" in event
            else None)
        before = {"rss": _rss_bytes(), "allocator": _allocator()}
        t0 = time.perf_counter()
        rep = scope_reduce.report(ctx)
        seen.update(readers_s=time.perf_counter() - t0, loads=len(loads))
        seen["report"] = rep
        seen["cost"] = cost_of_join(seen, before)
        return read_metrics(cell, kind, ctx)

    run.read_metrics = read_and_keep
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True,
                              t0=run.T0)
    except run.NoChip as exc:
        sys.stderr.write(f"scope_report: {exc}\n")
        return 2
    out = {"scope_report": seen.get("report"), "cost": seen.get("cost")}
    print(json.dumps(result), flush=True)
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"result": result, **out}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
