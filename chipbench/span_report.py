"""The builder's longer view of one traced run, for PERF.md section 5:

    python3 chipbench/span_report.py --workload <cell> --seed <n> \
        [--seconds 30] [--out chiprun_out/<file>.json]

One `--trace 1` run of the cell through `chipbench/run.py`'s own
`run_cell`, with `span_reduce.report` taken from what the readers are
given: per step, idle and self milliseconds by innermost span, the clock
offset between the trace's planes, the three-way split of the idle share,
the device operations over a millisecond a step.  Prints the run's result
line and then the report; the benchmark itself never runs this file.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run, span_reduce  # noqa: E402


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
        if "reduced" in ctx:
            seen.update(span_reduce.report(ctx))
        return read_metrics(cell, kind, ctx)

    run.read_metrics = read_and_keep
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True,
                              t0=run.T0)
    except run.NoChip as exc:
        sys.stderr.write(f"span_report: {exc}\n")
        return 2
    print(json.dumps(result), flush=True)
    print(json.dumps({"span_report": seen}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"result": result, "span_report": seen}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
