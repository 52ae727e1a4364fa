"""Run sets of runs of one cell, one process each, and print the spreads
the bounds are set from.

    python3 chipbench/sets.py --workload <name> --seeds 11,12,13 \
        --seconds 30 [--trace 0] [--sets 2] [--out chiprun_out/<file>.jsonl]

Each set runs every seed once, in order; the same seeds in every set.  Per
metric it prints the median and the spread (distance between the first and
third quartile of `statistics.quantiles(values, n=4)` as a share of the
median) of each set.  This process never touches JAX: each run is a child
of its own, which holds the chip alone and has ended before the next
starts.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            t0 = time.time()
            proc = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"),
                 "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True)
            wall = time.time() - t0
            lines = proc.stdout.strip().splitlines()
            try:
                row = json.loads(lines[-1])
            except (IndexError, ValueError):
                sys.stderr.write(f"run seed {seed} gave no result "
                                 f"(exit {proc.returncode}):\n"
                                 f"{proc.stderr[-4000:]}\n")
                return 1
            row["wall_s"] = wall
            row["set"] = k
            rows.append(row)
            print(json.dumps(row), flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            if not row["correct"]:
                sys.stderr.write(proc.stderr[-2000:] + "\n")
        sets.append(rows)
    for k, rows in enumerate(sets):
        names = sorted(rows[0]["metrics"])
        for name in names:
            vals = [r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]]
            print(f"set {k} {name}: median {statistics.median(vals):.6g} "
                  f"spread {spread(vals):.4%} n {len(vals)} "
                  f"min {min(vals):.6g} max {max(vals):.6g}")
        print(f"set {k} correct: {sum(r['correct'] for r in rows)}/{len(rows)}"
              f" peak GiB {max(r['device']['memory_peak_bytes'] for r in rows) / 2**30:.2f}"
              f" wall s {[round(r['wall_s']) for r in rows]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
