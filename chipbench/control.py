"""Readings the limits are set from, at the cell's own size, many seeds in
one process (set-up is long; the chip is held once).

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \
        [--what program,fp8,half_batch] [--rehearsal]

For each seed the plain reference (float32, "highest") follows the cell's
first three steps once, and each reading of `--what` is compared with it,
one JSON line a reading, every number of the cell's check beside its limit:

  program     the program itself: the entry built as a run builds it, its
              first three steps driven through the check's own `before`,
              then freed (the lower readings: the largest over a dozen seeds)
  fp8         the control: the reference put in the program's place with
              both operands of every convolution and matrix product in
              float8_e4m3, the nearest precision under the bfloat16 the
              configurations state (the upper reading: its smallest)
  half_batch  the fault "half of the batch left out, the mean taken over
              the rest", planted in the reference put in the program's place

The benchmark's own runs never run this.  A state left unchanged reads 1 by
the measure and needs no run.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import cell as cellmod  # noqa: E402

KINDS = {"fp8": dict(precision="fp8"), "half_batch": dict(half_batch=True)}


def program_side(cell):
    """What the check takes from the program in a run's set-up."""
    entry = cell.entry_mod.Entry(cell)
    entry.build()
    try:
        return cell.check.before(cell, entry)
    finally:
        entry.free()
        del entry
        gc.collect()


def readings(name, seed, what, rehearsal=False):
    cell = cellmod.Cell(name, seed, rehearsal)
    check = cell.check
    sides = {}
    if "program" in what:  # first: the reference comes once it is freed
        sides["program"] = program_side(cell)
    ref_ = check.run_reference(cell.ref, cell.opt, cell.cfg, cell.traffic,
                               cell.seed)
    out = []
    for kind in what:
        got = sides.get(kind) or check.run_reference(
            cell.ref, cell.opt, cell.cfg, cell.traffic, cell.seed,
            **KINDS[kind])
        numbers, worst = check.compare(got, ref_)
        ok, compared = check.judge(numbers, cell.limits)
        out.append({"workload": name, "seed": seed, "kind": kind,
                    "correct": ok, "worst_leaf": worst, "compared": compared,
                    # every leaf's norm on both sides, for the look that
                    # PERF.md section 2 asks of a number that does not
                    # separate its readings
                    "norms": {k: got[k] for k in ("grad_norm", "dparam_norm")},
                    "reference": {k: ref_[k] for k in ("grad_norm",
                                                       "dparam_norm")}})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="fp8,half_batch")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    import jax
    if not args.rehearsal and jax.devices()[0].platform != "tpu":
        sys.stderr.write("control: the cell's own size needs the chip "
                         "(--rehearsal reads the tiny size on the CPU)\n")
        return 2
    import mxnet_tpu as mx  # enables x64, as the program's process has it
    mx.base.enable_compile_cache(default_to_checkout=True)
    for seed in args.seeds.split(","):
        for row in readings(args.workload, int(seed), args.what.split(","),
                            args.rehearsal):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
