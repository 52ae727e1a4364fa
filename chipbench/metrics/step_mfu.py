"""The whole step's share of the chip's peak: required operations per unit
of work (the configuration's flops.py; nothing recomputed is counted)
times units completed per second of the traced window, over chips times
the published bf16 peak (chipbench/peaks.json).  Source: host_clock (the
rate is the loop's own count of steps over the traced window's length by
the host's clock; read in the traced run only).  Layer: whole step."""


def read(ctx):
    cell, win, peaks = ctx["cell"], ctx["window"], ctx["peaks"]
    if peaks is None or "reduced" not in ctx or not win["completed"]:
        return None
    per_unit = cell.flops.train_flops_per_unit(cell.cfg, cell.traffic)
    return 100.0 * per_unit * win["units_per_s"] / (
        cell.chips * peaks["bf16_flops_per_s"])
