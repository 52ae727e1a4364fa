"""Device time of the collective operations a step, per chip: the
durations of the `XLA Ops` events that are collectives (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute, and the fusions
XLA names after them), summed on each chip, averaged over the chips that
ran anything, over the steps of the traced window.  It is the time the
collectives hold the device, whether or not compute could have run beside
them.  Source: device_trace.  Layer: the step path (kvstore, the mesh).

A trace without such an event (one chip; a program with no collective):
None, never 0."""
from chipbench.trace_reduce import op_short_name

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def is_collective(hlo_text):
    return op_short_name(hlo_text).startswith(COLLECTIVES)


def read(ctx):
    red = ctx.get("reduced")
    steps = ctx["window"]["attempted"]
    if not red or not steps:
        return None
    per_chip = []
    for dev in red["events"]["devices"].values():
        if dev["ops"]:
            per_chip.append(sum((e - s) / 1e9 for name, s, e in dev["ops"]
                                if is_collective(name)))
    if not per_chip or sum(per_chip) <= 0:
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / steps
