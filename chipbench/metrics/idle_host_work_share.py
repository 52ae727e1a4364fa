"""Share of the traced window in which the device idled while the host
worked under one of the program's own spans: the device's idle intervals
(window minus the union of `XLA Ops`, the device plane shifted onto the
host's clock) that fall on instants whose innermost open `mx.` span is not
`mx.sync.read`, over the window.  The rest of `device_idle_share` is idle
while the host was blocked in `mx.sync.read` (launch and read latency) or
outside every program span (the user's loop): chipbench/span_reduce.py.
Source: device_trace.  Layer: the step path.

Nothing to read (a program without the spans, or without a `jit_mx_*`
launch to set the clocks by): None, never 0."""
from chipbench import span_reduce


def read(ctx):
    red = ctx.get("reduced")
    if not red:
        return None
    an = span_reduce.analyse(red["events"])
    if an is None:
        return None
    return span_reduce.idle_shares(an)["host_work"]
