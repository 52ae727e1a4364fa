"""Device time of the optimizer's update a step: the durations of the
`XLA Modules` events of the optimizer's own programs (`jit_mx_fused_update`,
`jit_mx_sparse_update`; averaged over the chips used) over the steps the
loop completed in the traced window.  An update fused into a whole-step
program has no event of its own and is not read.  Source: device_trace.
Layer: the step path (optimizer).

No such launch in the trace (a program that names its update otherwise):
None, never 0."""
from chipbench import span_reduce


def read(ctx):
    red = ctx.get("reduced")
    steps = ctx["window"]["attempted"]
    if not red or not steps:
        return None
    seconds, _launches = span_reduce.update_device_seconds(red["events"])
    if seconds is None:
        return None
    return 1e3 * seconds / steps
