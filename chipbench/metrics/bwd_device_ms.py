"""Device time of the backward pass a step: as `fwd_device_ms`, for the
passes `bwd` (an `op_name` path under a `transpose(`) and `recompute` (under
a checkpoint's `rematted_computation`: since PR 30 the Gluon backward
program runs the element-wise forward again) together: what the backward
pass costs; chipbench/scope_report.py prints the two apart.  Source:
device_trace.  Layer: step path.

Nothing to read: None, never 0."""
from chipbench import scope_reduce


def read(ctx):
    an = scope_reduce.analyse(ctx)
    if an is None:
        return None
    return scope_reduce.pass_ms(an, ("bwd", "recompute"))
