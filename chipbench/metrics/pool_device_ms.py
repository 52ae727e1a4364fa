"""Device time a step under the pooling layers, forward and backward: the
self times of the step programs' (`jit_mx_*`) `XLA Ops` events whose record
(`introspect.op_scopes`, joined by chipbench/scope_reduce.py) names a graph
node whose registered operator is `Pooling`, in every pass, summed on each
chip, averaged over the chips that ran anything, over the steps of the
traced window.  In the ResNet cells: the stem's 3 x 3 stride-2 max pooling
and the global average pooling before the classifier.  Source:
device_trace.  Layer: kernels (ops/nn.py `Pooling`).

A program without a pooling node, or without `op_scopes`, or no trace:
None, never 0."""
from chipbench import scope_reduce

POOL_OP = "Pooling"


def read(ctx):
    an = scope_reduce.analyse(ctx)
    if an is None:
        return None
    ms = [row["ms"] for (program, _q, op, _n), row in an["rows"].items()
          if op == POOL_OP and program.startswith(scope_reduce.STEP_PROGRAMS)]
    return sum(ms) if ms else None
