"""The blocked cross-entropy's share of its roofline: the least time the
chip could take for the REQUIRED work of a step's loss over the tied
matrix (three products of N x V x D: the logits, dh and dW, 6 N V D
operations; the matrix read twice and its gradient written once, in the
activations' dtype; chipbench/kernel_cost.py `least_seconds`), with N, V
and D from the configuration's flops.py `head_loss_shape`, over the device
time a step under the op: the self times of the step programs' `XLA Ops`
events whose record (`introspect.op_scopes`, joined by
chipbench/scope_reduce.py) names the operator
`_contrib_blocked_cross_entropy`, or the op's own scope
`blocked_cross_entropy` where no graph node is on the path, forward and
backward, averaged over the chips, over the steps of the traced window.
Source: device_trace.  Layer: kernels (ops/decoder.py
`blocked_cross_entropy`).

A configuration whose flops.py states no such shape, a program without
the op or without `op_scopes`, or no trace: None, never 0."""
from chipbench import kernel_cost, scope_reduce

OP = "_contrib_blocked_cross_entropy"
SCOPE = "blocked_cross_entropy"


def loss_cost(tokens, vocab, dim, bytes_per_element=2):
    """(FLOPs, bytes) of a step's blocked cross-entropy."""
    return 6 * tokens * vocab * dim, 3 * vocab * dim * bytes_per_element


def read(ctx):
    peaks, cell = ctx["peaks"], ctx["cell"]
    shape = getattr(cell.flops, "head_loss_shape", None)
    if peaks is None or shape is None:
        return None
    an = scope_reduce.analyse(ctx)
    if an is None:
        return None
    ms = sum(row["ms"] for (prog, _q, op, node), row in an["rows"].items()
             if prog.startswith(scope_reduce.STEP_PROGRAMS)
             and (op == OP or node == SCOPE))
    if ms <= 0:
        return None
    least, _bound = kernel_cost.least_seconds(
        *loss_cost(*shape(cell.cfg, cell.traffic)), peaks)
    return 100.0 * least / (ms / 1e3)
