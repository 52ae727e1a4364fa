"""The instrument's own health: the share of the device time of the step's
own programs (`jit_mx_*`; self times of their `XLA Ops` events) whose
instruction the program's record (`introspect.op_scopes`) names: a graph
node, or a literal scope (`optimizer`, `flash_bwd_dkv`).  The name is the
instruction's own `op_name` path where it has one; a fusion XLA left
without one takes its root's or its fused instructions', and what XLA made
with no path at all (a layout change, a copy) its reader's, else its
operand's (the record's `by`; scope_report prints the time under each).
Below 90 the tables of chipbench/scope_report.py are not to be trusted, and
an operator that a later PR adds without a scope, or a program without a
record, shows here.  Source: device_trace.  Layer: whole step.

Nothing to read: None, never 0."""
from chipbench import scope_reduce


def read(ctx):
    an = scope_reduce.analyse(ctx)
    if an is None:
        return None
    return scope_reduce.scoped_share(an)
