"""Device time of the forward pass a step: the self times of the `XLA Ops`
events (a container counts only what its body does not cover) of the
step's own programs (`jit_mx_*`) whose instruction the program's own record
(`introspect.op_scopes`: the `op_name` path of the optimized HLO) puts in
the pass `fwd`, summed on each chip, averaged over the chips that ran
anything, over the steps the loop completed in the traced window.  In the
Module cells one program holds both passes and only the scopes split it;
an instruction without a scope takes the pass of a program whose records
hold one (`jit_mx_cachedop_fwd`) and counts in neither where they hold
several (`unsplit`): chipbench/scope_reduce.py.  Source: device_trace.
Layer: step path.

Nothing to read (a program without `op_scopes`, `MXNET_INTROSPECT=0`, no
trace): None, never 0."""
from chipbench import scope_reduce


def read(ctx):
    an = scope_reduce.analyse(ctx)
    if an is None:
        return None
    return scope_reduce.pass_ms(an, ("fwd",))
