"""Device time a step under the compressed convolutional attention blocks,
forward and backward: the self times of the step programs' (`jit_mx_*`)
`XLA Ops` events whose record (`introspect.op_scopes`, joined by
chipbench/scope_reduce.py) names a graph node of such a block: the mixing
op (`cca_mix`), the attention kernels it feeds, the block's projections
and what lies between them, in every pass, summed on each chip, averaged
over the chips that ran anything, over the steps of the traced window.  A
block is found by its mixing node: the nodes that share that node's
prefix (`..._l0_attn_`) are the block's.  Not a roofline: XLA fuses the
mixing's element-wise work into the products' roots (PERF.md section 7).
Source: device_trace.  Layer: attention (gluon/model_zoo/decoder.py
`CompressedConvAttention`, ops/decoder.py `cca_mix`).

A program without such a block, or without `op_scopes`, or no trace:
None, never 0."""
from chipbench import scope_reduce

MIX_OP = "_contrib_cca_mix"


def read(ctx):
    an = scope_reduce.analyse(ctx)
    if an is None:
        return None
    rows = {k: row["ms"] for k, row in an["rows"].items()
            if k[0].startswith(scope_reduce.STEP_PROGRAMS)}
    blocks = {node.rsplit("cca_mix", 1)[0] for (_p, _q, op, node) in rows
              if op == MIX_OP}
    if not blocks:
        return None
    return sum(ms for (_p, _q, _t, node), ms in rows.items()
               if node.startswith(tuple(blocks)))
