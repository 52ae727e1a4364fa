"""The expert layers' grouped products' share of their roofline, for a
configuration that states the products' shape itself: as
`expert_gmm_roofline` (whose cost function and event filter this reader
imports: the least time for the REQUIRED work of the three grouped products
of the gated feed-forward, forward and backward, in every expert layer of a
step, over the device time a step of the trace's `ragged-dot*` Mosaic
calls), with the rows, the held experts, the widths and the number of
expert layers taken from the configuration's flops.py
(`grouped_ffn_shape`) in place of one model's key names.  Source:
device_trace.  Layer: kernels (ops/decoder.py `moe_ffn`).

A configuration whose flops.py states no such shape, or a trace without
such an event: None, never 0."""
import os

from chipbench import cell as cellmod
from chipbench import kernel_cost

_gmm = cellmod.load_module(
    os.path.join(cellmod.HERE, "metrics", "expert_gmm_roofline.py"),
    "chipbench_metric_expert_gmm_roofline")


def read(ctx):
    red, peaks, cell = ctx.get("reduced"), ctx["peaks"], ctx["cell"]
    steps = ctx["window"]["attempted"]
    shape = getattr(cell.flops, "grouped_ffn_shape", None)
    if not red or peaks is None or not steps or shape is None:
        return None
    total = 0.0
    for dev in red["events"]["devices"].values():
        total += sum((e - s) / 1e9 for name, s, e in dev["ops"]
                     if _gmm.is_grouped_product(name))
    if total <= 0:
        return None
    rows, held, d, f, layers = shape(cell.cfg, cell.traffic)
    least, _bound = kernel_cost.least_seconds(
        *_gmm.grouped_ffn_cost(rows, held, d, f), peaks)
    return 100.0 * least * layers * steps * cell.chips / total
