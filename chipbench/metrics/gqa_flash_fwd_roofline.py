"""The forward attention kernel's share of its roofline in a model of
grouped-query heads whose layers differ in their mask: the least time the
chip could take for the REQUIRED work of one step's forward calls (one a
layer; chipbench/gqa_kernel_cost.py: the pairs inside each layer's mask
only, a window's band where the layer has one, keys and values read once a
key/value head), over the device time of the trace's forward-kernel events,
scaled to a step by the events found (a step has one a layer).  Source:
device_trace.  Layer: kernels (ops/flash_attention.py through
ops/decoder.py `grouped_query_attention`).

The events are found as `flash_fwd_roofline` finds its own: an `XLA Ops`
event whose HLO text is a custom call with
`custom_call_target="tpu_custom_call"` and whose name carries the program's
scope of the op, `grouped_query_attention`.  The layers' windows come from
the configuration's flops.py (`layer_windows`).  A configuration without
such layers, or a trace without such an event: None, never 0."""
from chipbench import gqa_kernel_cost, kernel_cost
from chipbench.trace_reduce import op_short_name

TARGET = 'custom_call_target="tpu_custom_call"'
SCOPE = "grouped_query_attention"


def is_forward_attention(hlo_text):
    return TARGET in hlo_text and SCOPE in op_short_name(hlo_text)


def read(ctx):
    red, peaks, cell = ctx.get("reduced"), ctx["peaks"], ctx["cell"]
    cfg, tr = cell.cfg, cell.traffic
    windows = getattr(cell.flops, "layer_windows", None)
    if not red or peaks is None or "seq" not in tr or windows is None \
            or "num_key_value_heads" not in cfg:
        return None
    total, calls = 0.0, 0
    for dev in red["events"]["devices"].values():
        for name, s, e in dev["ops"]:
            if is_forward_attention(name):
                total += (e - s) / 1e9
                calls += 1
    if not calls or total <= 0:
        return None
    layers = windows(cfg)
    least = sum(kernel_cost.least_seconds(
        *gqa_kernel_cost.attention_forward(
            tr["batch"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], tr["seq"], cfg["head_dim"], w),
        peaks)[0] for w in layers)
    return 100.0 * least * calls / len(layers) / total
