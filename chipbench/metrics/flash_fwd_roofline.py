"""The forward attention kernel's share of its roofline: the least time
the chip could take for one forward attention call of the cell's shape
(chipbench/kernel_cost.py: FLOPs and bytes from B, H, T, D; at T 2048 and
D 64 the compute bound applies, 512 FLOP a byte against the chip's 240)
over the mean device time of the forward attention events in the trace.
Source: device_trace.  Layer: kernels (ops/flash_attention.py).

The events are found by what the trace prints for the Mosaic call (looked
at by hand, PR 24): an `XLA Ops` event whose HLO text is a custom call with
`custom_call_target="tpu_custom_call"` and whose name carries the program's
own scope of the attention op, `%transformerlm0_l3_attn_multihead_attention0.1`
in the forward program and `%jvp_transformerlm0_l3_attn_...` where the
backward program runs the same kernel again (the custom VJP's forward).
Both are calls of the forward kernel and both count.  The backward itself is
plain XLA (`lax.map`) and has no event of its own to find.  Where the trace
holds no such event (another attention implementation, or a scope that
changed) the reader returns nothing: never 0."""
from chipbench import kernel_cost
from chipbench.trace_reduce import op_short_name

TARGET = 'custom_call_target="tpu_custom_call"'
SCOPE = "attention"


def is_forward_attention(hlo_text):
    return TARGET in hlo_text and SCOPE in op_short_name(hlo_text)


def read(ctx):
    red, peaks, cell = ctx.get("reduced"), ctx["peaks"], ctx["cell"]
    if not red or peaks is None or "seq" not in cell.traffic:
        return None
    total, calls = 0.0, 0
    for dev in red["events"]["devices"].values():
        for name, s, e in dev["ops"]:
            if is_forward_attention(name):
                total += (e - s) / 1e9
                calls += 1
    if not calls or total <= 0:
        return None
    cfg, tr = cell.cfg, cell.traffic
    heads = cfg["num_attention_heads"]
    flops, nbytes = kernel_cost.attention_forward(
        tr["batch"], heads, tr["seq"], cfg["hidden_size"] // heads)
    least, _bound = kernel_cost.least_seconds(flops, nbytes, peaks)
    return 100.0 * least * calls / total
