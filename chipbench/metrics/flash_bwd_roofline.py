"""The attention backward kernels' share of their roofline: the least time
the chip could take for the REQUIRED work of the attention backward of one
step (chipbench/flash_bwd_cost.py: five products of 2 D operations for
every (query, key) pair inside each layer's mask, with the cell's query
and key/value heads, head size and windows; summed over the layers of a
step) over the device time of ALL backward-kernel events of the traced
window divided by the window's steps.  By steps, not by calls: the program
runs two kernels a layer (dK/dV and dQ: seven products for the five
required), and two kernels must not read as twice the roofline.  Source:
device_trace.  Layer: kernels (ops/flash_attention.py `_bwd_kernels`).

The events: an `XLA Ops` event whose HLO text is a custom call with
`custom_call_target="tpu_custom_call"` and whose name carries
`flash_bwd`: the program puts each backward `pallas_call` directly inside
a `jax.named_scope` of that name (`flash_bwd_dkv`, `flash_bwd_dq`), so the
events are `%flash_bwd_dkv.3`, not the layer's `...attention...` name that
the forward readers match.  The shapes: heads, key/value heads (the query
heads where the configuration names none), the head size (`head_dim`; in a
latent-attention model `qk_nope_head_dim + qk_rope_head_dim`; else
`hidden_size // heads`), one layer a hidden layer, each layer's window
from the configuration's flops.py `layer_windows` where it has one.

A program whose backward is no such kernel (the parent of PR 32: loops of
plain XLA, which have no event a reader could tell from others), or a cell
without attention: None, never 0."""
from chipbench import flash_bwd_cost, kernel_cost
from chipbench.trace_reduce import op_short_name

TARGET = 'custom_call_target="tpu_custom_call"'
SCOPE = "flash_bwd"


def is_backward_attention(hlo_text):
    return TARGET in hlo_text and SCOPE in op_short_name(hlo_text)


def call_shapes(cell):
    """[(heads, kv_heads, head_dim, window)] of a step's attention calls,
    one a layer, or None for a cell without attention."""
    cfg = cell.cfg
    heads = cfg.get("num_attention_heads")
    if not heads or "seq" not in cell.traffic:
        return None
    if "qk_nope_head_dim" in cfg:
        head_dim = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    else:
        head_dim = cfg.get("head_dim", cfg["hidden_size"] // heads)
    windows = getattr(cell.flops, "layer_windows", None)
    windows = windows(cfg) if windows else [None] * cfg["num_hidden_layers"]
    return [(heads, cfg.get("num_key_value_heads", heads), head_dim, w)
            for w in windows]


def read(ctx):
    red, peaks, cell = ctx.get("reduced"), ctx["peaks"], ctx["cell"]
    steps = ctx["window"].get("attempted")
    if not red or peaks is None or not steps:
        return None
    calls = call_shapes(cell)
    if not calls:
        return None
    total = 0.0
    for dev in red["events"]["devices"].values():
        total += sum((e - s) / 1e9 for name, s, e in dev["ops"]
                     if is_backward_attention(name))
    if total <= 0:
        return None
    tr = cell.traffic
    least = sum(kernel_cost.least_seconds(
        *flash_bwd_cost.attention_backward(
            tr["batch"], heads, kv_heads, tr["seq"], head_dim, window),
        peaks)[0] for heads, kv_heads, head_dim, window in calls)
    return 100.0 * least * steps * cell.chips / total
