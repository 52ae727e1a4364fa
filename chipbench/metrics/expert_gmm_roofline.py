"""The expert layers' grouped products' share of their roofline: the least
time the chip could take for the REQUIRED work of the three products of the
gated feed-forward, forward and backward, in every expert layer of a step,
over the device time of the trace's grouped-product events a step.  Source:
device_trace.  Layer: kernels (ops/decoder.py `moe_ffn`: `lax.ragged_dot`,
which XLA lowers on the TPU to Mosaic grouped-matmul calls).

Required (`grouped_ffn_cost`; the same whatever implements the products):
rows = tokens x `num_experts_per_tok` x held / router outputs, the
expectation under even routing, as the configuration's flops.py has it;
each product 2 x rows x D x F operations forward and twice that backward;
bytes: every held expert's three D x F matrices read once in the forward
pass, read once and their gradients written once in the backward pass, and
each product's input rows read and output rows written (forward once,
backward twice).  At 256 rows an expert the bytes bound the time, not the
operations.  Nothing recomputed is required: the step runs the forward
products three times (the forward program, the backward program's own
forward, the op's `jax.checkpoint`), and all of that is in the device time.

The events: `XLA Ops` events whose HLO text is a custom call with
`custom_call_target="tpu_custom_call"` and whose name starts with
`ragged-dot` (`%ragged-dot-none.7`, and the `%ragged-dot-metadata.3` calls
that lay out the tile visits; seen in the v5e compile and on the chip, PR
27).  XLA names them itself, so the op's scope is not in the name.  A
configuration without experts, or a trace without such an event: None,
never 0."""
from chipbench import kernel_cost
from chipbench.trace_reduce import op_short_name

TARGET = 'custom_call_target="tpu_custom_call"'
PREFIX = "ragged-dot"


def is_grouped_product(hlo_text):
    return TARGET in hlo_text and op_short_name(hlo_text).startswith(PREFIX)


def grouped_ffn_cost(rows, held, d, f, bytes_per_element=2):
    """(FLOPs, bytes) required of one expert layer's three grouped
    products, forward and backward, for `rows` rows over `held` experts."""
    flops = 3 * (3 * 2 * rows * d * f)          # forward + twice backward
    weights = 3 * held * d * f
    row_io = 2 * (rows * d + rows * f) + (rows * f + rows * d)
    nbytes = (3 * weights + 3 * row_io) * bytes_per_element
    return flops, nbytes


def read(ctx):
    red, peaks, cell = ctx.get("reduced"), ctx["peaks"], ctx["cell"]
    cfg, steps = cell.cfg, ctx["window"]["attempted"]
    if not red or peaks is None or not steps \
            or "moe_intermediate_size" not in cfg:
        return None
    total = 0.0
    for dev in red["events"]["devices"].values():
        total += sum((e - s) / 1e9 for name, s, e in dev["ops"]
                     if is_grouped_product(name))
    if total <= 0:
        return None
    held, outputs = (cfg["n_routed_experts"],
                     cfg["expert_parallel"]["router_outputs"])
    rows = cell.units_per_step() * cfg["num_experts_per_tok"] * held / outputs
    flops, nbytes = grouped_ffn_cost(rows, held, cfg["hidden_size"],
                                     cfg["moe_intermediate_size"])
    least, _bound = kernel_cost.least_seconds(flops, nbytes, peaks)
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * least * layers * steps * cell.chips / total
