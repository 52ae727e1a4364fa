"""The forward attention kernel's share of its roofline in a latent-
attention model: the least time the chip could take for one forward call
over (B, H, T, D) with D = `qk_nope_head_dim + qk_rope_head_dim`, which is
also the value head's size (chipbench/kernel_cost.py `attention_forward`:
FLOPs and bytes from the shapes; compute-bound at T 2048, D 256), over the
mean device time of the trace's forward-kernel events.  Source:
device_trace.  Layer: kernels (ops/flash_attention.py through
ops/decoder.py `latent_attention`).

`flash_fwd_roofline` reads `hidden_size // heads` for D, which is not this
model's head size, so this is a reader of its own.  The events are found as
that reader finds its own: an `XLA Ops` event whose HLO text is a custom
call with `custom_call_target="tpu_custom_call"` and whose name carries the
program's scope of the op, `%decoderlm0_l3_attn_latent_attention0.1` in the
forward program and `%jvp_decoderlm0_l3_attn_latent_attention0_.1` where the
backward program runs the kernel again (seen in the v5e compile and on the
chip, PR 27).  A configuration without latent attention, or a trace without
such an event: None, never 0."""
from chipbench import kernel_cost
from chipbench.trace_reduce import op_short_name

TARGET = 'custom_call_target="tpu_custom_call"'
SCOPE = "latent_attention"


def is_forward_attention(hlo_text):
    return TARGET in hlo_text and SCOPE in op_short_name(hlo_text)


def read(ctx):
    red, peaks, cell = ctx.get("reduced"), ctx["peaks"], ctx["cell"]
    cfg, tr = cell.cfg, cell.traffic
    if not red or peaks is None or "seq" not in tr \
            or "qk_nope_head_dim" not in cfg:
        return None
    total, calls = 0.0, 0
    for dev in red["events"]["devices"].values():
        for name, s, e in dev["ops"]:
            if is_forward_attention(name):
                total += (e - s) / 1e9
                calls += 1
    if not calls or total <= 0:
        return None
    flops, nbytes = kernel_cost.attention_forward(
        tr["batch"], cfg["num_attention_heads"], tr["seq"],
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    least, _bound = kernel_cost.least_seconds(flops, nbytes, peaks)
    return 100.0 * least * calls / total
