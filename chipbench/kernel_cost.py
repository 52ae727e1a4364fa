"""Operations and bytes a kernel's call needs, from its shapes alone: the
same whatever implements the kernel."""


def attention_forward(batch, heads, seq, head_dim, causal=True,
                      bytes_per_element=2):
    """FLOPs and bytes of one forward attention call over (B, H, T, D)
    queries, keys and values: two products of 2 T^2 D operations a head
    (half of them under a causal mask), q, k and v read and the output
    written once."""
    flops = 2 * 2 * batch * heads * seq * seq * head_dim
    if causal:
        flops = flops * (seq + 1) / (2 * seq)
    nbytes = 4 * batch * heads * seq * head_dim * bytes_per_element
    return flops, nbytes


def least_seconds(flops, nbytes, peaks):
    """(seconds, which bound) of the roofline."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
