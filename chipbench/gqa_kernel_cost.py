"""Operations and bytes that a forward attention call over grouped-query
heads needs, with a sliding window or without, from its shapes alone: the
same whatever implements the kernel.  Beside kernel_cost.py, whose
`attention_forward` has one head count and no window."""


def pairs_in_mask(seq, window=None):
    """(query, key) pairs of one head that a causal mask leaves, with a
    window W (query t sees keys t - W < s <= t) or without."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_forward(batch, heads, kv_heads, seq, head_dim, window=None,
                      bytes_per_element=2):
    """FLOPs and bytes of one causal forward attention call over (B, H, T,
    D) queries and (B, Hkv, T, D) keys and values: two products of 2 D
    operations for every (query, key) pair inside the mask, a query head;
    the queries read and the output written once a query head, keys and
    values read once a KEY/VALUE head."""
    flops = 2 * 2 * batch * heads * pairs_in_mask(seq, window) * head_dim
    nbytes = 2 * batch * (heads + kv_heads) * seq * head_dim \
        * bytes_per_element
    return flops, nbytes
