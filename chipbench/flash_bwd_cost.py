"""Operations and bytes that the backward pass of one causal attention call
needs, from its shapes alone: the same whatever implements it.  Beside
gqa_kernel_cost.py, whose forward call it is the backward of."""
from chipbench import gqa_kernel_cost


def attention_backward(batch, heads, kv_heads, seq, head_dim, window=None,
                       bytes_per_element=2):
    """FLOPs and bytes of the backward pass of one causal attention call
    over (B, H, T, D) queries and (B, Hkv, T, D) keys and values: five
    products of 2 D operations for every (query, key) pair inside the
    mask, a query head (the scores again, dO V^T, and one product each for
    dV, dQ and dK: 2.5 times the forward call's two; a kernel that computes
    the first two twice, once for dK and dV and once for dQ, is not asked
    to); q, o and dO read and dQ written once a query head, k and v read
    and dK and dV written once a KEY/VALUE head."""
    forward, _ = gqa_kernel_cost.attention_forward(
        batch, heads, kv_heads, seq, head_dim, window, bytes_per_element)
    nbytes = 4 * batch * (heads + kv_heads) * seq * head_dim \
        * bytes_per_element
    return 2.5 * forward, nbytes
