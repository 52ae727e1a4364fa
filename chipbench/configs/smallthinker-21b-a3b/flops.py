"""Required operations of one training step of `smallthinker-21b-a3b` as
it is cut (config.json), from its shapes, per token: six per parameter of
the matrices a token passes through (two forward, four backward): in every
layer the four attention projections (28 query heads, 4 key/value heads of
128), the router and `moe_num_active_primary_experts *
moe_num_primary_experts / router_outputs` routed experts, which is what
this chip's share computes IN EXPECTATION UNDER EVEN ROUTING (6 * 8 / 64 =
three quarters of an expert a token); the output head over the
vocabulary's slice; and attention's two products (scores and weighted
values, each at width heads * head_dim) over the keys a query sees on
average: (T + 1) / 2 in a global layer, and in a layer with a window W < T
the mean of min(t + 1, W) over the positions.  Embedding look-ups, norms,
rotary, softmax, the routing's sort and the loss are left out.  Nothing
recomputed is counted, and nothing a grouped product multiplies beyond its
required rows, nor a tile of the attention kernel beyond the mask.
"""


def attention_matrix_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * hkv * hd + h * hd * d


def expert_matrix_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def routed_experts_per_token(cfg):
    """Routed experts of this chip's share a token passes, in expectation
    under even routing."""
    return (cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"]
            / cfg["expert_parallel"]["router_outputs"])


def matrix_params_per_token(cfg):
    d = cfg["hidden_size"]
    layer = (attention_matrix_params(cfg)
             + d * cfg["expert_parallel"]["router_outputs"]
             + expert_matrix_params(cfg) * routed_experts_per_token(cfg))
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def layer_windows(cfg):
    """The window of each layer that is run, None for a global one."""
    n = cfg["num_hidden_layers"]
    return [cfg["sliding_window_size"] if w else None
            for w in cfg["sliding_window_layout"][:n]]


def keys_seen(seq, window=None):
    """Keys a query sees on average over positions 0..seq-1."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def train_flops_per_unit(cfg, traffic):
    width = 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    # forward: 2 ops x width x keys seen, for scores and for values;
    # backward twice that
    attention = 3 * 2 * width * sum(keys_seen(traffic["seq"], w)
                                    for w in layer_windows(cfg))
    return 6 * matrix_params_per_token(cfg) + attention


def grouped_ffn_shape(cfg, traffic):
    """(rows, held experts, D, F, expert layers) of the grouped products a
    step: rows in expectation under even routing, as above."""
    rows = units_per_step(cfg, traffic) * routed_experts_per_token(cfg)
    return (rows, cfg["moe_num_primary_experts"], cfg["hidden_size"],
            cfg["moe_ffn_hidden_size"], cfg["num_hidden_layers"])


def unit(cfg, traffic):
    return "tokens"


def units_per_step(cfg, traffic):
    return traffic["batch"] * traffic["seq"]
