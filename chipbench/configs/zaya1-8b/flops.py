"""Required operations of one training step of `zaya1-8b` as it is cut
(config.json), from its shapes, per token: six per parameter of the
matrices a token passes through (two forward, four backward): in every
layer the four projections of the compressed convolutional attention
(queries 8 x 128, keys 2 x 128, the two value halves 128 each, the output
back to 2,048), the matrices of its convolution over each head's channels
(10 heads, `cca_time1` taps of 128 x 128), the router (2,048 -> 256 ->
256 -> 256 -> 17) and `num_experts / router_outputs` of an expert, which
is what this chip's share computes IN EXPECTATION UNDER EVEN ROUTING over
the 17 choices (top-1; 8 experts held, the skip held nowhere); the head
over the vocabulary's slice; and attention's two products (scores and
weighted values, each at width heads x head_dim) over (T + 1) / 2 keys.
Embedding look-ups, norms, the depthwise convolution, the means, rotary,
softmax, the routing's sort and the loss's exponentials are left out.
Nothing recomputed is counted, nor anything a grouped product multiplies
beyond its required rows.
"""


def attention_matrix_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    projections = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mixing = (h + hkv) * cfg["cca_time1"] * hd * hd
    return projections + mixing


def router_matrix_params(cfg):
    r = cfg["router_hidden_size"]
    return cfg["hidden_size"] * r + 2 * r * r \
        + r * cfg["expert_parallel"]["router_outputs"]


def expert_matrix_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_experts_per_token(cfg):
    """Experts of this chip's share a token passes, in expectation under
    even routing over every choice, the skip among them."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["expert_parallel"]["router_outputs"])


def matrix_params_per_token(cfg):
    layer = (attention_matrix_params(cfg) + router_matrix_params(cfg)
             + expert_matrix_params(cfg) * routed_experts_per_token(cfg))
    return cfg["num_hidden_layers"] * layer \
        + cfg["hidden_size"] * cfg["vocab_size"]


def layer_windows(cfg):
    """No layer has a window."""
    return [None] * cfg["num_hidden_layers"]


def train_flops_per_unit(cfg, traffic):
    width = 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    # forward: 2 ops x width x keys seen, for scores and for values;
    # backward twice that
    attention = 3 * 2 * width * (traffic["seq"] + 1) / 2 \
        * cfg["num_hidden_layers"]
    return 6 * matrix_params_per_token(cfg) + attention


def grouped_ffn_shape(cfg, traffic):
    """(rows, held experts, D, F, expert layers) of the grouped products a
    step: rows in expectation under even routing, as above."""
    rows = units_per_step(cfg, traffic) * routed_experts_per_token(cfg)
    return (rows, cfg["num_experts"], cfg["hidden_size"],
            cfg["moe_intermediate_size"], cfg["num_hidden_layers"])


def head_loss_shape(cfg, traffic):
    """(tokens, vocabulary rows, D) of the step's blocked cross-entropy."""
    return (units_per_step(cfg, traffic), cfg["vocab_size"],
            cfg["hidden_size"])


def unit(cfg, traffic):
    return "tokens"


def units_per_step(cfg, traffic):
    return traffic["batch"] * traffic["seq"]
