"""Required operations of one training step of `opt-1.3b`, from its
shapes, per token: six per parameter of the matrices a token passes
through (two forward, four backward: the layers' projections and
feed-forward, and the output head), and causal attention's two products
(scores and weighted values) over the (T + 1) / 2 keys a query sees on
average.  Embedding look-ups, LayerNorm, biases, softmax and the loss are
left out.  Nothing recomputed is counted.
"""


def matrix_params_per_layer(cfg):
    d, f = cfg["hidden_size"], cfg["ffn_dim"]
    return 3 * d * d + d * d + 2 * d * f


def train_flops_per_unit(cfg, traffic):
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    seq = traffic["seq"]
    dense = 6 * (layers * matrix_params_per_layer(cfg)
                 + d * cfg["vocab_size"])
    # forward: 2 products x 2 ops x d x keys seen; backward twice that
    attention = 3 * 2 * 2 * d * (seq + 1) / 2 * layers
    return dense + attention


def unit(cfg, traffic):
    return "tokens"


def units_per_step(cfg, traffic):
    return traffic["batch"] * traffic["seq"]
