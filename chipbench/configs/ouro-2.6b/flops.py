"""Required operations of one training step of `ouro-2.6b` as it is cut
(config.json), from its shapes, per token: six per parameter of every
matrix a token passes through, EACH TIME it passes (two forward, four
backward): the stack's layers (four attention projections and the three
matrices of the gated feed-forward) `total_ut_steps` times, the output head
once an exit, so `total_ut_steps` times too, and the exit gate's product
as often; and attention's two products (scores and weighted values, each at
width heads * head_dim) over the (T + 1) / 2 keys a query sees on average,
once a layer APPLICATION.  Embedding look-ups, norms, rotary, softmax, the
exit distribution and the losses are left out.  Nothing recomputed is
counted, nor a tile of the attention kernel beyond the mask.
"""


def layer_matrix_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * hd + 2 * d * hkv * hd + h * hd * d \
        + 3 * d * cfg["intermediate_size"]


def layer_applications(cfg):
    return cfg["num_hidden_layers"] * cfg["total_ut_steps"]


def matrix_params_per_token(cfg):
    """Matrix parameters a token passes through in one forward pass, a
    parameter counted once for every time it is applied."""
    d = cfg["hidden_size"]
    return layer_applications(cfg) * layer_matrix_params(cfg) \
        + cfg["total_ut_steps"] * (d * cfg["vocab_size"] + d)


def layer_windows(cfg):
    """One entry a layer APPLICATION (a step runs the attention kernels
    once each), every one global: the readers of the attention kernels'
    rooflines count a step's calls from this list."""
    return [None] * layer_applications(cfg)


def keys_seen(seq):
    """Keys a query sees on average over positions 0..seq-1."""
    return (seq + 1) / 2


def train_flops_per_unit(cfg, traffic):
    width = 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    # forward: 2 ops x width x keys seen, for scores and for values;
    # backward twice that
    attention = 3 * 2 * width * keys_seen(traffic["seq"]) \
        * layer_applications(cfg)
    return 6 * matrix_params_per_token(cfg) + attention


def unit(cfg, traffic):
    return "tokens"


def units_per_step(cfg, traffic):
    return traffic["batch"] * traffic["seq"]
