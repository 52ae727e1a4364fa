"""Required operations of one training step of `glm-4.7-flash` as it is cut
(config.json), from its shapes, per token: six per parameter of the
matrices a token passes through (two forward, four backward): the five
latent-attention projections of every layer, the dense layer's gated
feed-forward, and in every expert layer the router, the shared expert and
`num_experts_per_tok * n_routed_experts / router_outputs` routed experts,
which is what this chip's share computes IN EXPECTATION UNDER EVEN ROUTING
(4 * 8 / 64 = half an expert a token); the output head over the
vocabulary's slice; and causal attention's two products (scores at width
heads * (nope + rope), weighted values at heads * v) over the (T + 1) / 2
keys a query sees on average.  Embedding look-ups, norms, rotary, softmax,
the routing's sort and the loss are left out.  Nothing recomputed is
counted, and nothing a grouped product multiplies beyond its required rows.
"""


def attention_matrix_params(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * qr + qr * h * (dn + dr) + d * (kvr + dr)
            + kvr * h * (dn + dv) + h * dv * d)


def expert_matrix_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def routed_experts_per_token(cfg):
    """Routed experts of this chip's share a token passes, in expectation
    under even routing."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["expert_parallel"]["router_outputs"])


def matrix_params_per_token(cfg):
    d = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    expert_layer = (d * cfg["expert_parallel"]["router_outputs"]
                    + expert_matrix_params(cfg)
                    * (cfg["n_shared_experts"]
                       + routed_experts_per_token(cfg)))
    return (layers * attention_matrix_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + (layers - dense) * expert_layer
            + d * cfg["vocab_size"])


def train_flops_per_unit(cfg, traffic):
    h = cfg["num_attention_heads"]
    width = h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) \
        + h * cfg["v_head_dim"]
    # forward: 2 ops x width x keys seen, for scores and for values;
    # backward twice that
    attention = 3 * 2 * width * (traffic["seq"] + 1) / 2 \
        * cfg["num_hidden_layers"]
    return 6 * matrix_params_per_token(cfg) + attention


def unit(cfg, traffic):
    return "tokens"


def units_per_step(cfg, traffic):
    return traffic["batch"] * traffic["seq"]
