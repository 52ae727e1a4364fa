"""`smallthinker-21b-a3b` through the program's normal API:
`gluon.model_zoo.decoder.DecoderLM` with a per-layer attention pattern of
`GroupedQueryAttention(attn_type="flash")` blocks (28 query heads over 4
key/value heads; a global layer without positions, then layers with a
sliding window and rotary positions, as the config's two layouts say) and
expert layers whose router reads the layer's normalised input, weighs the
chosen experts by a softmax over their logits and gates with ReLU; this
chip holds a share of the experts.  Next-token cross-entropy, net and loss
as one hybridized graph.  Departures are in config.json (`reduced`,
`assumed`).
"""
import functools


def attention_pattern(cfg):
    """[(rotary positions?, window or None)] of the layers that are run."""
    n = cfg["num_hidden_layers"]
    return [(bool(r), cfg["sliding_window_size"] if w else None)
            for r, w in zip(cfg["rope_layout"][:n],
                            cfg["sliding_window_layout"][:n])]


def build(cfg):
    from mxnet_tpu.gluon.model_zoo.decoder import (DecoderLM,
                                                   GroupedQueryAttention)
    ep = cfg["expert_parallel"]
    attention = [functools.partial(
        GroupedQueryAttention, cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], rope=rope, window=window, attn_type="flash",
        rope_base=float(cfg["rope_theta"]))
        for rope, window in attention_pattern(cfg)]
    return DecoderLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        attention=attention, expert_ffn_dim=cfg["moe_ffn_hidden_size"],
        num_experts=ep["router_outputs"],
        top_k=cfg["moe_num_active_primary_experts"],
        held_experts=cfg["moe_num_primary_experts"],
        first_expert=ep["first_expert"], shared_experts=0, first_k_dense=0,
        norm_topk=cfg["norm_topk_prob"], epsilon=cfg["rms_norm_eps"],
        router="softmax_topk", activation="relu",
        router_reads="attention_input")


def input_shape(cfg, traffic):
    return (traffic["batch"], traffic["seq"])


def trainable(net):
    return [p for p in net.collect_params().values() if p.grad_req != "null"]


def gluon_loss(net, cfg):
    """(tokens, next tokens) -> per-sequence mean loss, net and loss as one
    hybridized graph (the head's logits never leave the program)."""
    from mxnet_tpu import gluon
    vocab = cfg["vocab_size"]

    class LMLoss(gluon.HybridBlock):
        def __init__(self, net_, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = net_

        def hybrid_forward(self, F, tokens, labels):
            logits = F.cast(F.reshape(self.net(tokens), (-1, vocab)),
                            "float32")
            nll = -F.pick(F.log_softmax(logits, axis=-1),
                          F.reshape(labels, (-1,)), axis=-1)
            return F.mean(F.reshape_like(nll, labels), axis=1)

    block = LMLoss(net)
    block.hybridize()
    return block


def program_batch(x, y, dtype):
    """Token ids travel as float32, the program's convention."""
    import jax.numpy as jnp
    return x.astype(jnp.float32), y.astype(jnp.float32)
