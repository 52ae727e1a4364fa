"""`zaya1-8b` through the program's normal API:
`gluon.model_zoo.decoder.ZayaLM`, layers of `CompressedConvAttention`
(8 query over 2 key/value heads of 128, the mixing of `cca_mix`, rotary
positions over half of every head, the Pallas kernels) and top-1 experts
beside a skip choice, routed by MLP routers whose state each layer hands to
the next; this chip holds a share of the experts and of the vocabulary,
and the head is the embedding.  The loss is each token's cross-entropy over
the tied matrix by `blocked_cross_entropy`, which never holds the float32
logits; net and loss as one hybridized graph.  Departures are in
config.json (`reduced`, `assumed`).
"""
import functools


def build(cfg):
    from mxnet_tpu.gluon.model_zoo.decoder import (CompressedConvAttention,
                                                   ZayaLM)
    ep = cfg["expert_parallel"]
    d = cfg["head_dim"]
    attention = functools.partial(
        CompressedConvAttention, cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"], d,
        int(d * cfg["partial_rotary_factor"]),
        rope_base=float(cfg["rope_parameters"]["hybrid"]["rope_theta"]),
        conv_taps=(cfg["cca_time0"], cfg["cca_time1"]), attn_type="flash")
    return ZayaLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        attention, cfg["moe_intermediate_size"], ep["router_outputs"] - 1,
        held_experts=cfg["num_experts"], first_expert=ep["first_expert"],
        top_k=cfg["num_experts_per_tok"],
        router_dim=cfg["router_hidden_size"], epsilon=cfg["rms_norm_eps"])


def input_shape(cfg, traffic):
    return (traffic["batch"], traffic["seq"])


def trainable(net):
    return [p for p in net.collect_params().values() if p.grad_req != "null"]


def gluon_loss(net, cfg):
    """(tokens, next tokens) -> per-sequence mean loss, net and loss as one
    hybridized graph: each token's cross-entropy over the tied matrix by
    `blocked_cross_entropy`, so no logits leave the op."""
    from mxnet_tpu import gluon

    class LMLoss(gluon.HybridBlock):
        def __init__(self, net_, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = net_

        def hybrid_forward(self, F, tokens, labels):
            return F.mean(self.net.head(self.net.hidden(F, tokens), labels),
                          axis=1)

    block = LMLoss(net)
    block.hybridize()
    return block


def program_batch(x, y, dtype):
    """Token ids travel as float32, the program's convention."""
    import jax.numpy as jnp
    return x.astype(jnp.float32), y.astype(jnp.float32)
