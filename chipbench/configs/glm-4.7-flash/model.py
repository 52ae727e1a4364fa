"""`glm-4.7-flash` through the program's normal API:
`gluon.model_zoo.decoder.DecoderLM(attn_type="flash")` is this block
(RMS norm, latent attention with a shared rotary key, one dense gated
feed-forward layer, then expert layers of which this chip holds a share),
under next-token cross-entropy as one hybridized graph.  Departures are in
config.json (`reduced`, `assumed`).
"""


def build(cfg):
    from mxnet_tpu.gluon.model_zoo.decoder import DecoderLM
    ep = cfg["expert_parallel"]
    return DecoderLM(
        cfg["vocab_size"], cfg["hidden_size"], cfg["num_hidden_layers"],
        cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        num_experts=ep["router_outputs"], top_k=cfg["num_experts_per_tok"],
        held_experts=cfg["n_routed_experts"],
        first_expert=ep["first_expert"],
        shared_experts=cfg["n_shared_experts"],
        first_k_dense=cfg["first_k_dense_replace"],
        routed_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"], epsilon=cfg["rms_norm_eps"],
        rope_base=float(cfg["rope_theta"]), attn_type="flash")


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
