"""`opt-1.3b` through the program's normal API:
`gluon.model_zoo.transformer.TransformerLM(attn_type="flash")` is this
block (pre-LN decoder, ReLU, learned positions, biases everywhere), under
next-token cross-entropy as one hybridized graph.  Departures the program
fixes are in config.json (`assumed`).
"""


def build(cfg):
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    return TransformerLM(
        cfg["vocab_size"], dim=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], ffn_dim=cfg["ffn_dim"],
        max_len=cfg["max_position_embeddings"], attn_type="flash")


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
