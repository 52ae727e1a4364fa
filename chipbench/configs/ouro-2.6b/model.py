"""`ouro-2.6b` through the program's normal API:
`gluon.model_zoo.decoder.LoopedLM`, one stack of sandwich-norm decoder
blocks (`GroupedQueryAttention(attn_type="flash")` with as many key/value
heads as query heads, rotary positions; a SiLU-gated feed-forward) applied
`total_ut_steps` times by a loop in the graph, with an exit gate, and
`LoopedLMLoss`, the expected loss over all exits with its entropy term, net
and loss as one hybridized graph.  Departures are in config.json
(`reduced`, `assumed`).
"""
import functools


def build(cfg):
    from mxnet_tpu.gluon.model_zoo.decoder import (GroupedQueryAttention,
                                                   LoopedLM)
    attention = functools.partial(
        GroupedQueryAttention, cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], rope=True, rope_base=float(cfg["rope_theta"]),
        attn_type="flash")
    return LoopedLM(cfg["vocab_size"], cfg["hidden_size"],
                    cfg["num_hidden_layers"], cfg["total_ut_steps"],
                    attention, cfg["intermediate_size"],
                    epsilon=cfg["rms_norm_eps"])


def input_shape(cfg, traffic):
    return (traffic["batch"], traffic["seq"])


def trainable(net):
    return [p for p in net.collect_params().values() if p.grad_req != "null"]


def gluon_loss(net, cfg):
    """(tokens, next tokens) -> per-sequence objective, net and loss as one
    hybridized graph (no exit's logits ever leave the program)."""
    from mxnet_tpu.gluon.model_zoo.decoder import LoopedLMLoss
    block = LoopedLMLoss(net, beta=cfg["exit_entropy_weight"])
    block.hybridize()
    return block


def program_batch(x, y, dtype):
    """Token ids travel as float32, the program's convention."""
    import jax.numpy as jnp
    return x.astype(jnp.float32), y.astype(jnp.float32)
