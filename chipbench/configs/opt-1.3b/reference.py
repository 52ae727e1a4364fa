"""Plain reference for `opt-1.3b`: the OPT decoder (Zhang et al.,
arXiv:2205.01068; `facebook/opt-1.3b` config.json) in straightforward
`jax.numpy`, float32, matmul precision "highest": learned positions,
pre-LayerNorm blocks of causal multi-head attention and a ReLU
feed-forward, biases everywhere, a final LayerNorm and the output head,
under next-token cross-entropy.  Imports nothing of `mxnet_tpu`.

Departures from the published model, shared with the program (config.json
`assumed`): the head is untied and has a bias, positions start at 0, no
dropout.  The fused projection holds q, k and v in that order, each split
into heads of `hidden_size / num_attention_heads`.

Interface: see configs/resnet50_v1/reference.py.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NEG = -1e30


def leaves(cfg):
    d, f, v = cfg["hidden_size"], cfg["ffn_dim"], cfg["vocab_size"]
    out = [("tok.weight", (v, d), "embed"),
           ("pos.weight", (cfg["max_position_embeddings"], d), "embed")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "ln1.gamma", (d,), "gamma"), (p + "ln1.beta", (d,), "beta"),
                (p + "qkv.weight", (3 * d, d), "dense"),
                (p + "qkv.bias", (3 * d,), "bias"),
                (p + "proj.weight", (d, d), "dense"),
                (p + "proj.bias", (d,), "bias"),
                (p + "ln2.gamma", (d,), "gamma"), (p + "ln2.beta", (d,), "beta"),
                (p + "ffn1.weight", (f, d), "dense"),
                (p + "ffn1.bias", (f,), "bias"),
                (p + "ffn2.weight", (d, f), "dense"),
                (p + "ffn2.bias", (d,), "bias")]
    out += [("lnf.gamma", (d,), "gamma"), ("lnf.beta", (d,), "beta"),
            ("head.weight", (v, d), "dense"), ("head.bias", (v,), "bias")]
    return out


def parts(cfg):
    """The fused projection's bias is three leaves to the comparison: a
    key's bias has no gradient under softmax, so that third moves under
    Adam by round-off alone, and the rule that leaves such leaves out
    (checks/train_steps.py, compare) has to see it apart from q's and v's."""
    d = cfg["hidden_size"]
    return {f"l{i}.qkv.bias": [(f"l{i}.qkv.bias[{n}]", j * d, (j + 1) * d)
                               for j, n in enumerate("qkv")]
            for i in range(cfg["num_hidden_layers"])}


def init_leaf(key, shape, kind):
    """normal(0, 0.02) matrices and embeddings, gamma 1, the rest 0."""
    if kind in ("dense", "embed"):
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if kind == "gamma":
        return jnp.ones(shape, jnp.float32)
    return jnp.zeros(shape, jnp.float32)


def leaf_key(seed, i):
    return jax.random.fold_in(jax.random.key(seed), i)


def init_weights(seed, cfg, dtype=jnp.float32):
    """Every leaf from the seed, in one jitted call, in `dtype`."""
    spec = leaves(cfg)

    @jax.jit
    def make(seed_):
        return {name: init_leaf(leaf_key(seed_, i), shape, kind).astype(dtype)
                for i, (name, shape, kind) in enumerate(spec)}

    return make(jnp.uint32(seed % (2 ** 31)))


def make_batches(seed, n, batch, cfg, traffic):
    """n batches of token ids (n, B, T) and their next tokens (n, B, T):
    uniform over the vocabulary, every row its own."""
    seq, vocab = traffic["seq"], cfg["vocab_size"]

    @jax.jit
    def make(seed_):
        key = jax.random.fold_in(jax.random.key(seed_), 2 ** 20)
        toks = jax.random.randint(key, (n, batch, seq + 1), 0, vocab,
                                  jnp.int32)
        return toks[:, :, :-1], toks[:, :, 1:]

    return make(jnp.uint32(seed % (2 ** 31)))


def _ln(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def _dense(x, w, b, q):
    if q is not None:
        x, w = q(x), q(w)
    return jnp.einsum("...i,oi->...o", x, w, precision=HI) + b


def _layer(p, x, heads, eps, q):
    b, t, d = x.shape
    dh = d // heads
    h = _ln(x, p["ln1.gamma"], p["ln1.beta"], eps)
    qkv = _dense(h, p["qkv.weight"], p["qkv.bias"], q)
    qkv = qkv.reshape(b, t, 3, heads, dh).transpose(2, 0, 3, 1, 4)
    qq, kk, vv = qkv[0], qkv[1], qkv[2]                  # (B, H, T, dh)
    if q is not None:
        qq, kk, vv = q(qq), q(kk), q(vv)
    s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk, precision=HI) * dh ** -0.5
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(mask[None, None], s, NEG)
    att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), vv,
                     precision=HI)
    att = att.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + _dense(att, p["proj.weight"], p["proj.bias"], q)
    h = _ln(x, p["ln2.gamma"], p["ln2.beta"], eps)
    h = jax.nn.relu(_dense(h, p["ffn1.weight"], p["ffn1.bias"], q))
    return x + _dense(h, p["ffn2.weight"], p["ffn2.bias"], q)


def logits(params, tokens, cfg, q=None):
    eps = cfg["layer_norm_eps"]
    t = tokens.shape[1]
    x = params["tok.weight"][tokens.astype(jnp.int32)] \
        + params["pos.weight"][jnp.arange(t)][None]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"l{i}."
        sub = {k[len(pre):]: v for k, v in params.items()
               if k.startswith(pre)}
        layer = jax.checkpoint(functools.partial(
            _layer, heads=cfg["num_attention_heads"], eps=eps, q=q))
        x = layer(sub, x)
    x = _ln(x, params["lnf.gamma"], params["lnf.beta"], eps)
    return _dense(x, params["head.weight"], params["head.bias"], q)


def loss(params, tokens, labels, cfg, q=None):
    lg = logits(params, tokens, cfg, q)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                 axis=-1)
    return -jnp.mean(picked)
