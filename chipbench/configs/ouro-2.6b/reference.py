"""Plain reference for `ouro-2.6b`: the Ouro looped decoder
(`ByteDance/Ouro-2.6B` config.json; arXiv:2510.25741) in straightforward
`jax.numpy`, float32, matmul precision "highest".  Imports nothing of
`mxnet_tpu`.

With `n(x) = x / sqrt(mean(x^2) + eps) * g`, tokens `x`, next tokens `y`,
R = `total_ut_steps` and N layers:

  h_0 = E[x]
  for t = 1..R:                    the SAME N layers' weights every t
      u = h_{t-1}
      for l = 1..N:
          a = u + n1post_l(Attn_l(n1_l(u)))            sandwich norms
          u = a + n2post_l(W_d (silu(W_g n2_l(a)) * W_u n2_l(a)))
      h_t   = norm_f(u)            feeds exit t AND loop step t + 1
      z_t   = W_head h_t           logits of exit t
      lam_t = sigmoid(w_gate . h_t + b_gate)
  p_1 = lam_1; p_t = lam_t prod_{j<t} (1 - lam_j), 1 < t < R;
  p_R = prod_{j<R} (1 - lam_j)
  loss = mean over tokens of sum_t p_t CE(z_t, y) - beta H(p),
  H(p) = -sum_t p_t log p_t,  beta = `exit_entropy_weight`

Attn: q, k, v = W_q u, W_k u, W_v u without biases, 16 heads of 128 each,
rotary positions over the whole of every q and k head (base 1e6,
split-halves pairing), causal softmax(q k^T / sqrt(128)) v, heads joined,
W_o.  What the config has no key for is listed in config.json `assumed`.

The loop is a Python `for` over one set of weights: a tied leaf's gradient
is the sum over its R applications because the same array is used R times.

Departures from the plainest form, each so that three float32 steps with
Adam fit one chip at 2 x 2,048 tokens beside 407 M parameters: every layer
application runs under `jax.checkpoint`; attention takes the queries in
blocks of `Q_BLOCK`, each block under `jax.checkpoint` (`lax.map`), so one
block's scores against all keys is what is held; an exit's logits and
cross-entropy run under `jax.checkpoint`, so the four exits' float32
logits (0.8 GB each) are never held together.  Interface: see
configs/resnet50_v1/reference.py.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NEG = -1e30
Q_BLOCK = 512


def leaves(cfg):
    d, hd, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = [("tok.weight", (cfg["vocab_size"], d), "embed")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "n1.gamma", (d,), "gamma"),
                (p + "attn.q.weight", (h * hd, d), "dense"),
                (p + "attn.k.weight", (hkv * hd, d), "dense"),
                (p + "attn.v.weight", (hkv * hd, d), "dense"),
                (p + "attn.proj.weight", (d, h * hd), "dense"),
                (p + "n1post.gamma", (d,), "gamma"),
                (p + "n2.gamma", (d,), "gamma"),
                (p + "ffn.gate.weight", (f, d), "dense"),
                (p + "ffn.up.weight", (f, d), "dense"),
                (p + "ffn.down.weight", (d, f), "dense"),
                (p + "n2post.gamma", (d,), "gamma")]
    out += [("normf.gamma", (d,), "gamma"),
            ("head.weight", (cfg["vocab_size"], d), "dense"),
            ("gate.weight", (1, d), "dense"),
            ("gate.bias", (1,), "bias")]
    return out


def init_leaf(key, shape, kind):
    """normal(0, 0.02) matrices (the gate's weight among them), normal(0,
    1) embedding rows, norm scales 1, the gate's bias 0 (config.json
    `assumed`, `weights`, says why the rows are unit)."""
    if kind == "gamma":
        return jnp.ones(shape, jnp.float32)
    if kind == "bias":
        return jnp.zeros(shape, jnp.float32)
    std = 1.0 if kind == "embed" else 0.02
    return jax.random.normal(key, shape, jnp.float32) * std


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


def rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gamma


def _mm(x, w, q):
    """x W^T for an (out, in) matrix; both operands through the control's
    rounding when there is one."""
    if q is not None:
        x, w = q(x), q(w)
    return jnp.einsum("...i,oi->...o", x, w, precision=HI)


def rope(x, base):
    """(B, T, H, R): rotary positions 0..T-1 over the whole last axis,
    dimension i paired with i + R/2."""
    r = x.shape[-1]
    inv_freq = base ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang[None, :, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(p, x, cfg, q=None):
    """p: the block's leaves under their names without the `attn.` prefix."""
    b, t, _ = x.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    base = float(cfg["rope_theta"])
    qq = rope(_mm(x, p["q.weight"], q).reshape(b, t, h, hd), base)
    kk = rope(_mm(x, p["k.weight"], q).reshape(b, t, hkv, hd), base)
    vv = _mm(x, p["v.weight"], q).reshape(b, t, hkv, hd)
    if q is not None:
        qq, kk, vv = q(qq), q(kk), q(vv)
    # query head j reads key/value head j // (h / hkv): here its own
    kk, vv = (jnp.repeat(a, h // hkv, axis=2) for a in (kk, vv))
    blk = Q_BLOCK if t % Q_BLOCK == 0 else t

    def block(i):
        qs = lax.dynamic_slice_in_dim(qq, i * blk, blk, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, kk, precision=HI) * hd ** -0.5
        ahead = (i * blk + jnp.arange(blk))[:, None] - jnp.arange(t)[None, :]
        s = jnp.where(ahead >= 0, s, NEG)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vv,
                          precision=HI)

    att = lax.map(jax.checkpoint(block), jnp.arange(t // blk))
    att = jnp.moveaxis(att, 0, 1).reshape(b, t, h * hd)
    return _mm(att, p["proj.weight"], q)


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _layer(p, u, cfg, q):
    eps = cfg["rms_norm_eps"]
    a = u + rms_norm(attention(_sub(p, "attn."), rms_norm(u, p["n1.gamma"],
                                                         eps), cfg, q),
                     p["n1post.gamma"], eps)
    g = rms_norm(a, p["n2.gamma"], eps)
    mid = jax.nn.silu(_mm(g, p["ffn.gate.weight"], q)) \
        * _mm(g, p["ffn.up.weight"], q)
    return a + rms_norm(_mm(mid, p["ffn.down.weight"], q),
                        p["n2post.gamma"], eps)


def loop_step(params, h, cfg, q=None):
    """h_{t-1} -> h_t: the whole stack, then norm_f."""
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(functools.partial(_layer, cfg=cfg, q=q))
        h = layer(_sub(params, f"l{i}."), h)
    return rms_norm(h, params["normf.gamma"], cfg["rms_norm_eps"])


def exit_logits(params, h, q=None):
    return _mm(h, params["head.weight"], q)


def exit_gate(params, h, q=None):
    """lam_t (B, T)."""
    return jax.nn.sigmoid(_mm(h, params["gate.weight"], q)[..., 0]
                          + params["gate.bias"][0])


def exit_distribution(lam):
    """lam (R, ...), the gates after every step (the last is not read) ->
    p (R, ...): p_1 = lam_1, p_t = lam_t prod_{j<t}(1 - lam_j), and the
    last step takes the rest."""
    steps = lam.shape[0]
    p, rest = [], jnp.ones_like(lam[0])
    for t in range(steps - 1):
        p.append(lam[t] * rest)
        rest = rest * (1.0 - lam[t])
    return jnp.stack(p + [rest])


def hidden_states(params, tokens, cfg, q=None):
    """[h_1 .. h_R]."""
    h = params["tok.weight"][tokens.astype(jnp.int32)]
    out = []
    for _ in range(cfg["total_ut_steps"]):   # one set of weights, R times
        h = loop_step(params, h, cfg, q)
        out.append(h)
    return out


def logits(params, tokens, cfg, q=None):
    """The last exit's logits: what inference with threshold 1 answers
    from."""
    return exit_logits(params, hidden_states(params, tokens, cfg, q)[-1], q)


def _exit_ce(head, h, labels, q):
    logp = jax.nn.log_softmax(_mm(h, head, q), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def token_losses(params, tokens, labels, cfg, q=None):
    """(per-token objective (B, T), p (R, B, T))."""
    labels = labels.astype(jnp.int32)
    hs = hidden_states(params, tokens, cfg, q)
    ce = jnp.stack([jax.checkpoint(functools.partial(_exit_ce, q=q))(
        params["head.weight"], h, labels) for h in hs])
    p = exit_distribution(jnp.stack([exit_gate(params, h, q) for h in hs]))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.sum(p * ce, axis=0) - cfg["exit_entropy_weight"] * entropy, p


def loss(params, tokens, labels, cfg, q=None):
    return jnp.mean(token_losses(params, tokens, labels, cfg, q)[0])
