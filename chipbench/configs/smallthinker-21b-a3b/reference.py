"""Plain reference for `smallthinker-21b-a3b`: the SmallThinker decoder
(`PowerInfer/SmallThinker-21BA3B-Instruct` config.json) in straightforward
`jax.numpy`, float32, matmul precision "highest".  Imports nothing of
`mxnet_tpu`.

With `n(x) = x / sqrt(mean(x^2) + eps) * g`, a layer is

  h = n1(x)
  router      r = h W_r, 64 logits: the router reads the layer's
              normalised input, the tensor attention reads (ASSUMED)
  attention   q = h W_q -> 28 heads of 128; k = h W_k, v = h W_v -> 4
              heads of 128; no bias.  Where `rope_layout` is 1, rotary
              positions over the whole of every q and k head (base 1.5e6,
              split-halves pairing); where it is 0, no positions at all.
              Query head j attends to key/value head j // 7.  Scores
              q k / sqrt(128); key s is visible to query t iff s <= t and,
              where `sliding_window_layout` is 1, s > t - 4096; softmax;
              heads joined; W_o.  x1 = x + attention
  experts     g = n2(x1); the 6 experts of largest r; w = softmax over
              those 6 logits; y = sum_i w_i W_d,i (relu(W_g,i g) * W_u,i g)
              over the chosen experts that this chip holds.  x2 = x1 + y
  head        n_f(x) W_head; next-token cross-entropy, mean over tokens

The chip's share (config.json `expert_parallel`): of the `router_outputs`
experts the router scores, experts `first_expert .. first_expert +
moe_num_primary_experts - 1` are here.  A chosen expert that is absent adds
nothing, and that partial result goes on to the next layer; `moe_routed`
with all experts held is the uncut layer.  The vocabulary is a slice: ids,
logits and loss are over `vocab_size` entries.  No shared expert, no
secondary experts, no scaling factor (config.json `assumed`).

Departures from the plainest form, each so that three float32 steps with
Adam fit one chip at 2 x 8,192 tokens (a materialised score matrix would be
7.5 GB a sequence): every layer runs under `jax.checkpoint`; attention
takes the queries in blocks of `Q_BLOCK`, each block under `jax.checkpoint`
(`lax.map`), so one block's scores against all keys is what is held; the
held experts of a layer run as one `lax.scan` whose body is recomputed on
the way back.  Interface: see configs/resnet50_v1/reference.py.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NEG = -1e30
Q_BLOCK = 512


def leaves(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, fe = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    out = [("tok.weight", (cfg["vocab_size"], d), "embed")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "n1.gamma", (d,), "gamma"),
                (p + "attn.q.weight", (h * hd, d), "dense"),
                (p + "attn.k.weight", (hkv * hd, d), "dense"),
                (p + "attn.v.weight", (hkv * hd, d), "dense"),
                (p + "attn.proj.weight", (d, h * hd), "dense"),
                (p + "n2.gamma", (d,), "gamma"),
                (p + "ffn.router.weight",
                 (cfg["expert_parallel"]["router_outputs"], d), "dense"),
                (p + "ffn.experts.gate", (held, d, fe), "dense"),
                (p + "ffn.experts.up", (held, d, fe), "dense"),
                (p + "ffn.experts.down", (held, fe, d), "dense")]
    out += [("normf.gamma", (d,), "gamma"),
            ("head.weight", (cfg["vocab_size"], d), "dense")]
    return out


def init_leaf(key, shape, kind):
    """normal(0, 0.02) matrices, normal(0, 1) embedding rows, norm scales 1.

    The embedding's scale decides what the routers see.  With rows of 0.02
    a token's own embedding is no larger than what the position-free
    attention of layer 0 adds to it, the mean of some thousand random
    tokens' values, which every position shares: from layer 1 on most
    tokens then choose the same experts, which of them this chip holds is
    the seed's, and the rows its grouped products multiply (the step's
    length) follow the seed.  With unit rows the token decides, as in a
    trained model's first layers, and every seed sends the held experts an
    eighth of the assignments (config.json `assumed`, `weights`)."""
    if kind == "gamma":
        return jnp.ones(shape, jnp.float32)
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
    uniform over the vocabulary's slice, every row its own."""
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


def _mm(x, w, q, spec="...i,oi->...o"):
    """x W^T for an (out, in) matrix, or `spec`; both operands through the
    control's rounding when there is one."""
    if q is not None:
        x, w = q(x), q(w)
    return jnp.einsum(spec, x, w, precision=HI)


def rope(x, base):
    """(B, T, ..., R): rotary positions 0..T-1 over the whole last axis,
    dimension i paired with i + R/2."""
    r = x.shape[-1]
    inv_freq = base ** (-jnp.arange(r // 2, dtype=jnp.float32) * 2.0 / r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (r // 2,))
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(p, x, cfg, use_rope, window, q=None):
    """p: the block's leaves under their names without the `attn.` prefix;
    window None: every key up to the query's own."""
    b, t, _ = x.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    qq = _mm(x, p["q.weight"], q).reshape(b, t, h, hd)
    kk = _mm(x, p["k.weight"], q).reshape(b, t, hkv, hd)
    vv = _mm(x, p["v.weight"], q).reshape(b, t, hkv, hd)
    if use_rope:
        base = float(cfg["rope_theta"])
        qq, kk = rope(qq, base), rope(kk, base)
    if q is not None:
        qq, kk, vv = q(qq), q(kk), q(vv)
    # query head j = (key/value head j // g, its member j % g)
    qq = qq.reshape(b, t, hkv, h // hkv, hd)
    blk = Q_BLOCK if t % Q_BLOCK == 0 else t

    def block(i):
        qs = lax.dynamic_slice_in_dim(qq, i * blk, blk, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qs, kk, precision=HI) \
            * hd ** -0.5
        ahead = (i * blk + jnp.arange(blk))[:, None] - jnp.arange(t)[None, :]
        mask = ahead >= 0
        if window is not None:
            mask &= ahead < window
        s = jnp.where(mask, s, NEG)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1),
                          vv, precision=HI)

    att = lax.map(jax.checkpoint(block), jnp.arange(t // blk))
    att = jnp.moveaxis(att, 0, 1).reshape(b, t, h * hd)
    return _mm(att, p["proj.weight"], q)


def routing(h, router_w, top_k, norm_topk=True, q=None):
    """(chosen experts (..., k), their weights (..., k)): the `top_k`
    largest logits, a softmax over those alone (or, without `norm_topk`,
    their part of a softmax over all)."""
    r = _mm(h, router_w, q)
    if not norm_topk:
        r = jax.nn.softmax(r, axis=-1)
    w, idx = lax.top_k(r, top_k)
    return idx, jax.nn.softmax(w, axis=-1) if norm_topk else w


def moe_routed(router_w, gate, up, down, g, routed_by, top_k, norm_topk=True,
               first=0, q=None):
    """What the experts `first .. first + held - 1` give (`gate`, `up`:
    (held, D, F), `down`: (held, F, D)) for `g`, routed over all of
    `router_w`'s experts by `routed_by`: every token through every held
    expert, weighted by its routing weight, which is zero where the token
    did not choose the expert."""
    idx, w = routing(routed_by, router_w, top_k, norm_topk, q)

    def one_expert(y, ew):
        e, wg, wu, wd = ew
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        mid = jax.nn.relu(_mm(g, wg, q, "...i,io->...o")) \
            * _mm(g, wu, q, "...i,io->...o")
        return y + we[..., None] * _mm(mid, wd, q, "...i,io->...o"), None

    # a loop over the held experts, written as a scan so that the program
    # holds one expert's code and not `held` copies of it; recomputed on the
    # way back, so that only the running sum is kept for each expert
    y, _ = lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(g),
                    (jnp.arange(gate.shape[0]), gate, up, down))
    return y


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _layer(p, x, cfg, use_rope, window, q):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, p["n1.gamma"], eps)
    x = x + attention(_sub(p, "attn."), h, cfg, use_rope, window, q)
    g = rms_norm(x, p["n2.gamma"], eps)
    return x + moe_routed(
        p["ffn.router.weight"], p["ffn.experts.gate"], p["ffn.experts.up"],
        p["ffn.experts.down"], g, h, cfg["moe_num_active_primary_experts"],
        cfg["norm_topk_prob"], cfg["expert_parallel"]["first_expert"], q=q)


def logits(params, tokens, cfg, q=None):
    x = params["tok.weight"][tokens.astype(jnp.int32)]
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, use_rope=bool(cfg["rope_layout"][i]),
            window=cfg["sliding_window_size"]
            if cfg["sliding_window_layout"][i] else None, q=q))
        x = layer(_sub(params, f"l{i}."), x)
    x = rms_norm(x, params["normf.gamma"], cfg["rms_norm_eps"])
    return _mm(x, params["head.weight"], q)


def loss(params, tokens, labels, cfg, q=None):
    lg = logits(params, tokens, cfg, q)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                 axis=-1)
    return -jnp.mean(picked)
