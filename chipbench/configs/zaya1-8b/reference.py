"""Plain reference for `zaya1-8b`: the ZAYA1 decoder (`Zyphra/ZAYA1-8B`
config.json) in straightforward `jax.numpy`, float32, matmul precision
"highest".  Imports nothing of `mxnet_tpu`.

With `n(x) = x / sqrt(mean(x^2) + eps) * g` and `shift(a)_t = a_{t-1}`
(zeros at t = 0), a layer l is

  attention (compressed convolutional attention, arXiv:2510.04476)
    u = n1(x);  q0 = u W_q (8 heads of 128), k0 = u W_k (2 heads of 128)
    v = [u W_va | shift(u W_vb)]: value head 0 the token's, head 1 the one
        before it
    c = [q0 | k0];  c1 = a0 c + a1 shift(c) + b1          (per channel)
    c2^h = B0^h c1^h + B1^h shift(c1^h) + b2^h            (per head, 128 x 128)
    m_g = (mean of group g's query heads of q0 + key head g of k0) / 2
    q_j = c2^j + m_g(j), k_g = c2^(8+g) + m_g;  q <- sqrt(128) q / |q|,
        k <- sqrt(128) tau_g k / |k|;  rotary (base 5e6, split halves) over
        dims 0..63 of every head
    causal softmax(q k / sqrt(128)) v, query head j reading key/value head
        j // 4;  a = heads joined W_o
    x' = a1 * x + b1 * a
  experts
    w = n2(x');  rho = w W_down + b_down (+ gamma * rho of layer l-1, l > 0)
    z = W3 gelu(W2 gelu(W1 n_rho(rho)))      17 logits: 16 experts, skip
    p = softmax(z) + b_sel (zeros); e = argmax;  y = p_e E_e(w) if expert e
        is held here, else 0 (the skip, 16, is held nowhere)
    E_e(w) = W_d (silu(W_g w) * W_u w);  x_next = a2 * x' + b2 * y
  head  n_f(x) E^T, E the embedding; next-token cross-entropy, mean over
        tokens

The chip's share (config.json `expert_parallel`): of the 17 choices the
router scores, experts `first_expert .. first_expert + num_experts - 1` are
here; `moe_routed` with all 16 held is the uncut layer.  The vocabulary is
a slice: ids, the tied matrix, logits and loss are over `vocab_size` rows.

Departures from the plainest form, each so that three float32 steps with
Adam fit one chip at 4,096 tokens: every layer runs under `jax.checkpoint`;
attention takes the queries in blocks of `Q_BLOCK`, each under
`jax.checkpoint` (`lax.map`); the held experts run as one `lax.scan`
recomputed on the way back; the loss takes the tokens in blocks of
`LOSS_BLOCK`, each block's logits over the whole slice (134 MB a block of
512 tokens where the whole would be 2.15 GB) under `jax.checkpoint`.
Interface: see configs/resnet50_v1/reference.py.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NEG = -1e30
Q_BLOCK = 512
LOSS_BLOCK = 512
L2_EPS = 1e-12


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def leaves(cfg):
    d, h, g, hd = _dims(cfg)
    r, e1 = cfg["router_hidden_size"], cfg["expert_parallel"]["router_outputs"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    width = (h + g) * hd
    out = [("tok.weight", (cfg["vocab_size"], d), "embed")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + name, (d,), "ones") for name in (
            "attn_in_scale", "attn_out_scale", "ffn_in_scale",
            "ffn_out_scale")]
        out += [(p + "n1.gamma", (d,), "ones"),
                (p + "attn.conv0_weight", (cfg["cca_time0"], width), "conv0"),
                (p + "attn.conv0_bias", (width,), "zeros"),
                (p + "attn.conv1_weight", (h + g, cfg["cca_time1"], hd, hd),
                 "conv1"),
                (p + "attn.conv1_bias", (width,), "zeros"),
                (p + "attn.temperature", (g,), "ones"),
                (p + "attn.q.weight", (h * hd, d), "dense"),
                (p + "attn.k.weight", (g * hd, d), "dense"),
                (p + "attn.va.weight", (g * hd // 2, d), "dense"),
                (p + "attn.vb.weight", (g * hd // 2, d), "dense"),
                (p + "attn.proj.weight", (d, h * hd), "out"),
                (p + "n2.gamma", (d,), "ones"),
                (p + "router.down_weight", (r, d), "dense"),
                (p + "router.down_bias", (r,), "zeros")]
        if i:
            out.append((p + "router.state_scale", (r,), "ones"))
        out += [(p + "router.norm_gamma", (r,), "ones"),
                (p + "router.w1", (r, r), "router"),
                (p + "router.w2", (r, r), "router"),
                (p + "router.w3", (e1, r), "router"),
                (p + "ffn.experts.gate", (held, d, f), "dense"),
                (p + "ffn.experts.up", (held, d, f), "dense"),
                (p + "ffn.experts.down", (held, f, d), "out")]
    out.append(("normf.gamma", (d,), "ones"))
    return out


# the standard deviations of the seed's weights (config.json `assumed`,
# `weights`): Megatron-LM's init, 0.02 and, for the two projections that
# write into the residual stream (attention's output, an expert's down
# projection), 0.02 / sqrt(2 x the published 40 layers)
STD = 0.02
OUT_STD = 0.02 / (2 * 40) ** 0.5
EMBED_STD = 0.05


def init_leaf(key, shape, kind):
    """normal(0, STD) matrices and normal(0, OUT_STD) output projections;
    normal(0, EMBED_STD) embedding rows; the router's MLP normal(0,
    1/sqrt(fan-in)) with each row's mean taken out; convolution taps so
    that each convolution keeps its input's scale; scales 1, biases 0.

    Why the embedding's, the output projections' and the router's: the
    router reads n2(x'); where what attention adds, alike at neighbouring
    positions, outweighs a token's own row, or the GELUs' positive mean
    becomes a fixed preference of each expert, the tokens go where the
    seed's weights point (a load ratio of 4.7 and three quarters of the
    tokens on the 8 held of 17 choices in one run on a TPU v5 lite with
    rows and projections of 0.02 and uncentred rows); so the token
    decides, as in a balanced trained router, and every seed sends the
    held experts about 8/17.  The embedding is also the head: rows of EMBED_STD keep its
    logits' spread at about 2 (at 1 it is 45, and the softmax saturates)."""
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "router":
        w = jax.random.normal(key, shape, jnp.float32) * shape[-1] ** -0.5
        return w - jnp.mean(w, axis=-1, keepdims=True)
    if kind == "conv0":        # (taps, channels)
        std = shape[0] ** -0.5
    elif kind == "conv1":      # (heads, taps, out, in)
        std = (shape[1] * shape[3]) ** -0.5
    else:
        std = {"embed": EMBED_STD, "out": OUT_STD}.get(kind, STD)
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


def shift(x, steps=1):
    """Along axis 1: position t holds t - steps, the first ones zeros."""
    if steps == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :steps]), x[:, :-steps]],
                           axis=1)


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


def cca_qkv(p, u, cfg, q=None):
    """(q (B, T, H, d), k (B, T, G, d), v (B, T, G, d)) of compressed
    convolutional attention from the normalised input `u`; p: the block's
    leaves without the `attn.` prefix."""
    b, t, _ = u.shape
    _d, h, g, hd = _dims(cfg)
    q0 = _mm(u, p["q.weight"], q)
    k0 = _mm(u, p["k.weight"], q)
    v = jnp.concatenate([_mm(u, p["va.weight"], q),
                         shift(_mm(u, p["vb.weight"], q))], axis=-1)
    c = jnp.concatenate([q0, k0], axis=-1)
    w0 = p["conv0_weight"]
    c1 = sum(w0[j] * shift(c, j) for j in range(w0.shape[0])) \
        + p["conv0_bias"]
    c1 = c1.reshape(b, t, h + g, hd)
    w1 = p["conv1_weight"]
    c2 = sum(_mm(shift(c1, j), w1[:, j], q, "bthi,hoi->btho")
             for j in range(w1.shape[1])) \
        + p["conv1_bias"].reshape(h + g, hd)
    mean = 0.5 * (q0.reshape(b, t, g, h // g, hd).mean(axis=3)
                  + k0.reshape(b, t, g, hd))
    qq = c2[:, :, :h].reshape(b, t, g, h // g, hd) + mean[:, :, :, None]
    kk = c2[:, :, h:] + mean

    def unit(x):
        return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                            + L2_EPS)

    qq = unit(qq) * hd ** 0.5
    kk = unit(kk) * hd ** 0.5 * p["temperature"][:, None]
    r = int(hd * cfg["partial_rotary_factor"])
    base = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    qq = jnp.concatenate([rope(qq[..., :r], base), qq[..., r:]], axis=-1)
    kk = jnp.concatenate([rope(kk[..., :r], base), kk[..., r:]], axis=-1)
    return qq.reshape(b, t, h, hd), kk, v.reshape(b, t, g, hd)


def attention(p, u, cfg, q=None):
    b, t, _ = u.shape
    _d, h, g, hd = _dims(cfg)
    qq, kk, vv = cca_qkv(p, u, cfg, q)
    if q is not None:
        qq, kk, vv = q(qq), q(kk), q(vv)
    # query head j = (key/value head j // (h / g), its member)
    qq = qq.reshape(b, t, g, h // g, hd)
    blk = Q_BLOCK if t % Q_BLOCK == 0 else t

    def block(i):
        qs = lax.dynamic_slice_in_dim(qq, i * blk, blk, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qs, kk, precision=HI) \
            * hd ** -0.5
        ahead = (i * blk + jnp.arange(blk))[:, None] - jnp.arange(t)[None, :]
        s = jnp.where(ahead >= 0, s, NEG)
        return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1),
                          vv, precision=HI)

    att = lax.map(jax.checkpoint(block), jnp.arange(t // blk))
    att = jnp.moveaxis(att, 0, 1).reshape(b, t, h * hd)
    return _mm(att, p["proj.weight"], q)


def router(p, w, state, eps, q=None):
    """(17 logits, this layer's state); p: the router's leaves without the
    `router.` prefix; state None in the first layer."""
    rho = _mm(w, p["down_weight"], q) + p["down_bias"]
    if state is not None:
        rho = rho + p["state_scale"] * state
    hid = rms_norm(rho, p["norm_gamma"], eps)
    hid = jax.nn.gelu(_mm(hid, p["w1"], q), approximate=False)
    hid = jax.nn.gelu(_mm(hid, p["w2"], q), approximate=False)
    return _mm(hid, p["w3"], q), rho


def moe_routed(logits, gate, up, down, w, first=0, q=None):
    """What experts `first .. first + held - 1` give for `w` under top-1
    routing by softmax(logits): every token through every held expert,
    weighted by its chosen probability where it chose that expert, 0
    elsewhere; the last choice (the skip) is no expert."""
    prob = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(prob, axis=-1)
    pw = jnp.max(prob, axis=-1)

    def one_expert(y, ew):
        e, wg, wu, wd = ew
        we = jnp.where(idx == first + e, pw, 0.0)
        mid = jax.nn.silu(_mm(w, wg, q, "...i,io->...o")) \
            * _mm(w, wu, q, "...i,io->...o")
        return y + we[..., None] * _mm(mid, wd, q, "...i,io->...o"), None

    y, _ = lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(w),
                    (jnp.arange(gate.shape[0]), gate, up, down))
    return y


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _layer(p, x, state, cfg, q):
    eps = cfg["rms_norm_eps"]
    x = p["attn_in_scale"] * x + p["attn_out_scale"] * attention(
        _sub(p, "attn."), rms_norm(x, p["n1.gamma"], eps), cfg, q)
    w = rms_norm(x, p["n2.gamma"], eps)
    logits, state = router(_sub(p, "router."), w, state, eps, q)
    y = moe_routed(logits, p["ffn.experts.gate"], p["ffn.experts.up"],
                   p["ffn.experts.down"], w,
                   cfg["expert_parallel"]["first_expert"], q)
    return p["ffn_in_scale"] * x + p["ffn_out_scale"] * y, state


def hidden(params, tokens, cfg, q=None):
    x = params["tok.weight"][tokens.astype(jnp.int32)]
    state = None
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(functools.partial(_layer, cfg=cfg, q=q))
        x, state = layer(_sub(params, f"l{i}."), x, state)
    return rms_norm(x, params["normf.gamma"], cfg["rms_norm_eps"])


def logits(params, tokens, cfg, q=None):
    return _mm(hidden(params, tokens, cfg, q), params["tok.weight"], q)


def loss(params, tokens, labels, cfg, q=None):
    h = hidden(params, tokens, cfg, q)
    h = h.reshape(-1, h.shape[-1])
    labels = labels.reshape(-1).astype(jnp.int32)
    blk = LOSS_BLOCK if h.shape[0] % LOSS_BLOCK == 0 else h.shape[0]

    def block(i):
        hb = lax.dynamic_slice_in_dim(h, i * blk, blk)
        yb = lax.dynamic_slice_in_dim(labels, i * blk, blk)
        logp = jax.nn.log_softmax(_mm(hb, params["tok.weight"], q), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, yb[:, None], axis=-1))

    picked = lax.map(jax.checkpoint(block), jnp.arange(h.shape[0] // blk))
    return -jnp.sum(picked) / h.shape[0]
