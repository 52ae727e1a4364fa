"""Plain reference for `glm-4.7-flash`: the `glm4_moe_lite` decoder
(`zai-org/GLM-4.7-Flash` config.json) in straightforward `jax.numpy`,
float32, matmul precision "highest".  Imports nothing of `mxnet_tpu`.

With `n(x) = x / sqrt(mean(x^2) + eps) * g`:

  latent attention   c_q = n(x W_qa); q = c_q W_qb -> heads of q_nope | q_rope;
                     [c_kv | k_r] = x W_kva; [k_nope | v] = n(c_kv) W_kvb per
                     head; k_r is ONE rotary key for all heads; rotary over
                     the whole of q_rope and k_r; q = q_nope | rope(q_rope),
                     k = k_nope | rope(k_r); causal softmax(q k / sqrt(nope +
                     rope)) v; heads joined; W_o.  No bias anywhere.
  dense layer        x + attn(n1(x)); x + W_d(silu(W_g h) * W_u h), h = n2(x)
  expert layer       s = sigmoid(h W_r) (all experts); the top_k experts of
                     largest s + b; w_i = s_i / (sum of the chosen s + 1e-20)
                     * scale; y = sum_i w_i E_i(h) + E_shared(h), every E a
                     gated feed-forward
  head               n(x) W_head; next-token cross-entropy, mean over tokens

The chip's share (config.json `expert_parallel`): of the `router_outputs`
experts the router scores, experts `first_expert .. first_expert +
n_routed_experts - 1` are here.  A chosen expert that is absent adds
nothing, and that partial result goes on to the next layer; `moe_routed`
with `held == num_experts` is the uncut layer.  The vocabulary is a slice:
ids, logits and loss are over `vocab_size` entries.

Assumed, as the program has it (config.json `assumed`): the selection bias
b is zero; the rotary pairing is "split halves" (dimension i turns with
i + rope/2).

Every layer runs under `jax.checkpoint`, so that three float32 steps with
Adam fit one chip; the held experts of a layer run as one `lax.scan`, so
that the program holds one expert's products a layer and compiles in half
the time (each float32 "highest" product costs the TPU's compiler seconds).  Interface: see configs/resnet50_v1/reference.py.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NEG = -1e30


def _ffn_leaves(prefix, d, f):
    return [(prefix + "gate.weight", (f, d), "dense"),
            (prefix + "up.weight", (f, d), "dense"),
            (prefix + "down.weight", (d, f), "dense")]


def leaves(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    held, fe = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    out = [("tok.weight", (cfg["vocab_size"], d), "embed")]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "n1.gamma", (d,), "gamma"),
                (p + "attn.qa.weight", (qr, d), "dense"),
                (p + "attn.qnorm.gamma", (qr,), "gamma"),
                (p + "attn.qb.weight", (h * (dn + dr), qr), "dense"),
                (p + "attn.kva.weight", (kvr + dr, d), "dense"),
                (p + "attn.kvnorm.gamma", (kvr,), "gamma"),
                (p + "attn.kvb.weight", (h * (dn + dv), kvr), "dense"),
                (p + "attn.proj.weight", (d, h * dv), "dense"),
                (p + "n2.gamma", (d,), "gamma")]
        if i < cfg["first_k_dense_replace"]:
            out += _ffn_leaves(p + "ffn.", d, cfg["intermediate_size"])
        else:
            out += [(p + "ffn.router.weight",
                     (cfg["expert_parallel"]["router_outputs"], d), "dense"),
                    (p + "ffn.experts.gate", (held, d, fe), "dense"),
                    (p + "ffn.experts.up", (held, d, fe), "dense"),
                    (p + "ffn.experts.down", (held, fe, d), "dense")]
            out += _ffn_leaves(p + "ffn.shared.", d,
                               fe * cfg["n_shared_experts"])
    out += [("normf.gamma", (d,), "gamma"),
            ("head.weight", (cfg["vocab_size"], d), "dense")]
    return out


def init_leaf(key, shape, kind):
    """normal(0, 0.02) matrices and embeddings, norm scales 1."""
    if kind == "gamma":
        return jnp.ones(shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * 0.02


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


def latent_attention(p, x, cfg, q=None):
    """p: the block's leaves under their names without the `attn.` prefix."""
    b, t, _ = x.shape
    h, eps, base = (cfg["num_attention_heads"], cfg["rms_norm_eps"],
                    float(cfg["rope_theta"]))
    kvr, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    cq = rms_norm(_mm(x, p["qa.weight"], q), p["qnorm.gamma"], eps)
    qq = _mm(cq, p["qb.weight"], q).reshape(b, t, h, dn + dr)
    kva = _mm(x, p["kva.weight"], q)
    ckv, kr = kva[..., :kvr], kva[..., kvr:]
    kv = _mm(rms_norm(ckv, p["kvnorm.gamma"], eps), p["kvb.weight"],
             q).reshape(b, t, h, dn + dv)
    qq = jnp.concatenate([qq[..., :dn], rope(qq[..., dn:], base)], axis=-1)
    kr = jnp.broadcast_to(rope(kr, base)[:, :, None, :], (b, t, h, dr))
    kk = jnp.concatenate([kv[..., :dn], kr], axis=-1)
    vv = kv[..., dn:]
    if q is not None:
        qq, kk, vv = q(qq), q(kk), q(vv)
    s = jnp.einsum("bqhd,bkhd->bhqk", qq, kk, precision=HI) \
        * (dn + dr) ** -0.5
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(mask[None, None], s, NEG)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vv,
                     precision=HI).reshape(b, t, h * dv)
    return _mm(att, p["proj.weight"], q)


def gated_ffn(p, x, q=None):
    """p: gate.weight, up.weight (F, D), down.weight (D, F)."""
    return _mm(jax.nn.silu(_mm(x, p["gate.weight"], q))
               * _mm(x, p["up.weight"], q), p["down.weight"], q)


def routing(h, router_w, bias, top_k, scale, norm_topk, q=None):
    """(chosen experts (..., k), their weights (..., k))."""
    s = jax.nn.sigmoid(_mm(h, router_w, q))
    _, idx = lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scale


def moe_routed(router_w, gate, up, down, h, top_k, scale, norm_topk,
               first=0, bias=None, q=None):
    """The routed part of an expert layer that the experts `first .. first
    + held - 1` give (`gate`, `up`: (held, D, F), `down`: (held, F, D)),
    routed over all of `router_w`'s experts: every token through every held
    expert, weighted by its routing weight, which is zero where the token
    did not choose the expert."""
    bias = jnp.zeros((router_w.shape[0],), jnp.float32) if bias is None \
        else bias
    idx, w = routing(h, router_w, bias, top_k, scale, norm_topk, q)

    def one_expert(y, ew):
        e, g, u, d = ew
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        mid = jax.nn.silu(_mm(h, g, q, "...i,io->...o")) \
            * _mm(h, u, q, "...i,io->...o")
        return y + we[..., None] * _mm(mid, d, q, "...i,io->...o"), None

    # a loop over the held experts, written as a scan so that the program
    # holds one expert's code and not `held` copies of it; recomputed on the
    # way back, so that only the running sum is kept for each expert
    y, _ = lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                    (jnp.arange(gate.shape[0]), gate, up, down))
    return y


def _sub(p, prefix):
    return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}


def _layer(p, x, cfg, dense, q):
    eps = cfg["rms_norm_eps"]
    x = x + latent_attention(_sub(p, "attn."),
                             rms_norm(x, p["n1.gamma"], eps), cfg, q)
    h = rms_norm(x, p["n2.gamma"], eps)
    if dense:
        return x + gated_ffn(_sub(p, "ffn."), h, q)
    y = moe_routed(p["ffn.router.weight"], p["ffn.experts.gate"],
                   p["ffn.experts.up"], p["ffn.experts.down"], h,
                   cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
                   cfg["norm_topk_prob"],
                   cfg["expert_parallel"]["first_expert"], q=q)
    return x + y + gated_ffn(_sub(p, "ffn.shared."), h, q)


def logits(params, tokens, cfg, q=None):
    x = params["tok.weight"][tokens.astype(jnp.int32)]
    for i in range(cfg["num_hidden_layers"]):
        layer = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, dense=i < cfg["first_k_dense_replace"], q=q))
        x = layer(_sub(params, f"l{i}."), x)
    x = rms_norm(x, params["normf.gamma"], cfg["rms_norm_eps"])
    return _mm(x, params["head.weight"], q)


def loss(params, tokens, labels, cfg, q=None):
    lg = logits(params, tokens, cfg, q)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                 axis=-1)
    return -jnp.mean(picked)
