"""Operators of a current decoder block: RMS norm, rotary positions, two
attention assemblies (latent keys and values at one head count;
grouped-query heads with rotary positions or none and a sliding window or
none), the mixing of compressed convolutional attention (`cca_mix`), an
MLP router that carries a state from layer to layer, a mixture-of-experts
feed-forward that is told which experts it holds and how its model routes
(sigmoid scores with a selection bias, or a softmax over the chosen logits;
a SiLU or a ReLU gate; the router reading the experts' input, a tensor of
its own, or scores the block computed, whose last choice is a skip), and a
cross-entropy over an output matrix that walks the vocabulary in blocks
(`blocked_cross_entropy`).

Registered as ops (not python in the gluon blocks) for the reason
`_contrib_multihead_attention` is: the head splits, the rotation tables and
the ragged split of the expert buffer depend on shapes, and an op always
sees concrete shapes, so the blocks hybridize to one graph.

The expert op is what an expert-parallel step wraps its exchange around:
it routes over ALL `num_experts`, and computes the part of the result that
the experts `[first, first + held)` give.  A chosen expert that is absent
adds nothing.  No capacity, no dropped token: the (token, choice) pairs
are sorted by expert, the held experts' first, and the three products of
the gated feed-forward run as grouped products over that ragged split
(`lax.ragged_dot`; on the TPU XLA lowers it to a Mosaic grouped-matmul call
that visits only the row tiles its group sizes cover, so the tail of the
buffer that no held expert owns costs no MXU time).  Where the products'
outputs are kept for the backward pass the buffer has `T * top_k` rows, the
most a dropless layer can need.  Where they are run again in the backward
pass (`KEEP_BYTES_MAX`) the buffer is the shorter of two lengths fixed by
the shapes (`buffer_rungs`) that holds the rows the held experts got,
chosen on the device; the longer is `T * top_k`.  Either
way the rows are gathered straight from the tokens and each token reads
its results back from the buffer, so the gathers, masks and casts around
the products cost what the buffer costs, and nothing is as long as
`T * top_k` rows of width D unless the buffer is.  A layer routed by
scores with a skip (`moe_ffn_scored`) lays its buffer out so that the
products visit the same tiles whatever the routing (`_steady_rows`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..base import Arg
from ..observability import metrics as _metrics
from .flash_attention import _dense_reference, _flash_attention
from .registry import RESIDUAL_NAME, register

# rows of one tile of the TPU's grouped product (the v5e compile of
# `lax.ragged_dot` carries metadata of m / 512 + groups - 1 tile visits)
GROUPED_TILE_ROWS = 512


@register("_contrib_rms_norm", input_names=("data", "gamma"),
          aliases=("rms_norm",), args=[Arg("eps", float, 1e-5)])
def _rms_norm(p, x, gamma):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis, the
    statistics in float32 whatever the activations' dtype."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                    + p["eps"])
    return (xf * inv * gamma.astype(jnp.float32)).astype(x.dtype)


def _rope(x, base, time_axis=1):
    """Rotary positions over the whole last axis of `x`, positions
    0..T-1 along `time_axis`.  Pairing: dimension i turns with dimension
    i + R/2 (the "split halves" form); the interleaved form differs from
    it by a fixed permutation of the projection's columns."""
    rot = x.shape[-1]
    half = rot // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = jnp.arange(x.shape[time_axis], dtype=jnp.float32)[:, None] \
        * inv_freq[None, :]                                  # (T, R/2)
    shape = [1] * x.ndim
    shape[time_axis], shape[-1] = x.shape[time_axis], half
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


@register("_contrib_rotary_embedding", input_names=("data",),
          aliases=("rotary_embedding",),
          args=[Arg("base", float, 10000.0), Arg("time_axis", int, 1)])
def _rotary_embedding(p, x):
    """Rotary position embedding over the last axis of (B, T, ..., R)."""
    if x.shape[-1] % 2:
        raise ValueError(f"rotary_embedding: the last axis ({x.shape[-1]}) "
                         "must be even")
    return _rope(x, p["base"], p["time_axis"] % x.ndim)


@register("_contrib_latent_attention",
          input_names=("q", "kv", "k_rope"), aliases=("latent_attention",),
          args=[Arg("num_heads", int, required=True),
                Arg("nope_dim", int, required=True),
                Arg("rope_dim", int, required=True),
                Arg("v_dim", int, required=True),
                Arg("rope_base", float, 10000.0),
                Arg("impl", str, "dense")])
def _latent_attention(p, q, kv, k_rope):
    """Causal attention as a latent-attention block assembles it.

    q: (B, T, H * (nope + rope)), the up-projected queries, each head
    `q_nope | q_rope`; kv: (B, T, H * (nope + v)), the up-projected latent,
    each head `k_nope | v`; k_rope: (B, T, rope), ONE rotary key shared by
    all heads.  Rotary positions turn `q_rope` and `k_rope`; a head's key
    is `k_nope | rope(k_rope)`; scores are scaled by (nope + rope) ** -0.5.
    Returns (B, T, H * v).  impl='flash' is the Pallas kernel (it takes one
    head size for q, k and v, so nope + rope must equal v; it chooses its
    tiles from T, that head size and the dtype, multiplies q, k and v in
    the dtype they have with softmax and accumulation in float32, and
    skips the blocks above the diagonal), 'dense' materializes the scores.
    """
    B, T, _ = q.shape
    H, dn, dr, dv = p["num_heads"], p["nope_dim"], p["rope_dim"], p["v_dim"]
    q = q.reshape(B, T, H, dn + dr)
    kv = kv.reshape(B, T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], p["rope_base"])],
                        axis=-1)
    kr = _rope(k_rope, p["rope_base"])[:, :, None, :]
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(kr, (B, T, H, dr))], axis=-1)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., dn:]))
    scale = float(dn + dr) ** -0.5
    if p["impl"] == "flash":
        if dn + dr != dv:
            raise ValueError(
                f"latent_attention impl='flash': the kernel takes one head "
                f"size, got {dn}+{dr} for q and k and {dv} for v")
        out = _flash_attention(q, k, v, scale, True)
    elif p["impl"] == "dense":
        out = _dense_reference(q, k, v, scale, True)
    else:
        raise ValueError(f"latent_attention impl={p['impl']!r}: choose "
                         "'dense' or 'flash'")
    return out.transpose(0, 2, 1, 3).reshape(B, T, H * dv)


@register("_contrib_grouped_query_attention",
          input_names=("q", "k", "v"), aliases=("grouped_query_attention",),
          args=[Arg("num_heads", int, required=True),
                Arg("num_kv_heads", int, required=True),
                Arg("rope", bool, True), Arg("rope_base", float, 10000.0),
                Arg("window", int, -1), Arg("impl", str, "dense")])
def _grouped_query_attention(p, q, k, v):
    """Causal attention over grouped-query heads.

    q: (B, T, H * D); k, v: (B, T, Hkv * D), H a multiple of Hkv: query
    head j attends to key/value head j // (H / Hkv).  `rope`: rotary
    positions over the whole of every query and key head ("split halves"
    pairing, as `rotary_embedding`); off, the layer has no positions at
    all.  `window` W > 0: query t sees keys t - W < s <= t (its own and
    the W - 1 before it); left at -1, every key up to its own.  Scores are
    scaled by D ** -0.5.  Returns (B, T, H * D).  impl='flash' is the
    Pallas kernel (keys and values stay at Hkv heads; the tiles it visits
    follow the mask), 'dense' materializes the scores.
    """
    B, T, _ = q.shape
    H, Hkv = p["num_heads"], p["num_kv_heads"]
    D = q.shape[-1] // H
    if H % Hkv or k.shape[-1] != Hkv * D or v.shape[-1] != Hkv * D:
        raise ValueError(
            f"grouped_query_attention: {H} query heads of {D} over {Hkv} "
            f"key/value heads need k and v of width {Hkv * D}, got "
            f"{k.shape[-1]} and {v.shape[-1]}")
    q = q.reshape(B, T, H, D)
    k, v = k.reshape(B, T, Hkv, D), v.reshape(B, T, Hkv, D)
    if p["rope"]:
        q, k = _rope(q, p["rope_base"]), _rope(k, p["rope_base"])
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    scale = float(D) ** -0.5
    window = p["window"] if p["window"] > 0 else None
    if p["impl"] == "flash":
        out = _flash_attention(q, k, v, scale, True, None, None, window)
    elif p["impl"] == "dense":
        out = _dense_reference(q, k, v, scale, True, window)
    else:
        raise ValueError(f"grouped_query_attention impl={p['impl']!r}: "
                         "choose 'dense' or 'flash'")
    return out.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _shift(x, steps=1):
    """`x` moved `steps` positions later along axis 1 (time): position t
    holds t - steps, the first `steps` positions zeros (causal: nothing
    before t = 0)."""
    if steps == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (steps, 0)
    return lax.slice_in_dim(jnp.pad(x, pad), 0, x.shape[1], axis=1)


# under the square root of a query's or key's L2 norm, so that a head of
# zeros gives zeros and not a NaN
L2_EPS = 1e-12


@register("_contrib_cca_mix",
          input_names=("q", "k", "va", "vb", "conv0_weight", "conv0_bias",
                       "conv1_weight", "conv1_bias", "temperature"),
          aliases=("cca_mix",), num_outputs=3,
          args=[Arg("num_heads", int, required=True),
                Arg("num_kv_heads", int, required=True),
                Arg("rope_dims", int, 0), Arg("rope_base", float, 10000.0)])
def _cca_mix(p, q, k, va, vb, w0, b0, w1, b1, temp):
    """The mixing of compressed convolutional attention (arXiv:2510.04476,
    section 3): from the projected queries and keys to the ones attention
    reads.

    q: (B, T, H * d); k: (B, T, G * d); va, vb: (B, T, G * d / 2), the two
    halves of the values; conv0_weight (K0, (H + G) d), conv0_bias
    ((H + G) d,): a causal convolution over time, one filter a channel, tap
    j reading position t - j; conv1_weight (H + G, K1, d, d) [head, tap,
    out, in], conv1_bias ((H + G) d,): a causal convolution over time and
    the channels of one head; temperature (G,).  With c = [q | k]:

        c1 = sum_j w0[j] c_{t-j} + b0;  c2^h = sum_j w1[h, j] c1^h_{t-j} + b1^h
        m_g = (mean of the query heads of group g + key head g) / 2
        q_j = c2^j + m_g(j),  k_g = c2^(H+g) + m_g
        q <- sqrt(d) q / |q|,  k <- sqrt(d) temperature_g k / |k|

    then rotary positions (`rope_base`, split halves) over the first
    `rope_dims` of every head, the rest untouched.  v = [va | vb one
    position later]: the first G/2 value heads read the token, the last
    G/2 the one before it.  Returns (q, k, v) in q's dtype; the
    convolution over channels multiplies in that dtype with float32 sums,
    everything else is float32."""
    B, T, _ = q.shape
    H, G, r = p["num_heads"], p["num_kv_heads"], p["rope_dims"]
    d = q.shape[-1] // H
    if H % G or k.shape[-1] != G * d or w1.shape[2:] != (d, d) \
            or va.shape[-1] + vb.shape[-1] != G * d or r > d or r % 2:
        raise ValueError(
            f"cca_mix: {H} query heads of {d} over {G} key/value heads, "
            f"rope_dims {r}: got k {k.shape}, va {va.shape}, vb {vb.shape}, "
            f"conv1_weight {w1.shape}")
    f32, dt = jnp.float32, q.dtype
    with jax.named_scope("cca_mix"):
        c = jnp.concatenate([q, k], axis=-1).astype(f32)
        c1 = sum(w0[j].astype(f32) * _shift(c, j)
                 for j in range(w0.shape[0])) + b0.astype(f32)
        c1 = c1.astype(dt).reshape(B, T, H + G, d)
        c2 = sum(jnp.einsum("bthi,hoi->btho", _shift(c1, j), w1[:, j],
                            preferred_element_type=f32)
                 for j in range(w1.shape[1]))
        c2 = c2 + b1.astype(f32).reshape(H + G, d)
        q0 = q.astype(f32).reshape(B, T, G, H // G, d)
        k0 = k.astype(f32).reshape(B, T, G, d)
        m = 0.5 * (jnp.mean(q0, axis=3) + k0)
        qh = c2[:, :, :H].reshape(B, T, G, H // G, d) + m[:, :, :, None]
        kh = c2[:, :, H:] + m

        def unit(x):
            return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                         keepdims=True) + L2_EPS)

        qh = unit(qh) * d ** 0.5
        kh = unit(kh) * (d ** 0.5 * temp.astype(f32)[:, None])
        if r:
            qh = jnp.concatenate([_rope(qh[..., :r], p["rope_base"]),
                                  qh[..., r:]], axis=-1)
            kh = jnp.concatenate([_rope(kh[..., :r], p["rope_base"]),
                                  kh[..., r:]], axis=-1)
        v = jnp.concatenate([va, _shift(vb)], axis=-1)
    return (qh.reshape(B, T, H * d).astype(dt),
            kh.reshape(B, T, G * d).astype(dt), v)


ROUTERS = ("sigmoid", "softmax_topk")
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route(h, router_w, bias, top_k, scale, norm_topk, router="sigmoid"):
    """`select` over the logits of a linear router: `h` (T, D) scored by
    `router_w` (E, D), the product in float32."""
    s = jnp.einsum(
        "td,ed->te", h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
    return select(s, bias, top_k, scale, norm_topk, router)


def select(s, bias, top_k, scale, norm_topk, router="sigmoid"):
    """(chosen experts (T, k) int32, their weights (T, k) float32) from the
    router's logits `s` (T, E), everything in float32.

    'sigmoid': sigmoid scores; the `top_k` experts of largest score +
    bias; weights from the scores WITHOUT the bias, normalised over the
    chosen, times `scale`.  'softmax_topk': the `top_k` experts of largest
    logit + bias; weights a softmax over the chosen logits alone
    (`norm_topk`; the same numbers as a softmax over all experts
    renormalised over the chosen) or, without `norm_topk`, the chosen
    experts' part of a softmax over all; times `scale`."""
    if router == "sigmoid":
        s = jax.nn.sigmoid(s)
    elif router != "softmax_topk":
        raise ValueError(f"moe_ffn router={router!r}: choose one of "
                         f"{ROUTERS}")
    elif not norm_topk:
        s = jax.nn.softmax(s, axis=-1)
    _, idx = lax.top_k(s + lax.stop_gradient(bias.astype(jnp.float32)),
                       top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if router == "softmax_topk" and norm_topk:
        w = jax.nn.softmax(w, axis=-1)
    elif norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def _sorted_rows(key, held):
    """(order, sizes): the (token, choice) rows sorted by `key` (held
    experts 0..held-1, absent ones `held`, which sort last, so the live rows
    are a prefix), and how many rows each key got."""
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(held + 1, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    return order, sizes


def _grouped_ffn(xs, groups, gate_w, up_w, down_w, act, live=None):
    """The three grouped products (`act` on the gate's) over the ragged
    split of the buffer `xs` whose rows flagged `live` (by default the
    first sum(groups)) hold (token, choice) pairs."""
    # The tail of the buffer belongs to no held expert.  The TPU's grouped
    # product leaves rows it does not visit as they were in memory (read on
    # the chip: values up to 5 in the tail), on the way back too, so the
    # tail is cut off on both sides of the products: what comes out of them
    # there is not a result, and what flows back into them is not a gradient.
    if live is None:
        live = jnp.arange(xs.shape[0], dtype=jnp.int32) < jnp.sum(groups)
    live = live[:, None]
    xs = jnp.where(live, xs, jnp.zeros((), xs.dtype))
    mid = act(lax.ragged_dot(xs, gate_w, groups)) \
        * lax.ragged_dot(xs, up_w, groups)
    ys = lax.ragged_dot(mid, down_w, groups)
    return jnp.where(live, ys, jnp.zeros((), ys.dtype))


def buffer_rungs(rows, held, num_experts):
    """The lengths the expert buffer may take, shortest first: the shortest
    power-of-two fraction of `rows` (T * top_k, the dropless bound) that
    holds twice the rows the held experts get under even routing, rounded
    up to whole tiles of the grouped product, and `rows` itself, so that no
    token is ever dropped.  Two lengths, not one a power of two: every
    length carries its own grouped products and gathers in both programs,
    and a middle one (49,152 rows in `smallthinker21b_train_gluon`) won 1.3%
    of `tokens_per_s` for 14 s more compile and half as much again of the
    op's code (PR 36, PERF.md section 6)."""
    floor = 2 * rows * held / num_experts
    k = 0
    while rows / 2 ** (k + 1) >= floor:
        k += 1
    c = -(-rows // 2 ** k)
    return tuple(sorted(
        {rows, min(rows, -(-c // GROUPED_TILE_ROWS) * GROUPED_TILE_ROWS)}))


def _pick(table, slot, live, weight=None):
    """(T, D) float32: for each token the sum over its choices j of
    table[slot[t, j]] (times weight[t, j]) where slot[t, j] < live: the
    rows of a buffer of len(table) rows whose first `live` rows hold
    results, read back into the tokens that sent them.  One choice at a
    time, so each gather is T rows and fuses into the sum; no scatter (a
    scatter of float32 rows of width 2,560 took 6-8 MB of program code
    each, and the cell's programs outgrew the compile cache: PR 36)."""
    out = jnp.zeros((slot.shape[0], table.shape[1]), jnp.float32)
    for j in range(slot.shape[1]):
        rows = table.at[jnp.minimum(slot[:, j], table.shape[0] - 1)].get(
            mode="promise_in_bounds").astype(jnp.float32)
        if weight is not None:
            rows = rows * weight[:, j, None]
        out = out + jnp.where((slot[:, j] < live)[:, None], rows, 0.0)
    return out


@jax.custom_vjp
def _token_rows(x, order, slot, live):
    """x[order // top_k]: the buffer's rows gathered straight from the
    tokens.  The backward pass reads each token's rows back (`_pick`) and
    adds them in float32."""
    return x.at[order // slot.shape[1]].get(mode="promise_in_bounds")


_token_rows.defvjp(
    lambda x, order, slot, live: (_token_rows(x, order, slot, live),
                                  (slot, live)),
    lambda res, g: (_pick(g, *res).astype(g.dtype), None, None, None))


@jax.custom_vjp
def _combine(ys, w, order, slot, live):
    """Each token's results weighted by its choices' weights `w` (T,
    top_k) and added up in float32, in ys' dtype (`_pick`).  The backward
    pass gathers the cotangent in its own dtype, a row a row of `ys`."""
    return _pick(ys, slot, live, w).astype(ys.dtype)


def _combine_bwd(res, g):
    ys, w, order, slot, live = res
    top_k = slot.shape[1]
    g = g.at[order // top_k].get(mode="promise_in_bounds").astype(
        jnp.float32)
    wt = w.reshape(-1).at[order].get(mode="promise_in_bounds")
    dwt = jnp.sum(g * ys.astype(jnp.float32), axis=1)
    dw = jnp.where(slot < live, dwt.at[jnp.minimum(slot, ys.shape[0] - 1)]
                   .get(mode="promise_in_bounds"), 0.0)
    return ((g * wt[:, None]).astype(ys.dtype), dw.astype(w.dtype), None,
            None, None)


_combine.defvjp(
    lambda ys, w, order, slot, live: (_combine(ys, w, order, slot, live),
                                      (ys, w, order, slot, live)),
    _combine_bwd)


def _expert_rows(h, key, w, gate_w, up_w, down_w, act=jax.nn.silu, *,
                 buffer):
    """The held experts' part over a buffer of `buffer` rows, which must
    hold the live ones: the (token, choice) rows sorted by `key`, the first
    `buffer` of them gathered straight from `h` by token, the grouped
    products, and each token's results read back from the buffer by the
    place its choices sorted to, weighted and summed over its choices.
    Nothing is as long as `T * top_k` rows of width D unless `buffer` is.
    Returns (y, the rows of each held expert, of absent ones, and
    `buffer`)."""
    held, top_k = gate_w.shape[0], w.shape[1]
    rows = key.shape[0]
    order, sizes = _sorted_rows(key, held)
    slot = jnp.zeros_like(order).at[order].set(
        jnp.arange(rows, dtype=jnp.int32), unique_indices=True
    ).reshape(-1, top_k)
    live = jnp.sum(sizes[:held])
    order = order[:buffer]
    ys = _grouped_ffn(_token_rows(h, order, slot, live), sizes[:held],
                      gate_w, up_w, down_w, act)
    return _combine(ys, w, order, slot, live), jnp.append(sizes, buffer)


def steady_buffer(rows, held):
    """The buffer of `_steady_rows`: the `rows` pairs and a spare row for
    each held expert, in whole tiles of the grouped product."""
    return -(-(rows + held) // GROUPED_TILE_ROWS) * GROUPED_TILE_ROWS


def _steady_rows(h, key, w, gate_w, up_w, down_w, act=jax.nn.silu, *,
                 buffer):
    """`_expert_rows` over a buffer whose grouped products cost the same
    whatever the routing: each held expert's rows are followed by one spare
    row, so no group is empty and each of the held - 1 boundaries between
    groups falls inside a tile (on a tile's edge once in 512), and the last
    group runs to the end of the buffer, `steady_buffer(rows, held)` rows.
    The products then visit every tile and one more a boundary.  On the
    ragged split of `_expert_rows` the tiles visited follow the rows the
    held experts got and how many groups are empty: one layer of the zaya
    cell's shape took 2.9 to 5.0 ms forward and backward over fifteen
    routings, this layout 5.4 (TPU v5 lite, PERF.md section 6).  Spare and
    tail rows are gaps: gathered from token 0 and cut off on both sides of
    the products."""
    held, top_k = gate_w.shape[0], w.shape[1]
    rows = key.shape[0]
    order, sizes = _sorted_rows(key, held)
    k = key[order]
    # sorted row j of held expert k sits k spare rows further on
    place = jnp.where(k < held, jnp.arange(rows, dtype=jnp.int32) + k,
                      buffer)
    slot = jnp.zeros_like(order).at[order].set(
        place, unique_indices=True).reshape(-1, top_k)
    source = jnp.zeros((buffer,), jnp.int32).at[place].set(
        order, mode="drop", unique_indices=True)
    filled = jnp.zeros((buffer,), bool).at[place].set(True, mode="drop")
    groups = (sizes[:held] + 1).at[held - 1].add(
        buffer - held - jnp.sum(sizes[:held], dtype=sizes.dtype))
    ys = _grouped_ffn(_token_rows(h, source, slot, buffer), groups,
                      gate_w, up_w, down_w, act, live=filled)
    return _combine(ys, w, source, slot, buffer), jnp.append(sizes, buffer)


# The outputs of one layer's three grouped products are rows x (2 F + D)
# values, and a recorded CachedOp call keeps them for its backward program
# (gluon/block.py _RESIDUAL_POLICY reaches into this op's own
# `jax.checkpoint`).  Past this many bytes a layer they are not kept: the
# backward pass runs the layer's grouped products again from the op's
# inputs, over a buffer only as long as the rows the held experts got
# (`buffer_rungs`).  At 2 x 2,048 tokens, 4 a token, F 1,536, D 2,048 they
# are 168 MB a layer over the whole buffer of T x top_k rows and are kept;
# at 2 x 8,192 tokens, 6 a token, F 768, D 2,560
# they would be 805 MB a layer, 3.2 GB of four layers' residuals, most of it
# rows that no held expert owns, and the step does not fit the chip beside
# them (17.4 of 16.9 GB: v5e compiles, PR 31).  PROVISIONAL (PR 31): the
# bound lies between those two readings and no cell has been measured on
# the other side of it.  ROADMAP D10: run the SiLU expert cell under
# `_run_again_in_backward` and keep one mode; a kept buffer cut to a rung
# would keep every rung's products (a conditional that is differentiated
# keeps the union of its branches' residuals).
KEEP_BYTES_MAX = 2 ** 29


def _run_again_in_backward(experts, rungs):
    """`experts(h, key, w, gate_w, up_w, down_w, buffer=C) -> (y, sizes)`
    on the shortest of `rungs` that holds the rows the held experts got
    (chosen on the device from `key`, the same way forward and backward),
    whose backward pass starts from its inputs and nothing else, whatever
    an enclosing `jax.checkpoint`'s policy would keep of its products.  The
    choice is made around the whole backward of a rung, never inside what
    is differentiated: a conditional that is differentiated keeps the
    union of every branch's residuals."""
    def on_rung(key, held, run):
        if len(rungs) == 1:
            return run(rungs[0])
        live = jnp.sum(key < held, dtype=jnp.int32)
        i = jnp.sum(live > jnp.asarray(rungs[:-1], jnp.int32),
                    dtype=jnp.int32)
        return lax.switch(i, [functools.partial(run, c) for c in rungs])

    @jax.custom_vjp
    def call(h, key, w, *weights):
        return on_rung(key, weights[0].shape[0], lambda c: experts(
            h, key, w, *weights, buffer=c))

    def fwd(*args):
        return call(*args), args

    def bwd(args, cots):
        # tied to the cotangent, as `jax.checkpoint` ties its own: else the
        # compiler is free to run every layer's products again at the head
        # of the backward program and hold them all until their turn (12.6
        # GB of temporaries against 6.9: v5e compiles, PR 31)
        (h, key, *rest), dy = lax.optimization_barrier((args, cots[0]))

        def grads(c):
            return tuple(jax.vjp(
                lambda h_, *rest_: experts(h_, key, *rest_, buffer=c)[0],
                h, *rest)[1](dy))

        dh, *drest = on_rung(key, rest[1].shape[0], grads)
        return (dh, None, *drest)  # the rows' keys are integers

    call.defvjp(fwd, bwd)
    return call


_MOE_ARGS = [Arg("num_experts", int, required=True),
             Arg("top_k", int, required=True),
             Arg("first", int, 0), Arg("held", int, required=True),
             Arg("scale", float, 1.0), Arg("norm_topk", bool, True),
             Arg("router", str, "sigmoid"), Arg("activation", str, "silu")]


def _moe(p, x, routed_by, router_w, bias, gate_w, up_w, down_w, load):
    """The expert ops' body: the experts read `x`, the router `routed_by`
    (scored by `router_w`, or, where that is None, the router's logits
    (..., num_experts) themselves, whose last choice is a skip: no expert
    at all, held nowhere; a token that takes it adds nothing, and `load`
    has a slot for it)."""
    E, k, first, held = (p["num_experts"], p["top_k"], p["first"],
                         p["held"])
    skip = router_w is None
    if gate_w.shape[0] != held or not 0 <= first <= E - skip - held:
        raise ValueError(f"moe_ffn: held={held} first={first} of {E} "
                         f"experts against {gate_w.shape[0]} stacked")
    if p["activation"] not in ACTIVATIONS:
        raise ValueError(f"moe_ffn activation={p['activation']!r}: choose "
                         f"one of {sorted(ACTIVATIONS)}")
    if load.shape != (held + 2 + skip,):
        raise ValueError(
            f"moe_ffn: load must be ({held + 2 + skip},): each held "
            f"expert's rows, absent ones', {'skipped ones, ' * skip}the "
            f"buffer's; got {load.shape}")
    if routed_by.shape[:-1] != x.shape[:-1]:
        raise ValueError(f"moe_ffn: the router's input {routed_by.shape} "
                         f"and the experts' {x.shape} differ in tokens")
    h = x.reshape(-1, x.shape[-1])
    hr = h if routed_by is x else \
        routed_by.reshape(-1, routed_by.shape[-1])
    if router_w is None:
        if hr.shape[1] != E:
            raise ValueError(f"moe_ffn: {hr.shape[1]} scores a token for "
                             f"{E} choices")
        idx, w = select(hr, bias, k, p["scale"], p["norm_topk"], p["router"])
    else:
        idx, w = route(hr, router_w, bias, k, p["scale"], p["norm_topk"],
                       p["router"])
    local = idx - first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    rows = key.shape[0]
    required = rows * held / E
    _metrics.MOE_ROWS.set(required, kind="required")
    if skip:
        # one choice a token and about half of them held: the products
        # visit every tile of the steady buffer and one more a boundary,
        # one tile more than the most the ragged split can cost
        experts, rungs = _steady_rows, (steady_buffer(rows, held),)
        _metrics.MOE_ROWS.set(rungs[0] + (held - 1) * GROUPED_TILE_ROWS,
                              kind="multiplied")
    else:
        experts, rungs = _expert_rows, buffer_rungs(rows, held, E)
        # a product that skips the tiles its group sizes leave empty: the
        # rows it visits in expectation under even routing, every held
        # expert's split rounded out to whole tiles, never more than the
        # buffer
        _metrics.MOE_ROWS.set(
            min(rows, required + held * GROUPED_TILE_ROWS), kind="multiplied")
    experts = functools.partial(experts, act=ACTIVATIONS[p["activation"]])
    kept = rungs[-1] * (gate_w.shape[2] + up_w.shape[2] + down_w.shape[2]) \
        * x.dtype.itemsize
    if kept > KEEP_BYTES_MAX:
        y, sizes = _run_again_in_backward(experts, rungs)(
            h, key, w, gate_w, up_w, down_w)
    else:
        y, sizes = jax.checkpoint(functools.partial(
            experts, buffer=rungs[-1]))(h, key, w, gate_w, up_w, down_w)
    if skip:  # of the rows held nowhere, those that chose no expert
        skipped = jnp.sum(idx == E - 1, dtype=sizes.dtype)
        sizes = jnp.concatenate([sizes[:held], sizes[held:held + 1] - skipped,
                                 skipped[None], sizes[held + 1:]])
    new_load = load + sizes.astype(load.dtype)
    return (y.reshape(x.shape), lax.stop_gradient(bias),
            lax.stop_gradient(new_load))


@register("_contrib_moe_ffn",
          input_names=("data", "router_weight", "select_bias",
                       "gate_weight", "up_weight", "down_weight", "load"),
          aliases=("moe_ffn",), aux_inputs=[2, 6], f32_inputs=(2, 6),
          args=_MOE_ARGS)
def _moe_ffn(p, x, router_w, bias, gate_w, up_w, down_w, load):
    """Mixture-of-experts gated feed-forward over the experts held here.

    data (..., D); router_weight (num_experts, D); select_bias
    (num_experts,), auxiliary, added to the scores for the selection only;
    gate_weight, up_weight (held, D, F) and down_weight (held, F, D), the
    stacked matrices of experts `first .. first + held - 1`; load
    (held + 2,), auxiliary float32: the forward pass adds the assignments
    each held expert got, then those that fell on absent experts, and last
    the rows of the buffer its grouped products ran over.

    Routes every token over all `num_experts`, `top_k` a token (`router`:
    'sigmoid' scores normalised over the chosen, or 'softmax_topk', a
    softmax over the chosen logits; ops/decoder.py `route`) and returns
    sum_i w_i E_i(x) over the chosen experts that are held, E_i(x) =
    down_i(act(gate_i x) * up_i x) with `activation` 'silu' or 'relu': a
    partial result where held < num_experts.  Dropless: the buffer has
    room for `T * top_k` rows, so every token sent to one held expert still
    equals the reference.  The feed-forward's intermediates are recomputed
    in the backward pass (`jax.checkpoint`), as jobs that fill the chip do;
    under a caller's own `jax.checkpoint` (a recorded CachedOp call) the
    caller's policy decides instead, and keeps the grouped products, unless
    their outputs pass `KEEP_BYTES_MAX` a layer: then the backward pass runs
    them again from the op's inputs whoever calls, over a buffer only as
    long as the shortest of `buffer_rungs` that holds the rows the held
    experts got.
    """
    return _moe(p, x, x, router_w, bias, gate_w, up_w, down_w, load)


@register("_contrib_moe_ffn_routed_by",
          input_names=("data", "router_data", "router_weight", "select_bias",
                       "gate_weight", "up_weight", "down_weight", "load"),
          aliases=("moe_ffn_routed_by",), aux_inputs=[3, 7],
          f32_inputs=(3, 7), args=_MOE_ARGS)
def _moe_ffn_routed_by(p, x, routed_by, router_w, bias, gate_w, up_w, down_w,
                       load):
    """`moe_ffn` whose router reads a tensor of its own: `router_data`
    (..., D_r), as many tokens as `data`, with router_weight (num_experts,
    D_r).  A layer that routes before its attention hands the attention's
    input here and the feed-forward's to `data`.  Everything else as
    `moe_ffn`."""
    return _moe(p, x, routed_by, router_w, bias, gate_w, up_w, down_w, load)


@register("_contrib_moe_ffn_scored",
          input_names=("data", "scores", "select_bias", "gate_weight",
                       "up_weight", "down_weight", "load"),
          aliases=("moe_ffn_scored",), aux_inputs=[2, 6], f32_inputs=(2, 6),
          args=_MOE_ARGS)
def _moe_ffn_scored(p, x, scores, bias, gate_w, up_w, down_w, load):
    """`moe_ffn` for a layer whose router is not one matrix: `scores`
    (..., num_experts), as many tokens as `data`, are the router's logits
    as the block computed them (an MLP router), and the op routes by them
    as `moe_ffn` routes by its own product ('softmax_topk' without
    `norm_topk`: the chosen experts' softmax probability over all choices,
    not renormalised).  The last of the `num_experts` choices is a skip,
    held on no chip, which adds nothing; `load` is (held + 3,): each held
    expert's rows, absent experts', skipped tokens', the buffer's.  The
    buffer is `steady_buffer(T * top_k, held)` rows, each held expert's
    rows followed by a spare one (`_steady_rows`), so the grouped products
    cost the same whatever the routing.  Everything else as `moe_ffn`."""
    return _moe(p, x, scores, None, bias, gate_w, up_w, down_w, load)


def _mlp_router(p, x, state, down_w, down_b, scale, gamma, w1, w2, w3):
    """(logits (..., E), the layer's router state (..., R)), float32."""
    f32 = jnp.float32

    def mm(a, w):
        return jnp.einsum("...i,oi->...o", a, w.astype(f32),
                          precision=lax.Precision.HIGHEST)

    rho = mm(x.astype(f32), down_w) + down_b.astype(f32)
    if state is not None:
        rho = rho + scale.astype(f32) * state.astype(f32)
    hid = rho * lax.rsqrt(jnp.mean(jnp.square(rho), axis=-1, keepdims=True)
                          + p["eps"]) * gamma.astype(f32)
    hid = jax.nn.gelu(mm(hid, w1), approximate=False)
    hid = jax.nn.gelu(mm(hid, w2), approximate=False)
    return mm(hid, w3), rho


_ROUTER_ARGS = [Arg("eps", float, 1e-5)]


@register("_contrib_mlp_router",
          input_names=("data", "down_weight", "down_bias", "norm_gamma",
                       "w1", "w2", "w3"),
          aliases=("mlp_router",), num_outputs=2, args=_ROUTER_ARGS)
def _mlp_router_first(p, x, down_w, down_b, gamma, w1, w2, w3):
    """An MLP router, everything in float32 at "highest" precision:

        rho = x W_down + b_down            (the layer's router state, R wide)
        logits = W3 gelu(W2 gelu(W1 rmsnorm(rho)))      (gelu: the erf form)

    data (..., D); down_weight (R, D); down_bias, norm_gamma (R,); w1, w2
    (R, R); w3 (E, R).  Returns (logits (..., E), rho (..., R)), the state
    the next layer's router (`mlp_router_carried`) adds to its own."""
    return _mlp_router(p, x, None, down_w, down_b, None, gamma, w1, w2, w3)


@register("_contrib_mlp_router_carried",
          input_names=("data", "state", "down_weight", "down_bias",
                       "state_scale", "norm_gamma", "w1", "w2", "w3"),
          aliases=("mlp_router_carried",), num_outputs=2, args=_ROUTER_ARGS)
def _mlp_router_carried(p, x, state, down_w, down_b, scale, gamma, w1, w2,
                        w3):
    """`mlp_router` of a layer after the first: the state it hands on is
    its own plus `state_scale` (R,) times the previous layer's (`state`,
    (..., R), before that layer's norm):

        rho = x W_down + b_down + state_scale * state"""
    return _mlp_router(p, x, state, down_w, down_b, scale, gamma, w1, w2, w3)


# rows of the output matrix one block of the blocked cross-entropy
# multiplies: a block's float32 logits at 4,096 tokens are 134 MB
CE_BLOCK = 8192


def _ce_blocks(vocab, block):
    """(blocks, start of block i): the last block is moved back to end at
    `vocab`, so every block is `block` rows; its columns an earlier block
    took are masked."""
    n = -(-vocab // block)
    return n, lambda i: jnp.minimum(i * block, vocab - block)


def _ce_logits(h, w, start, block):
    """(N, block) float32 logits of rows start.. of `w`, the product in the
    operands' dtype with float32 sums."""
    wb = lax.dynamic_slice_in_dim(w, start, block, axis=0)
    return wb, lax.dot_general(h, wb, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _ce_forward(h, w, label, block):
    """(loss (N,), log-sum-exp (N,)), float32, one block of logits alive."""
    n, start_of = _ce_blocks(w.shape[0], block)

    def body(i, carry):
        top, total, picked = carry
        start = start_of(i)
        _wb, z = _ce_logits(h, w, start, block)
        col = start + jnp.arange(block, dtype=jnp.int32)
        fresh = (col >= i * block)[None, :]
        z = jnp.where(fresh, z, -jnp.inf)
        new_top = jnp.maximum(top, jnp.max(z, axis=1))
        total = total * jnp.exp(top - new_top) + jnp.sum(
            jnp.exp(z - new_top[:, None]), axis=1)
        picked = picked + jnp.sum(
            jnp.where(fresh & (col[None, :] == label[:, None]), z, 0.0),
            axis=1)
        return new_top, total, picked

    rows = h.shape[0]
    top, total, picked = lax.fori_loop(
        0, n, body, (jnp.full((rows,), -jnp.inf, jnp.float32),
                     jnp.zeros((rows,), jnp.float32),
                     jnp.zeros((rows,), jnp.float32)))
    lse = top + jnp.log(total)
    return lse - picked, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _blocked_ce(h, w, label, block):
    return _ce_forward(h, w, label, block)[0]


def _blocked_ce_fwd(h, w, label, block):
    loss, lse = _ce_forward(h, w, label, block)
    # kept for the backward program (ops/registry.py RESIDUAL_NAME): the
    # log-sum-exp, so that it does not walk the vocabulary twice, and the
    # hidden states it was taken over.  Made again there, h can differ in
    # its last bit (another fusion of the norm before it), and where the
    # logits are large that shifts exp(z - lse) far from a probability
    return loss, (checkpoint_name(h, RESIDUAL_NAME), w, label,
                  checkpoint_name(lse, RESIDUAL_NAME))


def _blocked_ce_bwd(block, res, g):
    """Each block's logits made again, then dh and that block's rows of dW;
    one block of logits alive at a time.  dW is written in W's dtype: each
    row is written once (the rows of the last block that an earlier one
    took get zeros added)."""
    h, w, label, lse = res
    n, start_of = _ce_blocks(w.shape[0], block)
    g = g.astype(jnp.float32)

    def body(i, carry):
        dh, dw = carry
        start = start_of(i)
        wb, z = _ce_logits(h, w, start, block)
        col = start + jnp.arange(block, dtype=jnp.int32)
        dz = jnp.exp(z - lse[:, None]) - (col[None, :] == label[:, None])
        dz = jnp.where((col >= i * block)[None, :], dz * g[:, None], 0.0)
        dz = dz.astype(h.dtype)
        dh = dh + jnp.dot(dz, wb, preferred_element_type=jnp.float32)
        dwb = lax.dot_general(dz, h, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        rows = lax.dynamic_slice_in_dim(dw, start, block, axis=0)
        dw = lax.dynamic_update_slice_in_dim(
            dw, rows + dwb.astype(dw.dtype), start, axis=0)
        return dh, dw

    dh, dw = lax.fori_loop(0, n, body, (
        jnp.zeros(h.shape, jnp.float32), jnp.zeros_like(w)))
    return dh.astype(h.dtype), dw, None


_blocked_ce.defvjp(_blocked_ce_fwd, _blocked_ce_bwd)


@register("_contrib_blocked_cross_entropy",
          input_names=("data", "weight", "label"),
          aliases=("blocked_cross_entropy",), f32_inputs=(2,))
def _blocked_cross_entropy(p, h, w, label):
    """Cross-entropy of the logits h W^T against `label`, never holding
    them: data (..., D), weight (V, D) (an output head, or a tied
    embedding), label (...) ids in [0, V) -> (...) float32, each token's
    log-sum-exp over V less its label's logit.

    Walks V in blocks of `CE_BLOCK` rows with a running max and sum in
    float32; each block's product runs in the operands' dtype with float32
    sums.  The backward pass makes each block's logits again for dh and
    that block's rows of dW, so no more than one block of logits (N x
    CE_BLOCK float32) is alive in either pass, against the N x V of a plain
    log-softmax.  The log-sum-exp (N,) is kept between the two.  The
    label's gradient is zero."""
    lead = h.shape[:-1]
    with jax.named_scope("blocked_cross_entropy"):
        out = _blocked_ce(h.reshape(-1, h.shape[-1]), w,
                          label.reshape(-1).astype(jnp.int32),
                          min(CE_BLOCK, w.shape[0]))
    return out.reshape(lead)


@register("_contrib_exit_distribution", input_names=("gate", "mass"),
          aliases=("exit_distribution",), num_outputs=2, aux_inputs=[1],
          f32_inputs=(1,))
def _exit_distribution(p, gate, mass):
    """Where a looped model's exit gates let a token leave.

    gate (R, ...): the gate's logit after each of the R loop steps; mass
    (R + 1,), auxiliary float32.  With lam_t = sigmoid(gate_t), a token
    leaves at step t with probability p_1 = lam_1, p_t = lam_t *
    prod_{j<t} (1 - lam_j) for 1 < t < R, and p_R = prod_{j<R} (1 -
    lam_j): the last step takes what is left and reads no gate of its own.
    Returns (p, log p), both (R, ...) float32, computed from log-sigmoids so
    that a saturated gate gives no NaN; p sums to 1 over axis 0.  The
    forward pass adds the sum of p over the tokens to mass[:R] and the
    number of tokens to mass[R] (read by
    `observability.metrics.refresh_loop`)."""
    g = gate.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)       # log prod (1 - lam)
    before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]], axis=0)
    leave = jnp.concatenate([jax.nn.log_sigmoid(g[:-1]),
                             jnp.zeros_like(g[:1])], axis=0)
    logp = before + leave
    prob = jnp.exp(logp)
    seen = jnp.concatenate([
        jnp.sum(prob.reshape(prob.shape[0], -1), axis=1),
        jnp.full((1,), prob[0].size, jnp.float32)])
    return prob, logp, lax.stop_gradient(mass + seen.astype(mass.dtype))
