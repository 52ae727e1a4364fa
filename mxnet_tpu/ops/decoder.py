"""Operators of a current decoder block: RMS norm, rotary positions, two
attention assemblies (latent keys and values at one head count;
grouped-query heads with rotary positions or none and a sliding window or
none), and a mixture-of-experts feed-forward that is told which experts it
holds and how its model routes (sigmoid scores with a selection bias, or a
softmax over the chosen logits; a SiLU or a ReLU gate; the router reading
the experts' input or a tensor of its own).

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
`T * top_k` rows of width D unless the buffer is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import Arg
from ..observability import metrics as _metrics
from .flash_attention import _dense_reference, _flash_attention
from .registry import register

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


ROUTERS = ("sigmoid", "softmax_topk")
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route(h, router_w, bias, top_k, scale, norm_topk, router="sigmoid"):
    """(chosen experts (T, k) int32, their weights (T, k) float32), the
    router's product and everything after it in float32.

    'sigmoid': sigmoid scores; the `top_k` experts of largest score +
    bias; weights from the scores WITHOUT the bias, normalised over the
    chosen, times `scale`.  'softmax_topk': the `top_k` experts of largest
    logit + bias; weights a softmax over the chosen logits alone
    (`norm_topk`; the same numbers as a softmax over all experts
    renormalised over the chosen) or, without `norm_topk`, the chosen
    experts' part of a softmax over all; times `scale`."""
    s = jnp.einsum(
        "td,ed->te", h.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST)
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


def _grouped_ffn(xs, groups, gate_w, up_w, down_w, act):
    """The three grouped products (`act` on the gate's) over the ragged
    split of the buffer `xs` whose first sum(groups) rows are live."""
    # The tail of the buffer belongs to no held expert.  The TPU's grouped
    # product leaves rows it does not visit as they were in memory (read on
    # the chip: values up to 5 in the tail), on the way back too, so the
    # tail is cut off on both sides of the products: what comes out of them
    # there is not a result, and what flows back into them is not a gradient.
    live = (jnp.arange(xs.shape[0], dtype=jnp.int32)
            < jnp.sum(groups))[:, None]
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
    """`moe_ffn`'s body: the experts read `x`, the router `routed_by`."""
    E, k, first, held = (p["num_experts"], p["top_k"], p["first"],
                         p["held"])
    if gate_w.shape[0] != held or not 0 <= first <= E - held:
        raise ValueError(f"moe_ffn: held={held} first={first} of {E} "
                         f"experts against {gate_w.shape[0]} stacked")
    if p["activation"] not in ACTIVATIONS:
        raise ValueError(f"moe_ffn activation={p['activation']!r}: choose "
                         f"one of {sorted(ACTIVATIONS)}")
    if load.shape != (held + 2,):
        raise ValueError(f"moe_ffn: load must be ({held + 2},): each held "
                         f"expert's rows, absent ones', the buffer's; got "
                         f"{load.shape}")
    if routed_by.shape[:-1] != x.shape[:-1]:
        raise ValueError(f"moe_ffn: the router's input {routed_by.shape} "
                         f"and the experts' {x.shape} differ in tokens")
    h = x.reshape(-1, x.shape[-1])
    hr = h if routed_by is x else \
        routed_by.reshape(-1, routed_by.shape[-1])
    idx, w = route(hr, router_w, bias, k, p["scale"], p["norm_topk"],
                   p["router"])
    local = idx - first
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    rows = key.shape[0]
    required = rows * held / E
    _metrics.MOE_ROWS.set(required, kind="required")
    # a product that skips the tiles its group sizes leave empty: the rows
    # it visits in expectation under even routing, every held expert's
    # split rounded out to whole tiles, never more than the buffer
    _metrics.MOE_ROWS.set(
        min(rows, required + held * GROUPED_TILE_ROWS), kind="multiplied")
    kept = rows * (gate_w.shape[2] + up_w.shape[2] + down_w.shape[2]) \
        * x.dtype.itemsize
    if kept > KEEP_BYTES_MAX:
        y, sizes = _run_again_in_backward(
            functools.partial(_expert_rows,
                              act=ACTIVATIONS[p["activation"]]),
            buffer_rungs(rows, held, E))(h, key, w, gate_w, up_w, down_w)
    else:
        y, sizes = jax.checkpoint(functools.partial(
            _expert_rows, act=ACTIVATIONS[p["activation"]], buffer=rows))(
                h, key, w, gate_w, up_w, down_w)
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
