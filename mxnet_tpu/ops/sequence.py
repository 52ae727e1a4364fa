"""Sequence operators + fused RNN as lax.scan.

Reference parity: `src/operator/sequence_{last,mask,reverse}.cc` and the
fused `RNN` op (`src/operator/rnn.cc` / `cudnn_rnn-inl.h`).  The reference's
RNN is GPU-only (`src/operator/rnn.cc:32-33` fatals on CPU); here it is a
`lax.scan` over time — XLA compiles the whole unrolled recurrence, runs on
TPU/CPU alike, and the packed-parameter layout matches cuDNN's so
`mx.rnn`/`gluon.rnn` weight pack/unpack round-trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import Arg, MXNetError
from .registry import register


@register("SequenceLast", input_names=("data", "sequence_length"), variadic=True,
          args=[Arg("use_sequence_length", bool, False), Arg("axis", int, 0)])
def _sequence_last(p, data, seq_len=None):
    ax = p["axis"]
    if not p["use_sequence_length"] or seq_len is None:
        return jnp.take(data, data.shape[ax] - 1, axis=ax)
    idx = jnp.maximum(seq_len.astype(jnp.int32) - 1, 0)  # (batch,)
    moved = jnp.moveaxis(data, ax, 0)  # (seq, batch, ...)
    return jnp.take_along_axis(
        moved, idx.reshape((1, -1) + (1,) * (moved.ndim - 2)), axis=0)[0]


@register("SequenceMask", input_names=("data", "sequence_length"), variadic=True,
          args=[Arg("use_sequence_length", bool, False), Arg("value", float, 0.0),
                Arg("axis", int, 0)])
def _sequence_mask(p, data, seq_len=None):
    if not p["use_sequence_length"] or seq_len is None:
        return data
    ax = p["axis"]
    steps = jnp.arange(data.shape[ax])
    # data layout: (seq, batch, ...) for axis=0 or (batch, seq, ...) for axis=1
    if ax == 0:
        mask = steps[:, None] < seq_len[None, :]
    else:
        mask = steps[None, :] < seq_len[:, None]
    mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(p["value"], data.dtype))


@register("SequenceReverse", input_names=("data", "sequence_length"), variadic=True,
          args=[Arg("use_sequence_length", bool, False), Arg("axis", int, 0)])
def _sequence_reverse(p, data, seq_len=None):
    if not p["use_sequence_length"] or seq_len is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T)[:, None]
    L = seq_len.astype(jnp.int32)[None, :]
    idx = jnp.where(steps < L, L - 1 - steps, steps)  # (seq, batch)
    return jnp.take_along_axis(
        data, idx.reshape(idx.shape + (1,) * (data.ndim - 2)), axis=0)


# ---------------------------------------------------------------------------
# Fused RNN (cuDNN-compatible packed parameters)
# ---------------------------------------------------------------------------
_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(num_layers, input_size, state_size, bidirectional, mode):
    """Total packed parameter count (matches cuDNN layout used by the
    reference's cudnn_rnn-inl.h and python/mxnet/rnn/rnn_cell.py unfuse)."""
    g = _GATES[mode]
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        size += d * g * state_size * (in_sz + state_size)  # W + R
    size += num_layers * d * g * state_size * 2  # biases bW + bR
    return size


def _unpack_rnn_params(params, num_layers, input_size, state_size, bidir, mode):
    g = _GATES[mode]
    d = 2 if bidir else 1
    ws, rs, bws, brs = [], [], [], []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        lw, lr = [], []
        for _ in range(d):
            n = g * state_size * in_sz
            lw.append(params[off:off + n].reshape(g * state_size, in_sz))
            off += n
            n = g * state_size * state_size
            lr.append(params[off:off + n].reshape(g * state_size, state_size))
            off += n
        ws.append(lw)
        rs.append(lr)
    for layer in range(num_layers):
        lbw, lbr = [], []
        for _ in range(d):
            n = g * state_size
            lbw.append(params[off:off + n])
            off += n
            lbr.append(params[off:off + n])
            off += n
        bws.append(lbw)
        brs.append(lbr)
    return ws, rs, bws, brs


def _cell_step(mode, state_size):
    if mode == "lstm":
        def step(carry, gates):
            h, c = carry
            i, f, gg, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            gg = jnp.tanh(gg)
            c2 = f * c + i * gg
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
        return step
    if mode == "gru":
        def step(carry, pair):
            h = carry[0]
            wx, rh = pair  # (batch, 3H) each: [r, z, n] cuDNN order
            rx, zx, nx = jnp.split(wx, 3, axis=-1)
            rh_, zh_, nh_ = jnp.split(rh, 3, axis=-1)
            r = jax.nn.sigmoid(rx + rh_)
            z = jax.nn.sigmoid(zx + zh_)
            n = jnp.tanh(nx + r * nh_)
            h2 = (1 - z) * n + z * h
            return (h2,), h2
        return step
    act = jnp.tanh if mode == "rnn_tanh" else (lambda x: jnp.maximum(x, 0))

    def step(carry, gates):
        h2 = act(gates)
        return (h2,), h2
    return step


@functools.partial(jax.jit, static_argnames=("mode", "reverse"))
def _run_layer(x, h0, c0, W, R, bW, bR, mode, reverse):
    """One direction of one layer. x: (T, B, in). Returns (T,B,H), hT, cT.

    Jitted as a module-level callable: the scan body below is a fresh
    closure at every call, and a scan bound eagerly (the recorded nd
    path differentiates op.fn with jax.vjp outside any jit) is compiled
    anew each time, forward and transpose -- a non-hybridized
    gluon.rnn layer paid two compiles a step.  Under an outer trace
    this is an inlined call."""
    T, B, _ = x.shape
    H = h0.shape[-1]
    # hoist the input projection out of the scan: one big MXU matmul
    wx = jnp.einsum("tbi,gi->tbg", x, W) + bW + bR
    step = _cell_step(mode, H)

    if mode == "lstm":
        def body(carry, wxt):
            h, c = carry
            gates = wxt + jnp.matmul(h, R.T)
            return step((h, c), gates)
        carry, out = lax.scan(body, (h0, c0), wx, reverse=reverse)
        return out, carry[0], carry[1]
    if mode == "gru":
        def body(carry, wxt):
            (h,) = carry
            rh = jnp.matmul(h, R.T)
            return step((h,), (wxt, rh))
        carry, out = lax.scan(body, (h0,), wx, reverse=reverse)
        return out, carry[0], None

    def body(carry, wxt):
        (h,) = carry
        gates = wxt + jnp.matmul(h, R.T)
        return step((h,), gates)
    carry, out = lax.scan(body, (h0,), wx, reverse=reverse)
    return out, carry[0], None


@register("RNN", input_names=("data", "parameters", "state", "state_cell"),
          variadic=True,
          args=[Arg("state_size", int, required=True), Arg("num_layers", int, required=True),
                Arg("bidirectional", bool, False), Arg("mode", str, required=True),
                Arg("p", float, 0.0), Arg("state_outputs", bool, False),
                Arg("lstm_state_clip_min", float, None),
                Arg("lstm_state_clip_max", float, None),
                Arg("use_default_state", bool, False)],
          num_outputs=3, takes_is_train=True, needs_rng=True)
def _rnn(p, data, parameters, *rest):
    """Fused multi-layer (bi)RNN/LSTM/GRU.

    data: (seq_len, batch, input_size); state: (L*D, batch, H).
    use_default_state=True builds zero initial states inside the op
    (shapes are concrete here), so symbol graphs / hybridized gluon RNN
    layers need no explicit state inputs.
    Outputs (out, state_out, statecell_out) — the executor exposes the first
    1 or 3 depending on state_outputs, mirroring the reference op.
    """
    key = rest[-1]                  # PRNG key (needs_rng appends last)
    rest = rest[:-1]
    state = rest[0] if len(rest) > 0 else None
    state_cell = rest[1] if len(rest) > 1 else None
    mode = p["mode"]
    if mode not in _GATES:
        raise MXNetError(f"unknown RNN mode {mode}")
    L, H = p["num_layers"], p["state_size"]
    bidir = p["bidirectional"]
    d = 2 if bidir else 1
    T, B, I = data.shape
    if state is None:
        # use_default_state marks graphs composed without state inputs;
        # an explicitly provided state always wins
        state = jnp.zeros((L * d, B, H), data.dtype)
    if mode == "lstm" and state_cell is None:
        state_cell = jnp.zeros((L * d, B, H), data.dtype)
    ws, rs, bws, brs = _unpack_rnn_params(parameters, L, I, H, bidir, mode)
    hs = state.reshape(L, d, B, H)
    cs = state_cell.reshape(L, d, B, H) if (mode == "lstm" and state_cell is not None) else None
    x = data
    h_out, c_out = [], []
    for layer in range(L):
        outs = []
        for direction in range(d):
            h0 = hs[layer, direction]
            c0 = cs[layer, direction] if cs is not None else None
            out, hT, cT = _run_layer(
                x, h0, c0, ws[layer][direction], rs[layer][direction],
                bws[layer][direction], brs[layer][direction], mode,
                reverse=(direction == 1))
            outs.append(out)
            h_out.append(hT)
            c_out.append(cT if cT is not None else hT)
        x = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)
        # inter-layer dropout (parity: rnn-inl.h — applied to every
        # layer's output except the last, training mode only)
        if p["p"] > 0 and layer < L - 1 and bool(p.get("__is_train__")):
            key, sub = jax.random.split(key)
            keep = jax.random.bernoulli(sub, 1.0 - p["p"], x.shape)
            x = jnp.where(keep, x / (1.0 - p["p"]),
                          jnp.zeros((), x.dtype)).astype(x.dtype)
    state_out = jnp.stack(h_out).reshape(L * d, B, H)
    cell_out = jnp.stack(c_out).reshape(L * d, B, H)
    if mode == "lstm" and p.get("lstm_state_clip_min") is not None:
        cell_out = jnp.clip(cell_out, p["lstm_state_clip_min"], p["lstm_state_clip_max"])
    return x, state_out, cell_out
