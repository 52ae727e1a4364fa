"""Neural-network layer operators, lowered to XLA (MXU-targeted).

Reference parity: `src/operator/nn/` (FullyConnected, Convolution,
Deconvolution, Pooling, BatchNorm, softmax, Dropout, Activation — 33 files of
mshadow/cuDNN kernels) plus legacy root ops (LeakyReLU, LRN, InstanceNorm,
L2Normalization, UpSampling, SoftmaxOutput, regression outputs, MakeLoss,
SVMOutput).  Conv/matmul map directly onto the MXU via
`lax.conv_general_dilated`/`jnp.matmul`; the cuDNN algo-autotuning layer
(`src/operator/nn/cudnn/`) has no analog because XLA picks conv algorithms.
"""
from __future__ import annotations

import functools
import itertools as _itertools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import Arg, MXNetError
from .. import layout as _layout
from .registry import register


# ---------------------------------------------------------------------------
# FullyConnected (parity: src/operator/nn/fully_connected-inl.h:69)
# ---------------------------------------------------------------------------
@register("FullyConnected", input_names=("data", "weight", "bias"),
          args=[Arg("num_hidden", int, required=True), Arg("no_bias", bool, False),
                Arg("flatten", bool, True)])
def _fully_connected(p, data, weight, bias=None):
    x = data.reshape(data.shape[0], -1) if p["flatten"] else data
    out = jnp.matmul(x, weight.T)
    if not p["no_bias"]:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------
def _conv_dims(kernel):
    n = len(kernel)
    if n == 1:
        return ("NCH", "OIH", "NCH")
    if n == 2:
        return ("NCHW", "OIHW", "NCHW")
    if n == 3:
        return ("NCDHW", "OIDHW", "NCDHW")
    raise MXNetError(f"unsupported conv kernel rank {n}")


def _conv_dims_cl(kernel):
    """Channels-last dimension numbers (mxnet_tpu.layout NHWC mode): the
    TPU-native form — channel on the minor (lane) axis, no internal
    transposes from XLA's conv emitter."""
    n = len(kernel)
    if n == 1:
        return ("NWC", "WIO", "NWC")
    if n == 2:
        return ("NHWC", "HWIO", "NHWC")
    if n == 3:
        return ("NDHWC", "DHWIO", "NDHWC")
    raise MXNetError(f"unsupported conv kernel rank {n}")


def _w_to_cl(w, n):
    """OI[spatial] kernel → [spatial]IO (constant-folded per step)."""
    return w.transpose(tuple(range(2, n + 2)) + (1, 0))


def _tup(v, n, default=1):
    if not v:
        return (default,) * n
    return v if len(v) == n else tuple(v) * n


@register("Convolution", input_names=("data", "weight", "bias"),
          aliases=("Convolution_v1",),
          args=[Arg("kernel", "shape", required=True), Arg("stride", "shape", ()),
                Arg("dilate", "shape", ()), Arg("pad", "shape", ()),
                Arg("num_filter", int, required=True), Arg("num_group", int, 1),
                Arg("no_bias", bool, False), Arg("layout", str, None),
                Arg("workspace", int, 1024), Arg("cudnn_tune", str, None),
                Arg("cudnn_off", bool, False)])
def _convolution(p, data, weight, bias=None):
    """Parity: src/operator/nn/convolution.cc (NCHW semantics).

    Lowering: one `lax.conv_general_dilated` → XLA conv → MXU.  The
    reference's im2col/cuDNN-autotune machinery is the compiler's job here.
    """
    k = p["kernel"]
    n = len(k)
    # __io_layout__ == "NHWC": GraphPlan's whole-graph layout pass says
    # the data input is ALREADY channels-last and the consumer wants a
    # channels-last output — no boundary transposes here (they exist
    # only at true graph edges).  Without it, the per-op global-flag
    # behavior stands (eager mx.nd.* calls).
    pre_cl = p.get("__io_layout__") == "NHWC"
    cl = pre_cl or (_layout.channels_last() and data.ndim == n + 2)
    if cl:
        # NCHW semantics, channels-last compute: boundary transposes
        # cancel pairwise across conv→BN→relu→conv chains (layout.py)
        if not pre_cl:
            data = _layout.to_cl(data)
        weight = _w_to_cl(weight, n)
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape, _conv_dims_cl(k) if cl else _conv_dims(k))
    pad = _tup(p["pad"], n, 0)
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=_tup(p["stride"], n),
        padding=[(q, q) for q in pad],
        rhs_dilation=_tup(p["dilate"], n),
        dimension_numbers=dn,
        feature_group_count=p["num_group"],
        # no preferred_element_type upcast: the MXU accumulates bf16
        # operands in f32 natively, and requesting f32 output breaks the
        # conv transpose rule (f32 cotangent x bf16 weight).
    )
    if not p["no_bias"]:
        out = out + (bias if cl else bias.reshape((1, -1) + (1,) * n))
    return out if pre_cl else (_layout.from_cl(out) if cl else out)


@register("Deconvolution", input_names=("data", "weight", "bias"),
          args=[Arg("kernel", "shape", required=True), Arg("stride", "shape", ()),
                Arg("dilate", "shape", ()), Arg("pad", "shape", ()),
                Arg("adj", "shape", ()), Arg("target_shape", "shape", ()),
                Arg("num_filter", int, required=True), Arg("num_group", int, 1),
                Arg("no_bias", bool, True), Arg("layout", str, None),
                Arg("workspace", int, 512), Arg("cudnn_tune", str, None),
                Arg("cudnn_off", bool, False)])
def _deconvolution(p, data, weight, bias=None):
    """Parity: src/operator/nn/deconvolution.cc — transposed convolution."""
    k = p["kernel"]
    n = len(k)
    stride = _tup(p["stride"], n)
    pad = _tup(p["pad"], n, 0)
    dilate = _tup(p["dilate"], n)
    adj = _tup(p["adj"], n, 0)
    # gradient-of-conv formulation: lhs_dilation=stride, padding k-1-p
    eff_k = tuple((k[i] - 1) * dilate[i] + 1 for i in range(n))
    # weight layout for Deconvolution is (in_ch, out_ch/group, *k) → flip+swap
    w = jnp.flip(weight, axis=tuple(range(2, 2 + n)))
    if p["num_group"] > 1:
        w = w.reshape((p["num_group"], -1) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((-1,) + w.shape[2:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    pre_cl = p.get("__io_layout__") == "NHWC"
    cl = pre_cl or (_layout.channels_last() and data.ndim == n + 2)
    if cl:
        if not pre_cl:
            data = _layout.to_cl(data)
        w = _w_to_cl(w, n)
    dn = lax.conv_dimension_numbers(
        data.shape, w.shape, _conv_dims_cl(k) if cl else _conv_dims(k))
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * n,
        padding=[(eff_k[i] - 1 - pad[i], eff_k[i] - 1 - pad[i] + adj[i]) for i in range(n)],
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=p["num_group"])
    if not p["no_bias"] and bias is not None:
        out = out + (bias if cl else bias.reshape((1, -1) + (1,) * n))
    return out if pre_cl else (_layout.from_cl(out) if cl else out)


# ---------------------------------------------------------------------------
# Pooling (parity: src/operator/nn/pooling.cc + legacy pooling_v1)
# ---------------------------------------------------------------------------
@register("Pooling", input_names=("data",), aliases=("Pooling_v1",),
          args=[Arg("kernel", "shape", ()), Arg("pool_type", str, "max"),
                Arg("global_pool", bool, False), Arg("stride", "shape", ()),
                Arg("pad", "shape", ()), Arg("pooling_convention", str, "valid"),
                Arg("cudnn_off", bool, False)])
def _pooling(p, x):
    n = x.ndim - 2
    pre_cl = p.get("__io_layout__") == "NHWC"
    if p["global_pool"]:
        axes = (tuple(range(1, x.ndim - 1)) if pre_cl
                else tuple(range(2, x.ndim)))
        red = jnp.max if p["pool_type"] == "max" else jnp.mean
        if p["pool_type"] == "sum":
            red = jnp.sum
        return red(x, axis=axes, keepdims=True)
    cl = pre_cl or (_layout.channels_last() and x.ndim >= 3)
    if cl and not pre_cl:
        x = _layout.to_cl(x)
    sp = 1 if cl else 2  # first spatial axis
    k = _tup(p["kernel"], n)
    stride = _tup(p["stride"], n)
    pad = _tup(p["pad"], n, 0)
    lo_hi = []
    for i in range(n):
        lo, hi = pad[i], pad[i]
        if p["pooling_convention"] == "full":
            # ceil output size: add extra high padding
            size = x.shape[sp + i] + 2 * pad[i] - k[i]
            extra = (-size) % stride[i]
            hi += extra
        lo_hi.append((lo, hi))
    if cl:
        window = (1,) + k + (1,)
        strides = (1,) + stride + (1,)
        padding = ((0, 0),) + tuple(lo_hi) + ((0, 0),)
    else:
        window = (1, 1) + k
        strides = (1, 1) + stride
        padding = ((0, 0), (0, 0)) + tuple(lo_hi)
    out = _pool_impl(p, x, n, sp, k, stride, lo_hi, window, strides,
                     padding, cl)
    return out if pre_cl else (_layout.from_cl(out) if cl else out)


def _pool_impl(p, x, n, sp, k, stride, lo_hi, window, strides, padding, cl):
    if p["pool_type"] == "max":
        # Patch-stack max instead of lax.reduce_window(max): the
        # select_and_gather_add gradient packs values into 64-bit pairs,
        # which the TPU backend rejects under jax_enable_x64; static
        # strided slices + reduce_max differentiate cleanly and XLA
        # fuses them.
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        vol = 1
        for ki in k:
            vol *= ki
        if vol > 64:
            # large kernels (SPP-style): patch-stack would emit vol slices
            # and a vol-times-output buffer; fall back to reduce_window
            # (grad unsupported on TPU+x64, but these never appear in
            # trained backbones)
            return lax.reduce_window(x, jnp.asarray(init, x.dtype), lax.max,
                                     window, strides, padding)
        xp = jnp.pad(x, padding, constant_values=jnp.asarray(init, x.dtype))
        out_sz = [(xp.shape[sp + i] - k[i]) // stride[i] + 1
                  for i in range(n)]
        parts = []
        for offs in _itertools.product(*[range(ki) for ki in k]):
            spatial = tuple(
                slice(offs[i], offs[i] + (out_sz[i] - 1) * stride[i] + 1,
                      stride[i]) for i in range(n))
            idx = (slice(None),) + spatial + (slice(None),) if cl \
                else (slice(None), slice(None)) + spatial
            parts.append(xp[idx])
        return jnp.max(jnp.stack(parts), axis=0)
    denom = 1
    for d in k:
        denom *= d
    if jnp.issubdtype(x.dtype, jnp.floating):
        # sum/avg pooling as a grouped conv with a uniform kernel: lands
        # on the MXU and differentiates cleanly — jax 0.9 cannot
        # linearize reduce_window_sum under jit ('Linearization failed
        # to produce known values'), so the reduce_window form would
        # break any training graph containing windowed avg pooling
        C = x.shape[-1] if cl else x.shape[1]
        w = jnp.ones((k + (1, C)) if cl else ((C, 1) + k), x.dtype)
        if p["pool_type"] != "sum":
            # reference 'valid' convention divides by the full kernel
            # size, padding included
            w = w / denom
        dn = lax.conv_dimension_numbers(
            x.shape, w.shape, _conv_dims_cl(k) if cl else _conv_dims(k))
        return lax.conv_general_dilated(
            x, w, window_strides=stride, padding=lo_hi,
            dimension_numbers=dn, feature_group_count=C)
    summed = lax.reduce_window(x, jnp.asarray(0, x.dtype), lax.add,
                               window, strides, padding)
    if p["pool_type"] == "sum":
        return summed
    # avg: reference divides by full kernel size (padding included)
    return summed / denom


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------
@register("BatchNorm", input_names=("data", "gamma", "beta", "moving_mean", "moving_var"),
          args=[Arg("eps", float, 1e-3), Arg("momentum", float, 0.9),
                Arg("fix_gamma", bool, True), Arg("use_global_stats", bool, False),
                Arg("output_mean_var", bool, False), Arg("axis", int, 1),
                Arg("cudnn_off", bool, False)],
          num_outputs=3, aux_inputs=[3, 4], takes_is_train=True,
          f32_inputs=(1, 2, 3, 4), aliases=("BatchNorm_v1",))
def _batch_norm(p, x, gamma, beta, mov_mean, mov_var):
    """Parity: src/operator/nn/batch_norm.cc.

    Outputs (out, saved_mean, saved_var) + updated aux (moving_mean,
    moving_var) which the runtime writes back into the aux NDArrays.
    """
    ax = p["axis"] % x.ndim
    pre_cl = p.get("__io_layout__") == "NHWC"  # logical axis 1, already CL
    cl = pre_cl or (_layout.channels_last() and ax == 1 and x.ndim >= 3)
    if cl:
        # channels-last compute: the normalize chain stays in the same
        # layout as the surrounding convs (boundary transposes cancel)
        if not pre_cl:
            x = _layout.to_cl(x)
        ax = x.ndim - 1
    red = tuple(i for i in range(x.ndim) if i != ax)
    bshape = tuple(x.shape[ax] if i == ax else 1 for i in range(x.ndim))
    train = bool(p.get("__is_train__")) and not p["use_global_stats"]
    g = jnp.ones_like(gamma) if p["fix_gamma"] else gamma
    if train:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=red)
        var = jnp.var(xf, axis=red)
        m = p["momentum"]
        new_mm = mov_mean * m + mean.astype(mov_mean.dtype) * (1 - m)
        new_mv = mov_var * m + var.astype(mov_var.dtype) * (1 - m)
    else:
        mean, var = mov_mean, mov_var
        new_mm, new_mv = mov_mean, mov_var
    inv_std = lax.rsqrt(var + p["eps"])
    # scale/shift cast to the activation dtype so bf16 stays bf16 end to
    # end (gamma/beta/moving stats themselves are f32, reference fp16 BN)
    out = (x - mean.reshape(bshape).astype(x.dtype)) * (
        inv_std.reshape(bshape).astype(x.dtype)) * \
        g.reshape(bshape).astype(x.dtype) + \
        beta.reshape(bshape).astype(x.dtype)
    if cl and not pre_cl:
        out = _layout.from_cl(out)
    return (out, mean.astype(x.dtype), var.astype(x.dtype),
            lax.stop_gradient(new_mm), lax.stop_gradient(new_mv))


@register("LayerNorm", input_names=("data", "gamma", "beta"),
          args=[Arg("axis", int, -1), Arg("eps", float, 1e-5),
                Arg("output_mean_var", bool, False)],
          num_outputs=3)
def _layer_norm(p, x, gamma, beta):
    ax = p["axis"] % x.ndim
    mean = jnp.mean(x, axis=ax, keepdims=True)
    var = jnp.var(x, axis=ax, keepdims=True)
    inv = lax.rsqrt(var + p["eps"])
    bshape = tuple(x.shape[ax] if i == ax else 1 for i in range(x.ndim))
    out = (x - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)
    return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)


@register("InstanceNorm", input_names=("data", "gamma", "beta"),
          args=[Arg("eps", float, 1e-3)])
def _instance_norm(p, x, gamma, beta):
    """Parity: src/operator/instance_norm.cc — normalize over spatial dims."""
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    return (x - mean) * lax.rsqrt(var + p["eps"]) * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization", input_names=("data",),
          args=[Arg("eps", float, 1e-10), Arg("mode", str, "instance")])
def _l2_normalization(p, x):
    """Parity: src/operator/l2_normalization.cc."""
    if p["mode"] == "instance":
        red = tuple(range(1, x.ndim))
        kd = True
    elif p["mode"] == "channel":
        red = (1,)
        kd = True
    else:  # spatial
        red = tuple(range(2, x.ndim))
        kd = True
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=red, keepdims=kd) + p["eps"])
    return x / norm


@register("LRN", input_names=("data",),
          args=[Arg("alpha", float, 1e-4), Arg("beta", float, 0.75),
                Arg("knorm", float, 2.0), Arg("nsize", int, required=True)])
def _lrn(p, x):
    """Parity: src/operator/lrn.cc — cross-channel local response norm.

    The window sum is nsize shifted channel slices added together (not
    lax.reduce_window: its sum flavor fails to LINEARIZE inside jit on
    this jax — 'Linearization failed to produce known values' — found
    by the finite-difference tier; slices also fuse better on TPU for
    the tiny windows LRN uses)."""
    if p["nsize"] % 2 == 0:
        raise MXNetError(
            f"LRN nsize must be odd (got {p['nsize']}): the window is "
            "centered on each channel")
    half = p["nsize"] // 2
    sq = jnp.square(x)
    padded = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (x.ndim - 2))
    C = x.shape[1]
    ssum = padded[:, 0:C]
    for i in range(1, p["nsize"]):
        ssum = ssum + padded[:, i:i + C]
    return x / jnp.power(p["knorm"] + p["alpha"] / p["nsize"] * ssum, p["beta"])


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------
@register("Activation", input_names=("data",),
          args=[Arg("act_type", str, required=True)])
def _activation(p, x):
    t = p["act_type"]
    if t == "relu":
        return jnp.maximum(x, 0)
    if t == "sigmoid":
        return jax.nn.sigmoid(x)
    if t == "tanh":
        return jnp.tanh(x)
    if t == "softrelu":
        return jnp.logaddexp(x, 0.0)
    if t == "softsign":
        return x / (1 + jnp.abs(x))
    if t == "silu":
        return jax.nn.silu(x)
    raise MXNetError(f"unknown act_type {t}")


@register("LeakyReLU", input_names=("args",), variadic=True,
          args=[Arg("act_type", str, "leaky"), Arg("slope", float, 0.25),
                Arg("lower_bound", float, 0.125), Arg("upper_bound", float, 0.334)])
def _leaky_relu(p, x, gamma=None):
    """Parity: src/operator/leaky_relu.cc (leaky/elu/prelu/selu; rrelu uses
    the midpoint slope deterministically, matching reference test mode)."""
    t = p["act_type"]
    if t == "leaky":
        return jnp.where(x > 0, x, p["slope"] * x)
    if t == "elu":
        return jnp.where(x > 0, x, p["slope"] * jnp.expm1(x))
    if t == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if gamma.ndim == 1 and x.ndim > 1 else gamma
        return jnp.where(x > 0, x, g * x)
    if t == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))
    if t == "rrelu":
        slope = (p["lower_bound"] + p["upper_bound"]) / 2.0
        return jnp.where(x > 0, x, slope * x)
    raise MXNetError(f"unknown act_type {t}")


# ---------------------------------------------------------------------------
# Softmax family
# ---------------------------------------------------------------------------
@register("softmax", input_names=("data",),
          args=[Arg("axis", int, -1), Arg("temperature", float, None)])
def _softmax(p, x):
    t = p.get("temperature") or 1.0
    return jax.nn.softmax(x / t, axis=p["axis"])


@register("log_softmax", input_names=("data",),
          args=[Arg("axis", int, -1), Arg("temperature", float, None)])
def _log_softmax(p, x):
    t = p.get("temperature") or 1.0
    return jax.nn.log_softmax(x / t, axis=p["axis"])


@register("SoftmaxActivation", input_names=("data",),
          args=[Arg("mode", str, "instance")])
def _softmax_activation(p, x):
    if p["mode"] == "channel":
        return jax.nn.softmax(x, axis=1)
    return jax.nn.softmax(x.reshape(x.shape[0], -1), axis=-1).reshape(x.shape)


@register("softmax_cross_entropy", input_names=("data", "label"))
def _softmax_cross_entropy(p, data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked)


# --- loss-output ops with MXNet's folded-gradient semantics ----------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _softmax_output_core(pt, data, label):
    p = dict(pt)
    ax = 1 if p["multi_output"] else -1
    if p["preserve_shape"] or p["multi_output"]:
        return jax.nn.softmax(data, axis=ax)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_fwd(pt, data, label):
    out = _softmax_output_core(pt, data, label)
    return out, (out, label)


def _softmax_output_bwd(pt, res, g):
    p = dict(pt)
    out, label = res
    ax = 1 if p["multi_output"] else out.ndim - 1
    nclass = out.shape[ax]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, nclass, axis=ax, dtype=out.dtype)
    grad = out - onehot
    valid = jnp.ones_like(lab, dtype=out.dtype)
    if p["use_ignore"]:
        keep = (lab != int(p["ignore_label"])).astype(out.dtype)
        grad = grad * jnp.expand_dims(keep, ax)
        valid = keep
    scale = p["grad_scale"]
    if p["normalization"] == "batch":
        scale = scale / out.shape[0]
    elif p["normalization"] == "valid":
        scale = scale / jnp.maximum(jnp.sum(valid), 1.0)
    grad = grad * scale
    if p["out_grad"]:
        grad = grad * g
    return grad.astype(out.dtype), jnp.zeros_like(label)


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", input_names=("data", "label"), aliases=("Softmax",),
          f32_inputs=(1,),
          args=[Arg("grad_scale", float, 1.0), Arg("ignore_label", float, -1.0),
                Arg("multi_output", bool, False), Arg("use_ignore", bool, False),
                Arg("preserve_shape", bool, False), Arg("normalization", str, "null"),
                Arg("out_grad", bool, False), Arg("smooth_alpha", float, 0.0)])
def _softmax_output(p, data, label):
    """Parity: src/operator/softmax_output-inl.h — forward softmax, backward
    (p − onehot(label))·grad_scale with ignore/normalization handling."""
    return _softmax_output_core(tuple(sorted(p.items())), data, label)


def _make_regression(name, fwd, bwd):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def core(scale, data, label):
        return fwd(data)

    def f(scale, data, label):
        out = fwd(data)
        return out, (out, label)

    def b(scale, res, g):
        out, label = res
        num_output = 1
        for d in label.shape[1:]:
            num_output *= d
        grad = bwd(out, label.reshape(out.shape)) * (scale / num_output)
        return grad.astype(out.dtype), jnp.zeros_like(label)

    core.defvjp(f, b)

    @register(name, input_names=("data", "label"),
              args=[Arg("grad_scale", float, 1.0)])
    def op(p, data, label):
        """Parity: src/operator/regression_output-inl.h:75-97 — gradient is
        grad_scale/num_output · BackwardOp(out, label)."""
        return core(p["grad_scale"], data, label)
    return op


_make_regression("LinearRegressionOutput", lambda x: x, lambda o, l: o - l)
_make_regression("LogisticRegressionOutput", jax.nn.sigmoid, lambda o, l: o - l)
_make_regression("MAERegressionOutput", lambda x: x, lambda o, l: jnp.sign(o - l))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _make_loss_core(pt, data):
    return data


def _make_loss_fwd(pt, data):
    # 0-size carrier keeps shape AND dtype in the residual (a bare
    # np.dtype is not a jax type)
    return data, (data.shape, jnp.zeros((0,), data.dtype))


def _make_loss_bwd(pt, res, g):
    shape, carrier = res
    p = dict(pt)
    scale = p["grad_scale"]
    if p["normalization"] == "batch":
        scale = scale / shape[0]
    # explicit dtype: a bare python float would make jnp.full emit f64
    # under jax_enable_x64, poisoning every upstream vjp with dtype
    # mismatches (lax.div f64 vs f32)
    return (jnp.full(shape, scale, carrier.dtype),)


_make_loss_core.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("MakeLoss", input_names=("data",),
          args=[Arg("grad_scale", float, 1.0), Arg("valid_thresh", float, 0.0),
                Arg("normalization", str, "null")])
def _make_loss_legacy(p, data):
    """Parity: src/operator/make_loss.cc — identity fwd, constant grad."""
    return _make_loss_core(tuple(sorted(p.items())), data)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _svm_output_op(pt, data, label):
    return data


def _svm_output_op_fwd(pt, data, label):
    return data, (data, label)


def _svm_output_op_bwd(pt, res, g):
    """Parity: src/operator/svm_output.cc L1_SVM/L2_SVM kernels —
    one-vs-all hinge gradient, incoming head gradient folded away
    (loss-output semantics like SoftmaxOutput)."""
    p = dict(pt)
    out, label = res
    flat = out.reshape(out.shape[0], -1)
    m = p["margin"]
    reg = p["regularization_coefficient"]
    onehot = jax.nn.one_hot(label.astype(jnp.int32).reshape(-1),
                            flat.shape[1], dtype=flat.dtype)
    if p["use_linear"]:  # L1-SVM
        g_true = -(m > flat).astype(flat.dtype) * reg
        g_other = (m > -flat).astype(flat.dtype) * reg
    else:  # L2-SVM (default)
        g_true = jnp.where(m > flat, -2.0 * reg * (m - flat),
                           jnp.zeros((), flat.dtype))
        g_other = jnp.where(m > -flat, 2.0 * reg * (m + flat),
                            jnp.zeros((), flat.dtype))
    grad = onehot * g_true + (1 - onehot) * g_other
    return grad.reshape(out.shape).astype(out.dtype), jnp.zeros_like(label)


_svm_output_op.defvjp(_svm_output_op_fwd, _svm_output_op_bwd)


@register("SVMOutput", input_names=("data", "label"),
          args=[Arg("margin", float, 1.0), Arg("regularization_coefficient", float, 1.0),
                Arg("use_linear", bool, False)])
def _svm_output(p, data, label):
    """Parity: src/operator/svm_output.cc — identity forward, one-vs-all
    hinge backward (L2-SVM default, L1 via use_linear)."""
    return _svm_output_op(tuple(sorted(p.items())), data, label)


# ---------------------------------------------------------------------------
# Dropout (needs RNG + is_train)
# ---------------------------------------------------------------------------
@register("Dropout", input_names=("data",),
          args=[Arg("p", float, 0.5), Arg("mode", str, "training"),
                Arg("axes", "shape", ())],
          needs_rng=True, takes_is_train=True)
def _dropout(p, x, key):
    """Parity: src/operator/nn/dropout.cc — inverted dropout."""
    rate = p["p"]
    train = bool(p.get("__is_train__")) or p["mode"] == "always"
    if not train or rate <= 0.0:
        return x
    shape = x.shape
    if p["axes"]:
        shape = tuple(1 if i in p["axes"] else s for i, s in enumerate(x.shape))
    keep = jax.random.bernoulli(key, 1.0 - rate, shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros((), x.dtype)).astype(x.dtype)


# ---------------------------------------------------------------------------
# UpSampling / misc vision
# ---------------------------------------------------------------------------
@register("UpSampling", input_names=("args",), variadic=True,
          args=[Arg("scale", int, required=True), Arg("sample_type", str, "nearest"),
                Arg("num_args", int, 1), Arg("workspace", int, 512),
                Arg("multi_input_mode", str, "concat"), Arg("num_filter", int, 0)])
def _upsampling(p, *xs):
    """Parity: src/operator/upsampling.cc (nearest; bilinear via resize)."""
    s = p["scale"]
    outs = []
    for x in xs:
        if p["sample_type"] == "nearest":
            out = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3)
        else:
            out = jax.image.resize(x, x.shape[:2] + (x.shape[2] * s, x.shape[3] * s),
                                   method="bilinear")
        outs.append(out)
    if len(outs) == 1:
        return outs[0]
    return jnp.concatenate(outs, axis=1)
