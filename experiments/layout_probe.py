"""Probe: ResNet-50 train-step throughput under layout/precision variants.

Finds the achievable ceiling on this chip so the framework ops can be
designed to hit it.  Variants:
  - layout: NCHW vs NHWC dimension numbers for all convs/BN
  - bn_dtype: compute BN stats in f32 vs bf16
  - resident: params resident bf16 (fp32 master outside step) vs fp32 cast-in
"""
import argparse
import functools
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

L = [3, 4, 6, 3]
WIDTHS = [64, 128, 256, 512]


def conv(x, w, stride, layout, pad="SAME"):
    if layout == "NCHW":
        dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    else:
        dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(x, w, (stride, stride), pad,
                                    dimension_numbers=dn)


def bn(x, p, name, layout, bn_dtype, train=True):
    ax = 1 if layout == "NCHW" else 3
    red = tuple(i for i in range(4) if i != ax)
    bshape = tuple(x.shape[ax] if i == ax else 1 for i in range(4))
    xc = x.astype(bn_dtype)
    mean = jnp.mean(xc, axis=red)
    var = jnp.var(xc, axis=red)
    inv = lax.rsqrt(var + 1e-5)
    out = (x - mean.reshape(bshape).astype(x.dtype)) * inv.reshape(bshape).astype(x.dtype)
    return out * p[name + "_g"].reshape(bshape) + p[name + "_b"].reshape(bshape)


def block(x, p, pre, stride, layout, bn_dtype, proj):
    out = conv(x, p[pre + "c1"], 1, layout)
    out = jax.nn.relu(bn(out, p, pre + "bn1", layout, bn_dtype))
    out = conv(out, p[pre + "c2"], stride, layout)
    out = jax.nn.relu(bn(out, p, pre + "bn2", layout, bn_dtype))
    out = conv(out, p[pre + "c3"], 1, layout)
    out = bn(out, p, pre + "bn3", layout, bn_dtype)
    if proj:
        sc = conv(x, p[pre + "sc"], stride, layout)
        sc = bn(sc, p, pre + "scbn", layout, bn_dtype)
    else:
        sc = x
    return jax.nn.relu(out + sc)


def maxpool3x3s2(x, layout):
    """Patch-stack max (9 static strided slices + reduce_max): the
    reduce_window(max) gradient lowers to select_and_gather_add, which
    this backend cannot linearize — same trick as ops/nn.py:_pool_impl."""
    sp = 2 if layout == "NCHW" else 1
    pad = [(0, 0)] * 4
    pad[sp] = pad[sp + 1] = (1, 1)
    init = jnp.asarray(-jnp.inf, x.dtype)
    xp = jnp.pad(x, pad, constant_values=init)
    out_h = (xp.shape[sp] - 3) // 2 + 1
    out_w = (xp.shape[sp + 1] - 3) // 2 + 1
    parts = []
    for oh in range(3):
        for ow in range(3):
            idx = [slice(None)] * 4
            idx[sp] = slice(oh, oh + (out_h - 1) * 2 + 1, 2)
            idx[sp + 1] = slice(ow, ow + (out_w - 1) * 2 + 1, 2)
            parts.append(xp[tuple(idx)])
    return jnp.max(jnp.stack(parts), axis=0)


def forward(p, x, layout, bn_dtype):
    out = conv(x, p["stem"], 2, layout)
    out = jax.nn.relu(bn(out, p, "stembn", layout, bn_dtype))
    out = maxpool3x3s2(out, layout)
    for si, (n, w) in enumerate(zip(L, WIDTHS)):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            proj = bi == 0
            out = block(out, p, f"s{si}b{bi}", stride, layout, bn_dtype, proj)
    ax = (2, 3) if layout == "NCHW" else (1, 2)
    out = jnp.mean(out, axis=ax)
    return jnp.dot(out.astype(jnp.bfloat16), p["fc"]) + p["fcb"]


def make_params(layout, dtype):
    rs = np.random.RandomState(0)
    p = {}

    def cw(o, i, k):
        w = rs.normal(0, 0.05, (o, i, k, k)).astype(np.float32)
        if layout == "NHWC":
            w = w.transpose(2, 3, 1, 0)  # OIHW -> HWIO
        return jnp.asarray(w, dtype)

    p["stem"] = cw(64, 3, 7)
    p["stembn_g"] = jnp.ones(64, dtype)
    p["stembn_b"] = jnp.zeros(64, dtype)
    cin = 64
    for si, (n, w) in enumerate(zip(L, WIDTHS)):
        for bi in range(n):
            pre = f"s{si}b{bi}"
            p[pre + "c1"] = cw(w, cin if bi == 0 else w * 4, 1)
            p[pre + "c2"] = cw(w, w, 3)
            p[pre + "c3"] = cw(w * 4, w, 1)
            for b in ("bn1", "bn2"):
                p[pre + b + "_g"] = jnp.ones(w, dtype)
                p[pre + b + "_b"] = jnp.zeros(w, dtype)
            p[pre + "bn3_g"] = jnp.ones(w * 4, dtype)
            p[pre + "bn3_b"] = jnp.zeros(w * 4, dtype)
            if bi == 0:
                p[pre + "sc"] = cw(w * 4, cin if bi == 0 else w * 4, 1)
                p[pre + "scbn_g"] = jnp.ones(w * 4, dtype)
                p[pre + "scbn_b"] = jnp.zeros(w * 4, dtype)
        cin = w * 4
    p["fc"] = jnp.asarray(rs.normal(0, 0.05, (2048, 1000)), jnp.bfloat16)
    p["fcb"] = jnp.zeros(1000, jnp.bfloat16)
    return p


def run(layout, bn_dtype, resident, batch, steps=10, img=224):
    dtype = jnp.bfloat16 if resident == "bf16" else jnp.float32
    p = make_params(layout, dtype)
    rs = np.random.RandomState(1)
    shape = (batch, 3, img, img) if layout == "NCHW" else (batch, img, img, 3)
    x = jnp.asarray(rs.normal(0, 1, shape), jnp.bfloat16)
    y = jnp.asarray(rs.randint(0, 1000, (batch,)), jnp.int32)
    bnd = jnp.float32 if bn_dtype == "f32" else jnp.bfloat16

    def step(p, x, y):
        def loss_fn(p):
            pc = p if resident == "bf16" else \
                {k: v.astype(jnp.bfloat16) for k, v in p.items()}
            logits = forward(pc, x, layout, bnd).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
        loss, g = jax.value_and_grad(loss_fn)(p)
        newp = jax.tree_util.tree_map(lambda w, gg: w - 0.05 * gg.astype(w.dtype), p, g)
        return loss, newp

    jstep = jax.jit(step, donate_argnums=0)
    loss, p = jstep(p, x, y)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, p = jstep(p, x, y)
    float(loss)
    dt = time.perf_counter() - t0
    return batch * steps / dt


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--layout", default="NHWC")
    ap.add_argument("--bn", default="f32")
    ap.add_argument("--resident", default="bf16")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--img", type=int, default=224)  # CPU smoke: 64
    a = ap.parse_args()
    r = run(a.layout, a.bn, a.resident, a.batch, img=a.img)
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from mxnet_tpu.chip import mfu
    # the FLOPs-per-image constant assumes 224^2, and only a TPU in the
    # peaks table has a peak to divide by
    if a.img != 224 or jax.devices()[0].platform != "tpu":
        tail = "smoke size or CPU; no MFU"
    else:
        m = mfu(r)
        tail = f"{m['mfu']*100:.1f}% MFU on {m['chip']}"
    print(f"layout={a.layout} bn={a.bn} resident={a.resident} batch={a.batch}: "
          f"{r:.1f} img/s  ({tail})")
