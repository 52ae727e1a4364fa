"""Reconciliation probe: re-run the ROUND-1 bench configuration on the
current stack (VERDICT r4 weak #7).

BENCH_r01.json (git show 58f48c3:BENCH_r01.json) recorded 1834.78 img/s; round 4's best product-path
number is 1577.63 (-14%).  The r01 bench (commit f8fc918) measured a
THINNER path than today's product bench:

  r01: hand-jitted train step over GraphPlan.run — plain SGD (lr only,
       no momentum / weight decay / multi-precision master weights),
       no Module.fit loop, no KVStore pushpull, no metric, no iterator;
       10 timed steps after one warmup.
  r04+: Module.fit + KVStore('tpu_sync') + fused mp-SGD(momentum, wd)
        + on-device NLL metric; per-epoch watchdogged timing.

This script reproduces the r01 measurement byte-for-byte in spirit on
whatever the current GraphPlan produces, so a single window can
attribute the delta: (a) if this prints ~1834, the gap is the product
path's cost (momentum+wd state math, pushpull, fit-loop dispatch);
(b) if it prints ~1577, the lowering itself changed since r01 (e.g.
correctness fixes to BN/conv) and the product path is already at the
r01 ceiling.

Prints one JSON line {"metric": "resnet50_r01_config", ...}.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

BATCH = int(os.environ.get("B", 256))
IMG = int(os.environ.get("IMG", 224))
STEPS = 10


def build():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.symbol.graph import GraphPlan

    net = vision.resnet50_v1()
    out = net(mx.sym.Variable("data"))
    plan = GraphPlan(out)

    arg_shapes, _, aux_shapes = out.infer_shape(data=(BATCH, 3, IMG, IMG))
    rs = np.random.RandomState(0)
    params = {}
    for name, shp in zip(out.list_arguments(), arg_shapes):
        if name == "data":
            continue
        params[name] = jnp.asarray(rs.normal(0, 0.05, shp).astype(np.float32))
    aux = {}
    for name, shp in zip(out.list_auxiliary_states(), aux_shapes):
        one = name.endswith("running_var") or name.endswith("gamma")
        aux[name] = (jnp.ones if one else jnp.zeros)(shp, jnp.float32)
    key = jax.random.PRNGKey(0)

    def train_step(ps, auxs, x, y):
        def loss_fn(ps32):
            d = {k: v.astype(jnp.bfloat16) for k, v in ps32.items()}
            d["data"] = x.astype(jnp.bfloat16)
            outs, new_aux = plan.run(d, auxs, key, True)
            logits = outs[0].astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1))
            return nll, new_aux

        (loss, new_aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(ps)
        new_ps = jax.tree_util.tree_map(
            lambda w, g: w - 0.05 * g.astype(jnp.float32), ps, grads)
        return loss, new_ps, new_aux

    x = jnp.asarray(rs.normal(0, 1, (BATCH, 3, IMG, IMG)).astype(np.float32))
    y = jnp.asarray(rs.randint(0, 1000, (BATCH,)).astype(np.int32))
    return jax.jit(train_step, donate_argnums=(0, 1)), params, aux, x, y


def main():
    step, params, aux, x, y = build()
    loss, params, aux = step(params, aux, x, y)  # compile + warmup
    float(loss)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        loss, params, aux = step(params, aux, x, y)
    float(loss)
    dt = time.perf_counter() - t0
    img_s = BATCH * STEPS / dt
    from mxnet_tpu.chip import mfu
    out = {"metric": "resnet50_r01_config", "value": round(img_s, 2),
           "unit": "img/s", "r01_value": 1834.78,
           "vs_r01": round(img_s / 1834.78, 3)}
    if jax.devices()[0].platform == "tpu":  # a CPU run has no MFU
        out.update(mfu(img_s))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
