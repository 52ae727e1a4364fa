"""Attribute the framework-vs-raw step-time gap WITHOUT a chip: compare
XLA cost analyses (flops / transcendentals / bytes accessed) of

  fw   — the framework executor's fused fwd+bwd program on the zoo
         resnet50_v1 graph (the exact program bench.py times), plus the
         FusedUpdater's multi-tensor sgd program
  raw  — experiments/layout_probe.py's hand-rolled train step (the
         measured on-chip ceiling), same layout/precision config

Round-4 on-chip data (git show 58f48c3:BENCH_WINDOW_r04.json vs
58f48c3:LAYOUT_r04.json):
fw 1577 img/s vs raw-NCHW 1860 — a ~25 ms/step gap at BS=256, of which
the dispatch probe attributed only ~4-5 ms to program-boundary costs.
If fw flops ≈ raw flops the rest is per-op lowering quality; a flops
excess pinpoints structural waste (recompute, f32 upcasts, transposes).

Runs entirely on CPU (lowering only, nothing executed): B=8 keeps
compile < ~2 min.  `python experiments/graph_cost_probe.py`
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # lowering-only probe: never touch the chip

import numpy as np

B = int(os.environ.get("B", 8))
IMG = 224


def fmt(name, ca):
    flops = ca.get("flops", float("nan"))
    trans = ca.get("transcendentals", 0.0)
    byts = ca.get("bytes accessed", float("nan"))
    print(f"{name:22s} gflops={flops/1e9:9.2f} transc(M)={trans/1e6:8.2f} "
          f"GB={byts/1e9:8.2f}", flush=True)
    return flops, byts


def framework_costs():
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import DataDesc

    net = vision.resnet50_v1()
    out = net(mx.sym.Variable("data"))
    out = mx.sym.SoftmaxOutput(out, name="softmax")
    mod = mx.mod.Module(out, context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (B, 3, IMG, IMG),
                                   np.dtype("bfloat16"))],
             label_shapes=[DataDesc("softmax_label", (B,), np.float32)])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    ex = mod._exec
    fb = ex._fwd_bwd  # property: the already-jitted fused program
    arg_vals = {k: v._data for k, v in ex.arg_dict.items()}
    aux_vals = {k: v._data for k, v in ex.aux_dict.items()}
    key = jax.random.PRNGKey(0)
    ograds = [None] * len(ex._plan.out_refs)
    lowered = fb.lower(arg_vals, aux_vals, key, ograds)
    try:
        ca = lowered.cost_analysis()  # pre-compile estimate, much cheaper
    except Exception:
        ca = lowered.compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return fmt("fw fwd+bwd", ca)


def raw_costs():
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__))))
    import layout_probe as lp

    # mirror lp.run('NCHW','f32','bf16') — the measured NCHW ceiling —
    # but lower the fwd+bwd only (no sgd) to match the fw program's scope
    layout = "NCHW"
    p = lp.make_params(layout, jnp.bfloat16)
    x = jnp.zeros((B, 3, IMG, IMG), jnp.bfloat16)
    y = jnp.zeros((B,), jnp.int32)

    def loss_fn(p_, x_, y_):
        logits = lp.forward(p_, x_, layout, jnp.float32).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y_[:, None], -1))

    def step(p_, x_, y_):
        return jax.value_and_grad(loss_fn)(p_, x_, y_)

    lowered = jax.jit(step).lower(p, x, y)
    try:
        ca = lowered.cost_analysis()  # pre-compile estimate, much cheaper
    except Exception:
        ca = lowered.compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return fmt("raw fwd+bwd(grad)", ca)


def main():
    fw_f, fw_b = framework_costs()
    raw_f, raw_b = raw_costs()
    print(f"flops ratio fw/raw = {fw_f / raw_f:.3f}   "
          f"bytes ratio = {fw_b / raw_b:.3f}", flush=True)


if __name__ == "__main__":
    main()
