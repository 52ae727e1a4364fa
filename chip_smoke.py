"""chip_smoke.py — does the system still start on the chip?

Drives the main path once, in ONE process, through the entry points a user
calls (`import mxnet_tpu as mx`), at the full published width of the model
this repo has always led with: gluon model-zoo ResNet-50 v1, 1000 classes,
224x224, batch 256, bf16 data with f32 master weights.  Weights and data are
random, made from a seed.  Phases, in order; each prints one JSON line
(platform, device_kind, device_count, compile seconds, steps or requests
done) and the first failure ends the run with exit code 1:

  device        device 0 must be a TPU whose device_kind is in the peaks
                table (mxnet_tpu/chip.py); says which host runtime loaded
  train_module  resnet50_v1 symbol -> mx.mod.Module(context=mx.tpu()) ->
                fit with kvstore="tpu_sync", sgd momentum, multi_precision
  train_gluon   the same net hybridized under gluon.Trainer(tpu_sync): the
                default fused path, then gluon.WholeStepCompiler with bf16
                autocast (one dispatch per steady step, no silent demotion)
  serve         serving.BucketedPredictor(dev=mx.tpu()) warmed on batch
                buckets 1 and 64, requests through ResilientServer, outputs
                against a CPU forward, zero compiles after warm-up
  kernel        _contrib_flash_attention forward+backward compiled by Mosaic
                (custom call asserted in the lowered text) against the dense
                reference; one TransformerLM(attn_type="flash") train step
                at full width and cut depth
  four_chips    (>= 4 devices) Module over four contexts and the Gluon
                whole-step under make_mesh(batch=4): parameters and batch on
                four devices, bytes in use on each chip, a collective in the
                lowered step

The last line of stdout is {"ok": true, "device": {...}} with the device as
JAX reports it.  Timings printed here are smoke timings (compile included,
few steps, unwarmed): they say the phase ran, they are not benchmark numbers.

`--rehearsal` runs the same phases on the CPU at tiny sizes (debugging the
script itself); every line it prints carries "rehearsal": true and the run
proves nothing about the chip.  `--phases a,b` runs a subset (`device`
always runs).  The compile cache is JAX's own persistent cache at
JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache (mxnet_tpu/base.py).
"""
import argparse
import gc
import json
import os
import sys
import time
import traceback

import jax
import numpy as np

PHASES = ("device", "train_module", "train_gluon", "serve", "kernel",
          "four_chips")

# Full size: the published ResNet-50 v1 configuration the repo leads with.
# The LM is 16 heads x 64 (width 1,024), depth cut to 2; T = 2048 is
# where a dense score matrix starts to hurt.
FULL = dict(
    batch=256, img=224, classes=1000, steps=4, lr=0.05,
    serve_buckets=(1, 64), serve_requests=6,
    attn=dict(B=2, H=16, T=2048, D=64),
    lm=dict(vocab=32768, dim=1024, heads=16, ffn=4096, layers=2, seq=2048,
            batch=4))
REHEARSAL = dict(
    batch=8, img=32, classes=1000, steps=3, lr=0.005,
    serve_buckets=(1, 4), serve_requests=4,
    attn=dict(B=1, H=2, T=256, D=64),
    lm=dict(vocab=256, dim=128, heads=2, ffn=256, layers=1, seq=256,
            batch=2))


class Smoke:
    """Run state: sizes, the devices, and the line printer."""

    def __init__(self, rehearsal):
        self.rehearsal = rehearsal
        self.cfg = REHEARSAL if rehearsal else FULL
        self.mx = None
        self.stamp = {}
        self.cache_dir = None

    # -- devices -------------------------------------------------------------
    def ctx(self, i=0):
        return self.mx.cpu(i) if self.rehearsal else self.mx.tpu(i)

    def emit(self, phase, **fields):
        rec = {"phase": phase, "ok": True, **self.stamp, **fields}
        if self.rehearsal:
            rec["rehearsal"] = True
        print(json.dumps(rec), flush=True)

    def hbm(self):
        """bytes_in_use per device, where the backend reports it."""
        out = []
        for d in jax.devices():
            st = d.memory_stats() or {}
            out.append(int(st.get("bytes_in_use", 0)))
        return out

    def cache_entries(self):
        return len(os.listdir(self.cache_dir)) \
            if os.path.isdir(self.cache_dir) else 0

    def data(self, n):
        """n images and labels from the seed; a class-correlated patch
        makes the loss learnable."""
        c = self.cfg
        rng = np.random.default_rng(0)
        labels = rng.integers(0, c["classes"], n).astype(np.float32)
        x = rng.standard_normal((n, 3, c["img"], c["img"]), dtype=np.float32)
        x[:, 0, :4, :4] += (labels / (c["classes"] / 2) - 1.0)[:, None, None]
        return x, labels


def _split_compile(times):
    """(compile seconds, steady step ms) from per-step wall times.  The
    steady step is the median of the later half; compile is everything
    the run spent above that (the Gluon path compiles on its first TWO
    steps, so the first step alone would undercount)."""
    steady = float(np.median(times[len(times) // 2:]))
    return (round(max(0.0, sum(times) - steady * len(times)), 2),
            round(steady * 1e3, 2))


def _assert_no_f64_programs(introspect, names):
    """jax_enable_x64 is on package-wide (mxnet_tpu/base.py) and the TPU
    refuses some f64 HLO: no captured step program may hold an f64 value."""
    progs = introspect.programs()
    checked = []
    for name in names:
        rec = progs.get(name)
        assert rec is not None and rec.get("hlo"), \
            f"program {name!r} not captured (have {sorted(progs)})"
        assert "f64[" not in rec["hlo"], f"f64 value in program {name!r}"
        checked.append(name)
    return checked


def _assert_on_devices(arrays, devices, what):
    want = set(devices)
    for name, arr in arrays.items():
        assert arr.dtype != np.float64, f"{what} {name} is float64"
        got = set(arr.devices())
        assert got == want, f"{what} {name} on {got}, expected {want}"


# -- phase 1 -----------------------------------------------------------------
def phase_device(s):
    devs = jax.devices()
    d0 = devs[0]
    if not s.rehearsal and d0.platform != "tpu":
        # no result on stdout: nothing ran
        sys.stderr.write(
            f"chip_smoke: device 0 is {d0.platform!r} ({d0.device_kind!r}), "
            f"not a TPU ({len(devs)} device(s) visible); there is no CPU "
            "fallback — `--rehearsal` debugs the script on the CPU\n")
        sys.exit(2)
    import mxnet_tpu as mx
    from mxnet_tpu import _native, chip
    s.mx = mx
    s.stamp = {"platform": d0.platform, "device_kind": d0.device_kind,
               "device_count": len(devs)}
    if not s.rehearsal:
        chip.peaks(d0.device_kind)  # raises for a kind not in the table
    # before the first compile: jax latches "no cache" at that point
    s.cache_dir = mx.base.enable_compile_cache(default_to_checkout=True)
    mx.observability.metrics.enable()
    mx.observability.introspect.configure(hlo=True)
    import jaxlib
    s.emit("device",
           runtime="native" if _native.lib() is not None else "python",
           jax=jax.__version__, jaxlib=jaxlib.__version__,
           numpy=np.__version__, cache_dir=s.cache_dir,
           cache_entries_at_start=s.cache_entries(),
           hbm_bytes_limit=int((d0.memory_stats() or {}).get(
               "bytes_limit", 0)))


# -- phase 2 -----------------------------------------------------------------
def _train_module(s, ctxs):
    """Module.fit over the tpu_sync kvstore and the fused update, as a
    user writes it; returns the record."""
    mx, c = s.mx, s.cfg
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import DataDesc
    B = c["batch"]
    net = vision.resnet50_v1(classes=c["classes"])
    out = mx.sym.SoftmaxOutput(net(mx.sym.Variable("data")), name="softmax")
    # two batches, epochs make up the steps: host RAM stays modest
    nbatch = 2
    epochs = max(2, c["steps"] // nbatch)
    x, y = s.data(B * nbatch)
    data_nd = mx.nd.array(x, ctx=ctxs[0]).astype("bfloat16")
    label_nd = mx.nd.array(y, ctx=ctxs[0])
    it = mx.io.NDArrayIter(data_nd, label_nd, batch_size=B)

    mod = mx.mod.Module(out, context=ctxs if len(ctxs) > 1 else ctxs[0])
    mod.bind(data_shapes=[DataDesc("data", (B, 3, c["img"], c["img"]),
                                   np.dtype("bfloat16"))],
             label_shapes=[DataDesc("softmax_label", (B,), np.float32)])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": c["lr"],
                                         "momentum": 0.9, "wd": 1e-4,
                                         "multi_precision": True})
    metric = mx.metric.CrossEntropy()
    times, losses, t_last = [], [], [time.perf_counter()]

    def batch_end(param):
        # CrossEntropy.update copied the outputs to the host: the step is
        # done when we get here
        losses.append(float(param.eval_metric.get()[1]))
        now = time.perf_counter()
        times.append(now - t_last[0])
        t_last[0] = now

    def epoch_end(epoch, sym_, arg, aux):
        t_last[0] = time.perf_counter()  # the param sync is not a step

    mod.fit(it, num_epoch=epochs, eval_metric=metric,
            batch_end_callback=batch_end, epoch_end_callback=epoch_end)

    assert len(times) == epochs * nbatch, times
    assert np.isfinite(losses).all(), losses
    devs = [cx.jax_device() for cx in ctxs]
    ex = mod._exec
    params = {n: a._data for n, a in ex.arg_dict.items()
              if n not in ("data", "softmax_label")}
    _assert_on_devices(params, devs, "param")
    _assert_on_devices({n: a._data for n, a in ex.aux_dict.items()},
                       devs, "aux state")
    probs = mod.get_outputs()[0]
    assert probs.shape == (B, c["classes"]), probs.shape
    assert np.isfinite(probs.asnumpy().astype(np.float32)).all()
    programs = _assert_no_f64_programs(
        mx.observability.introspect, ["executor:fwd_bwd", "fused_update"])
    compile_s, step_ms = _split_compile(times)
    rec = dict(steps=len(times), compile_s=compile_s, smoke_step_ms=step_ms,
               loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
               param_dtypes=sorted({str(a.dtype) for a in params.values()}),
               programs_checked_no_f64=programs)
    return rec, mod


def phase_train_module(s):
    rec, _mod = _train_module(s, [s.ctx(0)])
    s.emit("train_module", hbm_bytes_in_use=s.hbm(), **rec)


# -- phase 3 -----------------------------------------------------------------
def _gluon_setup(s, ctx, mesh=None, bf16_params=False):
    mx, c = s.mx, s.cfg
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    mx.random.seed(0)
    net = vision.resnet50_v1(classes=c["classes"])
    # shapes first, so initialize() defers nothing: WholeStepCompiler runs
    # the eager path, without a word, for a step whose parameters are
    # still waiting for a first forward to learn their shapes
    net.infer_shape(mx.nd.zeros((1, 3, c["img"], c["img"])))
    net.initialize(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                  magnitude=2), ctx=ctx)
    if bf16_params:
        # Gluon's bf16-with-f32-masters: bf16 parameters, and the
        # optimizer keeps the f32 master copy (multi_precision)
        net.cast("bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": c["lr"], "momentum": 0.9, "wd": 1e-4,
         "multi_precision": bf16_params},
        kvstore="tpu_sync", update_on_kvstore=False, mesh=mesh)
    return net, gluon.loss.SoftmaxCrossEntropyLoss(), trainer


def _wholestep(s, ctx, mesh=None):
    """Gluon ResNet-50 through WholeStepCompiler with bf16 autocast over
    f32 master weights; returns (record, net, compiler)."""
    mx, c = s.mx, s.cfg
    from mxnet_tpu import gluon
    metrics = mx.observability.metrics
    B = c["batch"]
    net, loss_fn, trainer = _gluon_setup(s, ctx, mesh)
    x, y = s.data(B)
    xd, yd = mx.nd.array(x, ctx=ctx), mx.nd.array(y, ctx=ctx)
    prev = {k: os.environ.get(k) for k in ("MXNET_WHOLE_STEP", "MXNET_AMP")}
    os.environ["MXNET_WHOLE_STEP"] = "1"
    os.environ["MXNET_AMP"] = "bf16"
    try:
        compiler = gluon.WholeStepCompiler(net, loss_fn, trainer)
        times, losses, dispatches = [], [], []
        for _ in range(c["steps"]):
            d0 = metrics.step_dispatches()
            t0 = time.perf_counter()
            loss = compiler.step(xd, yd)
            losses.append(float(loss.asnumpy().astype(np.float32).mean()))
            times.append(time.perf_counter() - t0)
            dispatches.append(metrics.step_dispatches() - d0)
            assert compiler.active, \
                f"whole-step demoted itself: {compiler.fallback_reason}"
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert np.isfinite(losses).all(), losses
    assert all(d == 1 for d in dispatches[1:]), \
        f"steady-state dispatches per step {dispatches[1:]} != 1"
    programs = _assert_no_f64_programs(mx.observability.introspect,
                                       ["whole_step"])
    compile_s, step_ms = _split_compile(times)
    rec = dict(steps=len(times), compile_s=compile_s, smoke_step_ms=step_ms,
               loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
               dispatches_per_step=dispatches[1:], active=compiler.active,
               programs_checked_no_f64=programs)
    return rec, net, compiler


def phase_train_gluon(s):
    mx, c = s.mx, s.cfg
    from mxnet_tpu import autograd
    ctx = s.ctx(0)
    B = c["batch"]
    # (a) the default fused path: forward, backward, Trainer.step
    net, loss_fn, trainer = _gluon_setup(s, ctx, bf16_params=True)
    x, y = s.data(B)
    xd = mx.nd.array(x, ctx=ctx).astype("bfloat16")
    yd = mx.nd.array(y, ctx=ctx)
    times, losses = [], []
    for _ in range(c["steps"]):
        t0 = time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(xd), yd)
        loss.backward()
        trainer.step(B)
        losses.append(float(loss.asnumpy().astype(np.float32).mean()))
        times.append(time.perf_counter() - t0)
    assert np.isfinite(losses).all(), losses
    dev = ctx.jax_device()
    _assert_on_devices({n: p.data()._data for n, p in
                        net.collect_params().items()}, [dev], "param")
    fused_programs = _assert_no_f64_programs(
        mx.observability.introspect, ["gluon:fwd", "gluon:bwd",
                                      "fused_update"])
    compile_s, step_ms = _split_compile(times)
    fused = dict(steps=len(times), compile_s=compile_s,
                 smoke_step_ms=step_ms, loss_first=round(losses[0], 4),
                 loss_last=round(losses[-1], 4),
                 programs_checked_no_f64=fused_programs)
    del net, trainer, loss, xd, yd
    gc.collect()
    # (b) one donated program per step
    whole, net, _compiler = _wholestep(s, ctx)
    _assert_on_devices({n: p.data()._data for n, p in
                        net.collect_params().items()}, [dev], "param")
    s.emit("train_gluon", fused=fused, wholestep=whole,
           compile_s=round(fused["compile_s"] + whole["compile_s"], 2),
           steps=fused["steps"] + whole["steps"],
           hbm_bytes_in_use=s.hbm())


# -- phase 4 -----------------------------------------------------------------
def phase_serve(s):
    mx, c = s.mx, s.cfg
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import vision
    metrics = mx.observability.metrics
    small, big = c["serve_buckets"]
    shape = (3, c["img"], c["img"])
    sym = vision.resnet50_v1(classes=c["classes"])(mx.sym.Variable("data"))

    # weights from the seed, initialised on the host; the same Module is
    # the CPU reference the chip's answers are checked against
    nreq = c["serve_requests"]
    ref_mod = mx.mod.Module(sym, context=mx.cpu(), label_names=None)
    ref_mod.bind(data_shapes=[("data", (nreq,) + shape)], for_training=False)
    mx.random.seed(0)
    ref_mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
    arg, aux = ref_mod.get_params()
    x, _ = s.data(nreq)
    ref_mod.forward(mx.io.DataBatch([mx.nd.array(x, ctx=mx.cpu())]),
                    is_train=False)
    ref = ref_mod.get_outputs()[0].asnumpy()

    params = {"arg:" + k: v for k, v in arg.items()}
    params.update({"aux:" + k: v for k, v in aux.items()})
    pred = serving.BucketedPredictor(
        sym, params, {"data": (big,) + shape}, dev=s.ctx(0),
        batch_buckets=[small, big])
    t0 = time.perf_counter()
    with serving.ResilientServer(pred, max_wait_ms=20.0) as srv:
        srv.warmup()
        compile_s = time.perf_counter() - t0
        assert pred.num_compiled == 2, pred.num_compiled
        compiles0 = metrics.SERVE_COMPILES.value
        # one alone (bucket 1), then the rest at once (they coalesce)
        t0 = time.perf_counter()
        outs = [srv.predict(data=x[:1])]
        futs = [srv.submit(data=x[i:i + 1]) for i in range(1, nreq)]
        outs += [f.result(timeout=300) for f in futs]
        serve_s = time.perf_counter() - t0
        served = srv.stats()["tenants"]["default"]["served"]
        new_compiles = metrics.SERVE_COMPILES.value - compiles0
    got = np.concatenate([np.asarray(o[0], np.float32) for o in outs])
    assert got.shape == ref.shape == (nreq, c["classes"]), got.shape
    assert np.isfinite(got).all()
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(got - ref))) / scale
    # f32 convolutions run at bf16 MXU precision on the chip: the repo's
    # own consistency tier allows ResNet-50 5e-2 (tests_tpu)
    assert err <= 5e-2, f"serve vs CPU forward: {err:.3e} of max |ref|"
    assert new_compiles == 0, f"{new_compiles} compile(s) after warm-up"
    assert served == nreq, f"{served} of {nreq} requests served"
    s.emit("serve", requests=nreq, compile_s=round(compile_s, 2),
           buckets=[small, big], compiles_after_warmup=int(new_compiles),
           max_err_vs_cpu=round(err, 5), smoke_serve_s=round(serve_s, 3),
           served=served, hbm_bytes_in_use=s.hbm())


# -- phase 5 -----------------------------------------------------------------
def phase_kernel(s):
    import jax.numpy as jnp
    mx, c = s.mx, s.cfg
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    from mxnet_tpu.ops import flash_attention as fa
    ctx = s.ctx(0)
    a = c["attn"]
    B, H, T, D = a["B"], a["H"], a["T"], a["D"]
    scale = D ** -0.5

    # (a) the op, forward and backward, against the dense reference
    rng = np.random.default_rng(1)
    q, k, v = (mx.nd.array(rng.standard_normal((B, H, T, D),
                                               dtype=np.float32),
                           ctx=ctx).astype("bfloat16") for _ in range(3))
    lowered = jax.jit(
        lambda a_, b_, c_: fa._flash_attention(a_, b_, c_, scale, True)
    ).lower(q._data, k._data, v._data).as_text()
    mosaic = "tpu_custom_call" in lowered
    assert mosaic or s.rehearsal, \
        "flash attention did not lower to the Mosaic custom call"
    for arr in (q, k, v):
        arr.attach_grad()
    t0 = time.perf_counter()
    with autograd.record():
        o = mx.nd.flash_attention(q, k, v, causal=True)
        # nd.cast, not .astype(): astype is not on the autograd tape
        loss = (mx.nd.cast(o, dtype="float32") ** 2).sum()
    loss.backward()
    got = [o.asnumpy().astype(np.float32)] + \
        [g.grad.asnumpy().astype(np.float32) for g in (q, k, v)]
    kernel_s = time.perf_counter() - t0

    def ref_loss(a_, b_, c_):
        out = fa._dense_reference(a_, b_, c_, scale, True)
        return (out.astype(jnp.float32) ** 2).sum(), out
    (_, ro), rg = jax.value_and_grad(ref_loss, argnums=(0, 1, 2),
                                     has_aux=True)(q._data, k._data, v._data)
    ref = [np.asarray(r, np.float32) for r in (ro,) + tuple(rg)]
    errs = {}
    for name, g_, r_ in zip(("out", "dq", "dk", "dv"), got, ref):
        assert np.isfinite(g_).all(), name
        errs[name] = round(float(np.max(np.abs(g_ - r_)))
                           / max(float(np.max(np.abs(r_))), 1e-6), 5)
        # bf16 operands and outputs on both sides
        assert errs[name] <= 3e-2, (name, errs[name])
    del q, k, v, o, loss, got, ref, rg, ro
    gc.collect()

    # (b) one training step of the LM that uses the kernel
    m = c["lm"]

    class LMLoss(gluon.HybridBlock):
        """net + next-token cross-entropy as one hybridized graph."""

        def __init__(self, net, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.net = net

        def hybrid_forward(self, F, tokens, labels):
            logits = F.cast(F.reshape(self.net(tokens), (-1, m["vocab"])),
                            "float32")
            nll = -F.pick(F.log_softmax(logits, axis=-1),
                          F.reshape(labels, (-1,)), axis=-1)
            return F.mean(nll)

    mx.random.seed(0)
    block = LMLoss(TransformerLM(
        m["vocab"], dim=m["dim"], num_layers=m["layers"],
        num_heads=m["heads"], ffn_dim=m["ffn"], max_len=m["seq"],
        attn_type="flash"))
    block.initialize(mx.init.Xavier(), ctx=ctx)
    block.cast("bfloat16")
    block.hybridize()
    trainer = gluon.Trainer(block.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9,
                             "multi_precision": True},
                            kvstore="tpu_sync", update_on_kvstore=False)
    toks = rng.integers(0, m["vocab"], (m["batch"], m["seq"] + 1)) \
        .astype(np.float32)
    xd = mx.nd.array(toks[:, :-1], ctx=ctx)
    yd = mx.nd.array(toks[:, 1:], ctx=ctx)
    times, losses = [], []
    for _ in range(4):
        t0 = time.perf_counter()
        with autograd.record():
            loss = block(xd, yd)
        loss.backward()
        trainer.step(m["batch"])
        losses.append(float(loss.asnumpy().astype(np.float32).mean()))
        times.append(time.perf_counter() - t0)
    assert np.isfinite(losses).all(), losses
    fwd = mx.observability.introspect.programs().get("gluon:fwd") or {}
    lm_mosaic = "tpu_custom_call" in (fwd.get("hlo") or "")
    assert lm_mosaic or s.rehearsal, \
        "the LM's forward program holds no Mosaic custom call"
    compile_s, step_ms = _split_compile(times)
    s.emit("kernel", attn_shape=[B, H, T, D], mosaic_custom_call=mosaic,
           rel_err_vs_dense=errs, smoke_kernel_fwd_bwd_s=round(kernel_s, 2),
           lm=dict(m, steps=len(times), loss_first=round(losses[0], 4),
                   loss_last=round(losses[-1], 4),
                   mosaic_custom_call=lm_mosaic, smoke_step_ms=step_ms),
           compile_s=compile_s, steps=len(times), hbm_bytes_in_use=s.hbm())


# -- phase 6 -----------------------------------------------------------------
def phase_four_chips(s):
    mx = s.mx
    devs = jax.devices()
    if len(devs) < 4:
        s.emit("four_chips", ran=False,
               reason=f"{len(devs)} device(s) visible, the phase needs 4")
        return
    from mxnet_tpu.analysis import program_audit
    introspect = mx.observability.introspect
    four = devs[:4]
    ctxs = [s.ctx(i) for i in range(4)]

    def spread(what):
        used = s.hbm()[:4]
        assert s.rehearsal or all(b > 0 for b in used), \
            f"{what}: bytes in use per chip {used} — a chip holds nothing"
        return used

    # (a) Module over four contexts: the KVStore's dp mesh
    rec_m, mod = _train_module(s, ctxs)
    ex = mod._exec
    # what the last step's program consumed (the executor shards the
    # batch over its dp mesh on the way in)
    step_args = ex._snapshot[0]
    batch_devs = {n: len(step_args[n].sharding.device_set)
                  for n in ("data", "softmax_label")}
    assert set(batch_devs.values()) == {4}, batch_devs
    ncoll = program_audit.count_collectives(
        introspect.programs()["executor:fwd_bwd"]["hlo"])
    assert ncoll >= 1, "no collective in the Module's step"
    rec_m.update(batch_devices=batch_devs, hbm_bytes_in_use=spread("module"),
                 collectives_in_step=ncoll)
    del mod, ex
    gc.collect()

    # (b) Gluon whole-step under the 2-D mesh's batch axis
    mesh = mx.parallel.make_mesh(batch=4, devices=four)
    rec_g, net, compiler = _wholestep(s, ctxs[0], mesh=mesh)
    assert compiler.mesh is not None and compiler.mesh.size == 4
    _assert_on_devices({n: p.data()._data for n, p in
                        net.collect_params().items()}, four, "param")
    ncoll = program_audit.count_collectives(
        introspect.programs()["whole_step"]["hlo"])
    assert ncoll >= 1, "no collective in the whole-step program"
    rec_g.update(mesh=mx.parallel.mesh.mesh_signature(mesh),
                 hbm_bytes_in_use=spread("gluon"), collectives_in_step=ncoll)
    s.emit("four_chips", ran=True, module=rec_m, gluon_wholestep=rec_g,
           compile_s=round(rec_m["compile_s"] + rec_g["compile_s"], 2),
           steps=rec_m["steps"] + rec_g["steps"])


RUN = dict(device=phase_device, train_module=phase_train_module,
           train_gluon=phase_train_gluon, serve=phase_serve,
           kernel=phase_kernel, four_chips=phase_four_chips)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Start the system on the chip, once, at full width.")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on the CPU, to debug this script; "
                         'every line says "rehearsal": true')
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of: " + ", ".join(PHASES))
    args = ap.parse_args(argv)
    want = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = sorted(set(want) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; known: {list(PHASES)}")
    s = Smoke(args.rehearsal)
    t_start = time.perf_counter()
    for phase in PHASES:
        if phase != "device" and phase not in want:
            continue
        t0 = time.perf_counter()
        try:
            RUN[phase](s)
        except Exception as e:  # noqa: BLE001 — reported, ends the run
            traceback.print_exc()
            sys.stderr.write(json.dumps(
                {"phase": phase, "ok": False, **s.stamp,
                 "error": f"{type(e).__name__}: {e}"[:2000]}) + "\n")
            return 1
        sys.stderr.write("chip_smoke: %s done in %.1fs\n"
                         % (phase, time.perf_counter() - t0))
        gc.collect()
    entries = s.cache_entries()
    assert entries > 0, f"no compile-cache entry under {s.cache_dir}"
    s.emit("summary", phases=[p for p in PHASES
                              if p == "device" or p in want],
           cache_dir=s.cache_dir, cache_entries=entries,
           smoke_wall_s=round(time.perf_counter() - t_start, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": s.stamp["platform"], "kind": s.stamp["device_kind"],
        "count": s.stamp["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
