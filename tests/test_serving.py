"""Inference fast path (mxnet_tpu.serving): bucket routing, padded-forward
parity, zero-recompile serving, micro-batching, donation knobs.

The serving acceptance invariant this file pins (ISSUE 4): after
`warmup()`, serving N requests of mixed batch/sequence sizes inside the
bucket set performs ZERO XLA recompiles and one dispatch per
request/coalesced batch, and padded-bucket outputs are bitwise-equal to
the unpadded forward on the valid rows.
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, sym
from mxnet_tpu import observability as obs
from mxnet_tpu.observability import metrics as m
from mxnet_tpu.serving.buckets import (BucketSpec, covering_bucket,
                                       pad_to_shape, pow2_buckets)


# -- helpers -----------------------------------------------------------------

def _mlp_symbol(nin=8, nhid=16, nout=4):
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=nhid,
                             name="fc1")
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, num_hidden=nout, name="fc2")
    return sym.SoftmaxOutput(net, name="softmax")


def _init_params(net, seed=0, **input_shapes):
    """arg:-prefixed random params for every non-input argument."""
    rs = np.random.RandomState(seed)
    arg_shapes, _, _ = net.infer_shape(**input_shapes)
    params = {}
    for n, s in zip(net.list_arguments(), arg_shapes):
        if n in input_shapes or n.endswith("_label"):
            continue
        params["arg:" + n] = mx.nd.array(
            rs.normal(0, 0.1, s).astype("f"))
    return params


def _mlp_predictor(max_batch=8, **kw):
    net = _mlp_symbol()
    params = _init_params(net, data=(max_batch, 8))
    return serving.BucketedPredictor(
        net, params, {"data": (max_batch, 8)}, **kw), net, params


# -- bucket math -------------------------------------------------------------

def test_pow2_bucket_derivation():
    assert pow2_buckets(8) == [1, 2, 4, 8]
    assert pow2_buckets(9) == [1, 2, 4, 8, 16]   # pow2 ceiling
    assert pow2_buckets(1) == [1]
    assert pow2_buckets(100, lo=16) == [16, 32, 64, 128]
    with pytest.raises(mx.MXNetError):
        pow2_buckets(0)


def test_bucket_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_SERVE_BUCKETS", "2,16,4")
    spec = BucketSpec({"data": (16, 8)})
    assert spec.batch_buckets == [2, 4, 16]
    monkeypatch.setenv("MXNET_SERVE_BUCKETS", "banana")
    with pytest.raises(mx.MXNetError, match="MXNET_SERVE_BUCKETS"):
        BucketSpec({"data": (16, 8)})


def test_route_picks_smallest_covering_bucket():
    spec = BucketSpec({"data": (16, 8)}, batch_buckets=[2, 4, 8, 16])
    assert spec.route({"data": (1, 8)}) == (2,)
    assert spec.route({"data": (2, 8)}) == (2,)
    assert spec.route({"data": (3, 8)}) == (4,)
    assert spec.route({"data": (9, 8)}) == (16,)
    assert spec.route({"data": (17, 8)}) == (None,)  # caller chunks
    # seq axis: smallest covering on BOTH axes
    spec2 = BucketSpec({"data": (4, 16, 3)}, seq_axes={"data": 1},
                       batch_buckets=[2, 4], seq_buckets=[4, 8, 16])
    assert spec2.route({"data": (1, 5, 3)}) == (2, 8)
    assert spec2.route({"data": (3, 16, 3)}) == (4, 16)
    with pytest.raises(mx.MXNetError, match="seq bucket"):
        spec2.route({"data": (2, 17, 3)})


def test_pad_to_shape():
    a = np.arange(6, dtype="f").reshape(2, 3)
    p = pad_to_shape(a, (4, 3))
    np.testing.assert_array_equal(p[:2], a)
    np.testing.assert_array_equal(p[2:], 0)
    assert pad_to_shape(a, (2, 3)) is not None  # no-op path
    with pytest.raises(mx.MXNetError):
        pad_to_shape(a, (1, 3))  # shrink is not padding


def test_non_batch_major_output_rejected_at_compile():
    """A symbol whose output is not batch-major (here: a scalar whole-
    batch reduction) cannot be served through bucket padding — padding
    would silently dilute the reduction.  Must fail LOUDLY at
    precompile, never corrupt at slice time."""
    net = sym.sum(sym.Variable("data"))  # scalar output
    pred = serving.BucketedPredictor(net, {}, {"data": (4, 3)},
                                     batch_buckets=[4])
    with pytest.raises(mx.MXNetError, match="batch-major"):
        pred.warmup()


def test_kwarg_buckets_validated():
    with pytest.raises(mx.MXNetError, match="positive"):
        BucketSpec({"data": (4, 3)}, batch_buckets=[0, 4])


def test_covering_bucket():
    assert covering_bucket([2, 4, 8], 3) == 4
    assert covering_bucket([2, 4, 8], 8) == 8
    assert covering_bucket([2, 4, 8], 9) is None


# -- padded-forward parity ---------------------------------------------------

def test_padded_output_bitwise_equals_unpadded():
    """Rows of a padded-bucket dispatch must be BITWISE equal to the
    unpadded forward of the same params (the correctness contract that
    makes bucket padding invisible to callers).  Pinned bitwise on the
    CPU tier-1 backend, where XLA kernel choice is shape-stable; on TPU
    the same property holds at ULP level (docs/inference.md)."""
    from mxnet_tpu.predictor import Predictor
    pred, net, params = _mlp_predictor(max_batch=8)
    pred.warmup()
    rs = np.random.RandomState(1)
    for rows in (1, 3, 5, 8):
        x = rs.normal(0, 1, (rows, 8)).astype("f")
        got = pred.predict(x)[0]
        ref_p = Predictor(net.tojson(),
                          {k: v for k, v in params.items()},
                          {"data": (rows, 8)})
        ref_p.set_input("data", x)
        ref_p.forward()
        ref = ref_p.get_output(0)
        assert got.shape == ref.shape == (rows, 4)
        np.testing.assert_array_equal(got, ref)


def test_seq_bucket_valid_region_equals_unpadded():
    """Sequence-axis padding: for a position-independent graph the valid
    (rows, seq) region is bitwise-equal to the unpadded forward."""
    net = sym.Activation(sym.Variable("data") * 2.0 + 1.0,
                         act_type="tanh")
    pred = serving.BucketedPredictor(
        net, {}, {"data": (4, 16, 3)}, seq_axes={"data": 1},
        batch_buckets=[4], seq_buckets=[8, 16])
    pred.warmup()
    exact = serving.BucketedPredictor(
        net, {}, {"data": (3, 10, 3)}, batch_buckets=[3],
        seq_axes={"data": 1}, seq_buckets=[10])
    rs = np.random.RandomState(2)
    x = rs.normal(0, 1, (3, 10, 3)).astype("f")
    got = pred.predict(x)[0]          # (3, 16, 3) routed to bucket (4,16)
    ref = exact.predict(x)[0]         # (3, 10, 3), no padding
    assert got.shape == (3, 16, 3)
    np.testing.assert_array_equal(got[:, :10], ref)


def test_oversize_request_chunks_over_largest_bucket():
    pred, _, _ = _mlp_predictor(max_batch=4)
    pred.warmup()
    rs = np.random.RandomState(3)
    x = rs.normal(0, 1, (11, 8)).astype("f")
    whole = pred.predict(x)[0]
    # chunking slices at the largest bucket (4): compare against direct
    # requests at the same geometry so both sides run the SAME bucket
    # executables (different buckets may pick different XLA kernels,
    # which is allowed to differ in ULPs)
    parts = np.concatenate([pred.predict(x[lo:lo + 4])[0]
                            for lo in range(0, 11, 4)])
    assert whole.shape == (11, 4)
    np.testing.assert_array_equal(whole, parts)


# -- the zero-recompile serving invariant ------------------------------------

@pytest.mark.perf_smoke
def test_zero_recompiles_one_dispatch_after_warmup():
    """ISSUE 4 acceptance gate: after warmup(), mixed-size traffic
    inside the bucket set performs ZERO XLA compiles and exactly ONE
    compiled-program launch per request — no device_puts, no executor
    jit-cache misses (dispatch_counts() + serving counters)."""
    pred, _, _ = _mlp_predictor(max_batch=8)
    pred.warmup()
    assert pred.num_compiled == 4  # buckets 1,2,4,8
    rs = np.random.RandomState(4)
    sizes = [1, 3, 5, 8, 2, 7, 4, 6, 1, 8]
    compiles0 = m.SERVE_COMPILES.value
    misses0 = m.JIT_CACHE_MISSES.value
    c0 = obs.dispatch_counts()
    for rows in sizes:
        out = pred.predict(rs.normal(0, 1, (rows, 8)).astype("f"))
        assert out[0].shape == (rows, 4)
    c1 = obs.dispatch_counts()
    delta = {k: c1.get(k, 0) - c0.get(k, 0)
             for k in c1 if c1.get(k, 0) != c0.get(k, 0)}
    assert m.SERVE_COMPILES.value == compiles0, "hot-path recompile!"
    assert m.JIT_CACHE_MISSES.value == misses0
    assert delta.get("xla:serve", 0) == len(sizes), delta
    assert delta.get("device_put", 0) == 0, delta
    assert delta.get("total", 0) == len(sizes), delta


def test_unwarmed_bucket_compiles_once_then_caches():
    pred, _, _ = _mlp_predictor(max_batch=4)
    rs = np.random.RandomState(5)
    x = rs.normal(0, 1, (3, 8)).astype("f")
    c0 = m.SERVE_COMPILES.value
    pred.predict(x)
    assert m.SERVE_COMPILES.value == c0 + 1  # bucket 4, first sight
    pred.predict(x)
    pred.predict(rs.normal(0, 1, (4, 8)).astype("f"))  # same bucket
    assert m.SERVE_COMPILES.value == c0 + 1


# -- micro-batching ----------------------------------------------------------

def test_microbatcher_coalesces_concurrent_requests():
    pred, _, _ = _mlp_predictor(max_batch=8)
    pred.warmup()
    rs = np.random.RandomState(6)
    xs = [rs.normal(0, 1, (1, 8)).astype("f") for _ in range(6)]
    refs = [pred.predict(x)[0] for x in xs]
    batches0 = m.SERVE_BATCHES.value
    with serving.MicroBatcher(pred, max_wait_ms=200) as bat:
        futs = [bat.submit(data=x) for x in xs]
        outs = [f.result(timeout=30) for f in futs]
    # every caller gets exactly its own rows back (tight tolerance, not
    # bitwise: the coalesced batch runs a LARGER bucket executable than
    # the solo reference, and XLA may pick a different kernel per shape)
    for ref, out in zip(refs, outs):
        np.testing.assert_allclose(ref, out[0], rtol=1e-6, atol=1e-7)
    # 6 concurrent 1-row submits coalesced into far fewer dispatches
    # (first may fire alone before the rest enqueue; 200 ms of hold
    # makes full coalescing overwhelmingly likely)
    assert m.SERVE_BATCHES.value - batches0 <= 3


def test_microbatcher_max_wait_timeout():
    """A lone request must dispatch after ~max_wait, not wait for
    max_batch rows."""
    pred, _, _ = _mlp_predictor(max_batch=8)
    pred.warmup()
    with serving.MicroBatcher(pred, max_wait_ms=30) as bat:
        t0 = time.perf_counter()
        out = bat.predict(data=np.ones((2, 8), "f"))
        dt = time.perf_counter() - t0
    assert out[0].shape == (2, 4)
    assert dt < 10.0  # dispatched on timeout, not starved


def test_microbatcher_max_batch_flush():
    """Row cap flushes a group early; the overflow request leads the
    next group and nothing is lost or duplicated."""
    pred, _, _ = _mlp_predictor(max_batch=8)
    pred.warmup()
    rs = np.random.RandomState(7)
    xs = [rs.normal(0, 1, (2, 8)).astype("f") for _ in range(5)]
    refs = [pred.predict(x)[0] for x in xs]
    with serving.MicroBatcher(pred, max_wait_ms=100, max_batch=4) as bat:
        futs = [bat.submit(data=x) for x in xs]
        outs = [f.result(timeout=30) for f in futs]
    for ref, out in zip(refs, outs):
        np.testing.assert_allclose(ref, out[0], rtol=1e-6, atol=1e-7)


def test_microbatcher_mixed_seq_lengths_coalesce():
    net = sym.Activation(sym.Variable("data") * 2.0 + 1.0,
                         act_type="tanh")
    pred = serving.BucketedPredictor(
        net, {}, {"data": (4, 16, 3)}, seq_axes={"data": 1},
        batch_buckets=[4], seq_buckets=[8, 16]).warmup()
    rs = np.random.RandomState(8)
    a = rs.normal(0, 1, (1, 5, 3)).astype("f")
    b = rs.normal(0, 1, (2, 9, 3)).astype("f")
    ra, rb = pred.predict(a)[0], pred.predict(b)[0]
    with serving.MicroBatcher(pred, max_wait_ms=200) as bat:
        fa, fb = bat.submit(data=a), bat.submit(data=b)
        oa, ob = fa.result(30), fb.result(30)
    # valid regions agree with the solo dispatches (both padded to the
    # group's covering seq bucket, so compare the common valid window)
    np.testing.assert_array_equal(oa[0][:, :5], ra[:, :5])
    np.testing.assert_array_equal(ob[0][:, :9], rb[:, :9])


def test_microbatcher_propagates_errors():
    pred, _, _ = _mlp_predictor(max_batch=4)
    with serving.MicroBatcher(pred, max_wait_ms=10) as bat:
        fut = bat.submit(data=np.ones((1, 9), "f"))  # wrong feature dim
        with pytest.raises(mx.MXNetError, match="dim 1"):
            fut.result(timeout=30)
        # the batcher survives a poisoned request
        out = bat.predict(data=np.ones((1, 8), "f"))
    assert out[0].shape == (1, 4)


def test_microbatcher_bad_request_does_not_poison_group():
    """A malformed submit fails ITS OWN future at enqueue time; a
    well-formed request in the same wait window still succeeds."""
    pred, _, _ = _mlp_predictor(max_batch=8)
    pred.warmup()
    with serving.MicroBatcher(pred, max_wait_ms=200) as bat:
        bad = bat.submit(data=np.ones((2, 9), "f"))   # wrong feature dim
        good = bat.submit(data=np.ones((2, 8), "f"))
        with pytest.raises(mx.MXNetError):
            bad.result(timeout=30)
        out = good.result(timeout=30)
    assert out[0].shape == (2, 4)


def test_microbatcher_oversized_submit_is_async_and_chunked():
    """rows > max_batch rides the dispatcher thread (submit never runs
    the model on the caller's thread) and chunks over the largest
    bucket; results match the direct predict."""
    pred, _, _ = _mlp_predictor(max_batch=4)
    pred.warmup()
    rs = np.random.RandomState(13)
    x = rs.normal(0, 1, (11, 8)).astype("f")
    ref = pred.predict(x)[0]
    with serving.MicroBatcher(pred, max_wait_ms=10, max_batch=4) as bat:
        fut = bat.submit(data=x)
        out = fut.result(timeout=30)
    np.testing.assert_array_equal(ref, out[0])


# -- BucketingModule: switching warmed buckets never recompiles ---------------

def _bucket_sym_gen(seq_len):
    # embedding + pool so every parameter shape is seq-independent (the
    # bucketed-LM shape; per-bucket FC over raw seq would fork weights)
    data = sym.Variable("data")
    emb = sym.Embedding(data, input_dim=16, output_dim=8, name="embed")
    net = sym.FullyConnected(sym.sum(emb, axis=1), num_hidden=4,
                             name="fc")
    net = sym.SoftmaxOutput(net, name="softmax")
    return net, ("data",), ("softmax_label",)


def _bucket_batch(seq_len, batch=2, fill=1.0):
    return mx.io.DataBatch(
        [mx.nd.ones((batch, seq_len)) * fill], [mx.nd.zeros((batch,))],
        bucket_key=seq_len,
        provide_data=[mx.io.DataDesc("data", (batch, seq_len))],
        provide_label=[mx.io.DataDesc("softmax_label", (batch,))])


@pytest.mark.perf_smoke
def test_bucketing_module_switch_costs_no_recompile():
    """Regression gate: once every bucket has run, switch_bucket is a
    dict lookup — re-visiting buckets adds ZERO jit-cache misses and one
    compiled launch per forward (the reference's shared-memory-pool
    bucketing executor, realized through the shared executor jit
    cache)."""
    mod = mx.mod.BucketingModule(_bucket_sym_gen, default_bucket_key=16,
                                 context=mx.cpu())
    b16, b8 = _bucket_batch(16), _bucket_batch(8)
    mod.bind(b16.provide_data, b16.provide_label)
    mod.init_params(mx.init.Xavier())
    # warm both buckets (compiles happen here)
    mod.forward(b16, is_train=False)
    mod.forward(b8, is_train=False)
    misses0 = m.JIT_CACHE_MISSES.value
    c0 = obs.dispatch_counts()
    for i in range(6):  # alternate buckets — the bucketed-LM pattern
        mod.forward(_bucket_batch(16 if i % 2 else 8, fill=float(i)),
                    is_train=False)
        mod.get_outputs()[0].asnumpy()
    c1 = obs.dispatch_counts()
    assert m.JIT_CACHE_MISSES.value == misses0, "bucket switch recompiled"
    delta = {k: c1.get(k, 0) - c0.get(k, 0)
             for k in c1 if c1.get(k, 0) != c0.get(k, 0)}
    assert delta.get("xla:fwd", 0) == 6, delta
    assert delta.get("device_put", 0) == 0, delta


def test_bucketing_module_warmup_buckets():
    """warmup_buckets pre-materializes+compiles a bucket list without
    changing the active bucket; traffic after it adds no misses."""
    mod = mx.mod.BucketingModule(_bucket_sym_gen, default_bucket_key=16,
                                 context=mx.cpu())
    b16 = _bucket_batch(16)
    mod.bind(b16.provide_data, b16.provide_label)
    mod.init_params(mx.init.Xavier())
    triples = [
        (s, [mx.io.DataDesc("data", (2, s))],
         [mx.io.DataDesc("softmax_label", (2,))]) for s in (8, 16, 32)]
    mod.warmup_buckets(triples)
    assert mod._active_key == 16  # warmup must not switch the bucket
    misses0 = m.JIT_CACHE_MISSES.value
    for s in (8, 32, 16, 8):
        mod.forward(_bucket_batch(s), is_train=False)
        mod.get_outputs()[0].asnumpy()
    assert m.JIT_CACHE_MISSES.value == misses0
    # training programs are distinct executables: warm them explicitly,
    # then training traffic over the warmed buckets adds no misses
    mod.warmup_buckets(triples, for_training=True)
    misses1 = m.JIT_CACHE_MISSES.value
    for s in (32, 8, 16):
        mod.forward_backward(_bucket_batch(s))
        mod.get_outputs()[0].asnumpy()
    assert m.JIT_CACHE_MISSES.value == misses1


# -- satellites: blob loading, donation, metrics ------------------------------

def test_load_frombuffer_roundtrip(tmp_path):
    rs = np.random.RandomState(9)
    data = {"arg:w": mx.nd.array(rs.normal(0, 1, (3, 4)).astype("f")),
            "aux:s": mx.nd.array(rs.normal(0, 1, (4,)).astype("f"))}
    f = str(tmp_path / "p.params")
    mx.nd.save(f, data)
    blob = open(f, "rb").read()
    loaded = mx.nd.load_frombuffer(blob)
    assert set(loaded) == set(data)
    for k in data:
        np.testing.assert_array_equal(loaded[k].asnumpy(),
                                      data[k].asnumpy())
    # reference-era dmlc container blob too
    f2 = str(tmp_path / "ref.params")
    mx.nd.save_reference_format(f2, data)
    loaded2 = mx.nd.load_frombuffer(open(f2, "rb").read())
    for k in data:
        np.testing.assert_array_equal(loaded2[k].asnumpy(),
                                      data[k].asnumpy())


def test_predictor_bytes_blob_no_tempfile(tmp_path, monkeypatch):
    """The param blob parses IN MEMORY — the tempfile round trip is
    gone from the model-load path."""
    import tempfile

    def _boom(*a, **k):
        raise AssertionError("predictor wrote the param blob to disk")

    net = _mlp_symbol()
    params = _init_params(net, data=(2, 8))
    f = str(tmp_path / "p.params")
    mx.nd.save(f, params)
    blob = open(f, "rb").read()
    monkeypatch.setattr(tempfile, "NamedTemporaryFile", _boom)
    from mxnet_tpu.predictor import Predictor
    p = Predictor(net.tojson(), blob, {"data": (2, 8)})
    p.set_input("data", np.ones((2, 8), "f"))
    p.forward()
    assert p.get_output(0).shape == (2, 4)


def test_serving_predictor_accepts_bytes_blob(tmp_path):
    net = _mlp_symbol()
    params = _init_params(net, data=(4, 8))
    f = str(tmp_path / "p.params")
    mx.nd.save(f, params)
    pred = serving.BucketedPredictor(
        net.tojson(), open(f, "rb").read(), {"data": (4, 8)})
    out = pred.predict(np.ones((3, 8), "f"))
    assert out[0].shape == (3, 4)


def test_donated_inference_parity(monkeypatch):
    """MXNET_DONATE_INFER=1: the donated cached-op forward is numerically
    identical to the standard one, and recording-mode training still
    rides the non-donated path."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(3)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(2))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    x = mx.nd.array(np.random.RandomState(10).normal(
        0, 1, (4, 6)).astype("f"))
    monkeypatch.setenv("MXNET_DONATE_INFER", "0")
    ref = net(x).asnumpy()
    monkeypatch.setenv("MXNET_DONATE_INFER", "1")
    got = net(x).asnumpy()
    np.testing.assert_array_equal(ref, got)
    # training under the env flag: the recording path must bypass
    # donation (a donated weight/input would break the vjp replay)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.01}, kvstore="tpu_sync",
                       update_on_kvstore=False)
    with autograd.record():
        loss = gluon.loss.L2Loss()(net(x), mx.nd.zeros((4, 2)))
    loss.backward()
    tr.step(4)
    assert np.isfinite(float(loss.asnumpy().ravel()[0]))


def test_donate_weights_update_parity(monkeypatch):
    """MXNET_DONATE_WEIGHTS=1 changes buffer ownership, never math: a
    3-step training run matches the non-donated run bitwise."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    def run(flag):
        monkeypatch.setenv("MXNET_DONATE_WEIGHTS", flag)
        mx.random.seed(11)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(8, activation="relu"))
            net.add(nn.Dense(1))
        net.hybridize()
        net.initialize(mx.init.Xavier(), ctx=mx.cpu())
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.05, "momentum": 0.9},
                           kvstore="tpu_sync", update_on_kvstore=False)
        rs = np.random.RandomState(12)
        x = mx.nd.array(rs.normal(0, 1, (8, 6)).astype("f"))
        y = mx.nd.array(rs.normal(0, 1, (8, 1)).astype("f"))
        for _ in range(3):
            with autograd.record():
                loss = gluon.loss.L2Loss()(net(x), y)
            loss.backward()
            tr.step(8)
        return [p.data().asnumpy() for p in net.collect_params().values()]

    ref = run("0")
    got = run("1")
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


def test_gluon_jit_cache_counters_populated():
    """snapshot()["jit_cache"] covers the gluon cached-op path: the
    first hybridized forward is a miss, repeats are hits."""
    from mxnet_tpu.gluon import nn
    mx.random.seed(4)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(4))
    net.hybridize()
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    x = mx.nd.ones((2, 3))
    h0, m0 = m.JIT_CACHE_HITS.value, m.JIT_CACHE_MISSES.value
    net(x)  # first call: one miss (deferred-init retry may also hit)
    assert m.JIT_CACHE_MISSES.value == m0 + 1
    h1 = m.JIT_CACHE_HITS.value
    net(x)
    net(x)
    assert m.JIT_CACHE_HITS.value == h1 + 2
    assert m.JIT_CACHE_MISSES.value == m0 + 1
    snap = obs.snapshot()["jit_cache"]
    assert snap["hits"] >= 2 and snap["misses"] >= 1


def test_serving_snapshot_and_padding_waste():
    pred, _, _ = _mlp_predictor(max_batch=8)
    pred.warmup()
    pred.predict(np.ones((6, 8), "f"))  # bucket 8 -> waste 0.25
    snap = obs.snapshot()["serving"]
    for k in ("requests", "batches", "compiles", "queue_depth",
              "padding_waste", "latency_ms_mean"):
        assert k in snap, snap
    assert abs(m.SERVE_PADDING_WASTE.get() - 0.25) < 1e-9
    assert snap["requests"] >= 1 and snap["batches"] >= 1


def test_compile_cache_dir_wires(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR populates a persistent on-disk cache at
    serving compile time (restart-skips-compile is the product claim;
    on-disk artifacts are the observable) — even when the variable
    arrives after this process's first compile."""
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import mxnet_tpu.base as base
    saved = {k: getattr(jax.config, k) for k in
             ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        pred, _, _ = _mlp_predictor(max_batch=2)
        pred.warmup()
        assert base.compile_cache_active()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        assert os.listdir(tmp_path), "no cache entry written"
    finally:
        # un-wire: jax config is process-global, and tmp_path is deleted
        # after this test — later compiles must not try to persist into
        # a dead directory
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


# -- close()/worker-death contract (ISSUE 6 satellite) ------------------------

def test_microbatcher_submit_after_close_raises_immediately():
    pred, _, _ = _mlp_predictor(max_batch=4)
    bat = serving.MicroBatcher(pred, max_wait_ms=5)
    bat.close()
    with pytest.raises(serving.BatcherClosedError, match="closed"):
        bat.submit(data=np.ones((1, 8), "f"))


def test_microbatcher_close_timeout_fails_pending_not_hang():
    """close(timeout) overrunning a hung dispatch must fail every
    queued request (including the displaced pending-slot one) with a
    typed error — callers never hang in Future.result()."""
    from mxnet_tpu import faultinject as fi
    pred, _, _ = _mlp_predictor(max_batch=4)
    pred.warmup()
    with fi.active(fi.FaultPlan().add("serving.dispatch", "delay",
                                      delay_s=0.6)):
        bat = serving.MicroBatcher(pred, max_wait_ms=0, max_batch=4)
        first = bat.submit(data=np.ones((1, 8), "f"))  # enters dispatch
        time.sleep(0.05)
        # 4-row request displaces into the pending slot; 1-row queues
        disp = bat.submit(data=np.ones((4, 8), "f"))
        tail = bat.submit(data=np.ones((1, 8), "f"))
        t0 = time.perf_counter()
        bat.close(timeout=0.05)  # join times out mid-dispatch
        assert time.perf_counter() - t0 < 0.5
        for fut in (disp, tail):
            with pytest.raises(serving.BatcherClosedError,
                               match="before dispatch"):
                fut.result(timeout=5)
        # the in-flight request still completes (or fails) on its own
        assert first.result(timeout=5)[0].shape == (1, 4)
    bat._thread.join(timeout=5)  # dispatcher exits via re-armed sentinel
    assert not bat._thread.is_alive()


# -- auto-reload hardening (ISSUE 6 satellite) --------------------------------

def test_auto_reload_survives_transient_failure_and_counts(tmp_path):
    """A transiently failing checkpoint scan must not kill the reload
    thread: failures are counted in serving reload_failures, old
    weights keep serving, and the poller recovers when storage does."""
    from mxnet_tpu import checkpoint as ckpt
    net = sym.FullyConnected(sym.Variable("data"), num_hidden=2,
                             name="fc")
    w = np.ones((2, 8), "f")
    pred = serving.BucketedPredictor(
        net, {"arg:fc_weight": w, "arg:fc_bias": np.zeros(2, "f")},
        {"data": (2, 8)})
    x = np.ones((1, 8), "f")
    ref = pred.predict(x)[0]
    mgr = ckpt.CheckpointManager(str(tmp_path), async_save=False)
    orig, calls = mgr.latest_step, {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise OSError("transient checkpoint-dir scan failure")
        return orig()

    mgr.latest_step = flaky
    f0 = m.SERVE_RELOAD_FAILURES.value
    pred.start_auto_reload(mgr, interval_s=0.02)
    try:
        deadline = time.monotonic() + 10
        while m.SERVE_RELOAD_FAILURES.value < f0 + 2:
            assert time.monotonic() < deadline, "failures not counted"
            time.sleep(0.02)
        assert pred._reload_thread.is_alive(), "reload thread died"
        np.testing.assert_array_equal(pred.predict(x)[0], ref)
        assert obs.snapshot()["serving"]["reload_failures"] >= 2
        # storage recovers: the next poll picks up the new checkpoint
        mgr.save(7, {"param:fc_weight": w * 2,
                     "param:fc_bias": np.zeros(2, "f")})
        deadline = time.monotonic() + 10
        while pred.loaded_step != 7:
            assert time.monotonic() < deadline, "never reloaded"
            time.sleep(0.02)
        np.testing.assert_array_equal(pred.predict(x)[0], ref * 2)
    finally:
        pred.stop_auto_reload()
