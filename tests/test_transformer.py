"""Transformer LM family (model_zoo/transformer.py): causal masking,
flash-vs-dense attention parity, hybridized CachedOp equivalence, and a
training step.  (Beyond-reference capability — the long-context flagship;
the sharded legs live in tests/test_parallel.py ring/ulysses.)"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
from mxnet_tpu.test_utils import assert_almost_equal

V, T, B = 17, 12, 2


def make_net(attn_type="dense", seed=0):
    mx.random.seed(seed)
    net = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                        max_len=16, attn_type=attn_type)
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    return net


def copy_params(dst, src):
    # a forward pass materializes deferred-init params on both sides
    probe = mx.nd.zeros((1, 4))
    src(probe)
    dst(probe)
    sp = {k.split("_", 1)[1]: v for k, v in src.collect_params().items()}
    for k, v in dst.collect_params().items():
        v.set_data(sp[k.split("_", 1)[1]].data())


def test_causal_masking():
    """Perturbing future tokens must not change past logits."""
    rs = np.random.RandomState(0)
    net = make_net()
    t1 = rs.randint(0, V, (1, T)).astype("f")
    t2 = t1.copy()
    t2[0, 8:] = (t2[0, 8:] + 3) % V
    o1 = net(mx.nd.array(t1)).asnumpy()
    o2 = net(mx.nd.array(t2)).asnumpy()
    assert_almost_equal(o1[:, :8], o2[:, :8], rtol=1e-5, atol=1e-6)
    # and future logits DO change (the perturbation is visible)
    assert np.abs(o1[:, 8:] - o2[:, 8:]).max() > 1e-3


def test_flash_dense_parity():
    """The Pallas flash-attention path must match dense attention in both
    the forward logits and the parameter gradients."""
    rs = np.random.RandomState(1)
    dense = make_net("dense")
    flash = make_net("flash")
    copy_params(flash, dense)
    x = mx.nd.array(rs.randint(0, V, (B, T)).astype("f"))
    y = mx.nd.array(rs.randint(0, V, (B, T)).astype("f"))
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    outs, grads = [], []
    for net in (dense, flash):
        with autograd.record():
            logits = net(x)
            loss = sce(logits.reshape((-1, V)), y.reshape((-1,)))
        loss.backward()
        outs.append(logits.asnumpy())
        grads.append({k.split("_", 1)[1]: p.grad().asnumpy()
                      for k, p in net.collect_params().items()})
    assert_almost_equal(outs[0], outs[1], rtol=1e-4, atol=1e-5)
    for k in grads[0]:
        assert_almost_equal(grads[0][k], grads[1][k], rtol=1e-3, atol=1e-4,
                            names=(f"dense:{k}", f"flash:{k}"))


def test_hybridize_equivalence():
    """hybridize() compiles the stack into one CachedOp with identical
    numbers."""
    rs = np.random.RandomState(2)
    net = make_net()
    x = mx.nd.array(rs.randint(0, V, (B, T)).astype("f"))
    eager = net(x).asnumpy()
    net.hybridize()
    compiled = net(x).asnumpy()
    assert_almost_equal(eager, compiled, rtol=1e-5, atol=1e-6)


def test_training_reduces_loss():
    rs = np.random.RandomState(3)
    net = make_net()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    # fixed batch: loss must drop when memorizing it
    x = mx.nd.array(rs.randint(0, V, (4, T)).astype("f"))
    y = mx.nd.array(rs.randint(0, V, (4, T)).astype("f"))
    losses = []
    for _ in range(12):
        with autograd.record():
            logits = net(x)
            loss = sce(logits.reshape((-1, V)), y.reshape((-1,)))
        loss.backward()
        trainer.step(4)
        losses.append(float(loss.mean().asnumpy()))
    assert losses[-1] < losses[0] * 0.7, losses


def test_generate_memorizes_sequence():
    """After memorizing one sequence, greedy generation from its prefix
    reproduces the continuation (decode loop + causal cache semantics)."""
    rs = np.random.RandomState(5)
    net = make_net()
    seq = rs.randint(0, V, (1, T)).astype("f")
    x = mx.nd.array(seq[:, :-1])
    y = mx.nd.array(seq[:, 1:])
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 5e-3})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(60):
        with autograd.record():
            logits = net(x)
            loss = sce(logits.reshape((-1, V)), y.reshape((-1,)))
        loss.backward()
        trainer.step(1)
    prefix = mx.nd.array(seq[:, :4])
    out = net.generate(prefix, T - 4).asnumpy()[0]
    assert (out[4:] == seq[0, 4:]).mean() > 0.7, (out, seq)


def test_generate_static_matches_eager():
    """static_shapes decoding (fixed (B, max_len) buffer, one cached
    program per step kind) must produce the same greedy tokens as the
    growing-prefix eager reference, and must not recompile per step."""
    rs = np.random.RandomState(7)
    net = make_net(seed=3)
    prefix = mx.nd.array(rs.randint(0, V, (2, 5)).astype("f"))
    out_static = net.generate(prefix, 8, static_shapes=True).asnumpy()
    out_eager = net.generate(prefix, 8, static_shapes=False).asnumpy()
    assert out_static.shape == (2, 13)
    assert (out_static == out_eager).all(), (out_static, out_eager)
    # one compiled forward reused across all greedy steps: the step
    # block's CachedOp must hold exactly one shape specialization
    steps = net._decode_steps()
    cached_op = getattr(steps["greedy"], "_cached_op", None)
    if cached_op is not None and hasattr(cached_op._fwd, "_cache_size"):
        assert cached_op._fwd._cache_size() == 1


def test_generate_static_sampling():
    """temperature>0: the static path must draw the SAME tokens as the
    eager reference under a same-seeded rng (identical logits ->
    identical softmax -> identical draws), catching any off-by-one in
    the static read/write positions."""
    rs = np.random.RandomState(11)
    net = make_net(seed=4)
    prefix = mx.nd.array(rs.randint(0, V, (2, 4)).astype("f"))
    out_s = net.generate(prefix, 6, temperature=1.0,
                         rng=np.random.RandomState(0),
                         static_shapes=True).asnumpy()
    out_e = net.generate(prefix, 6, temperature=1.0,
                         rng=np.random.RandomState(0),
                         static_shapes=False).asnumpy()
    assert out_s.shape == (2, 10)
    assert (out_s[:, :4] == prefix.asnumpy()).all()
    assert ((out_s >= 0) & (out_s < V)).all()
    assert (out_s == out_e).all(), (out_s, out_e)


def test_generate_kv_cache_matches_eager():
    """kv_cache=True (mha_decode_step: O(Tmax*D)/token over per-layer
    K/V caches) must reproduce the eager reference exactly — greedy
    AND same-seeded sampling — catching cache-write position errors,
    mask off-by-ones, and any decode/training weight drift (the cell
    re-composes the same sub-blocks)."""
    rs = np.random.RandomState(17)
    net = make_net(seed=6)
    prefix = mx.nd.array(rs.randint(0, V, (2, 5)).astype("f"))
    out_kv = net.generate(prefix, 8, kv_cache=True).asnumpy()
    out_eager = net.generate(prefix, 8, static_shapes=False).asnumpy()
    assert (out_kv == out_eager).all(), (out_kv, out_eager)
    s_kv = net.generate(prefix, 5, temperature=1.0, kv_cache=True,
                        rng=np.random.RandomState(2)).asnumpy()
    s_eager = net.generate(prefix, 5, temperature=1.0,
                           static_shapes=False,
                           rng=np.random.RandomState(2)).asnumpy()
    assert (s_kv == s_eager).all(), (s_kv, s_eager)
    # conflicting strategy flags are an error, not a silent choice
    with pytest.raises(ValueError):
        net.generate(prefix, 2, kv_cache=True, static_shapes=False)
    # sp attention types decode over SHARDED caches and need an active
    # sp_scope — without one, both fail loudly (see the ring/ulysses
    # decode tests for the working sharded paths)
    from mxnet_tpu.base import MXNetError
    for sp_type in ("ring", "ulysses"):
        sp_net = make_net()
        for blk in sp_net.blocks._children:
            blk.attn._type = sp_type
        with pytest.raises(MXNetError):
            sp_net.generate(prefix, 2, kv_cache=True)


def test_generate_leaves_hybrid_state_alone():
    """generate() must not flip a deliberately-eager net into hybrid
    mode (the decode wrappers activate only their own flag)."""
    rs = np.random.RandomState(13)
    net = make_net(seed=5)
    assert net._active is False
    net.generate(mx.nd.array(rs.randint(0, V, (1, 3)).astype("f")), 2)
    assert net._active is False
    assert all(not b._active for b in net.blocks._children)


def test_beam_search_width1_is_greedy_and_scores_are_exact():
    """beam=1 must reproduce greedy KV decoding exactly, and the
    returned log-prob must equal the teacher-forced rescoring of the
    returned sequence (pins the combined-score/top-k/reindex
    bookkeeping inside the on-device beam step)."""
    rs = np.random.RandomState(23)
    net = make_net(seed=10)
    t0, new = 4, 7
    prompt = mx.nd.array(rs.randint(0, V, (2, t0)).astype("f"))
    greedy = net.generate(prompt, new, kv_cache=True).asnumpy()
    b1, s1 = net.beam_search(prompt, new, beam=1)
    assert (b1.asnumpy() == greedy).all()
    b3, s3 = net.beam_search(prompt, new, beam=3)
    # (no width-monotonicity assert: beam search keeps the W best
    # PREFIXES, so a wider beam is not provably >= greedy in score)
    # exact-score pin: rescore the winning sequences teacher-forced
    seq = b3.asnumpy()
    logits = net(b3).asnumpy()
    m = logits.max(-1, keepdims=True)
    lp = logits - m - np.log(np.exp(logits - m).sum(-1, keepdims=True))
    resc = np.array([
        sum(lp[b, t, int(seq[b, t + 1])] for t in range(t0 - 1,
                                                        t0 + new - 1))
        for b in range(seq.shape[0])])
    assert np.allclose(s3.asnumpy(), resc, atol=1e-3), (s3.asnumpy(),
                                                        resc)
    with pytest.raises(ValueError):
        net.beam_search(prompt, new, beam=0)


def test_save_load_roundtrip_with_decode_wrappers(tmp_path):
    """save_params/load_params must round-trip a net whose decode
    wrappers were already built (the wrappers share the net's
    parameters — building them must not add/rename anything), and the
    reloaded net must decode identically."""
    rs = np.random.RandomState(19)
    net = make_net(seed=8)
    prefix = mx.nd.array(rs.randint(0, V, (1, 4)).astype("f"))
    out1 = net.generate(prefix, 6, kv_cache=True).asnumpy()
    _ = net.generate(prefix, 2)               # static wrappers built too
    path = str(tmp_path / "lm.params")
    net.save_params(path)
    net2 = make_net(seed=9)                   # different init
    net2.load_params(path)
    out2 = net2.generate(prefix, 6, kv_cache=True).asnumpy()
    assert (out1 == out2).all(), (out1, out2)


def test_sequence_parallel_attn_types():
    """impl='ring'/'ulysses' as FIRST-CLASS attn types (SURVEY §5:
    sequence parallelism exposed through the same Gluon APIs): under
    parallel.sp_scope(mesh) the same TransformerLM forward runs the
    sharded kernels and matches the dense variant; without the scope it
    raises the documented error."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.base import MXNetError

    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("sp",))

    # op-level parity first (T divisible by the axis; H % n == 0 for
    # ulysses)
    rs = np.random.RandomState(0)
    qkv = nd.array(rs.normal(0, 1, (2, 16, 3 * 32)).astype("f"))
    ref = nd._contrib_multihead_attention(qkv, num_heads=4,
                                          impl="dense").asnumpy()
    for impl in ("ring", "ulysses"):
        with parallel.sp_scope(mesh):
            got = nd._contrib_multihead_attention(
                qkv, num_heads=4, impl=impl).asnumpy()
        assert_almost_equal(got, ref, rtol=1e-4, atol=1e-5,
                            names=(impl, "dense"))

    # scope required, loudly
    with pytest.raises(MXNetError):
        nd._contrib_multihead_attention(qkv, num_heads=4, impl="ring")

    # model-level: same params, dense vs ring forward agree
    dense_net = make_net("dense", seed=5)
    x = mx.nd.array(rs.randint(0, V, (B, T)).astype("f"))
    ref_out = dense_net(x).asnumpy()
    ring_net = make_net("ring", seed=5)  # same seed -> same init
    # T=12 does not divide 4 -> pad path must be handled by the caller;
    # use a divisible length for the sharded run
    x16 = mx.nd.array(rs.randint(0, V, (B, 16)).astype("f"))
    ref16 = dense_net(x16).asnumpy()
    with parallel.sp_scope(mesh):
        got16 = ring_net(x16).asnumpy()
    assert_almost_equal(got16, ref16, rtol=1e-4, atol=1e-5,
                        names=("ring-lm", "dense-lm"))
    assert ref_out.shape == (B, T, V)


def test_sequence_parallel_training_step():
    """The review-found gap: eager autograd THROUGH a ring-attention
    model (make_vjp places primals on the sp mesh and round-trips
    outputs/cotangents/grads).  One training step must run, produce
    finite grads matching the dense net's, and a custom scale must
    plumb through to the sharded kernels."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.test_utils import assert_almost_equal

    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("sp",))
    rs = np.random.RandomState(2)
    x = mx.nd.array(rs.randint(0, V, (B, 16)).astype("f"))
    y = mx.nd.array(rs.randint(0, V, (B, 16)).astype("f"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def step(net, scoped):
        with autograd.record():
            out = net(x)
            loss = loss_fn(out.reshape((-1, V)), y.reshape((-1,)))
        loss.backward()
        grads = {k: p.grad().asnumpy()
                 for k, p in net.collect_params().items()}
        return float(loss.mean().asnumpy()), grads

    # gluon params initialize lazily at first forward: seed -> build ->
    # STEP for each net, so both first-draws start from the same state
    dense_net = make_net("dense", seed=9)
    l_ref, g_ref = step(dense_net, False)
    ring_net = make_net("ring", seed=9)
    with parallel.sp_scope(mesh):
        l_ring, g_ring = step(ring_net, True)
    assert abs(l_ring - l_ref) < 1e-4, (l_ring, l_ref)
    assert set(g_ring) == {k.replace("transformerlm1", "transformerlm0")
                           for k in g_ref} or len(g_ring) == len(g_ref)
    # param names differ only by the auto prefix counter; compare sorted
    for (ka, ga), (kb, gb) in zip(sorted(g_ring.items()),
                                  sorted(g_ref.items())):
        assert_almost_equal(ga, gb, rtol=1e-3, atol=1e-5,
                            names=(f"ring:{ka}", f"dense:{kb}"))

    # custom scale is honored by the sharded kernels
    qkv = nd.array(rs.normal(0, 1, (2, 16, 3 * 32)).astype("f"))
    ref = nd._contrib_multihead_attention(qkv, num_heads=4, impl="dense",
                                          scale=0.125).asnumpy()
    with parallel.sp_scope(mesh):
        got = nd._contrib_multihead_attention(
            qkv, num_heads=4, impl="ring", scale=0.125).asnumpy()
    assert_almost_equal(got, ref, rtol=1e-4, atol=1e-5,
                        names=("ring-scale", "dense-scale"))


def test_generate_top_k_top_p():
    """top_k=1 sampling must equal greedy on every strategy; nucleus
    filtering keeps tokens in-vocab and respects the prefix; the
    filtered distribution is renormalized (tiny top_p ~ greedy)."""
    rs = np.random.RandomState(37)
    net = make_net(seed=12)
    prefix = mx.nd.array(rs.randint(0, V, (2, 4)).astype("f"))
    greedy = net.generate(prefix, 6, kv_cache=True).asnumpy()
    for kw in ({"static_shapes": True}, {"static_shapes": False},
               {"kv_cache": True}):
        topk1 = net.generate(prefix, 6, temperature=1.0, top_k=1,
                             rng=np.random.RandomState(3), **kw).asnumpy()
        assert (topk1 == greedy).all(), (kw, topk1, greedy)
    tiny_p = net.generate(prefix, 6, temperature=1.0, top_p=1e-9,
                          rng=np.random.RandomState(4),
                          kv_cache=True).asnumpy()
    assert (tiny_p == greedy).all()
    out = net.generate(prefix, 6, temperature=1.2, top_k=5, top_p=0.9,
                       rng=np.random.RandomState(5),
                       kv_cache=True).asnumpy()
    assert out.shape == (2, 10)
    assert (out[:, :4] == prefix.asnumpy()).all()
    assert ((out >= 0) & (out < V)).all()


def test_ring_kv_decode_op_matches_dense():
    """impl='ring' mha_decode_step (sequence-sharded caches, distributed
    softmax via pmax/psum) must reproduce the dense decode step at every
    position when fed a sequence token-by-token on a CPU mesh."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import nd, parallel

    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("sp",))
    rs = np.random.RandomState(29)
    Bq, H, Tmax, D = 2, 4, 8, 32        # Tmax divisible by the axis
    dh = D // H
    qkv_seq = nd.array(rs.normal(0, 1, (Bq, Tmax, 3 * D)).astype("f"))
    kc_d = nd.zeros((Bq, H, Tmax, dh))
    vc_d = nd.zeros((Bq, H, Tmax, dh))
    kc_r = nd.zeros((Bq, H, Tmax, dh))
    vc_r = nd.zeros((Bq, H, Tmax, dh))
    for t in range(Tmax):
        step_qkv = nd.slice_axis(qkv_seq, axis=1, begin=t, end=t + 1)
        pos = nd.array([float(t)])
        od, kc_d, vc_d = nd.mha_decode_step(step_qkv, kc_d, vc_d, pos,
                                            num_heads=H)
        with parallel.sp_scope(mesh):
            orr, kc_r, vc_r = nd.mha_decode_step(step_qkv, kc_r, vc_r,
                                                 pos, num_heads=H,
                                                 impl="ring")
        assert_almost_equal(orr.asnumpy(), od.asnumpy(),
                            rtol=1e-4, atol=1e-5)
    assert_almost_equal(kc_r.asnumpy(), kc_d.asnumpy(), rtol=1e-5,
                        atol=1e-6)
    assert_almost_equal(vc_r.asnumpy(), vc_d.asnumpy(), rtol=1e-5,
                        atol=1e-6)


def test_ring_kv_decode_generate():
    """A ring-attention TransformerLM decodes with kv_cache=True under
    an sp_scope — sequence-sharded caches end to end — and emits the
    same greedy tokens as an identically-initialized dense model's KV
    decode (max_len divisible by the mesh axis)."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import parallel

    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("sp",))
    dense = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                          max_len=16, attn_type="dense")
    ring = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                         max_len=16, attn_type="ring")
    mx.random.seed(31)
    dense.initialize(mx.init.Xavier(), ctx=mx.cpu())
    ring.initialize(mx.init.Xavier(), ctx=mx.cpu())
    with parallel.sp_scope(mesh):      # ring's probe forward needs it
        copy_params(ring, dense)
    rs = np.random.RandomState(33)
    prompt = mx.nd.array(rs.randint(0, V, (2, 4)).astype("f"))
    want = dense.generate(prompt, 8, kv_cache=True).asnumpy()
    with parallel.sp_scope(mesh):
        got = ring.generate(prompt, 8, kv_cache=True).asnumpy()
    assert (got == want).all(), (got, want)
    # max_len not divisible by the axis -> loud error
    bad = TransformerLM(vocab=V, dim=32, num_layers=1, num_heads=4,
                        max_len=15, attn_type="ring")
    bad.initialize(mx.init.Xavier(), ctx=mx.cpu())
    with parallel.sp_scope(mesh), pytest.raises(ValueError):
        bad.generate(prompt, 2, kv_cache=True)


@pytest.mark.parametrize("attn_type", ["ring", "ulysses"])
def test_sp_kv_decode_loads_each_program_once(attn_type, caplog):
    """The sequence-parallel KV decode builds its programs once per
    shape: the first generate() under an sp_scope compiles no signature
    twice, and a second one of the same shapes loads no program at all
    (a bare shard_map bound eagerly compiled its body primitive by
    primitive at every call and every position: 2,383 compiles of 105
    signatures for 11 decode steps)."""
    import logging
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import parallel
    from mxnet_tpu.observability import metrics

    mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("sp",))
    # shapes no other test of the process uses: a program another test
    # already compiled would not be compiled, or seen, here
    net = TransformerLM(vocab=V + 2, dim=16, num_layers=2, num_heads=4,
                        max_len=24, attn_type=attn_type)
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    prompt = mx.nd.array(
        np.random.RandomState(5).randint(0, V, (3, 4)).astype("f"))
    with parallel.sp_scope(mesh):
        net(mx.nd.zeros((1, 4)))       # deferred shapes, off the count
        with caplog.at_level(logging.DEBUG,
                             logger="jax._src.interpreters.pxla"):
            first = net.generate(prompt, 6, kv_cache=True).asnumpy()
        compiled = [r.getMessage().split(". Argument mapping")[0]
                    for r in caplog.records
                    if r.getMessage().startswith("Compiling ")]
        assert compiled, "no compile was logged: the probe is blind"
        twice = sorted({c for c in compiled if compiled.count(c) > 1})
        assert not twice, twice
        loads = metrics.PROGRAM_LOADS.value
        again = net.generate(prompt, 6, kv_cache=True).asnumpy()
    assert metrics.PROGRAM_LOADS.value == loads
    assert (again == first).all()


def test_sample_top_k_ties_and_validation():
    """top_k keeps exactly k survivors under ties (top_k=1 == argmax
    even with duplicated maxima); invalid top_k/top_p raise."""
    tied = mx.nd.array(np.array([[3.0, 3.0, 1.0, 0.0]], "f"))
    for _ in range(5):
        nxt = TransformerLM._sample(tied, 1.0, np.random.RandomState(0),
                                    top_k=1)
        assert nxt[0, 0] == 0.0          # first-occurrence max, = argmax
    with pytest.raises(ValueError):
        TransformerLM._sample(tied, 1.0, None, top_k=-1)
    with pytest.raises(ValueError):
        TransformerLM._sample(tied, 1.0, None, top_p=1.5)


def test_ulysses_kv_decode_matches_dense():
    """impl='ulysses' mha_decode_step (HEAD-sharded full-length caches,
    purely local attention per head shard) must match the dense decode
    step token-by-token, and a ulysses TransformerLM must generate
    kv_cache=True under an sp_scope with the same greedy tokens as an
    identically-initialized dense model."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import nd, parallel

    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("sp",))
    rs = np.random.RandomState(43)
    Bq, H, Tmax, D = 2, 4, 8, 32          # H divisible by the axis
    dh = D // H
    qkv_seq = nd.array(rs.normal(0, 1, (Bq, Tmax, 3 * D)).astype("f"))
    kc_d = nd.zeros((Bq, H, Tmax, dh))
    vc_d = nd.zeros((Bq, H, Tmax, dh))
    kc_u = nd.zeros((Bq, H, Tmax, dh))
    vc_u = nd.zeros((Bq, H, Tmax, dh))
    for t in range(Tmax):
        step_qkv = nd.slice_axis(qkv_seq, axis=1, begin=t, end=t + 1)
        pos = nd.array([float(t)])
        od, kc_d, vc_d = nd.mha_decode_step(step_qkv, kc_d, vc_d, pos,
                                            num_heads=H)
        with parallel.sp_scope(mesh):
            ou, kc_u, vc_u = nd.mha_decode_step(step_qkv, kc_u, vc_u,
                                                pos, num_heads=H,
                                                impl="ulysses")
        assert_almost_equal(ou.asnumpy(), od.asnumpy(),
                            rtol=1e-4, atol=1e-5)
    assert_almost_equal(kc_u.asnumpy(), kc_d.asnumpy(), rtol=1e-5,
                        atol=1e-6)
    assert_almost_equal(vc_u.asnumpy(), vc_d.asnumpy(), rtol=1e-5,
                        atol=1e-6)

    # model level
    dense = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                          max_len=16, attn_type="dense")
    uly = TransformerLM(vocab=V, dim=32, num_layers=2, num_heads=4,
                        max_len=16, attn_type="ulysses")
    mx.random.seed(47)
    dense.initialize(mx.init.Xavier(), ctx=mx.cpu())
    uly.initialize(mx.init.Xavier(), ctx=mx.cpu())
    with parallel.sp_scope(mesh):
        copy_params(uly, dense)
    rs2 = np.random.RandomState(49)
    prompt = mx.nd.array(rs2.randint(0, V, (2, 4)).astype("f"))
    want = dense.generate(prompt, 8, kv_cache=True).asnumpy()
    with parallel.sp_scope(mesh):
        got = uly.generate(prompt, 8, kv_cache=True).asnumpy()
    assert (got == want).all(), (got, want)
    # heads not divisible by the axis -> loud error (3 heads, 4 devs)
    bad = TransformerLM(vocab=V, dim=33, num_layers=1, num_heads=3,
                        max_len=16, attn_type="ulysses")
    bad.initialize(mx.init.Xavier(), ctx=mx.cpu())
    with parallel.sp_scope(mesh), pytest.raises(ValueError):
        bad.generate(prompt, 2, kv_cache=True)


def test_sp_backward_after_scope_exit():
    """backward() issued AFTER the sp_scope exited must still work: the
    cached sp fwd/bwd jits re-enter their KEYED scope around every
    call, so lazy (re)traces never read the wrong ambient scope."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import nd, parallel

    mesh = Mesh(np.array(jax.devices("cpu")[:4]), ("sp",))
    rs = np.random.RandomState(53)
    qkv = mx.nd.array(rs.normal(0, 1, (2, 16, 96)).astype("f"))
    qkv.attach_grad()
    with parallel.sp_scope(mesh):
        with autograd.record():
            out = nd._contrib_multihead_attention(qkv, num_heads=4,
                                                  impl="ring")
            loss = out.sum()
    loss.backward()                      # scope no longer active
    assert np.isfinite(qkv.grad.asnumpy()).all()


def test_beam_and_export_refuse_sp_models():
    """Beam search and the decode-step export are dense-cache paths:
    on sp-attention models they refuse loudly (allow_sp=False) even
    under an active scope."""
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import parallel

    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("sp",))
    net = make_net("ring", seed=14)
    prompt = mx.nd.array(np.zeros((1, 3), "f"))
    with parallel.sp_scope(mesh):
        with pytest.raises(NotImplementedError):
            net.beam_search(prompt, 2, beam=2)
        with pytest.raises(NotImplementedError):
            net.export_decode_step("/tmp/should_not_exist")
