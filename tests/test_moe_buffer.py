"""The expert buffer only as long as the rows the held experts got (ISSUE
36, ops/decoder.py `buffer_rungs`, `_expert_rows`,
`_run_again_in_backward`): where a layer runs its grouped products again in
the backward pass, the op picks on the device the shortest rung of a ladder
fixed by shapes that holds the live rows.  Against the whole buffer of
`T * top_k` rows, on the CPU, at a small layer whose ladder has two
rungs: outputs and every gradient on each rung and at each rung's edges,
none live, all live, the counter's buffer slot, a recorded CachedOp call,
the names the compiled branches keep, and the reader of
`expert_rows_processed_over_live`.  The TPU's half is
tests_tpu/test_moe_buffer.py."""
import os
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import autograd, nd
from mxnet_tpu.gluon.model_zoo import decoder
from mxnet_tpu.observability import introspect, metrics
from mxnet_tpu.ops import decoder as ops

from chipbench import cell as cellmod

# a layer of the grouped-query cell's counts (64 experts, 8 held, 6 a
# token) at 1,024 tokens: the ladder is 1,536 / 6,144 rows
T, D, DR, F, E, HELD, K = 1024, 32, 16, 16, 64, 8, 6
RUNGS = (1536, 6144)
P = dict(num_experts=E, top_k=K, first=0, held=HELD, scale=1.0,
         norm_topk=True, router="softmax_topk", activation="relu")


def _close(a, b, rtol=1e-5, atol=1e-6):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                               atol=atol)


def test_the_ladder_is_fixed_by_shapes():
    assert ops.buffer_rungs(T * K, HELD, E) == RUNGS
    # the grouped-query cell's layer: 16,384 tokens, 6 a token; the first
    # rung is twice the 12,288 rows of even routing
    assert ops.buffer_rungs(16384 * 6, 8, 64) == (24576, 98304)
    # every expert held: the even share is the whole buffer
    assert ops.buffer_rungs(98304, 64, 64) == (98304,)
    # under a tile of the grouped product every rung is the buffer
    assert ops.buffer_rungs(288, 4, 8) == (288,)
    # a rung is whole tiles, the last the whole buffer
    for rows in (6000, 6144, 98304, 100000):
        rungs = ops.buffer_rungs(rows, 8, 64)
        assert rungs[-1] == rows
        assert all(c % ops.GROUPED_TILE_ROWS == 0 for c in rungs[:-1])


def _inputs(all_held, one_held, seed=0):
    """Routed so that exactly `6 * all_held + one_held` pairs are live: the
    first `all_held` tokens choose held experts 0-5, the next `one_held`
    choose held expert 6 and five absent ones, the rest only absent ones.
    The selection bias puts every absent expert above a held one a token's
    router input does not point at."""
    rs = np.random.RandomState(seed)
    by = rs.normal(0, 0.1, (T, DR)).astype("f")
    by[:, :2] = 0
    by[:all_held, 0] = 1
    by[all_held:all_held + one_held, 1] = 1
    router = rs.normal(0, 0.1, (E, DR)).astype("f")
    router[:, :2] = 0
    router[:6, 0] = 20
    router[6, 1] = 20
    bias = np.zeros(E, "f")
    bias[HELD:] = 5
    return dict(
        h=rs.normal(0, 1, (T, D)).astype("f"), by=by, router=router,
        bias=bias, gate=rs.normal(0, 0.2, (HELD, D, F)).astype("f"),
        up=rs.normal(0, 0.2, (HELD, D, F)).astype("f"),
        down=rs.normal(0, 0.2, (HELD, F, D)).astype("f"),
        r=rs.normal(0, 1, (T, D)).astype("f"))


TRAINED = ("h", "by", "router", "gate", "up", "down")


def _loss_and_grads(a):
    """(loss, gradients of TRAINED, the load counter after one call),
    the op as the recorded programs see it."""
    def loss(h, by, router, gate, up, down):
        y, _bias, load = ops._moe_ffn_routed_by(
            P, h, by, router, jnp.asarray(a["bias"]), gate, up, down,
            jnp.zeros(HELD + 2, jnp.float32))
        return jnp.sum(y * a["r"]), load
    vals = [jnp.asarray(a[k]) for k in TRAINED]
    (value, load), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*vals)
    return value, grads, np.asarray(load)


@pytest.mark.parametrize("all_held, one_held, rung", [
    (0, 0, 1536),       # no pair live: the first rung, all of it masked
    (128, 0, 1536),     # n 768, inside the first rung
    (256, 0, 1536),     # n = C
    (256, 1, 6144),     # n = C + 1
    (333, 2, 6144),     # n 2,000
    (512, 0, 6144),     # n 3,072
    (512, 1, 6144),     # n 3,073
    (833, 2, 6144),     # n 5,000
    (T, 0, 6144),       # every pair live: the last rung, dropless
], ids=["n0", "n768", "n1536", "n1537", "n2000", "n3072", "n3073", "n5000",
        "all_live"])
def test_each_rung_equals_the_whole_buffer(monkeypatch, all_held, one_held,
                                           rung):
    a = _inputs(all_held, one_held)
    full = _loss_and_grads(a)        # under the bound: the whole buffer
    monkeypatch.setattr(ops, "KEEP_BYTES_MAX", 0)
    cut = _loss_and_grads(a)
    live = 6 * all_held + one_held
    # the routing is what the inputs steer it to, on both paths
    for _v, _g, load in (full, cut):
        assert load[:HELD].sum() == live
        assert load[HELD] == T * K - live
    assert full[2][-1] == T * K
    assert cut[2][-1] == rung        # the counter adds the rung's length
    _close(cut[0], full[0], rtol=1e-5)
    for name, g, want in zip(TRAINED, cut[1], full[1]):
        scale = float(jnp.max(jnp.abs(want)))
        if not live:  # no held expert answers anybody: y is 0
            assert scale == 0 and float(jnp.max(jnp.abs(g))) == 0, name
            continue
        assert scale > 0, name
        _close(g, want, rtol=1e-4, atol=1e-5 * scale)


def _lowered_text(tokens, d, f, held, e, k, dr=None):
    """StableHLO of one layer's forward and backward at full size, from
    shapes alone (nothing compiles)."""
    p = dict(num_experts=e, top_k=k, first=0, held=held, scale=1.0,
             norm_topk=True, router="softmax_topk", activation="relu")
    spec = jax.ShapeDtypeStruct

    def loss(h, by, router, gate, up, down, bias, load):
        return jnp.sum(ops._moe_ffn_routed_by(
            p, h, by, router, bias, gate, up, down, load)[0]
            .astype(jnp.float32))
    bf = jnp.bfloat16
    args = (spec((tokens, d), bf), spec((tokens, dr or d), bf),
            spec((e, dr or d), bf), spec((held, d, f), bf),
            spec((held, d, f), bf), spec((held, f, d), bf),
            spec((e,), jnp.float32), spec((held + 2,), jnp.float32))
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)))).lower(
        *args).as_text()


def test_the_conditional_is_only_where_the_products_are_run_again():
    # the glm cell's layer (4,096 tokens, 4 a token, F 1,536, D 2,048)
    # keeps its products: no conditional, one buffer of T x top_k rows
    keep = _lowered_text(4096, 2048, 1536, 8, 64, 4)
    assert "stablehlo.case" not in keep
    assert "16384x2048" in keep
    # the grouped-query cell's layer runs them again: one conditional of
    # two rungs in the forward pass and one in the backward pass, and no
    # tensor of T x top_k x D
    again = _lowered_text(16384, 2560, 768, 8, 64, 6)
    assert again.count("stablehlo.case") == 2
    assert "49152x2560" not in again
    for c in (24576, 98304):
        assert f"{c}x2560" in again
    assert "16384x6x2560" not in again


def test_a_recorded_call_names_what_its_branches_run(monkeypatch):
    """Through a hybridized block under `autograd.record`: the gradient is
    the whole buffer's, no tracer escapes the conditional, and the compiled
    programs' branch instructions carry the block's node and their pass."""
    prev = (introspect.ENABLED, introspect.HLO)
    introspect.reset()
    introspect.enable()
    introspect.configure(hlo=False)
    try:
        a = _inputs(200, 3)          # n 1,203: the first rung
        x = nd.array(a["h"].reshape(2, T // 2, D))
        by = nd.array(a["by"].reshape(2, T // 2, DR))
        grads = []
        for bound in (ops.KEEP_BYTES_MAX, 0):
            monkeypatch.setattr(ops, "KEEP_BYTES_MAX", bound)
            blk = decoder.MoEFeedForward(
                D, F, E, K, held_experts=HELD, shared_experts=0,
                router="softmax_topk", activation="relu", prefix="moe_")
            blk.initialize()
            for name, key in (("router_weight", "router"),
                              ("gate_weight", "gate"), ("up_weight", "up"),
                              ("down_weight", "down"),
                              ("select_bias", "bias")):
                getattr(blk, name).set_data(nd.array(a[key]))
            blk.hybridize()
            x.attach_grad()
            with jax.checking_leaks(), autograd.record():
                y = blk(x, by)
                loss = (y * nd.array(a["r"].reshape(2, T // 2, D))).sum()
            loss.backward()
            grads.append(x.grad.asnumpy().copy())
            assert blk.load.data().asnumpy()[-1] == (T * K if bound else 1536)
        _close(grads[1], grads[0], rtol=1e-4, atol=1e-6)
        names = introspect.op_scopes("jit_mx_cachedop_bwd")[-1]
        moe = {r["pass"] for r in names.values()
               if r["node"] == "moe_moe_ffn_routed_by0"}
        assert {"recompute", "bwd"} <= moe, moe
        # nothing of the op falls outside its node: every ragged product
        # and every scatter, in whatever branch, is the block's
        for r in names.values():
            if r["opcode"] in ("scatter", "custom-call") or \
                    "ragged" in (r["scope"] or ""):
                assert r["node"] in ("moe_moe_ffn_routed_by0",
                                     introspect.UNATTRIBUTED), r
        named = [r for r in names.values()
                 if r["node"] == "moe_moe_ffn_routed_by0"
                 and "branch" in (r["scope"] or "")]
        assert named, "no instruction of a branch is named by its node"
        del blk
    finally:
        introspect.reset()
        (introspect.enable if prev[0] else introspect.disable)()
        introspect.configure(hlo=prev[1])


def _reader():
    return cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics",
                     "expert_rows_processed_over_live.py"),
        "test_reader_expert_rows_processed_over_live")


def test_processed_over_live_reader(monkeypatch):
    import gc
    rd = _reader()
    monkeypatch.setattr(metrics, "_moe_layers", weakref.WeakKeyDictionary())
    metrics.MOE_BUFFER_ROWS.reset()
    blk = decoder.MoEFeedForward(16, 8, 8, 2, held_experts=4)
    blk.initialize()
    blk.load.set_data(nd.array(np.array([10., 30., 10., 10., 99., 240.],
                                        "f")))
    assert rd.read({}) == pytest.approx(4.0)
    # a second read counts only what came since: the ratio stands
    assert rd.read({}) == pytest.approx(4.0)
    blk.load.set_data(nd.array(np.array([20., 60., 20., 20., 99., 360.],
                                        "f")))
    assert rd.read({}) == pytest.approx(360 / 120)
    del blk
    gc.collect()
    metrics.MOE_BUFFER_ROWS.reset()
    assert rd.read({}) is None  # no expert layer alive
    monkeypatch.delattr(metrics, "MOE_BUFFER_ROWS")
    assert rd.read({}) is None  # a program without the counter


def test_a_load_counter_without_the_buffer_slot_is_refused():
    a = _inputs(8, 0)
    with pytest.raises(ValueError, match=r"load must be \(10,\)"):
        ops._moe_ffn_routed_by(
            P, *(jnp.asarray(a[k]) for k in ("h", "by", "router", "bias",
                                              "gate", "up", "down")),
            jnp.zeros(HELD + 1, jnp.float32))
