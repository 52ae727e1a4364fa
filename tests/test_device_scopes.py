"""Device time gets the model's names (ISSUE 35): `introspect.op_scopes`
names every instruction of a compiled program by graph node, registered
operator and pass, and `chipbench/scope_reduce.py` joins those names to the
events of a device trace.

(a) the three step paths' programs on pinned nets (a hybridized Gluon net
    with a recorded backward and a `Trainer.step`, `Module`, a
    `contrib.foreach` body);
(b) `scope_reduce` on synthetic events whose answers are known from how
    they were made;
(c) with `MXNET_INTROSPECT=0` the three readers find nothing;
(d) an untraced step path calls neither `compile()` nor `as_text()`.
"""
import os

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import introspect

from chipbench import cell as cellmod
from chipbench import scope_reduce as sc

UN = introspect.UNATTRIBUTED
#: opcodes that run nothing: no trace event is named after them
SILENT = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


@pytest.fixture(autouse=True)
def _clean():
    prev = (introspect.ENABLED, introspect.HLO)
    introspect.reset()
    introspect.enable()
    introspect.configure(hlo=False)
    yield
    introspect.reset()
    (introspect.enable if prev[0] else introspect.disable)()
    introspect.configure(hlo=prev[1])


# -- (a) the programs name their own instructions -----------------------------

def _gluon_step(steps=2):
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu", in_units=8),
                nn.Dense(16, in_units=32), nn.Dense(8, in_units=16))
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(4, 8).astype("f"))
    y = nd.array(rs.randn(4, 8).astype("f"))
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(4)
    return net, trainer


def _only(jit_name):
    maps = introspect.op_scopes(jit_name)
    assert maps is not None and len(maps) == 1, (jit_name, maps)
    return maps[0]


def _scoped(names):
    return {n: r for n, r in names.items() if r["node"] != UN}


GLUON_NODES = {"net_dense0_fwd": "FullyConnected",
               "net_dense0_relu_fwd": "Activation",
               "net_dense1_fwd": "FullyConnected",
               "net_dense2_fwd": "FullyConnected"}


@pytest.mark.parametrize("jit_name,passes,nodes", [
    ("jit_mx_cachedop_fwd", {"fwd"}, GLUON_NODES),
    ("jit_mx_cachedop_bwd", {"bwd", "recompute"}, GLUON_NODES),
    ("jit_mx_fused_update", {"update"}, {"optimizer": "optimizer"}),
])
def test_gluon_programs_name_node_operator_and_pass(jit_name, passes, nodes):
    _held = _gluon_step()     # a program lives as long as its owner
    names = _only(jit_name)
    scoped = _scoped(names)
    assert {r["node"]: r["op_type"] for r in scoped.values()} == nodes
    assert {r["pass"] for r in scoped.values()} <= passes
    if jit_name == "jit_mx_cachedop_bwd":
        # both halves of the backward program are there: the transposed
        # products and the element-wise forward run again
        assert {r["pass"] for r in scoped.values()} == passes
    for r in scoped.values():
        assert r["scope"].startswith("jit(" + jit_name[4:] + ")/"), r
    # what carries no scope carries no pass either: the reader gives it
    # the program's
    assert all(r["pass"] is None for r in names.values()
               if r["node"] == UN and "transpose(" not in (r["scope"] or ""))


def _module_fit():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="conv0")
    net = mx.sym.BatchNorm(net, name="bn0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.Pooling(net, global_pool=True, pool_type="avg",
                         kernel=(1, 1), name="pool0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net, name="flat0"),
                                num_hidden=10, name="fc0")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.randn(16, 3, 8, 8).astype("f"),
                           rs.randint(0, 10, (16,)).astype("f"),
                           batch_size=8)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    return mod


def test_module_program_holds_both_passes():
    _held = _module_fit()
    scoped = _scoped(_only("jit_mx_executor_fwd_bwd"))
    types = {r["node"]: r["op_type"] for r in scoped.values()}
    types.pop("flat0", None)      # a reshape: it may leave no instruction
    assert types == {"conv0": "Convolution", "bn0": "BatchNorm",
                     "relu0": "Activation", "pool0": "Pooling",
                     "fc0": "FullyConnected", "softmax": "SoftmaxOutput"}
    by_node = {}
    for r in scoped.values():
        by_node.setdefault(r["node"], set()).add(r["pass"])
    # one program, both passes, and only the scopes tell them apart (a
    # fusion named by what was fused into it says no pass: None)
    for node in ("conv0", "bn0", "fc0"):
        assert by_node[node] - {None} == {"fwd", "bwd"}, by_node
    assert all(r["by"] == "inner" for r in scoped.values()
               if r["pass"] is None)
    assert {r["pass"] for r in _scoped(
        _only("jit_mx_fused_update")).values()} == {"update"}


class _Loop(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.d1 = nn.Dense(16, flatten=False, in_units=8, prefix="d1_")
            self.d2 = nn.Dense(8, flatten=False, in_units=16, prefix="d2_")

    def hybrid_forward(self, F, x):
        def body(_step, states):
            h = states[0]
            h = h + self.d2(F.Activation(self.d1(h), act_type="tanh"))
            return [F.sum(h, axis=-1)], [h]
        outs, states = F.contrib.foreach(body, F.arange(0, 3), [x])
        return outs[0], states[0]


def test_instructions_inside_a_loop_body_are_in_the_map():
    net = _Loop(prefix="loop_")
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.RandomState(1).randn(2, 5, 8).astype("f"))
    with autograd.record():
        sums, last = net(x)
        loss = (sums * sums).sum() + last.sum()
    loss.backward()
    for jit_name, passes in (("jit_mx_cachedop_fwd", {"fwd"}),
                             ("jit_mx_cachedop_bwd", {"bwd", "recompute"})):
        names = _only(jit_name)
        whiles = [n for n, r in names.items() if r["opcode"] == "while"]
        assert whiles, sorted({r["opcode"] for r in names.values()})
        # the loop's node carries the loop, the body's nodes what runs in
        # it: the products of d1 and d2 are instructions of the `while`
        # body's computation, not of the entry
        assert all(names[w]["op_type"] == "_foreach" for w in whiles)
        inside = {r["node"]: r for r in names.values()
                  if r["scope"] and "/while/body/" in r["scope"]
                  and r["node"] != UN}
        assert {"loop_d1_fwd", "loop_d2_fwd"} <= set(inside), sorted(inside)
        assert inside["loop_d1_fwd"]["op_type"] == "FullyConnected"
        assert {r["pass"] for r in inside.values()} <= passes


def test_two_cachedops_under_one_program_name_keep_their_own_records():
    net, _trainer = _gluon_step(steps=1)
    other = nn.HybridSequential(prefix="other_")
    with other.name_scope():
        other.add(nn.Dense(4, in_units=8))
    other.initialize()
    other.hybridize()
    other(nd.ones((2, 8)))
    maps = introspect.op_scopes("jit_mx_cachedop_fwd")
    assert len(maps) == 2
    nodes = [{r["node"] for r in m.values()} - {UN} for m in maps]
    assert nodes[0] == set(GLUON_NODES) and nodes[1] == {"other_dense0_fwd"}
    # a program that is freed leaves the registry with its function
    del net, other
    import gc
    gc.collect()
    assert introspect.op_scopes("jit_mx_cachedop_fwd") is None


def test_parse_walks_every_computation_and_names_fusions_by_their_root():
    text = "\n".join([
        "HloModule jit_f, is_scheduled=true",
        "",
        "%fused_computation (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  %inside = f32[8]{0} add(%p, %p), metadata={op_name=\"x\"}",
        "  ROOT %m = f32[8]{0} multiply(%inside, %p), metadata={op_name="
        "\"jit(f)/jit(main)/transpose(jvp(fc1))/mul\"}",
        "}",
        "",
        "%region_0.1 (a: f32[], b: f32[]) -> f32[] {",
        "  %a = f32[] parameter(0)",
        "  %b = f32[] parameter(1)",
        "  ROOT %sum = f32[] add(%a, %b)",
        "}",
        "",
        "%body (s: (s32[], f32[8])) -> (s32[], f32[8]) {",
        "  %s = (s32[], f32[8]{0}) parameter(0)",
        "  %g = f32[8]{0} get-tuple-element(%s), index=1",
        "  %fusion.5 = f32[8]{0} fusion(%g), kind=kLoop, "
        "calls=%fused_computation",
        "  %r = f32[] reduce(%fusion.5, %g), dimensions={0}, "
        "to_apply=%region_0.1, metadata={op_name=\"jit(f)/jit(main)/"
        "checkpoint/rematted_computation/fc1/reduce_sum\"}",
        "  ROOT %t = (s32[], f32[8]{0}) tuple(%g, %fusion.5)",
        "}",
        "",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        "  %x = f32[8]{0} parameter(0)",
        "  %while.1 = (s32[], f32[8]{0}) while(%x), condition=%cond, "
        "body=%body, metadata={op_name=\"jit(f)/jit(main)/loop0/while\"}",
        "  %copy.3 = f32[8]{0} copy(%x)",
        "  ROOT %u = f32[8]{0:T(8,128)S(1)} add(%x, %x), "
        "metadata={op_name=\"jit(f)/jit(main)/optimizer/add\"}",
        "}"])
    names = introspect.parse_op_scopes(
        text, {"fc1": "FullyConnected", "loop0": "_foreach",
               "optimizer": None})
    # fused and reduction computations give nothing but the fusion itself
    assert set(names) == {"s", "g", "fusion.5", "r", "t", "x", "while.1",
                          "copy.3", "u"}
    f5 = names["fusion.5"]     # no metadata of its own: its root's
    assert (f5["node"], f5["op_type"], f5["pass"], f5["opcode"]) == \
        ("fc1", "FullyConnected", "bwd", "fusion")
    assert (names["r"]["node"], names["r"]["pass"]) == ("fc1", "recompute")
    assert (names["while.1"]["op_type"], names["while.1"]["pass"]) == \
        ("_foreach", "fwd")
    assert (names["u"]["node"], names["u"]["op_type"], names["u"]["pass"]) \
        == ("optimizer", "optimizer", "update")
    assert names["copy.3"] == {"node": UN, "op_type": None, "pass": None,
                               "scope": None, "opcode": "copy", "by": None}
    assert (f5["by"], names["r"]["by"]) == ("root", "self")
    # what XLA made without any path is named after its reader (the loop's
    # element is read by the fc1 fusion), else after what it reads (the
    # tuple of the body's results); a copy of a parameter that nothing
    # reads keeps no name
    assert (names["g"]["node"], names["g"]["by"], names["g"]["opcode"]) == \
        ("fc1", "user", "get-tuple-element")
    assert (names["t"]["node"], names["t"]["by"]) == ("fc1", "operand")
    # a root that XLA left without a path (a convolution it rewrote, a
    # tuple): the commonest path of what was fused into it
    rootless = text.replace(', metadata={op_name="jit(f)/jit(main)/'
                            'transpose(jvp(fc1))/mul"}', "").replace(
        'metadata={op_name="x"}',
        'metadata={op_name="jit(f)/jit(main)/jvp(fc1)/add"}')
    f5 = introspect.parse_op_scopes(rootless, {"fc1": "FullyConnected"})[
        "fusion.5"]
    # the place is named, the pass is not: what was fused into a rewritten
    # product is its INPUT's path (a forward activation read by a backward
    # convolution), so the reader gives it its program's one pass or none
    assert (f5["node"], f5["pass"], f5["by"]) == ("fc1", None, "inner")


def test_the_text_is_never_cut_for_the_map(monkeypatch):
    """`HLO_CAP_BYTES` cuts what a record carries, never the names."""
    introspect.configure(hlo=True, hlo_cap_bytes=64)
    try:
        _held = _gluon_step(steps=1)
        rec = introspect.programs()["gluon:bwd"]
        assert rec["hlo_truncated"] and len(rec["hlo"]) == 64
        names = _only("jit_mx_cachedop_bwd")
        assert {r["node"] for r in names.values()} >= set(GLUON_NODES)
    finally:
        introspect.configure(hlo=False, hlo_cap_bytes=8 << 20)


# -- (b) the join, on events whose answers are known ---------------------------

MS = 1e6


def _rec(node, op_type, pass_, opcode="fusion"):
    return {"node": node, "op_type": op_type, "pass": pass_,
            "scope": None, "opcode": opcode}


def _ev(name, start_ms, end_ms, opcode="fusion"):
    return (f"%{name} = f32[8]{{0}} {opcode}(%p)", start_ms * MS,
            end_ms * MS)


def _ctx(modules, ops, steps=1, planes=1):
    devices = {f"/device:TPU:{i}": {"modules": list(modules),
                                    "ops": list(ops)}
               for i in range(planes)}
    return {"reduced": {"events": {"devices": devices, "host": []},
                        "busy_s": 0.0},
            "window": {"attempted": steps}}


def _analyse(monkeypatch, maps, ctx):
    monkeypatch.setattr(sc, "_op_scopes", lambda: lambda p: maps.get(p))
    return sc.analyse(ctx)


def _row(an, **want):
    keys = ("program", "pass", "op_type", "node")
    hits = [row["ms"] for key, row in an["rows"].items()
            if all(dict(zip(keys, key))[k] == v for k, v in want.items())]
    return sum(hits)


def test_a_while_counts_its_leaves_only_and_the_rows_tile_the_busy_time(
        monkeypatch):
    """A `while` of 10 ms holds three leaves of 2, 3 and 4 ms: the leaves
    count whole, the loop for the 1 ms its body does not cover, and rows
    sum to the union of the intervals (PR 31 counted body and loop)."""
    ops = [_ev("while.1", 0, 10, "while"), _ev("fusion.1", 0.5, 2.5),
           _ev("fusion.2", 3, 6), _ev("fusion.3", 6, 10),
           _ev("copy.9", 11, 12, "copy")]
    maps = {"jit_mx_cachedop_fwd": [{
        "while.1": _rec("loop0", "_foreach", "fwd", "while"),
        "fusion.1": _rec("fc1", "FullyConnected", "fwd"),
        "fusion.2": _rec("fc1", "FullyConnected", "fwd"),
        "fusion.3": _rec("norm1", "rms_norm", "fwd"),
        "copy.9": _rec(UN, None, None, "copy")}]}
    an = _analyse(monkeypatch, maps, _ctx(
        [("jit_mx_cachedop_fwd(7)", 0, 12 * MS)], ops))
    assert _row(an, node="fc1") == pytest.approx(5.0)
    assert _row(an, node="norm1") == pytest.approx(4.0)
    assert _row(an, node="loop0") == pytest.approx(1.0)
    assert an["busy_ms"] == pytest.approx(11.0)      # the union: 10 + 1
    assert an["leaf_ms"] == pytest.approx(10.0)
    assert sum(r["ms"] for r in an["rows"].values()) == \
        pytest.approx(an["busy_ms"])
    # the copy has no scope: it takes the one pass its program holds
    assert _row(an, node=UN, **{"pass": "fwd"}) == pytest.approx(1.0)
    assert sc.pass_ms(an, ("fwd",)) == pytest.approx(11.0)
    assert sc.scoped_share(an) == pytest.approx(100.0 * 10 / 11)
    assert an["unnamed_by_opcode"] == {
        ("jit_mx_cachedop_fwd", "copy"): pytest.approx(1.0)}


def test_two_programs_that_hold_a_fusion_5_are_told_apart_by_their_launch(
        monkeypatch):
    modules = [("jit_mx_cachedop_fwd(1)", 0, 4 * MS),
               ("jit_mx_cachedop_bwd(2)", 5 * MS, 12 * MS),
               ("jit_mx_fused_update(3)", 12 * MS, 14 * MS)]
    ops = [_ev("fusion.5", 0, 4), _ev("fusion.5", 5, 12),
           _ev("fusion.5", 12, 14)]
    maps = {
        "jit_mx_cachedop_fwd": [{"fusion.5": _rec("fc1", "FullyConnected",
                                                  "fwd")}],
        "jit_mx_cachedop_bwd": [{"fusion.5": _rec("attn", "flash_attention",
                                                  "recompute")}],
        "jit_mx_fused_update": [{"fusion.5": _rec("optimizer", "optimizer",
                                                  "update")}]}
    an = _analyse(monkeypatch, maps, _ctx(modules, ops, steps=2))
    assert _row(an, program="jit_mx_cachedop_fwd", node="fc1") == \
        pytest.approx(2.0)                      # 4 ms over two steps
    assert _row(an, program="jit_mx_cachedop_bwd", node="attn") == \
        pytest.approx(3.5)
    assert sc.pass_ms(an, ("fwd",)) == pytest.approx(2.0)
    assert sc.pass_ms(an, ("bwd", "recompute")) == pytest.approx(3.5)
    assert sc.pass_ms(an, ("update",)) == pytest.approx(1.0)


def test_two_cachedops_under_one_name_are_told_apart_by_their_instructions(
        monkeypatch):
    """A net and a hybridized loss are both `jit_mx_cachedop_fwd`: the
    launch goes to the map that knows all of its instructions."""
    modules = [("jit_mx_cachedop_fwd(1)", 0, 6 * MS),
               ("jit_mx_cachedop_fwd(2)", 6 * MS, 8 * MS)]
    ops = [_ev("fusion.1", 0, 3), _ev("fusion.2", 3, 6),
           _ev("fusion.1", 6, 7), _ev("reduce.4", 7, 8, "reduce")]
    net = {"fusion.1": _rec("fc1", "FullyConnected", "fwd"),
           "fusion.2": _rec("fc2", "FullyConnected", "fwd")}
    loss = {"fusion.1": _rec("ce", "softmax_cross_entropy", "fwd"),
            "reduce.4": _rec("ce", "softmax_cross_entropy", "fwd", "reduce")}
    an = _analyse(monkeypatch, {"jit_mx_cachedop_fwd": [net, loss]},
                  _ctx(modules, ops))
    assert _row(an, node="fc1") == pytest.approx(3.0)
    assert _row(an, node="fc2") == pytest.approx(3.0)
    assert _row(an, op_type="softmax_cross_entropy") == pytest.approx(2.0)
    assert an["unknown_instructions"] == 0


def test_a_launch_without_a_record_lands_under_its_program_name(monkeypatch):
    modules = [("jit_mx_executor_fwd_bwd(1)", 0, 10 * MS),
               ("jit__threefry_split(2)", 10 * MS, 11 * MS)]
    ops = [_ev("fusion.1", 0, 4), _ev("fusion.2", 4, 9),
           _ev("copy.1", 9, 10, "copy"), _ev("fusion.1", 10, 11),
           _ev("fusion.7", 20, 21)]            # under no launch at all
    maps = {"jit_mx_executor_fwd_bwd": [{
        "fusion.1": _rec("conv0", "Convolution", "fwd"),
        "fusion.2": _rec("conv0", "Convolution", "bwd"),
        "copy.1": _rec(UN, None, None, "copy")}]}
    ctx = _ctx(modules, ops)
    an = _analyse(monkeypatch, maps, ctx)
    assert _row(an, program="jit__threefry_split") == pytest.approx(1.0)
    assert _row(an, program="jit__threefry_split",
                node="jit__threefry_split", **{"pass": sc.RECORDLESS}) \
        == pytest.approx(1.0)
    assert _row(an, program=sc.NO_LAUNCH) == pytest.approx(1.0)
    # the program holds both passes: what has no scope is in neither
    assert _row(an, **{"pass": sc.UNSPLIT}) == pytest.approx(1.0)
    assert sc.pass_ms(an, ("fwd",)) == pytest.approx(4.0)
    assert sc.pass_ms(an, ("bwd", "recompute")) == pytest.approx(5.0)
    # forward + backward + update + unsplit + recordless tile the busy step
    assert sum(_row(an, **{"pass": p}) for p in
               ("fwd", "bwd", "recompute", "update", sc.UNSPLIT,
                sc.RECORDLESS)) == pytest.approx(an["busy_ms"]) \
        == pytest.approx(12.0)
    # the share is of the step's own programs: 9 of their 10 ms are named
    assert sc.scoped_share(an) == pytest.approx(90.0)
    rep = sc.report(ctx)
    assert rep["costliest_nodes"][0]["node"] == "conv0"
    assert rep["costliest_nodes"][0]["largest"] == ["fusion.2",
                                                    pytest.approx(5.0)]
    assert rep["unnamed_ms_a_step_by_program_and_opcode"] == {
        "jit_mx_executor_fwd_bwd": {"copy": pytest.approx(1.0)}}
    assert rep["ms_a_step_by_op_type_and_pass"]["Convolution"] == {
        "fwd": pytest.approx(4.0), "bwd": pytest.approx(5.0)}


def test_the_chips_are_averaged(monkeypatch):
    modules = [("jit_mx_executor_fwd_bwd(1)", 0, 10 * MS)]
    ops = [_ev("fusion.1", 0, 4), _ev("all-reduce.2", 4, 10, "all-reduce")]
    maps = {"jit_mx_executor_fwd_bwd": [{
        "fusion.1": _rec("conv0", "Convolution", "fwd"),
        "all-reduce.2": _rec("allreduce", "allreduce", "bwd",
                             "all-reduce")}]}
    an = _analyse(monkeypatch, maps, _ctx(modules, ops, steps=2, planes=4))
    assert an["devices"] == 4
    assert _row(an, node="conv0") == pytest.approx(2.0)
    assert _row(an, node="allreduce") == pytest.approx(3.0)
    assert an["busy_ms"] == pytest.approx(5.0)


# -- (c), (d): off means off, and an untraced step reads nothing ---------------

READERS = ("fwd_device_ms", "bwd_device_ms", "scoped_device_share")


def _reader(name):
    return cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", name + ".py"),
        "chipbench_metric_test_" + name)


def _gluon_trace_ctx():
    """Events named after the instructions this process's programs hold:
    every instruction of each of the three programs runs for 1 ms."""
    modules, ops, t = [], [], 0.0
    for k, jit_name in enumerate(("jit_mx_cachedop_fwd",
                                  "jit_mx_cachedop_bwd",
                                  "jit_mx_fused_update")):
        names = [n for n, r in (_only(jit_name)).items()
                 if r["opcode"] not in SILENT]
        modules.append((f"{jit_name}({k})", t * MS, (t + len(names)) * MS))
        for n in names:
            ops.append(_ev(n, t, t + 1))
            t += 1
    return _ctx(modules, ops)


@pytest.mark.parametrize("reader", READERS)
def test_readers_read_this_process_s_own_programs(reader):
    _held = _gluon_step()
    ctx = _gluon_trace_ctx()
    value = _reader(reader).read(ctx)
    an = sc.analyse(ctx)
    assert an["unknown_instructions"] == 0
    assert value is not None and value > 0
    if reader == "scoped_device_share":
        assert 0 < value <= 100
    else:
        fwd, bwd = (sc.pass_ms(an, p) for p in (("fwd",),
                                                ("bwd", "recompute")))
        assert fwd + bwd + sc.pass_ms(an, ("update",)) == \
            pytest.approx(an["busy_ms"])


@pytest.mark.parametrize("reader", READERS)
def test_with_introspection_off_the_readers_return_none(reader):
    introspect.disable()
    _held = _gluon_step()
    assert introspect.op_scopes("jit_mx_cachedop_fwd") is None
    assert introspect.program_sources() == []
    modules = [("jit_mx_cachedop_fwd(1)", 0, 4 * MS)]
    ctx = _ctx(modules, [_ev("fusion.5", 0, 4)])
    assert _reader(reader).read(ctx) is None


@pytest.mark.parametrize("path", ["gluon", "module"])
def test_an_untraced_step_path_compiles_and_renders_nothing(path,
                                                            monkeypatch):
    """`note_jit` keeps a `Lowered` and nothing else happens until a
    reader asks: no `compile()` of a lowering, no `as_text()` of an
    executable, and no program compiled or loaded beyond the step's own."""
    from jax._src import stages
    calls = {"compile": 0, "as_text": 0}
    real_compile, real_text = stages.Lowered.compile, stages.Compiled.as_text

    def compile_(self, *a, **kw):
        calls["compile"] += 1
        return real_compile(self, *a, **kw)

    def as_text(self, *a, **kw):
        calls["as_text"] += 1
        return real_text(self, *a, **kw)

    monkeypatch.setattr(stages.Lowered, "compile", compile_)
    monkeypatch.setattr(stages.Compiled, "as_text", as_text)
    if path == "gluon":
        _held = _gluon_step(steps=3)
        programs = ("jit_mx_cachedop_fwd", "jit_mx_cachedop_bwd",
                    "jit_mx_fused_update")
    else:
        _held = _module_fit()
        programs = ("jit_mx_executor_fwd_bwd", "jit_mx_fused_update")
    assert calls == {"compile": 0, "as_text": 0}
    assert all(s["text_bytes"] == 0 and s["instructions"] is None
               for s in introspect.program_sources())
    # the first read compiles nothing either: the executable the step runs
    # is found on the lowering jax cached
    loads = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _s, **_kw: loads.append(event)
        if "backend_compile" in event else None)
    for jit_name in programs:
        assert introspect.op_scopes(jit_name)
    assert calls["compile"] == len(programs) == calls["as_text"]
    assert loads == []
