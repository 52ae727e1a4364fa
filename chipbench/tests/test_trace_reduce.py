"""The trace reduction against a small trace recorded on the chip
(`small.xplane.pb`, 50 KB; tests/record_trace.py made it on a TPU v5 lite,
PR 24): five steps of two jitted programs, a 20 ms host sleep under a
`host_pause` span after the third step, the whole loop under
`chipbench_window`.  What has to come out is known from how it was made,
and the sums are checked against a second, brute-force computation."""
import os

import pytest

from chipbench import trace_reduce as tr

PB = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                  "small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(PB)


def _brute_busy_ns(intervals):
    """Length of the union by a sweep over sorted end points."""
    points = sorted([(s, 1) for s, _e in intervals]
                    + [(e, -1) for _s, e in intervals])
    depth, last, total = 0, None, 0.0
    for t, d in points:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


def test_what_the_trace_holds(trace):
    (plane,) = trace["devices"]
    assert plane == "/device:TPU:0"
    dev = trace["devices"][plane]
    assert len(dev["modules"]) == 10 and len(dev["ops"]) == 50
    names = {h[0] for h in trace["host"]}
    assert {"chipbench_window", "step_call", "loss_read",
            "host_pause"} <= names


def test_launches_busy_union_and_window(trace):
    red = tr.reduce(trace)
    assert red["devices"] == 1
    # five steps of two programs
    assert red["launches"] == 10
    assert red["modules"] == {"jit_small_step": 5, "jit_small_update": 5}
    # the window is the harness's span
    assert red["window_s"] == pytest.approx(0.029846057, abs=1e-9)
    ops = trace["devices"]["/device:TPU:0"]["ops"]
    brute = _brute_busy_ns([(s, e) for _n, s, e in ops]) / 1e9
    assert red["busy_s"] == pytest.approx(brute, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.002052545, abs=1e-8)
    # five runs of each program: about 0.375 ms + 0.036 ms a step
    assert 5 * 0.0004 < red["busy_s"] < 5 * 0.00042
    idle_share = 1 - red["busy_s"] / red["window_s"]
    assert idle_share == pytest.approx(0.9312, abs=1e-3)


def test_gaps_and_their_labels(trace):
    red = tr.reduce(trace)
    label, secs = red["idle_gaps"][0]
    # the host slept 20 ms after the third step, under `host_pause`
    assert label == "host_pause"
    assert secs == pytest.approx(0.021886743, abs=1e-8)
    assert 0.020 < secs < 0.023
    assert [g[1] for g in red["idle_gaps"]] == sorted(
        (g[1] for g in red["idle_gaps"]), reverse=True)
    # the other waits between steps sit in the loop's own read of the loss
    assert red["idle_gaps"][1][0] == "loss_read"
    assert len(red["idle_gaps"]) == 5
    # busy + gaps + edges add up to the window
    total = red["busy_s"] + sum(red["idle_by_host_span"].values())
    assert total == pytest.approx(red["window_s"], rel=1e-6)
    # with only the longest gaps labelled the rest is one bucket, same sum
    few = tr.reduce(trace, labelled_gaps=2)
    assert set(few["idle_by_host_span"]) == {
        "window_edges", "host_pause", "loss_read", "shorter_gaps"}
    assert few["busy_s"] + sum(few["idle_by_host_span"].values()) == \
        pytest.approx(red["window_s"], rel=1e-6)


def test_per_operation_sums(trace):
    red = tr.reduce(trace)
    top = dict(red["device_ops"])
    # four matmul+tanh fusions a step; the names are the trace's own
    fusions = [k for k in top if k.startswith("convolution_tanh_fusion")]
    assert len(fusions) >= 3
    for k in fusions:
        assert top[k] == pytest.approx(5 * 0.00009, rel=0.05)
    assert tr.op_short_name(
        "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop") == "fusion.3"


def test_union_merges_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == \
        [[0, 3], [5, 9]]


def test_label_picks_the_largest_overlap_then_the_innermost():
    host = [("outer", 0.0, 100.0), ("inner", 10.0, 20.0), ("late", 50.0, 60.0)]
    assert tr.label_gap(host, 11.0, 19.0) == "inner"
    assert tr.label_gap(host, 11.0, 40.0) == "outer"
    assert tr.label_gap([], 1.0, 2.0) == "host_idle"


def test_flash_forward_events_are_found_by_their_text():
    from chipbench import cell as cellmod
    reader = cellmod.load_module(
        os.path.join(cellmod.HERE, "metrics", "flash_fwd_roofline.py"),
        "flash_reader_under_test")
    fwd = ('%transformerlm0_l3_attn_multihead_attention0.1 = bf16[64,2048,64]'
           '{2,1,0} custom-call(bf16[64,2048,64]{2,1,0} %bitcast.228), '
           'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    again = fwd.replace("%transformerlm0", "%jvp_transformerlm0")
    other = ('%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop')
    kernel_elsewhere = fwd.replace("attn_multihead_attention0", "rope0")
    assert reader.is_forward_attention(fwd)
    assert reader.is_forward_attention(again)
    assert not reader.is_forward_attention(other)
    assert not reader.is_forward_attention(kernel_elsewhere)
    # one call a layer of 8.79 ms against the 0.1745 ms the chip needs: 1.98%
    cell = cellmod.Cell("opt1.3b_train_gluon", 1)
    trace = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        (fwd, 0.0, 8.79e6), (again, 1e7, 1e7 + 8.79e6), (other, 3e7, 4e7)]}},
        "host": []}
    ctx = {"cell": cell, "peaks": cellmod.peaks("TPU v5 lite"),
           "reduced": {"events": trace}}
    assert reader.read(ctx) == pytest.approx(1.985, abs=0.01)
    # nothing to read: nothing returned, never 0
    trace["devices"]["/device:TPU:0"]["ops"] = [(other, 0.0, 1e6)]
    assert reader.read(ctx) is None
