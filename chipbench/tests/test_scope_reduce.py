"""scope_reduce on the trace recorded on the chip (small.xplane.pb): the
self times of the `XLA Ops` events tile what `trace_reduce.reduce` calls
busy, and every operation finds its launch (CPU; the programs of that
trace are not this process's, so every launch is recordless)."""
import os

import pytest

from chipbench import scope_reduce as sc
from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    return tr.load(os.path.join(HERE, "small.xplane.pb"))


def test_self_times_tile_the_busy_time(trace):
    red = tr.reduce(trace)
    (dev,) = [d for d in trace["devices"].values() if d["ops"]]
    own = sc.self_times(dev["ops"])
    assert len(own) == len(dev["ops"])
    assert sum(ns for _n, _s, ns, _leaf in own) / 1e9 == \
        pytest.approx(red["busy_s"], rel=1e-9)
    # that trace holds no loop: every event is a leaf
    assert all(leaf for _n, _s, _ns, leaf in own)


def test_every_operation_has_a_launch_and_lands_under_its_program(trace):
    red = tr.reduce(trace)
    (dev,) = [d for d in trace["devices"].values() if d["ops"]]
    rows, unnamed, tally = sc.device_rows(dev, sc.Records(lambda _p: None))
    assert tally["busy_ns"] / 1e9 == pytest.approx(red["busy_s"], rel=1e-9)
    assert tally["leaf_ns"] == tally["busy_ns"]
    assert unnamed == {}
    programs = {key[0] for key in rows}
    assert programs == {"jit_small_step", "jit_small_update"}
    assert all(key[1] == sc.RECORDLESS and key[3] == key[0] for key in rows)
    by_program = {p: sum(r["ns"] for k, r in rows.items() if k[0] == p)
                  for p in programs}
    # the matmul chain is the larger program, and both ran five times
    assert by_program["jit_small_step"] > by_program["jit_small_update"] > 0
    assert red["modules"] == {"jit_small_step": 5, "jit_small_update": 5}


def test_without_a_record_the_readers_find_nothing(trace):
    red = tr.reduce(trace)
    ctx = {"reduced": red, "window": {"attempted": 5}}
    # no `jit_mx_*` program in the trace has a record: nothing to read
    assert sc.analyse(ctx) is None
    assert sc.report(ctx) is None
