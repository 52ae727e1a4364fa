"""The FLOP and byte functions against hand-worked values."""
import json
import os

import pytest

from chipbench import cell as cellmod
from chipbench import kernel_cost

CONFIGS = os.path.join(cellmod.HERE, "configs")


def _load(config):
    cfg = cellmod.load_json(os.path.join(CONFIGS, config, "config.json"))
    flops = cellmod.load_module(os.path.join(CONFIGS, config, "flops.py"),
                                "flops_under_test")
    return cfg, flops


def test_resnet50_flops_per_image():
    cfg, flops = _load("resnet50_v1")
    # by hand, multiply-adds forward at 224x224, stride in the first 1x1:
    stem = 64 * 3 * 49 * 112 * 112                       # 118,013,952
    s1 = (64 * 64 + 64 * 64 * 9 + 256 * 64 + 256 * 64) * 56 * 56 \
        + 2 * (64 * 256 + 64 * 64 * 9 + 256 * 64) * 56 * 56
    s2 = (128 * 256 + 128 * 128 * 9 + 512 * 128 + 512 * 256) * 28 * 28 \
        + 3 * (128 * 512 + 128 * 128 * 9 + 512 * 128) * 28 * 28
    s3 = (256 * 512 + 256 * 256 * 9 + 1024 * 256 + 1024 * 512) * 14 * 14 \
        + 5 * (256 * 1024 + 256 * 256 * 9 + 1024 * 256) * 14 * 14
    s4 = (512 * 1024 + 512 * 512 * 9 + 2048 * 512 + 2048 * 1024) * 7 * 7 \
        + 2 * (512 * 2048 + 512 * 512 * 9 + 2048 * 512) * 7 * 7
    fc = 1000 * 2048
    macs = stem + s1 + s2 + s3 + s4 + fc
    assert flops.forward_macs_per_image(cfg) == macs == 3_857_973_248
    # two operations a multiply-add, forward once and backward twice:
    # 23.15 GFLOP.  (chip.py's 24.6 GFLOP is v1.5's 4.1 GMAC, stride in the
    # 3x3; the model zoo's v1 needs 6% less.)
    assert flops.train_flops_per_unit(cfg, {"batch": 256}) == 6 * macs
    assert 6 * macs == pytest.approx(23.15e9, rel=1e-3)
    assert flops.units_per_step(cfg, {"batch": 256}) == 256


@pytest.mark.parametrize("layers,expect", [(6, 2.58075e9), (24, 8.46977e9)])
def test_opt_flops_per_token(layers, expect):
    cfg, flops = _load("opt-1.3b")
    cfg = dict(cfg, num_hidden_layers=layers)
    tr = {"batch": 2, "seq": 2048}
    # by hand: a layer's matrices 4 d^2 + 2 d f = 50,331,648 parameters,
    # the head 2048 x 50272 = 102,957,056; six operations a parameter;
    # causal attention 12 d (T + 1) / 2 = 25,178,112 a layer
    d, f, v, t = 2048, 8192, 50272, 2048
    per_layer = 4 * d * d + 2 * d * f
    assert per_layer == 50_331_648 == flops.matrix_params_per_layer(cfg)
    by_hand = 6 * (layers * per_layer + d * v) + 12 * d * (t + 1) / 2 * layers
    got = flops.train_flops_per_unit(cfg, tr)
    assert got == by_hand
    assert got == pytest.approx(expect, rel=1e-5)
    assert flops.units_per_step(cfg, tr) == 4096


def test_attention_forward_cost_and_bound():
    flops, nbytes = kernel_cost.attention_forward(2, 32, 2048, 64)
    # 2 products x 2 ops x B H T^2 D, times (T + 1) / 2T under the mask
    assert flops == 4 * 2 * 32 * 2048 * 2048 * 64 * 2049 / 4096
    assert nbytes == 4 * 2 * 32 * 2048 * 64 * 2
    peaks = cellmod.peaks("TPU v5 lite")
    least, bound = kernel_cost.least_seconds(flops, nbytes, peaks)
    assert bound == "compute"
    assert least == pytest.approx(flops / 197e12)
    # short sequences are bound by memory: 2 T^2 D / (8 T D) = T / 4 FLOP a byte
    f2, b2 = kernel_cost.attention_forward(2, 32, 256, 64)
    assert kernel_cost.least_seconds(f2, b2, peaks)[1] == "memory"


def test_peaks_table():
    row = cellmod.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert "TPU v5e" in row["source"]
    with pytest.raises(KeyError):
        cellmod.peaks("TPU v9 imaginary")


def test_benchmark_json_finds_its_files():
    bench = cellmod.benchmark()
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        c = cellmod.Cell(w["name"], 1)
        assert c.limits, w["name"]
        assert [m["name"] for m in c.metrics["end_to_end"]]
        for m in c.metrics["per_layer"]:
            assert m["moves"] in e2e
            reader = m["name"].split(".", 1)[0]
            assert os.path.isfile(os.path.join(cellmod.HERE, "metrics",
                                               reader + ".py"))
    for conf in bench["configs"]:
        cfg = json.load(open(os.path.join(cellmod.ROOT, conf["file"])))
        assert cfg["reduced"] == conf["reduced"]
        assert len(cfg["source"]) <= 200 and cfg["source"] == conf["source"]
