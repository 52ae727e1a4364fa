"""Smokes of the measuring harnesses on the CPU: bench.py's rehearsal in both
layouts, tools/bench_io.py, tools/bandwidth, benchmark/ and the
experiments/ probes.  They prove the harness runs; none is a measurement."""
import json
import os
import sys

import numpy as np
import pytest

from example_runner import REPO, run_example, run_python


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_bench_product_path_smoke(layout):
    """bench.py drives Module.fit + tpu_sync kvstore + fused updates; the
    explicit CPU rehearsal checks the whole path wires up (both internal
    layouts) and the loss-sanity assert passes.  Every record of a
    rehearsal says so and names the device it ran on."""
    proc = run_python(
        ["bench.py", "--rehearsal"], cwd=REPO,
        env={"MXT_BENCH_BATCH": "8", "MXT_BENCH_IMG": "64",
             "MXT_BENCH_BATCHES": "2", "MXT_BENCH_LR": "0.01",
             "MXNET_TPU_CONV_LAYOUT": layout})
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["metric"] == "resnet50_train_throughput"
    assert rec["value"] > 0
    assert rec["rehearsal"] is True and rec["platform"] == "cpu"
    assert rec["device_kind"] and rec["device_count"] >= 1
    assert "chip_mfu" not in rec  # a CPU has no peak to divide by
    assert "failed" not in rec and "error" not in rec, rec


def test_bench_refuses_cpu_without_rehearsal():
    """bench.py cannot run on the CPU by accident: with no TPU it prints
    its JSON line (device stamped, error named) and exits non-zero."""
    proc = run_python(["bench.py"], cwd=REPO, rc=1)
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["platform"] == "cpu" and rec["value"] == 0.0
    assert "--rehearsal" in rec["error"] and rec["phase"] == "device"


def test_bench_io_harness():
    """Standalone input-pipeline benchmark (parallel decode pool)."""
    out = run_example("tools/bench_io.py", "--num-images", "64",
                      "--batch-size", "16", "--image-size", "64",
                      "--threads", "4", "--epochs", "1")
    assert "decode+augment throughput" in out


def test_bandwidth_harness():
    sys.path.insert(0, os.path.join(REPO, "tools", "bandwidth"))
    import importlib
    measure = importlib.import_module("measure")
    gbps = measure.run("local", size_mb=1, num_keys=2, repeats=2)
    assert gbps > 0


def test_sparse_benchmark_harness():
    out = run_example("benchmark/python/sparse/sparse_bench.py",
                      "--quick")
    assert "sparse bench done" in out
    assert "grad stype=row_sparse" in out  # rows-only path exercised


def test_benchmark_sweep_driver(tmp_path):
    """The training-throughput sweep driver (reference benchmark.py):
    dry-run lists the planned cells; one tiny real cell produces a
    parsed img/s row and a JSONL report."""
    out = run_example("example/image-classification/benchmark.py",
                      "--dry-run", "--networks", "resnet-18,mobilenet",
                      "--batch-sizes", "8,16")
    assert out.count("train_imagenet.py") == 4
    report = str(tmp_path / "report.jsonl")
    out = run_example("example/image-classification/benchmark.py",
                      "--networks", "mlp", "--batch-sizes", "8",
                      "--image-size", "28", "--batches", "3",
                      "--timeout", "200", "--output", report)
    assert "| mlp | 8 |" in out
    rec = json.loads(open(report).read().splitlines()[0])
    assert rec["rc"] == 0 and rec["img_s"] > 0, rec


def test_lm_mfu_probe_smoke():
    """experiments/lm_mfu_probe.py (transformer-LM MFU window leg):
    smoke config must train (finite decreasing-ish loss) and emit one
    JSON line with the tok/s + FLOPs accounting fields."""
    proc = run_python(["experiments/lm_mfu_probe.py"], cwd=REPO,
                      env={"MXT_LM_PROBE_SMOKE": "1"})
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "transformer_lm_train_throughput"
    assert rec["value"] > 0 and rec["train_tflops_per_step"] >= 0
    assert np.isfinite(rec["loss_first"]) and np.isfinite(rec["loss_final"])
    # 2 smoke steps on random tokens: loss must move and not blow up
    assert rec["loss_final"] < rec["loss_first"] + 1.0


def test_decode_probe_smoke():
    """experiments/decode_probe.py (decode window leg): both decode
    strategies must run, agree token-for-token, and emit JSON rows."""
    proc = run_python(["experiments/decode_probe.py"], cwd=REPO,
                      env={"MXT_DECODE_PROBE_SMOKE": "1"})
    rows = [json.loads(ln) for ln in proc.stdout.strip().splitlines()
            if ln.startswith("{")]
    metrics = {r["metric"]: r for r in rows}
    assert metrics["decode_static_throughput"]["value"] > 0
    assert metrics["decode_kv_cache_throughput"]["value"] > 0
    assert metrics["decode_paths_agree"]["value"] is True


def test_benchmark_score_watchdogged(tmp_path):
    """benchmark_score.py (VERDICT r4 #6): per-cell subprocess watchdogs
    + --out durable partials — a per-cell timeout records an error row
    instead of killing the run, and good cells still land."""
    def score(cell_timeout, out):
        run_example("example/image-classification/benchmark_score.py",
                    "--networks", "squeezenet", "--batch-sizes", "1",
                    "--repeats", "2", "--cell-timeout", cell_timeout,
                    "--out", str(out), cwd=REPO)
        return [json.loads(l) for l in out.read_text().splitlines()]

    rows = score("200", tmp_path / "score.jsonl")
    assert rows and rows[0]["network"] == "squeezenet"
    assert rows[0]["img_s"] > 0

    # a hopeless per-cell budget must yield an error row, rc 0
    rows = score("3", tmp_path / "score2.jsonl")
    assert rows and "error" in rows[0], rows
