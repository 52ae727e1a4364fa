"""Smokes of the reference's two benchmark examples on the CPU
(example/image-classification/benchmark.py and benchmark_score.py).  They
prove the scripts run; none is a measurement.  The benchmark the repo is
judged by is BENCHMARK.json + chipbench/, whose cells are rehearsed in
tests/test_benchmark_cells.py."""
import json

from example_runner import REPO, run_example


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


def test_benchmark_score_watchdogged(tmp_path):
    """benchmark_score.py: per-cell subprocess watchdogs
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
