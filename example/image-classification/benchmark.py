"""Training-throughput sweep driver (parity:
example/image-classification/benchmark.py — the reference sweeps
networks x batch-sizes x device counts through the train scripts,
scrapes samples/sec from the logs, and emits a report).

Each sweep cell runs `train_imagenet.py --benchmark 1` (synthetic data,
no IO) in a subprocess with a timeout, scrapes the epoch speed, and
appends one JSON line to the report; a markdown table prints at the
end.  Multi-chip cells ride the same script's kvstore path — on real
hardware set --kv-store tpu_sync and a device mesh via the launcher.

    python benchmark.py --networks resnet-18,mobilenet \
        --batch-sizes 32,64 [--image-size 64] [--timeout 900]
    python benchmark.py --dry-run            # print the planned cells
"""
import argparse
import json
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def sweep_cells(args):
    for net in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            yield {"network": net.strip(), "batch_size": bs,
                   "image_size": args.image_size,
                   "kv_store": args.kv_store}


def cell_cmd(cell, args):
    return [sys.executable, os.path.join(HERE, "train_imagenet.py"),
            "--benchmark", "1",
            "--network", cell["network"],
            "--batch-size", str(cell["batch_size"]),
            "--image-shape", "3,%d,%d" % (cell["image_size"],
                                          cell["image_size"]),
            "--num-epochs", "1",
            "--num-examples", str(cell["batch_size"] * args.batches),
            "--kv-store", cell["kv_store"],
            "--disp-batches", "2"]


SPEED_RE = re.compile(r"Speed[:=]\s*([\d.]+)\s*samples")


def run_cell(cell, args):
    cmd = cell_cmd(cell, args)
    t0 = time.time()
    def scrape(text):
        speeds = [float(m) for m in SPEED_RE.findall(text or "")]
        # skip the first sample (pays compile); mean of the rest
        steady = speeds[1:] if len(speeds) > 1 else speeds
        return (round(sum(steady) / len(steady), 2) if steady else 0.0,
                bool(steady))

    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=args.timeout, cwd=HERE)
        out = proc.stdout + proc.stderr
        img_s, parsed = scrape(out)
        err = None
        if proc.returncode != 0:
            err = out[-300:]
        elif not parsed:
            # rc=0 with nothing scraped is a BAD cell, not a zero
            err = ("no Speed lines parsed (need batches > disp-batches); "
                   "tail: " + out[-200:])
        return {**cell, "img_s": img_s, "rc": proc.returncode,
                "wall_s": round(time.time() - t0, 1), "error": err}
    except subprocess.TimeoutExpired as e:
        # durable partial: speeds already printed before the timeout
        # still count
        partial = e.stdout.decode("utf-8", "replace") \
            if isinstance(e.stdout, bytes) else (e.stdout or "")
        img_s, _ = scrape(partial)
        return {**cell, "img_s": img_s, "rc": "timeout",
                "wall_s": round(time.time() - t0, 1),
                "error": "timeout after %ss" % args.timeout}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--networks", default="resnet-18,resnet-50,mobilenet")
    ap.add_argument("--batch-sizes", default="32,64")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--batches", type=int, default=6,
                    help="batches per cell (first pays compile)")
    ap.add_argument("--kv-store", default="tpu_sync")
    ap.add_argument("--timeout", type=float, default=900)
    ap.add_argument("--output", default="benchmark_report.jsonl")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()

    cells = list(sweep_cells(args))
    if args.dry_run:
        for c in cells:
            print(" ".join(cell_cmd(c, args)))
        return

    rows = []
    with open(args.output, "w") as f:
        for cell in cells:
            rec = run_cell(cell, args)
            rows.append(rec)
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print("%-20s bs=%-4d -> %8.1f img/s (rc=%s)"
                  % (rec["network"], rec["batch_size"], rec["img_s"],
                     rec["rc"]), flush=True)

    print("\n| network | batch | img/s |")
    print("|---|---|---|")
    for r in rows:
        print("| %s | %d | %.1f |" % (r["network"], r["batch_size"],
                                      r["img_s"]))


if __name__ == "__main__":
    main()
