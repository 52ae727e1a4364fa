#!/usr/bin/env python
"""Inference throughput across the model zoo (behavioral parity:
example/image-classification/benchmark_score.py — img/s per network per
batch size).

    python benchmark_score.py [--networks resnet-50,mobilenet] [--batch-sizes 1,32]

Every (network, batch) cell runs in its own SUBPROCESS with a per-cell
budget (--cell-timeout); results append to --out as soon as each cell
retires, and a hang or crash costs one cell, not the run.  The parent
imports no JAX, so each child in turn is the one process on the device.
MXT_SCORE_INPROC=1 restores the old single-process mode (CI smoke).
"""
import argparse
import json
import logging
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

logging.basicConfig(level=logging.INFO)

NETWORKS = ("alexnet", "vgg-11", "resnet-18", "resnet-50", "resnet-152",
            "squeezenet", "mobilenet", "densenet-121", "inception-v3")


def _zoo(network):
    # heavy imports live here, NOT at module level: the watchdog
    # orchestrator only spawns subprocesses and must stay import-light
    # (a stalled jax import in the parent would hang outside any
    # per-cell budget and lose every cell)
    from mxnet_tpu.gluon.model_zoo import vision
    zoo = {
        "alexnet": vision.alexnet,
        "vgg-11": vision.vgg11,
        "resnet-18": lambda **kw: vision.resnet18_v1(**kw),
        "resnet-50": lambda **kw: vision.resnet50_v1(**kw),
        "resnet-152": lambda **kw: vision.resnet152_v1(**kw),
        "squeezenet": vision.squeezenet1_0,
        "mobilenet": lambda **kw: vision.mobilenet1_0(**kw),
        "densenet-121": vision.densenet121,
        "inception-v3": vision.inception_v3,
    }
    return zoo[network]


def score(network, batch_size, image_shape=(3, 224, 224), repeats=10):
    import mxnet_tpu as mx
    if network == "inception-v3":
        image_shape = (3, 299, 299)
    net = _zoo(network)(classes=1000)
    net.initialize()
    net.hybridize()
    data = mx.nd.random.uniform(shape=(batch_size,) + image_shape)

    def sync(o):
        # host scalar fetch: the timed region ends when a value of the
        # last output is on the host
        float(np.asarray(o._data.ravel()[0]))

    out = net(data)       # build + compile
    sync(out)
    tic = time.time()
    for _ in range(repeats):
        out = net(data)
    sync(out)
    return batch_size * repeats / (time.time() - tic)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--networks", type=str,
                   default="resnet-18,resnet-50,mobilenet")
    p.add_argument("--batch-sizes", type=str, default="1,32")
    p.add_argument("--repeats", type=int, default=10)
    p.add_argument("--cell-timeout", type=float, default=300.0,
                   help="watchdog per (network, batch) subprocess")
    p.add_argument("--out", type=str, default=None,
                   help="append one JSON line per cell (durable partial "
                        "artifact; written as each cell retires)")
    p.add_argument("--one-cell", type=str, default=None,
                   help=argparse.SUPPRESS)  # internal: "network,batch"
    args = p.parse_args()

    if args.one_cell:
        network, bs = args.one_cell.rsplit(",", 1)
        img_s = score(network, int(bs), repeats=args.repeats)
        print(json.dumps({"network": network, "batch": int(bs),
                          "img_s": round(img_s, 1)}), flush=True)
        return

    inproc = bool(os.environ.get("MXT_SCORE_INPROC"))
    for network in args.networks.split(","):
        for bs in (int(x) for x in args.batch_sizes.split(",")):
            if inproc:
                img_s = score(network, bs, repeats=args.repeats)
                rec = {"network": network, "batch": bs,
                       "img_s": round(img_s, 1)}
            else:
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--repeats", str(args.repeats),
                       "--one-cell", f"{network},{bs}"]
                try:
                    r = subprocess.run(cmd, timeout=args.cell_timeout,
                                       capture_output=True, text=True)
                    rec = None
                    if r.returncode == 0:  # rc!=0 is an error row even
                        for ln in reversed(r.stdout.splitlines()):
                            try:  # if something JSON-shaped printed
                                cand = json.loads(ln)
                                if isinstance(cand, dict) and \
                                        "img_s" in cand:
                                    rec = cand
                                    break
                            except ValueError:
                                continue
                    if rec is None:
                        rec = {"network": network, "batch": bs,
                               "rc": r.returncode,
                               "error": ((r.stdout + r.stderr).strip()
                                         or "no output")[-300:]}
                except subprocess.TimeoutExpired:
                    rec = {"network": network, "batch": bs,
                           "error": "timeout %.0fs" % args.cell_timeout}
            if "img_s" in rec:
                logging.info("network: %s batch: %d  %.1f img/s",
                             rec["network"], rec["batch"], rec["img_s"])
            else:
                logging.warning("network: %s batch: %d  FAILED (%s)",
                                network, bs, rec.get("error", "?"))
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")
                    f.flush()
                    os.fsync(f.fileno())


if __name__ == "__main__":
    main()
