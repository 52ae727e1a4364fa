#!/usr/bin/env python
"""Standalone input-pipeline benchmark (parity model: the reference's
`test_io`/`benchmark` harnesses + iter_image_recordio_2.cc OMP decode).

Packs a synthetic JPEG .rec and measures sustained iterator throughput —
the number to compare against the training step's img/s so the host
pipeline provably keeps the chip fed.

    python tools/bench_io.py --num-images 2048 --batch-size 256 \
        --image-size 224 --threads 8
"""
import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pack(path, n, size, seed=0):
    from mxnet_tpu import recordio
    rs = np.random.RandomState(seed)
    w = recordio.MXRecordIO(path, "w")
    img = (rs.rand(size, size, 3) * 255).astype(np.uint8)
    for i in range(n):
        # shift so records differ without regenerating noise each time
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        w.write(recordio.pack_img(header, np.roll(img, i, axis=0),
                                  quality=85, img_fmt=".jpg"))
    w.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-images", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--rec", type=str, default="")
    ap.add_argument("--device-augment", action="store_true",
                    help="host decodes to uint8; mirror/normalize/"
                         "transpose fuse into one on-device program")
    ap.add_argument("--sweep", type=str, default="",
                    help="comma list of thread counts: measure each and "
                         "report the scaling curve + the thread count "
                         "needed for the MFU-derived target (run on a "
                         "real multi-core host; 1 thread == 1 vCPU here)")
    args = ap.parse_args()

    import mxnet_tpu as mx
    rec = args.rec or os.path.join(tempfile.mkdtemp(), "bench.rec")
    if not os.path.exists(rec):
        t0 = time.perf_counter()
        pack(rec, args.num_images, args.image_size)
        print(f"packed {args.num_images} imgs in "
              f"{time.perf_counter() - t0:.1f}s -> {rec}")

    def measure(threads):
        it = mx.io.ImageRecordIter(
            path_imgrec=rec,
            data_shape=(3, args.image_size, args.image_size),
            batch_size=args.batch_size, preprocess_threads=threads,
            rand_mirror=True, mean_r=123.7, mean_g=116.3, mean_b=103.5,
            std_r=58.4, std_g=57.1, std_b=57.4,
            device_augment=args.device_augment)
        # warm epoch (thread pool spin-up, file cache, XLA compile for
        # the device_augment program)
        for b in it:
            pass
        it.reset()
        t0 = time.perf_counter()
        total = 0
        last = None
        for _ in range(args.epochs):
            for b in it:
                total += b.data[0].shape[0]
                last = b.data[0]
            # fair under async dispatch: execution is FIFO per device,
            # so a host fetch of the LAST batch proves every queued
            # augmentation program retired before the clock stops
            float(np.asarray(last.asnumpy()).ravel()[0])
            it.reset()
        return total / (time.perf_counter() - t0)

    if args.sweep:
        counts = [int(x) for x in args.sweep.split(",") if x.strip()]
        rates = []
        for t in counts:
            r = measure(t)
            rates.append(r)
            print(f"threads={t:3d}: {r:.1f} img/s "
                  f"({r / t:.1f} img/s/thread)")
        # the budget the pipeline must clear, derived from the MFU
        # north star (BASELINE.md): img/s = MFU * peak / flops-per-img
        from mxnet_tpu.chip import PEAKS, RESNET50_TRAIN_FLOPS_PER_IMG
        per_thread = max(r / t for r, t in zip(rates, counts))
        for kind, peak in PEAKS.items():
            need = 0.6 * peak.bf16_flops / RESNET50_TRAIN_FLOPS_PER_IMG
            print(f"60% MFU on {kind}: need {need:.0f} img/s "
                  f"≈ {need / per_thread:.0f} threads at the best "
                  f"measured per-thread rate")
    else:
        r = measure(args.threads)
        print(f"decode+augment throughput: {r:.1f} img/s "
              f"({args.threads} threads, {args.image_size}px)")


if __name__ == "__main__":
    main()
