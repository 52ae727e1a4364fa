"""Kaggle NDSB-1 plankton-classification pipeline (parity:
/root/reference/example/kaggle-ndsb1/ — gen_img_list.py splits a
class-per-directory image tree into train/val .lst files, train_dsb.py
fits the symbol_dsb convnet, predict_dsb.py + submission_dsb.py write
the per-class-probability Kaggle CSV).  The real competition data is a
download; zero-egress here, so a synthetic many-class plankton-like
tree stands in — the full list→train→predict→submission flow runs.

    python train_dsb.py --num-epochs 4
"""
import argparse
import csv
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))
import mxnet_tpu as mx

from symbol_dsb import get_symbol


def gen_img_list(n, classes, rs, val_frac=0.2):
    """Synthetic analog of gen_img_list.py: (index, label, path) rows
    split into train/val — the reference writes .lst files consumed by
    ImageRecordIter; here the 'images' are generated per row."""
    labels = rs.randint(0, classes, n)
    rows = [(i, int(c), "cls%03d/img_%05d.jpg" % (c, i))
            for i, c in enumerate(labels)]
    n_val = int(n * val_frac)
    return rows[n_val:], rows[:n_val]


def render(rows, stencils, rs, img=48):
    """Grayscale plankton-ish blobs: each class is a fixed random 8x8
    stencil (drawn ONCE, shared by the train/val splits) pasted at a
    random position over noise — translation-invariant, so the conv
    stack has to do the work."""
    x = rs.normal(0, 0.3, (len(rows), 1, img, img)).astype(np.float32)
    y = np.zeros(len(rows), np.float32)
    for k, (_, c, _) in enumerate(rows):
        oy, ox = rs.randint(0, img - 8, 2)
        x[k, 0, oy:oy + 8, ox:ox + 8] += stencils[c]
        y[k] = c
    return x, y


def gen_sub(probs, rows, path):
    """submission_dsb.py analog: image,prob_class0,...,probN CSV."""
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(["image"] + ["class_%d" % c
                                for c in range(probs.shape[1])])
        for (_, _, name), p in zip(rows, probs):
            w.writerow([os.path.basename(name)] +
                       ["%.6f" % v for v in p])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-epochs", type=int, default=6)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--num-examples", type=int, default=2048)
    ap.add_argument("--classes", type=int, default=12)
    ap.add_argument("--submission", default="submission.csv")
    args = ap.parse_args()

    # NDArrayIter(shuffle=True) draws from numpy's global stream: seed it,
    # or the epoch order, and with it the accuracy, differs run to run
    np.random.seed(11)
    rs = np.random.RandomState(11)
    train_rows, val_rows = gen_img_list(args.num_examples, args.classes, rs)
    stencils = rs.normal(0, 1, (args.classes, 8, 8)).astype(np.float32)
    xt, yt = render(train_rows, stencils, rs)
    xv, yv = render(val_rows, stencils, rs)
    train = mx.io.NDArrayIter(xt, yt, args.batch_size, shuffle=True,
                              label_name="softmax_label")
    val = mx.io.NDArrayIter(xv, yv, args.batch_size,
                            label_name="softmax_label")

    sym = get_symbol(num_classes=args.classes)
    mod = mx.mod.Module(sym)
    mod.fit(train, eval_data=val, optimizer="adam",
            optimizer_params={"learning_rate": 2e-3},
            initializer=mx.init.Xavier(factor_type="in", magnitude=2.34),
            num_epoch=args.num_epochs, eval_metric="acc")
    acc = mod.score(val, mx.metric.Accuracy())[0][1]
    print("ndsb1 validation accuracy %.3f" % acc)

    # predict_dsb.py analog: probabilities over the "test" set
    val.reset()
    probs = mod.predict(val).asnumpy()
    gen_sub(probs, val_rows, args.submission)
    print("wrote %s (%d rows x %d classes)"
          % (args.submission, probs.shape[0], probs.shape[1]))


if __name__ == "__main__":
    main()
