"""Deep Embedded Clustering (parity: /root/reference/example/
deep-embedded-clustering/dec.py — Xie 2016: autoencoder pretraining,
k-means-initialized cluster centers, then joint refinement of encoder +
centers under the KL(P||Q) objective with Student-t soft assignments).

Zero-egress: runs on the synthetic prototype-digit dataset
(test_utils.get_mnist).  TPU-native: pretraining and refinement steps are
fused gluon programs; cluster centers are a Parameter updated by the same
Trainer; k-means init is a few host-side Lloyd iterations on embeddings.
"""
import argparse
import logging
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import get_mnist


class AE(gluon.HybridBlock):
    def __init__(self, dims, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.enc = nn.HybridSequential(prefix="enc_")
            for d in dims[:-1]:
                self.enc.add(nn.Dense(d, activation="relu"))
            self.enc.add(nn.Dense(dims[-1]))
            self.dec = nn.HybridSequential(prefix="dec_")
            for d in reversed(dims[:-1]):
                self.dec.add(nn.Dense(d, activation="relu"))
            self.dec.add(nn.Dense(784))

    def hybrid_forward(self, F, x):
        z = self.enc(x)
        return z, self.dec(z)


def kmeans(z, k, rs, iters=20):
    centers = z[rs.permutation(len(z))[:k]].copy()
    for _ in range(iters):
        d = ((z[:, None] - centers[None]) ** 2).sum(-1)
        a = d.argmin(1)
        for j in range(k):
            pts = z[a == j]
            if len(pts):
                centers[j] = pts.mean(0)
    return centers, a


def cluster_acc(assign, labels, k):
    """Best greedy cluster→label mapping accuracy."""
    acc = 0
    for j in range(k):
        members = labels[assign == j]
        if len(members):
            acc += np.bincount(members.astype(int)).max()
    return acc / len(labels)


def main():
    ap = argparse.ArgumentParser(description="deep embedded clustering")
    ap.add_argument("--num-examples", type=int, default=1500)
    ap.add_argument("--pretrain-epochs", type=int, default=15)
    ap.add_argument("--dec-epochs", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--clusters", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--dec-lr", type=float, default=1e-4,
                    help="refinement lr (DEC collapses if too high)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    ctx = mx.cpu()
    rs = np.random.RandomState(0)

    data = get_mnist(num_train=args.num_examples, num_test=1)
    X = data["train_data"].reshape(args.num_examples, -1)
    y = data["train_label"]

    ae = AE([256, 64, 10])
    ae.initialize(mx.init.Xavier(), ctx=ctx)
    trainer = gluon.Trainer(ae.collect_params(), "adam",
                            {"learning_rate": args.lr})

    # ---- phase 1: autoencoder pretraining
    nb = args.num_examples // args.batch_size
    t0 = time.time()
    for epoch in range(args.pretrain_epochs):
        tot = 0.0
        perm = rs.permutation(args.num_examples)
        for b in range(nb):
            idx = perm[b * args.batch_size:(b + 1) * args.batch_size]
            x = mx.nd.array(X[idx], ctx=ctx)
            with autograd.record():
                _, recon = ae(x)
                loss = ((recon - x) ** 2).mean()
            loss.backward()
            trainer.step(1)
            tot += float(loss.asnumpy())
        if epoch % 5 == 0 or epoch == args.pretrain_epochs - 1:
            logging.info("pretrain[%d] mse=%.5f (%.1fs)", epoch, tot / nb,
                         time.time() - t0)

    # ---- k-means init of centers on embeddings
    Z = ae(mx.nd.array(X, ctx=ctx))[0].asnumpy()
    centers_np, assign = kmeans(Z, args.clusters, rs)
    logging.info("k-means init cluster acc %.3f",
                 cluster_acc(assign, y, args.clusters))

    centers = mx.nd.array(centers_np, ctx=ctx)
    centers.attach_grad()

    # ---- phase 2: DEC refinement (KL(P||Q), Student-t q)
    trainer.set_learning_rate(args.dec_lr)
    opt = mx.optimizer.create("adam", learning_rate=args.dec_lr)
    cstate = opt.create_state(0, centers)
    for epoch in range(args.dec_epochs):
        # target distribution P from current Q over the full set
        z_all = ae(mx.nd.array(X, ctx=ctx))[0].asnumpy()
        d2 = ((z_all[:, None] - centers.asnumpy()[None]) ** 2).sum(-1)
        q = 1.0 / (1.0 + d2)
        q = q / q.sum(1, keepdims=True)
        f = q.sum(0)
        p = (q ** 2) / f
        p = p / p.sum(1, keepdims=True)

        perm = rs.permutation(args.num_examples)
        tot = 0.0
        for b in range(nb):
            idx = perm[b * args.batch_size:(b + 1) * args.batch_size]
            x = mx.nd.array(X[idx], ctx=ctx)
            pt = mx.nd.array(p[idx], ctx=ctx)
            with autograd.record():
                z, _ = ae(x)
                dist = ((z.expand_dims(1) - centers.expand_dims(0)) ** 2) \
                    .sum(axis=-1)
                qb = 1.0 / (1.0 + dist)
                qb = qb / qb.sum(axis=1, keepdims=True)
                kl = (pt * (mx.nd.log(pt + 1e-9) -
                            mx.nd.log(qb + 1e-9))).sum(axis=1).mean()
            kl.backward()
            # the KL reads z only: the decoder stays as pretrained
            trainer.step(1, ignore_stale_grad=True)
            opt.update(0, centers, centers.grad, cstate)
            tot += float(kl.asnumpy())
        logging.info("dec[%d] kl=%.5f", epoch, tot / nb)

    z_all = ae(mx.nd.array(X, ctx=ctx))[0].asnumpy()
    d2 = ((z_all[:, None] - centers.asnumpy()[None]) ** 2).sum(-1)
    assign = d2.argmin(1)
    acc = cluster_acc(assign, y, args.clusters)
    print("final cluster accuracy %.3f" % acc)


if __name__ == "__main__":
    main()
