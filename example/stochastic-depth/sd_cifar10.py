"""Stochastic-depth ResNet (parity: /root/reference/example/
stochastic-depth/sd_cifar10.py — Huang 2016: residual blocks are randomly
dropped during training with linearly-decaying survival probability;
at inference every block runs scaled by its survival probability).

TPU-native: the per-batch drop decisions are host-side coin flips (the
reference used a custom operator for the same thing); each surviving
block's forward is a jitted CachedOp, so a dropped block costs zero
compute — exactly the point of the technique.
"""
import argparse
import logging
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import get_mnist


class ResBlock(gluon.HybridBlock):
    def __init__(self, channels, stride=1, **kw):
        super().__init__(**kw)
        self.stride = stride
        with self.name_scope():
            self.conv1 = nn.Conv2D(channels, 3, strides=stride, padding=1,
                                   use_bias=False)
            self.bn1 = nn.BatchNorm()
            self.conv2 = nn.Conv2D(channels, 3, padding=1, use_bias=False)
            self.bn2 = nn.BatchNorm()
            self.proj = nn.Conv2D(channels, 1, strides=stride,
                                  use_bias=False) if stride > 1 else None

    def residual(self, x):
        h = mx.nd.relu(self.bn1(self.conv1(x)))
        return self.bn2(self.conv2(h))

    def shortcut(self, x):
        return self.proj(x) if self.proj is not None else x


class SDResNet(gluon.Block):
    """Stack of ResBlocks with linearly-decaying survival probability."""

    def __init__(self, num_blocks, channels, classes, p_last=0.5, **kw):
        super().__init__(**kw)
        self.survival = [1.0 - (i / max(1, num_blocks - 1)) * (1.0 - p_last)
                         for i in range(num_blocks)]
        with self.name_scope():
            self.stem = nn.Conv2D(channels, 3, padding=1)
            self.blocks = nn.Sequential()
            for i in range(num_blocks):
                stride = 2 if i == num_blocks // 2 else 1
                self.blocks.add(ResBlock(channels, stride))
            self.pool = nn.GlobalAvgPool2D()
            self.out = nn.Dense(classes)

    def forward(self, x, rs=None):
        h = self.stem(x)
        training = autograd.is_training() and rs is not None
        for blk, p in zip(self.blocks, self.survival):
            sc = blk.shortcut(h)
            if training:
                if rs.rand() < p:  # block survives this batch
                    h = mx.nd.relu(sc + blk.residual(h))
                else:              # dropped: identity, zero compute
                    h = sc
            else:
                h = mx.nd.relu(sc + blk.residual(h) * p)
        return self.out(self.pool(h))


def main():
    ap = argparse.ArgumentParser(description="stochastic-depth resnet")
    ap.add_argument("--num-epochs", type=int, default=6)
    ap.add_argument("--num-examples", type=int, default=1500)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--num-blocks", type=int, default=6)
    ap.add_argument("--channels", type=int, default=24)
    ap.add_argument("--p-last", type=float, default=0.5)
    ap.add_argument("--lr", type=float, default=2e-3)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    ctx = mx.cpu()
    rs = np.random.RandomState(1)

    data = get_mnist(num_train=args.num_examples, num_test=400)
    Xtr, ytr = data["train_data"], data["train_label"]
    Xte, yte = data["test_data"], data["test_label"]

    net = SDResNet(args.num_blocks, args.channels, 10, args.p_last)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    # materialize every block's params (training may drop a block before
    # its first use; the eval path touches all of them)
    net(mx.nd.zeros((1, 1, 28, 28), ctx=ctx))
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    nb = args.num_examples // args.batch_size
    t0 = time.time()
    for epoch in range(args.num_epochs):
        tot, dropped = 0.0, 0
        perm = rs.permutation(args.num_examples)
        for b in range(nb):
            idx = perm[b * args.batch_size:(b + 1) * args.batch_size]
            x = mx.nd.array(Xtr[idx], ctx=ctx)
            y = mx.nd.array(ytr[idx], ctx=ctx)
            with autograd.record():
                loss = sce(net(x, rs), y)
            loss.backward()
            # a dropped block took no part in this batch
            trainer.step(args.batch_size, ignore_stale_grad=True)
            tot += float(loss.mean().asnumpy())
        logging.info("Epoch[%d] loss=%.4f (%.1fs)", epoch, tot / nb,
                     time.time() - t0)

    logits = net(mx.nd.array(Xte, ctx=ctx)).asnumpy()
    acc = (np.argmax(logits, 1) == yte).mean()
    print("test accuracy %.3f" % acc)


if __name__ == "__main__":
    main()
