"""Child-sum Tree-LSTM sentiment classification on synthetic trees.

Parity: /root/reference/example/gluon/tree_lstm/ (Tai 2015 child-sum
TreeLSTM over parse trees; the reference trains on SICK, which needs a
download — this zero-egress version builds synthetic sentiment trees
whose label is determined by a recursive polarity rule, so learning it
requires genuinely composing children).

TPU-native notes: tree recursion is data-dependent control flow, so the
cell runs eagerly per node (like the reference's imperative gluon code);
each node's gates are one fused CachedOp-style dispatch and the per-tree
backward is the autograd tape.
"""
import argparse
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn


class ChildSumLSTMCell(gluon.Block):
    """h = TreeLSTM(x, children h/c): child-sum formulation (Tai eq. 2-8)."""

    def __init__(self, hidden, embed, **kw):
        super().__init__(**kw)
        self.hidden = hidden
        with self.name_scope():
            # explicit in_units: the forget-gate layers first run only on
            # the first tree that has children, which may be mid-epoch —
            # deferred shape inference would land inside autograd.record
            self.iou_x = nn.Dense(3 * hidden, in_units=embed)
            self.iou_h = nn.Dense(3 * hidden, use_bias=False,
                                  in_units=hidden)
            self.f_x = nn.Dense(hidden, in_units=embed)
            self.f_h = nn.Dense(hidden, use_bias=False, in_units=hidden)

    def forward(self, x, child_h, child_c):
        """x: (1, D); child_h/child_c: list of (1, H)."""
        if child_h:
            h_sum = child_h[0]
            for h in child_h[1:]:
                h_sum = h_sum + h
        else:
            h_sum = mx.nd.zeros((1, self.hidden), ctx=x.context)
        iou = self.iou_x(x) + self.iou_h(h_sum)
        i = mx.nd.sigmoid(iou[:, :self.hidden])
        o = mx.nd.sigmoid(iou[:, self.hidden:2 * self.hidden])
        u = mx.nd.tanh(iou[:, 2 * self.hidden:])
        c = i * u
        if child_h:
            fx = self.f_x(x)  # shared across children (W_f x, Tai eq. 4)
            for h, cc in zip(child_h, child_c):
                f = mx.nd.sigmoid(fx + self.f_h(h))
                c = c + f * cc
        h = o * mx.nd.tanh(c)
        return h, c


class TreeNet(gluon.Block):
    def __init__(self, vocab, embed, hidden, classes, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, embed)
            self.cell = ChildSumLSTMCell(hidden, embed)
            self.out = nn.Dense(classes)

    def encode(self, tree, ctx):
        tok, children = tree
        ch = [self.encode(c, ctx) for c in children]
        x = self.embed(mx.nd.array([tok], ctx=ctx))
        h, c = self.cell(x, [h for h, _ in ch], [c for _, c in ch])
        return h, c

    def forward(self, tree, ctx):
        h, _ = self.encode(tree, ctx)
        return self.out(h)


def make_tree(rs, vocab, depth):
    """(token, children).  Polarity rule: NEG tokens (second half of the
    vocab) flip the subtree sentiment; leaf sentiment = token parity."""
    tok = int(rs.randint(0, vocab))
    if depth == 0 or rs.rand() < 0.3:
        return (tok, []), tok % 2
    n = int(rs.randint(1, 3))
    children, sent = [], 0
    for _ in range(n):
        c, s = make_tree(rs, vocab, depth - 1)
        children.append(c)
        sent += s
    sent = 1 if sent >= (n + 1) // 2 else 0
    if tok >= vocab // 2:  # negation head flips
        sent = 1 - sent
    return (tok, children), sent


def main():
    ap = argparse.ArgumentParser(description="child-sum TreeLSTM")
    ap.add_argument("--num-trees", type=int, default=200)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--vocab", type=int, default=20)
    ap.add_argument("--embed", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--depth", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.01)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    rs = np.random.RandomState(7)
    data = [make_tree(rs, args.vocab, args.depth)
            for _ in range(args.num_trees)]
    ctx = mx.cpu()
    net = TreeNet(args.vocab, args.embed, args.hidden, 2)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    tot, correct = 0.0, 0
    for epoch in range(args.epochs):
        tot, correct = 0.0, 0
        for tree, label in data:
            y = mx.nd.array([label], ctx=ctx)
            with autograd.record():
                logits = net(tree, ctx)
                loss = sce(logits, y)
            loss.backward()
            # a tree that is a single leaf never calls f_x / f_h
            trainer.step(1, ignore_stale_grad=True)
            tot += float(loss.asnumpy())
            correct += int(np.argmax(logits.asnumpy()) == label)
        logging.info("Epoch[%d] loss=%.4f acc=%.3f", epoch,
                     tot / len(data), correct / len(data))
    if args.epochs > 0:
        print("final acc %.3f" % (correct / len(data)))


if __name__ == "__main__":
    main()
