"""Sparse benchmark suite (parity: /root/reference/benchmark/python/
sparse/{dot,cast_storage,sparse_op,sparse_end2end}.py — the reference
times csr dot vs dense dot, cast_storage conversions, elementwise
sparse ops, and an end-to-end sparse linear model; this single harness
covers the same four tiers with synthetic data and prints one line per
measurement).

On TPU, in-graph compute is dense by design (XLA has no first-class
sparsity; PARITY.md documents the divergence) — what these benchmarks
measure here is the ROWS-ONLY storage tier: construction, conversions,
rows-only gradient deposit, and the lazy sparse optimizer path, i.e.
the paths whose asymptotics the reference's sparse storage bought.

    python sparse_bench.py [--quick]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", ".."))
import mxnet_tpu as mx
from mxnet_tpu import nd


def _sync(out):
    """Force the result to materialize — jax dispatch is async and
    engine waitall only covers host-side ops, so timing must block on
    the device buffers themselves (a host fetch does)."""
    if isinstance(out, (list, tuple)):
        for o in out:
            _sync(o)
    elif hasattr(out, "_values"):  # sparse: nnz storage
        np.asarray(out._values)
    elif hasattr(out, "asnumpy"):
        out.asnumpy()
    elif out is not None:
        np.asarray(out)


def timeit(fn, repeat=10):
    """ms/call; fn must RETURN what it computes so the timer can sync
    every result (the previous cut timed async dispatch only — dense
    65536x1024 dot 'took' 0.03 ms)."""
    _sync(fn())  # warm (compile)
    t0 = time.perf_counter()
    outs = [fn() for _ in range(repeat)]
    _sync(outs)
    return (time.perf_counter() - t0) / repeat * 1e3


def bench_dot(rows, dim, density, repeat, n_out=64):
    """csr dot vs dense dot (reference dot.py).  Times csr under both
    forced paths plus the auto heuristic's pick — the data behind the
    nnz/dense cutoff in ndarray/sparse.py:_dot_sparse_ex."""
    rs = np.random.RandomState(0)
    dense = rs.normal(0, 1, (rows, dim)).astype("f")
    mask = rs.rand(rows, dim) < density
    sp = np.where(mask, dense, 0).astype("f")
    w = nd.array(rs.normal(0, 1, (dim, n_out)).astype("f"))
    csr = nd.sparse.array(sp).tostype("csr")
    dns = nd.array(sp)

    def forced(mode):
        prev = os.environ.get("MXNET_SPARSE_DOT")
        os.environ["MXNET_SPARSE_DOT"] = mode
        try:
            return timeit(lambda: nd.sparse.dot(csr, w), repeat)
        finally:
            if prev is None:
                os.environ.pop("MXNET_SPARSE_DOT", None)
            else:
                os.environ["MXNET_SPARSE_DOT"] = prev

    t_nnz = forced("nnz")
    t_csr_dense = forced("dense")
    t_auto = forced("auto")
    t_dns = timeit(lambda: nd.dot(dns, w), repeat)
    from mxnet_tpu.ndarray.sparse import _dot_use_nnz
    pick = "nnz" if _dot_use_nnz(int(csr.data.shape[0]), rows, dim,
                                 n_out, 4) else "dense"
    print("dot        rows=%d dim=%d N=%d density=%.2f: csr[nnz] %.2f ms  "
          "csr[dense] %.2f ms  csr[auto->%s] %.2f ms  dense %.2f ms"
          % (rows, dim, n_out, density, t_nnz, t_csr_dense, pick, t_auto,
             t_dns))


def bench_cast_storage(rows, dim, density, repeat):
    """dense<->rsp/csr conversions (reference cast_storage.py)."""
    rs = np.random.RandomState(1)
    x = rs.normal(0, 1, (rows, dim)).astype("f")
    x[rs.rand(rows) > density] = 0  # sparse ROWS
    dns = nd.array(x)
    rsp = dns.tostype("row_sparse")
    t_to_rsp = timeit(lambda: dns.tostype("row_sparse"), repeat)
    t_to_csr = timeit(lambda: dns.tostype("csr"), repeat)
    t_back = timeit(lambda: rsp.tostype("default"), repeat)
    print("cast       rows=%d dim=%d density=%.2f: ->rsp %.2f ms  "
          "->csr %.2f ms  rsp->dense %.2f ms"
          % (rows, dim, density, t_to_rsp, t_to_csr, t_back))


def bench_sparse_op(vocab, dim, batch, repeat):
    """rows-only embedding gradient (reference sparse_op.py's
    embedding/take tier): forward lookup + sparse_grad backward."""
    from mxnet_tpu import autograd, gluon
    emb = gluon.nn.Embedding(vocab, dim, sparse_grad=True)
    emb.initialize(mx.init.Normal(0.1))
    rs = np.random.RandomState(2)
    ids = nd.array(rs.randint(0, vocab, batch).astype("f"))

    def step():
        with autograd.record():
            out = emb(ids).sum()
        out.backward()
        return emb.weight.grad()

    g = step()
    stype = g.stype if hasattr(g, "stype") else "default"
    t = timeit(step, repeat)
    print("embedding  vocab=%d dim=%d batch=%d: fwd+sparse-bwd %.2f ms "
          "(grad stype=%s)" % (vocab, dim, batch, t, stype))


def bench_end2end(rows, dim, batch, epochs):
    """Sparse linear classification end to end (reference
    sparse_end2end.py): LibSVM-style CSR batches through Module."""
    rs = np.random.RandomState(3)
    w_true = rs.normal(0, 1, dim).astype("f")
    xs = np.where(rs.rand(rows, dim) < 0.05,
                  rs.normal(0, 1, (rows, dim)), 0).astype("f")
    y = (xs @ w_true > 0).astype("f")

    data = mx.sym.Variable("data", stype="csr")
    out = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=2, name="fc"),
        name="softmax")

    class CSRIter(mx.io.DataIter):
        """NDArrayIter wrapper yielding CSR data batches (the
        reference's sparse_end2end reads LibSVM CSR directly)."""

        def __init__(self, inner):
            super().__init__(inner.batch_size)
            self._it = inner
            self.provide_data = inner.provide_data
            self.provide_label = inner.provide_label

        def reset(self):
            self._it.reset()

        def next(self):
            b = self._it.next()
            b.data = [d.tostype("csr") for d in b.data]
            return b

    it = CSRIter(mx.io.NDArrayIter(xs, y, batch, shuffle=False,
                                   label_name="softmax_label"))
    mod = mx.mod.Module(out)
    t0 = time.perf_counter()
    mod.fit(it, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    dt = time.perf_counter() - t0
    acc = mod.score(it, mx.metric.Accuracy())[0][1]
    print("end2end    rows=%d dim=%d: %d epochs in %.2f s (acc %.2f)"
          % (rows, dim, epochs, dt, acc))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes for CI smoke")
    args = ap.parse_args()
    if args.quick:
        bench_dot(512, 256, 0.05, 3)
        bench_cast_storage(512, 64, 0.1, 3)
        bench_sparse_op(2048, 32, 128, 3)
        bench_end2end(512, 128, 64, 2)
    else:
        bench_dot(65536, 1024, 0.01, 10)
        bench_dot(65536, 1024, 0.10, 10)
        bench_cast_storage(65536, 128, 0.05, 10)
        bench_sparse_op(1000000, 128, 1024, 10)
        bench_end2end(16384, 4096, 256, 3)
    print("sparse bench done")


if __name__ == "__main__":
    main()
