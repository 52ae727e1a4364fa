"""KVStore: the distributed key-value parameter store.

Reference parity: `include/mxnet/kvstore.h:47`, `src/kvstore/` (local comm
tree-reduce, NCCL collectives, ps-lite dist_sync/dist_async — SURVEY.md §2.3)
and `python/mxnet/kvstore.py`.

TPU-native design (SURVEY.md §2.3 "TPU-native equivalent"):
  - 'local' / 'device': in-process aggregation across per-device copies —
    push reduces (sum) the listed values, pull broadcasts; XLA executes the
    reduce as one fused kernel.  (replaces CommCPU/CommDevice, comm.h:102,484)
  - 'tpu_sync' (also accepted: 'nccl', 'dist_sync', 'dist_device_sync'):
    synchronous data parallelism over the ICI mesh.  Within one process,
    device-parallel gradients are averaged by XLA all-reduce (jnp sum over
    stacked device shards → compiler collective); across processes
    (multi-host pods), push/pull lower to `jax.lax.psum` inside a
    `shard_map` over the global mesh — see `mxnet_tpu.parallel`.  rank =
    jax.process_index(), num_workers = jax.process_count().
  - 'dist_async' has no ICI analog (parameter-server asynchrony); it is
    accepted and runs synchronously (documented divergence).
  - gradient compression: the reference's 2-bit stochastic quantization
    with error feedback (`src/kvstore/gradient_compression.h:37-134`) is
    implemented here as jit-compiled XLA ops (quantize/pack into uint8,
    4 codes/byte; per-key residual carries the quantization error forward).
    On ICI it is off by default (bandwidth makes it unnecessary); when
    enabled via `set_gradient_compression` it is applied on the push path —
    the useful case is DCN-connected multi-slice training.  The fused
    Trainer path composes it with bucketed allreduce:
    `allreduce(values, compression=..., residuals=...)` quantizes flat
    gradient buckets against flat residuals in one program and ships only
    the packed payload on the dist leg (worker-quantize /
    dequantize-sum split, parity: kvstore_dist.h PushCompressed).
"""
from __future__ import annotations

import functools
import pickle
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as _np

from .analysis import hot_path
from .base import MXNetError, atomic_write, getenv
from .faultinject import fire as _fi_fire
from . import ndarray as nd
from .ndarray import NDArray
from .observability import memory as _memory
from .observability import metrics as _metrics
from .observability.tracing import span
from . import optimizer as opt


def _nd_bytes(v) -> int:
    """Byte size of an NDArray / sparse NDArray / raw jax array.  Sparse
    is checked FIRST: RowSparseNDArray._data is a densifying property, so
    going through it would dispatch an O(N) scatter-add per accounted
    value and report dense bytes instead of nnz bytes."""
    iv = getattr(v, "_values", None)
    if iv is not None:
        ii = getattr(v, "_indices", None)
        return int((getattr(iv, "nbytes", 0) or 0)
                   + (getattr(ii, "nbytes", 0) or 0))
    d = getattr(v, "_data", v)
    return int(getattr(d, "nbytes", 0) or 0)


def _handoff(src: NDArray, dst: NDArray) -> None:
    """Pull a store value into `dst`.  Arrays are immutable jax values, so
    when dtype and placement already match this is a pointer hand-off —
    zero device operations — instead of the reference's engine CopyTo.
    Per-key device_puts here were the Module.update bottleneck (one
    transfer per parameter per step)."""
    from .ndarray.sparse import RowSparseNDArray
    if isinstance(dst, RowSparseNDArray):
        if isinstance(src, RowSparseNDArray):
            dst._assign_rows(src._indices, src._values)
        else:
            from .ndarray.sparse import row_sparse_array
            rs = row_sparse_array(src)
            dst._assign_rows(rs._indices, rs._values)
        return
    sd, dd = src._data, dst._data
    if (sd.dtype == dd.dtype and
            getattr(sd, "sharding", None) == getattr(dd, "sharding", None)):
        dst._set_data(sd)
    else:
        src.copyto(dst)


def _quantize_2bit_impl(arr, residual, threshold):
    """2-bit quantization with error feedback (pure; traceable inside any
    outer jit — the fused pushpull path inlines it).

    Parity: GradientCompression::Quantize2Bit
    (`src/kvstore/gradient_compression.h:111`, kernel in
    gradient_compression-inl.h): r = grad + residual; elements >= +T map to
    +T (code 1), <= -T map to -T (code 2), else 0 (code 0); the residual
    keeps r - quantized so the error feeds the next step.  Codes are packed
    four-per-byte (the reference packs 16 per float32 — same 2 bits/elt).
    """
    r = arr.astype(jnp.float32) + residual
    pos = r >= threshold
    neg = r <= -threshold
    out = jnp.where(pos, threshold, jnp.where(neg, -threshold, 0.0))
    new_residual = r - out
    codes = jnp.where(pos, 1, jnp.where(neg, 2, 0)).astype(jnp.uint8).ravel()
    n = codes.shape[0]
    pad = (-n) % 4
    codes = jnp.pad(codes, (0, pad)).reshape(-1, 4)
    packed = (codes[:, 0] | (codes[:, 1] << 2) | (codes[:, 2] << 4)
              | (codes[:, 3] << 6))
    return packed, new_residual


def _dequantize_2bit_impl(packed, threshold, size):
    """Parity: GradientCompression::Dequantize2Bit (pure; traceable)."""
    codes = jnp.stack([packed & 3, (packed >> 2) & 3, (packed >> 4) & 3,
                       (packed >> 6) & 3], axis=1).ravel()[:size]
    return jnp.where(codes == 1, threshold,
                     jnp.where(codes == 2, -threshold, 0.0))


_quantize_2bit = jax.jit(_quantize_2bit_impl,
                         static_argnames=("threshold",))
_dequantize_2bit = jax.jit(_dequantize_2bit_impl,
                           static_argnames=("threshold", "size"))


# -- bucket-level compressed allreduce programs -------------------------------
# The quantizer is purely elementwise, so running it over FLAT GRADIENT
# BUCKETS (kvstore.GradBucketer) with flat residual buffers preserves the
# reference's per-parameter error-feedback semantics exactly — each
# parameter's residual occupies its own slice of the bucket residual.
# That is what lets 2-bit compression compose with the O(1)-dispatch fused
# Trainer path instead of forcing the O(num_params) per-key loop.
# jax.jit keys these module-level programs on bucket shapes + threshold,
# so a signature change re-selects a cached program rather than retracing
# under the same entry (same dispatch-stability rule as FusedUpdater).

def _quantize_buckets_impl(flats, residuals, threshold):
    """Per-bucket quantize with the residual update fused into the SAME
    program (worker-side half of kvstore_dist.h PushCompressed) — one
    launch for every bucket.  Also emits each bucket's mean |error| (=
    mean |new residual|) so the compression_error histogram costs no
    extra program."""
    packeds, new_res, errs = [], [], []
    for f, r in zip(flats, residuals):
        packed, nr = _quantize_2bit_impl(f.reshape(-1), r, threshold)
        packeds.append(packed)
        new_res.append(nr)
        errs.append(jnp.mean(jnp.abs(nr)))
    return packeds, new_res, errs


def _dequantize_sum_impl(stacks, threshold, shapes, dtypes):
    """Dequantize every worker's packed payload and sum — the
    server-side half of the reference split (kvstore_dist_server.h
    DecompressAndMerge), one launch for every bucket.  stacks[k] is
    (num_workers, packed_len) uint8."""
    outs = []
    for st, shape, dt in zip(stacks, shapes, dtypes):
        size = 1
        for s in shape:
            size *= s
        vals = jax.vmap(
            lambda p, _t=threshold, _n=size: _dequantize_2bit_impl(
                p, _t, _n))(st)
        outs.append(jnp.sum(vals, axis=0).reshape(shape).astype(dt))
    return outs


def _compressed_reduce_local_impl(flats, residuals, threshold):
    """Single-process compressed reduce: quantize + residual update +
    dequantize fused into ONE program (there is no wire to cross, but
    the quantize→dequantize round trip must still run so training sees
    the same error-feedback trajectory as a multi-host pod — and as the
    reference's per-key path)."""
    packeds, new_res, errs = _quantize_buckets_impl(flats, residuals,
                                                    threshold)
    outs = [_dequantize_2bit_impl(p, threshold, f.size)
            .reshape(f.shape).astype(f.dtype)
            for p, f in zip(packeds, flats)]
    return outs, new_res, errs


# single-process: residuals (argnum 1) are donated — one fused program,
# the caller always replaces its copy with the returned one, so the old
# grad-sized f32 buffers back the new values in place.  The multi-host
# _quantize_buckets deliberately does NOT donate: the all-gather wire
# leg runs between quantize and the caller's reassignment, and a
# transient DCN failure there must leave the caller's residuals valid
# for retry, not pointing at deleted buffers.
_quantize_buckets = jax.jit(_quantize_buckets_impl,
                            static_argnames=("threshold",))
_compressed_reduce_local = jax.jit(_compressed_reduce_local_impl,
                                   static_argnames=("threshold",),
                                   donate_argnums=(1,))
_dequantize_sum = jax.jit(_dequantize_sum_impl,
                          static_argnames=("threshold", "shapes", "dtypes"))


def reduce_buckets_inline(flats, residuals, threshold):
    """Pure single-process compressed bucket reduce for tracing INSIDE an
    outer jit: quantize + residual update + dequantize, no metrics, no
    NDArray wrapping, no dispatch of its own.  The gluon whole-step
    compiler (`gluon/wholestep.py`) inlines this into its one-program
    training step so 2-bit error feedback composes with whole-step
    compilation at zero extra launches; the math (and therefore the
    residual trajectory) is identical to the fused path's
    `_compressed_reduce_local` program.  Returns (reduced flats, new
    residuals, per-bucket mean |error|)."""
    return _compressed_reduce_local_impl(flats, residuals, threshold)


def reduce_rowsparse_inline(ids_parts, rows_parts, size=None, dedup=True,
                            fill=None):
    """Pure row-sparse gradient reduce (ISSUE 20): unique-concat +
    segment-sum over gathered (ids, rows) pairs, traceable INSIDE an
    outer jit exactly like ``reduce_buckets_inline`` — no metrics, no
    NDArray wrapping, no dispatch of its own.  The gluon whole-step
    compiler inlines this math into its donated one-program step; the
    eager ``KVStore.allreduce_rowsparse`` wrapper runs the same ops so
    the two trajectories stay bitwise-interchangeable.

    ``ids_parts``: int id vectors (one per gathered shard/copy);
    ``rows_parts``: the matching ``(n_i, ...)`` row blocks.  Returns
    ``(ids, rows)`` with ids sorted-unique and rows segment-summed
    (``zeros.at[inverse].add`` — the same op ``RowSparseNDArray``'s
    dedup uses, so already-unique input round-trips bitwise).

    ``size``: static output length for jit tracing (pad tail ids with
    ``fill``, default ``iinfo(ids.dtype).max`` — positively out of
    range for every table, so a downstream ``.at[ids].set/add(...,
    mode="drop")`` scatter ignores the padding; NEVER a negative fill,
    which python indexing would wrap onto real rows).  ``size=None``
    returns the exact nnz (eager use only — data-dependent shape).

    ``dedup=False`` (the ``MXNET_EMBED_DEDUP_IDS=0`` wire format) skips
    the unique pass and returns the raw concatenation — token-duplicate
    ids stay on the wire and the consumer (the fused sparse updater /
    whole-step scatter leg) performs the segment-sum itself."""
    ids = jnp.concatenate([jnp.ravel(i) for i in ids_parts])
    rows = jnp.concatenate(list(rows_parts))
    if not dedup:
        return ids, rows
    if fill is None:
        fill = jnp.iinfo(ids.dtype).max
    if size is None:
        uids, inv = jnp.unique(ids, return_inverse=True)
        n = int(uids.shape[0])
    else:
        n = int(size)
        uids, inv = jnp.unique(ids, size=n, fill_value=fill,
                               return_inverse=True)
    summed = jnp.zeros((n,) + rows.shape[1:], rows.dtype) \
        .at[jnp.ravel(inv)].add(rows)
    return uids, summed


class GradientCompression:
    """Parity: `src/kvstore/gradient_compression.h:37` — holds type +
    threshold; quantize/dequantize as XLA-compiled kernels."""

    def __init__(self, type="2bit", threshold=0.5):
        if type != "2bit":
            raise MXNetError("Unknown type for gradient compression " + type)
        if threshold <= 0:
            raise MXNetError("threshold must be greater than 0")
        self.type = type
        self.threshold = float(threshold)

    def quantize(self, grad: NDArray, residual):
        """Returns (packed uint8 NDArray — 4 elements/byte, new residual)."""
        packed, new_res = _quantize_2bit(grad.handle, residual,
                                         self.threshold)
        return NDArray(packed, grad.context), new_res

    def dequantize(self, packed: NDArray, shape) -> NDArray:
        size = 1
        for s in shape:
            size *= s
        vals = _dequantize_2bit(packed.handle, self.threshold, size)
        return NDArray(vals.reshape(shape), packed.context)

    def get_params(self):
        return {"type": self.type, "threshold": self.threshold}


class GradBucketer:
    """Size-capped dense-gradient bucketing for O(1)-dispatch allreduce.

    The reference allreduces one engine push per key (kvstore_local.h); here
    all dense grads are grouped into dtype-homogeneous, order-preserving
    buckets of at most `cap_bytes` (MXNET_BUCKET_SIZE_MB) and each bucket
    crosses the kvstore as ONE flat array — pushes per step become
    O(total grad bytes / cap), independent of parameter count.

    `flatten` runs as a single jitted program over every bucket.  `views`
    maps each input position to (bucket, offset, shape) so
    `FusedUpdater.update_all(grad_views=...)` slices gradients straight out
    of the reduced flat buckets inside its own fused program (un-flattening
    is free on the trainer hot path); `unflatten` materializes per-key
    grads only for the public `Trainer.allreduce_grads()` contract.
    """

    def __init__(self, sig, cap_bytes: int):
        # sig: tuple of (shape, dtype_str) in input order
        self.sig = tuple((tuple(s), str(d)) for s, d in sig)
        self.cap = max(1, int(cap_bytes))
        layout: List[tuple] = []
        cur: List[int] = []
        cur_dtype, cur_bytes = None, 0
        for pos, (shape, dtype) in enumerate(self.sig):
            nbytes = int(_np.dtype(dtype).itemsize * _np.prod(shape)) \
                if shape else _np.dtype(dtype).itemsize
            if cur and (dtype != cur_dtype or cur_bytes + nbytes > self.cap):
                layout.append(tuple(cur))
                cur, cur_bytes = [], 0
            cur.append(pos)
            cur_dtype, cur_bytes = dtype, cur_bytes + nbytes
        if cur:
            layout.append(tuple(cur))
        self.layout = tuple(layout)
        self.views: List[tuple] = [None] * len(self.sig)
        sizes: List[int] = []
        for b, bucket in enumerate(self.layout):
            off = 0
            for pos in bucket:
                shape, _ = self.sig[pos]
                size = int(_np.prod(shape)) if shape else 1
                self.views[pos] = (b, off, shape)
                off += size
            sizes.append(off)
        # total elements per flat bucket — the Trainer sizes its
        # error-feedback residual buffers off this
        self.sizes = tuple(sizes)
        lay, sig_ = self.layout, self.sig

        def flattener(buckets):
            def mx_kv_flatten(gs):
                return [jnp.concatenate([gs[p].reshape(-1) for p in bucket])
                        if len(bucket) > 1 else gs[bucket[0]].reshape(-1)
                        for bucket in buckets]
            return mx_kv_flatten

        mx_kv_flatten = flattener(lay)

        def mx_kv_unflatten(flats):
            out = [None] * len(sig_)
            for b, bucket in enumerate(lay):
                off = 0
                for p in bucket:
                    shape = sig_[p][0]
                    size = int(_np.prod(shape)) if shape else 1
                    out[p] = flats[b][off:off + size].reshape(shape)
                    off += size
            return out

        # pure, jit-inlinable forms (no metrics, no dispatch of their
        # own): the whole-step compiler traces these inside its single
        # training-step program instead of issuing the jitted wrappers
        self.flatten_inline = mx_kv_flatten
        self.unflatten_inline = mx_kv_unflatten
        self._flatten = jax.jit(mx_kv_flatten)
        self._unflatten = jax.jit(mx_kv_unflatten)
        # the buckets `flatten` copies: those of several gradients (and of
        # a scalar's); a bucket of one array is that gradient itself
        self._several = tuple(b for b, bucket in enumerate(lay)
                              if len(bucket) > 1 or not sig_[bucket[0]][0])
        self._flatten_several = jax.jit(
            flattener([lay[b] for b in self._several]))

    @hot_path
    def flatten(self, grads: List, whole: bool = True) -> List:
        """Raw jax arrays in sig order -> the buckets, in one dispatch.  A
        bucket of several gradients is one flat array; a bucket of one is
        that gradient as it is, not copied: a view (bucket, 0, shape)
        slices it over its size along the first axis and reshapes, which
        reads the whole array.  At the chip's memory the copy of the large
        gradients was what stalled a step (PERF.md, Open questions 24).
        `whole=False` makes every bucket flat (the 2-bit reduce's flat
        residuals need that)."""
        if _metrics.ENABLED:
            _metrics.ALLREDUCE_BUCKETS.set(len(self.layout))
        if not whole:
            if _metrics.ENABLED:
                _metrics.XLA_LAUNCHES.inc(kind="allreduce")
            return self._flatten(grads)
        out = [grads[bucket[0]] for bucket in self.layout]
        if self._several:
            if _metrics.ENABLED:
                _metrics.XLA_LAUNCHES.inc(kind="allreduce")
            for b, flat in zip(self._several, self._flatten_several(grads)):
                out[b] = flat
        return out

    @hot_path
    def unflatten(self, flats: List) -> List:
        """Flat bucket arrays -> per-key arrays (one dispatch)."""
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="allreduce")
        return self._unflatten(flats)


def _key_list(key):
    if isinstance(key, (int, str)):
        return [key], False
    return list(key), True


def _val_list(value):
    if isinstance(value, NDArray):
        return [[value]]
    if isinstance(value, (list, tuple)):
        if value and isinstance(value[0], NDArray):
            return [list(value)]
        return [list(v) if isinstance(v, (list, tuple)) else [v] for v in value]
    raise MXNetError("invalid kvstore value")


class KVStore:
    def __init__(self, kv_type: str = "local"):
        self.type = kv_type
        self._store: Dict = {}
        self._updater = None
        self._update_on_kvstore = True
        self._compression_params = None
        self._gc: Optional[GradientCompression] = None
        self._residuals: Dict = {}
        self._merge_cache: Dict = {}
        self._optimizer = None

    # -- identity -----------------------------------------------------------
    @property
    def rank(self) -> int:
        return jax.process_index() if self.type.startswith(("dist", "tpu")) else 0

    @property
    def num_workers(self) -> int:
        return jax.process_count() if self.type.startswith(("dist", "tpu")) else 1

    # -- core ops -----------------------------------------------------------
    def init(self, key, value) -> None:
        keys, _ = _key_list(key)
        vals = _val_list(value)
        # HBM ledger: the backing store pins one device copy per key —
        # a full model's worth of HBM that the bucketed fast path never
        # touches; attributing it is exactly what makes that cost
        # visible in memory.report()
        with _memory.memory_scope("kvstore"):
            for k, vlist in zip(keys, vals):
                self._store[k] = vlist[0].copy()

    @staticmethod
    def _merge_local(vlist):
        """Reduce per-device copies of one key (parity: comm.h Reduce).
        All-rsp lists take the union-of-rows path — O(sum nnz) concat +
        dedup, never dense — so the updater stays on the lazy path."""
        from .ndarray.sparse import RowSparseNDArray
        if len(vlist) > 1 and all(isinstance(v, RowSparseNDArray)
                                  for v in vlist):
            return RowSparseNDArray(
                jnp.concatenate([v._indices for v in vlist]),
                jnp.concatenate([v._values for v in vlist]),
                vlist[0].shape, vlist[0].context)
        merged = vlist[0]
        for v in vlist[1:]:
            merged = merged + v
        return merged

    def _global_dense(self, k, merged):
        """Cross-host leg for one dense key: compress (dist only), then
        DCN all-reduce (parity: kvstore_dist.h PushCompressed)."""
        if self._gc is not None:
            merged = self._compress(k, merged)
        return self._allreduce(merged)

    def _apply_merged(self, k, merged) -> None:
        """Updater-or-assign for one key's globally-merged value."""
        if self._updater is not None:
            if k not in self._store:
                raise MXNetError(f"key {k} has not been inited")
            self._updater(_updater_key(k), merged, self._store[k])
        else:
            # parity: kvstore_local.h:191 — assign, not accumulate
            self._store[k] = merged.copy()

    def push(self, key, value, priority: int = 0) -> None:
        """Aggregate `value` (list = per-device copies) into the store.
        If an optimizer is set (update_on_kvstore), applies the update."""
        with span("kvstore_push", cat="kvstore") as sp:
            self._push_impl(key, value, priority)
        if _metrics.ENABLED:
            # success path only: a failed push must not count as pushed
            _metrics.KVSTORE_ALLREDUCE_SECONDS.observe(sp.seconds)
            _metrics.KVSTORE_PUSH_BYTES.inc(sum(
                _nd_bytes(v) for vl in _val_list(value) for v in vl))

    def _push_impl(self, key, value, priority: int = 0) -> None:
        keys, _ = _key_list(key)
        vals = _val_list(value)
        from .ndarray.sparse import RowSparseNDArray
        for k, vlist in zip(keys, vals):
            merged = self._merge_local(vlist)
            if isinstance(merged, RowSparseNDArray):
                # rows-only cross-host union: ship rows+indices over DCN
                # (parity: kvstore_dist.h rsp push; compression applies
                # to dense grads only, as in the reference)
                if self.num_workers > 1 and self.type != "local":
                    from .parallel import collectives
                    ids, vls = collectives.allgather_rows(
                        merged._indices, merged._values)
                    merged = RowSparseNDArray(ids, vls, merged.shape,
                                              merged.context)
            else:
                merged = self._global_dense(k, merged)
            self._apply_merged(k, merged)

    def pushpull(self, key, value, out=None, priority: int = 0) -> None:
        """Fused push+pull over MANY keys in O(1) XLA dispatches.

        The TPU redesign of the reference's per-key engine pushes
        (`_update_params_on_kvstore`, model.py:126): device-copy merge +
        gradient compression trace into one jitted program, the optimizer
        applies to every key via FusedUpdater.update_all (one more program),
        and pull is a pointer hand-off.  Semantics are identical to
        push(key, value); pull(key, out) — verified by tests/test_kvstore.py.
        """
        with span("mx.kvstore.pushpull", cat="kvstore") as sp:
            self._pushpull_impl(key, value, out, priority)
        if _metrics.ENABLED:
            # success path only: a failed pushpull must not count bytes
            _metrics.KVSTORE_ALLREDUCE_SECONDS.observe(sp.seconds)
            _metrics.KVSTORE_PUSH_BYTES.inc(sum(
                _nd_bytes(v) for vl in _val_list(value) for v in vl))
            if out is not None:
                _metrics.KVSTORE_PULL_BYTES.inc(sum(
                    _nd_bytes(o) for ol in _val_list(out) for o in ol))

    def _pushpull_impl(self, key, value, out=None, priority: int = 0) -> None:
        keys, _ = _key_list(key)
        vals = _val_list(value)
        for k in keys:
            if k not in self._store:
                raise MXNetError(f"key {k} has not been inited")
        from .ndarray.sparse import BaseSparseNDArray, RowSparseNDArray
        if any(isinstance(v, BaseSparseNDArray) for vl in vals for v in vl):
            # sparse values keep their storage class (row-sparse lazy
            # updates; parity: kvstore_local.h rsp).  The cross-host
            # union for ALL rsp keys is batched into one two-program
            # collective per step (VERDICT r3 #4) — dense keys and the
            # updater stay per-key.
            outs = _val_list(out) if out is not None else [None] * len(keys)
            # one local merge per key OCCURRENCE (repeated keys apply
            # each occurrence's gradient, like the per-key push path)
            merged_all = [self._merge_local(vl) for vl in vals]
            if self.num_workers > 1 and self.type != "local":
                rsp_pos = [i for i, m in enumerate(merged_all)
                           if isinstance(m, RowSparseNDArray)]
                if rsp_pos:
                    from .parallel import collectives
                    got = collectives.allgather_rows_many(
                        [(merged_all[i]._indices, merged_all[i]._values)
                         for i in rsp_pos])
                    for i, (ids, vls) in zip(rsp_pos, got):
                        m = merged_all[i]
                        merged_all[i] = RowSparseNDArray(
                            ids, vls, m.shape, m.context)
            for k, m, ol in zip(keys, merged_all, outs):
                if not isinstance(m, RowSparseNDArray):
                    m = self._global_dense(k, m)
                self._apply_merged(k, m)
                if ol is not None:
                    self.pull(k, out=ol)
            return
        if any(len(v) > 1 for v in vals) or self._gc is not None:
            merged = self._fused_merge(keys, vals)
        else:
            merged = [v[0]._data if isinstance(v[0], NDArray) else v[0]
                      for v in vals]
        if self.num_workers > 1 and self.type != "local":
            from .parallel import collectives
            merged = collectives.allreduce_hosts_many(merged)
        if self._updater is not None:
            if isinstance(self._updater, opt.FusedUpdater):
                self._updater.update_all([_updater_key(k) for k in keys],
                                         merged, [self._store[k] for k in keys])
            else:
                for k, m in zip(keys, merged):
                    m = m if isinstance(m, NDArray) else \
                        NDArray(m, self._store[k].context)
                    self._updater(_updater_key(k), m, self._store[k])
        else:
            for k, m in zip(keys, merged):
                m = m if isinstance(m, NDArray) else \
                    NDArray(m, self._store[k].context)
                self._store[k] = m.copy()
        if out is not None:
            outs = _val_list(out)
            for k, olist in zip(keys, outs):
                src = self._store[k]
                for o in olist:
                    if o is not src:
                        _handoff(src, o)

    def _fused_merge(self, keys, vals) -> List:
        """One jitted program: per-key device-copy sum (+2-bit compression
        with error-feedback residuals).  Returns raw jax arrays."""
        gc = self._gc
        thr = gc.threshold if gc is not None else 0.0
        vdata = [[v._data if isinstance(v, NDArray) else v for v in vl]
                 for vl in vals]
        res = []
        if gc is not None:
            for k, vl in zip(keys, vdata):
                r = self._residuals.get(k)
                if r is None:
                    r = jnp.zeros(vl[0].size, dtype=jnp.float32)
                res.append(r)
        fkey = ("merge", tuple(keys), tuple(len(v) for v in vals),
                thr, gc is not None)
        fn = self._merge_cache.get(fkey)
        if fn is None:
            use_gc = gc is not None

            def _m(vlists, residuals):
                outs, new_res = [], []
                for i, vl in enumerate(vlists):
                    m = vl[0]
                    for v in vl[1:]:
                        m = m + v
                    if use_gc:
                        packed, nr = _quantize_2bit_impl(
                            m.reshape(-1), residuals[i], thr)
                        m = _dequantize_2bit_impl(packed, thr, m.size) \
                            .reshape(m.shape).astype(m.dtype)
                        new_res.append(nr)
                    outs.append(m)
                return outs, new_res

            fn = jax.jit(_m, donate_argnums=(1,))
            self._merge_cache[fkey] = fn
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="kvstore_merge")
        merged, new_res = fn(vdata, res)
        if gc is not None:
            for k, nr in zip(keys, new_res):
                self._residuals[k] = nr
        return merged

    def pull(self, key, out=None, priority: int = 0) -> None:
        keys, _ = _key_list(key)
        outs = _val_list(out)
        for k, olist in zip(keys, outs):
            if k not in self._store:
                raise MXNetError(f"key {k} not initialized")
            src = self._store[k]
            for o in olist:
                _handoff(src, o)
            if _metrics.ENABLED:
                _metrics.KVSTORE_PULL_BYTES.inc(
                    _nd_bytes(src) * len(olist))

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None) -> None:
        """Pull only the rows in row_ids (parity: KVStore::PullRowSparse)."""
        keys, _ = _key_list(key)
        outs = _val_list(out)
        rids = row_ids if isinstance(row_ids, (list, tuple)) else [row_ids]
        from .ndarray.sparse import RowSparseNDArray, gather_rows
        for k, olist in zip(keys, outs):
            src = self._store[k]
            for o, rid in zip(olist, rids * len(olist)):
                idx = _np.unique(
                    rid.asnumpy().astype("int64").ravel())
                # device-side gather of just the requested rows —
                # no host round trip, no dense copy (parity:
                # kvstore_local.h PullRowSparse)
                rows = gather_rows(src, idx)
                if isinstance(o, RowSparseNDArray):
                    o._assign_rows(idx, rows)
                else:
                    o._set_data(jnp.zeros(src.shape, rows.dtype)
                                .at[jnp.asarray(idx)].set(rows))

    # -- allreduce across processes (multi-host pods) ------------------------
    def _allreduce(self, merged: NDArray) -> NDArray:
        if self.num_workers <= 1 or self.type == "local":
            return merged
        from .parallel import collectives
        with span("kvstore_allreduce", cat="kvstore"):
            return collectives.allreduce_hosts(merged)

    @hot_path
    def allreduce(self, values: List[NDArray], compression=None,
                  residuals=None):
        """Store-less dense allreduce: sum each value across its per-device
        copies and across hosts, return the reduced arrays.

        For TRANSIENT keys (the Trainer's gradient buckets) — unlike
        push/pull nothing is `init`ed or persisted, so reducing N bytes
        costs no store copy and pins no store memory.  `values` is a list
        with one entry PER VALUE: an NDArray, or that value's
        per-device-copy list of NDArrays.  (Unlike push/pushpull, a flat
        NDArray list here means N distinct values — never N device
        copies of one value.)

        compression: a GradientCompression (or compression_params dict)
        switches on the 2-bit error-feedback leg and changes the return
        to ``(reduced, new_residuals)``.  The intra-host device-copy
        merge stays FULL precision (parity: the reference compresses
        only the worker→server leg, kvstore_dist.h PushCompressed);
        each value is then quantized against its entry in `residuals`
        (flat f32 arrays OWNED BY THE CALLER, zero-initialized here when
        None — note the old arrays are donated to XLA, so the caller
        must replace its copy with the returned ones) and only the
        PACKED payload (4 codes/byte) crosses the dist leg, which
        all-gathers the packed buckets and dequantize-sums them.  On a
        single process the quantize→dequantize round trip still runs —
        same training trajectory as a pod, and as the reference's
        per-key path — fused into one program."""
        vals = [list(v) if isinstance(v, (list, tuple)) else [v]
                for v in values]
        # chaos site: a raise here models a failed gradient collective
        # (dropped pod peer, lost device).  Fires BEFORE any reduce
        # work, so residuals/buckets are untouched and the supervisor's
        # snapshot retry re-executes the step cleanly.  (Whole-step mode
        # inlines the reduce into the donated program — this site only
        # fires on the fused/legacy paths.)
        _fi_fire("kvstore.allreduce", values=len(vals))
        if compression is not None and not isinstance(
                compression, GradientCompression):
            compression = GradientCompression(**compression)
        # no span of its own: the Trainer's mx.trainer.allreduce is
        # this interval (plus the flatten that feeds it)
        t0 = time.perf_counter() if _metrics.ENABLED else 0.0
        out = self._allreduce_impl(vals) if compression is None \
            else self._compressed_allreduce_impl(vals, residuals,
                                                 compression)
        if _metrics.ENABLED:
            _metrics.KVSTORE_ALLREDUCE_SECONDS.observe(
                time.perf_counter() - t0)
            _metrics.KVSTORE_PUSH_BYTES.inc(sum(
                _nd_bytes(v) for vl in vals for v in vl))
        return out

    def _allreduce_impl(self, vals: List[List[NDArray]]) -> List[NDArray]:
        merged = [self._merge_local(vl) for vl in vals]
        raw = [m._data if isinstance(m, NDArray) else m for m in merged]
        if self.num_workers > 1 and self.type != "local":
            from .parallel import collectives
            raw = collectives.allreduce_hosts_many(raw)
        return [r if isinstance(r, NDArray) else NDArray(r, vl[0].context)
                for r, vl in zip(raw, vals)]

    @hot_path
    def allreduce_rowsparse(self, values):
        """Store-less ROW-SPARSE allreduce (ISSUE 20): the sparse twin of
        ``allreduce`` — each value's per-device (ids, rows) pairs reduce
        by unique-concat + segment-sum (``reduce_rowsparse_inline``),
        never densifying the O(vocab) gradient.  For TRANSIENT keys (the
        Trainer's row-sparse embedding grads): nothing is init'ed or
        persisted, so reducing nnz rows costs nnz — not vocab — bytes.

        ``values``: one entry per VALUE — a RowSparseNDArray or that
        value's per-device-copy list.  Returns the reduced
        RowSparseNDArrays (sorted-unique ids, summed rows).

        ``MXNET_EMBED_DEDUP_IDS=0`` keeps token-duplicate ids on the
        wire (the unique pass is skipped here; the fused sparse updater
        segment-sums at the scatter instead) — the knob trades wire rows
        for one fused dedup, and both settings train bitwise-identically
        because the segment-sum runs exactly once either way."""
        from .ndarray import sparse as _sp
        vals = [list(v) if isinstance(v, (list, tuple)) else [v]
                for v in values]
        # chaos site: a raise here models a failed SPARSE gradient
        # collective.  Fires BEFORE any reduce work, so grads and
        # per-row optimizer state are untouched and the supervisor's
        # snapshot retry replays the step bitwise.  (Whole-step mode
        # inlines the sparse reduce into the donated program — this
        # site only fires on the fused/legacy paths.)
        _fi_fire("kvstore.sparse_allreduce", values=len(vals))
        for vl in vals:
            for v in vl:
                if not isinstance(v, _sp.RowSparseNDArray):
                    raise MXNetError(
                        "allreduce_rowsparse expects row_sparse values, "
                        f"got {type(v).__name__}")
        if self.num_workers > 1 and self.type != "local":
            raise MXNetError(
                "multi-host row-sparse allreduce is not wired yet — "
                "cast the gradient to dense storage or train this "
                "parameter single-host (documented in docs/embedding.md)")
        dedup = bool(getenv("MXNET_EMBED_DEDUP_IDS", True))
        t0 = time.perf_counter() if _metrics.ENABLED else 0.0
        out = []
        with span("kvstore_sparse_allreduce", cat="kvstore"):
            for vl in vals:
                if len(vl) == 1 and dedup:
                    # construction guarantees sorted-unique ids — the
                    # single-copy reduce is the identity (rows-only, no
                    # segment-sum rerun: bitwise either way)
                    out.append(vl[0])
                    continue
                ids, rows = reduce_rowsparse_inline(
                    [v._indices for v in vl],
                    [v._values for v in vl], size=None, dedup=dedup)
                out.append(_sp.RowSparseNDArray(
                    ids, rows, shape=vl[0].shape, ctx=vl[0].context,
                    _dedup=not dedup))
        if _metrics.ENABLED:
            _metrics.KVSTORE_ALLREDUCE_SECONDS.observe(
                time.perf_counter() - t0)
            _metrics.KVSTORE_PUSH_BYTES.inc(sum(
                _nd_bytes(v) for vl in vals for v in vl))
        return out

    def _compressed_allreduce_impl(self, vals, residuals,
                                   gc: GradientCompression):
        """2-bit error-feedback allreduce over transient values (the
        Trainer's flat gradient buckets).  Returns (reduced NDArrays,
        new residuals).  Steady-state launches: 1 (fused quantize+
        dequantize+residual) on a single process; 3 (quantize, packed
        all-gather, dequantize-sum) on a multi-host pod — the wire
        moves ~1/16 of the float32 gradient bytes either way."""
        if not vals:
            return [], []
        merged = [self._merge_local(vl) for vl in vals]
        raw = [m._data if isinstance(m, NDArray) else m for m in merged]
        if residuals is None:
            residuals = [jnp.zeros(x.size, dtype=jnp.float32) for x in raw]
        thr = gc.threshold
        dist = self.num_workers > 1 and self.type != "local"
        if _metrics.ENABLED:
            _metrics.XLA_LAUNCHES.inc(kind="allreduce")
        if dist:
            packed, new_res, errs = _quantize_buckets(raw, residuals, thr)
            from .parallel import collectives
            stacks = collectives.allgather_stack_many(packed)
            if _metrics.ENABLED:
                _metrics.XLA_LAUNCHES.inc(2, kind="allreduce")
            out = _dequantize_sum(
                stacks, thr, tuple(tuple(x.shape) for x in raw),
                tuple(str(x.dtype) for x in raw))
        else:
            out, new_res, errs = _compressed_reduce_local(
                raw, residuals, thr)
        if _metrics.ENABLED:
            # wire accounting: dist stage=raw is what full precision
            # WOULD ship per worker; stage=compressed is the packed
            # payload that actually does (on a single process the dist
            # leg is virtual, but the payload math is exact — the CPU
            # acceptance gate reads these)
            _metrics.KVSTORE_WIRE_BYTES.set(
                sum(int(x.nbytes) for x in raw), leg="dist", stage="raw")
            _metrics.KVSTORE_WIRE_BYTES.set(
                sum((int(x.size) + 3) // 4 for x in raw),
                leg="dist", stage="compressed")
            _metrics.KVSTORE_WIRE_BYTES.set(
                sum(_nd_bytes(v) for vl in vals for v in vl),
                leg="intra", stage="raw")
            if getenv("MXNET_COMPRESSION_ERROR_METRIC", True):
                # float() blocks on the reduce program's tiny scalar
                # outputs; =0 skips the sync on latency-critical runs
                for e in errs:
                    _metrics.COMPRESSION_ERROR.observe(float(e))
        return ([o if isinstance(o, NDArray) else NDArray(o, vl[0].context)
                 for o, vl in zip(out, vals)], new_res)

    # -- optimizer plumbing --------------------------------------------------
    def set_optimizer(self, optimizer: "opt.Optimizer") -> None:
        """Run this optimizer on push (parity: server-side optimizer —
        kvstore_dist_server.h ApplyUpdates; here updates run worker-side,
        sharded by XLA, since there are no server processes on ICI)."""
        self._optimizer = optimizer
        self._set_updater(opt.get_updater(optimizer))

    def _set_updater(self, updater) -> None:
        self._updater = updater

    def set_gradient_compression(self, compression_params: Dict) -> None:
        """Parity: python/mxnet/kvstore.py:363 set_gradient_compression —
        like the reference, only dist kvstores support compression (the
        worker→server leg is what it shrinks)."""
        if "type" not in compression_params:
            raise MXNetError("compression_params requires 'type'")
        if not ("device" in self.type or "dist" in self.type
                or self.type.startswith(("tpu", "nccl"))):
            # parity: kvstore.py set_gradient_compression — supported for
            # 'device' and 'dist' kvstores, rejected for CPU-local
            raise MXNetError(
                "gradient compression is not supported on kvstore type "
                f"'{self.type}' (supported: device/dist/tpu_sync/nccl)")
        try:
            self._gc = GradientCompression(**compression_params)
        except TypeError as e:
            raise MXNetError(f"invalid compression_params: {e}") from None
        self._compression_params = self._gc.get_params()
        self._residuals = {}

    def _compress(self, k, v: NDArray) -> NDArray:
        res = self._residuals.get(k)
        if res is None:
            res = jnp.zeros(v.size, dtype=jnp.float32)
        packed, new_res = self._gc.quantize(v.reshape((-1,)), res)
        self._residuals[k] = new_res
        return self._gc.dequantize(packed, v.shape)

    # -- cluster control ------------------------------------------------------
    def barrier(self) -> None:
        """Global barrier (parity: KVStore::Barrier)."""
        if self.num_workers > 1:
            from .parallel import collectives
            collectives.host_barrier()

    def _barrier(self):
        self.barrier()

    def num_dead_node(self, node_id: int = 0, timeout_sec: int = 60) -> int:
        """Parity: kvstore.h:338 — PJRT surfaces device failure as errors, so
        a live call implies zero dead nodes."""
        return 0

    def _send_command_to_servers(self, head, body) -> None:
        pass  # no server processes in the TPU design

    def save_optimizer_states(self, fname: str, dump_optimizer=False) -> None:
        if self._updater is None:
            raise MXNetError("no optimizer set")
        # crash-atomic like every other state writer (PR 5): a save
        # interrupted mid-write must not corrupt the previous states
        atomic_write(fname, self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname: str) -> None:
        if self._updater is None:
            raise MXNetError("no optimizer set")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())


def _updater_key(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k


_TYPES = ("local", "device", "local_allreduce_cpu", "local_allreduce_device",
          "nccl", "tpu_sync", "dist", "dist_sync", "dist_async",
          "dist_device_sync", "dist_sync_device")


def create(name: str = "local") -> KVStore:
    """Create a KVStore (parity: kvstore.cc:38 KVStore::Create)."""
    if not isinstance(name, str) or name not in _TYPES:
        raise MXNetError(f"unknown kvstore type {name}; known: {_TYPES}")
    return KVStore(name)
