"""gluon.data.DataLoader (parity: python/mxnet/gluon/data/dataloader.py:73-124).

The reference forks worker *processes* and ships batches through POSIX
shared memory (CPUSharedStorageManager).  Here workers are a thread pool:
batchification is numpy-side (releases the GIL) and the device transfer is a
single PJRT host-to-HBM DMA per batch — the multiprocess+shm design exists
to feed GPUs from python, which the TPU path doesn't need.  num_workers
keeps its meaning (parallel prefetch depth).
"""
from __future__ import annotations

import concurrent.futures as _futures
import time as _time

import numpy as _np

from ... import ndarray as nd
from ...ndarray import NDArray
from ...observability import metrics as _metrics
from .sampler import BatchSampler, RandomSampler, SequentialSampler


def default_batchify_fn(data):
    """Stack samples into a batch (parity: dataloader.default_batchify_fn).

    NDArray samples stack in ONE device-side dispatch — the old path paid
    a per-sample `asnumpy()` device→host sync plus a re-upload, which made
    batchification O(batch_size) blocking device round trips."""
    if isinstance(data[0], NDArray):
        from ...ndarray.sparse import BaseSparseNDArray
        if not any(isinstance(d, BaseSparseNDArray) for d in data):
            import jax.numpy as jnp
            if _metrics.ENABLED:
                _metrics.XLA_LAUNCHES.inc(kind="data")
            return NDArray(jnp.stack([d._data for d in data]),
                           data[0].context)
        # sparse samples: rows-only storage densifies through the host
        return nd.array(_np.stack([d.asnumpy() for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = _np.asarray(data)
    return nd.array(data, dtype=data.dtype)


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless "
                                 "batch_sampler is specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be specified if sampler "
                                 "is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError("batch_size, shuffle, sampler and last_batch "
                             "must not be specified if batch_sampler is "
                             "specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._batchify_fn = batchify_fn or default_batchify_fn

    def __iter__(self):
        if self._num_workers == 0:
            for batch in self._batch_sampler:
                on = _metrics.ENABLED
                t0 = _time.perf_counter() if on else 0.0
                out = self._batchify_fn([self._dataset[idx] for idx in batch])
                if on:
                    _metrics.DATA_WAIT_SECONDS.observe(
                        _time.perf_counter() - t0)
                yield out
            return
        with _futures.ThreadPoolExecutor(self._num_workers) as pool:
            futures = [pool.submit(
                lambda b: self._batchify_fn([self._dataset[i] for i in b]),
                batch) for batch in self._batch_sampler]
            for fut in futures:
                # time the consumer-side stall, not the worker's build:
                # with enough workers this is ~0 even when batchify is slow
                on = _metrics.ENABLED
                t0 = _time.perf_counter() if on else 0.0
                out = fut.result()
                if on:
                    _metrics.DATA_WAIT_SECONDS.observe(
                        _time.perf_counter() - t0)
                yield out

    def __len__(self):
        return len(self._batch_sampler)


# parity alias: the reference's multiprocessing batchify is the same
# stacking logic (shared-memory pickling is a CUDA-host concern the
# jax.Array path doesn't have)
default_mp_batchify_fn = default_batchify_fn
