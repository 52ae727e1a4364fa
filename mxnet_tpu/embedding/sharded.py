"""``ShardedEmbedding``: a mesh-sharded, row-sparse-gradient embedding
table (ISSUE 20).

Partitioning is BLOCK-mod over rows: with ``S`` shards, shard ``s``
owns the contiguous row range ``[s*vocab/S, (s+1)*vocab/S)`` — exactly
the layout ``PartitionSpec(axis, None)`` commits under GSPMD, so the
"route ids to their owner, return rows" exchange is the gather
collective XLA inserts for a sharded ``jnp.take``, ONE all-to-all each
way per lookup, not hand-written sends.  The block inherits
``nn.Embedding`` math verbatim (``sparse_grad=True`` forced), and adds
the three hooks the rest of the stack keys on:

* ``weight._memory_tag = "embed_shards"`` — the table registers under
  its own HBM-ledger tag (``gluon.Parameter._init_impl`` reads the
  hook), so ``memory.report()`` shows table bytes as their own class
  and the registry cost model can arbitrate against them.
* ``weight._spec_hint`` — ``WholeStepCompiler._bind_graph`` consults
  the hook before ``default_param_spec``, pinning ROW partitioning
  along ``MXNET_EMBED_SHARD_AXIS`` regardless of which table dim is
  larger (the default rule would shard a wide table by columns).
* an ``ensure_headroom`` ask at construction — a table that cannot fit
  the HBM budget fails LOUDLY at build time with the byte count in the
  message, not at first dispatch with an opaque allocator error.
"""
from __future__ import annotations

import numpy as _np
from jax.sharding import PartitionSpec

from ..base import MXNetError
from ..gluon.nn import Embedding
from ..observability import memory as _memory
from ..parallel import mesh as _pmesh


def row_partition_spec(mesh) -> PartitionSpec:
    """The table's GSPMD annotation: rows along ``embed_axis(mesh)``,
    columns replicated; a mesh without the axis (or carrying it at
    size 1) replicates the whole table — same model, no config fork."""
    axis = _pmesh.embed_axis(mesh)
    if axis is None:
        return PartitionSpec()
    return PartitionSpec(axis, None)


class ShardedEmbedding(Embedding):
    """``nn.Embedding`` with mesh-sharded storage and row-sparse grads.

    ``input_dim`` rows x ``output_dim`` columns, looked up exactly like
    the parent block; gradients are ALWAYS row-sparse (unique ids +
    rows — the fused trainer leg and the whole-step scatter update both
    consume that format natively, docs/embedding.md)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(input_dim, output_dim, dtype=dtype,
                         weight_initializer=weight_initializer,
                         sparse_grad=True, **kwargs)
        w = self.weight
        w._memory_tag = "embed_shards"
        w._spec_hint = row_partition_spec
        nbytes = int(input_dim) * int(output_dim) * \
            _np.dtype(dtype).itemsize
        if _memory.ENABLED and not _memory.ensure_headroom(
                nbytes, why=f"embed_shards:{w.name}"):
            raise MXNetError(
                f"embedding table {w.name} ({input_dim}x{output_dim} "
                f"{dtype}, {nbytes} bytes) does not fit the HBM budget "
                "even after arbitration — shrink the table, raise "
                "MXNET_HBM_BUDGET_MB, or shard across a larger mesh axis")

    # -- introspection helpers (smoke gate) ----------------------------------
    def partition_plan(self, mesh=None) -> dict:
        """Static description of the committed layout: shard count, the
        axis, rows per shard, and the wire economics a dense gradient
        would forfeit (``dense_rows`` = vocab rows allreduced per step
        vs the row-sparse path's O(touched) ``wire_rows``)."""
        mesh = _pmesh.resolve_mesh(mesh)
        axis = _pmesh.embed_axis(mesh) if mesh is not None else None
        shards = int(mesh.shape[axis]) if axis is not None else 1
        vocab = int(self._kwargs["input_dim"])
        return {
            "axis": axis,
            "shards": shards,
            "rows": vocab,
            "rows_per_shard": -(-vocab // shards),
            "dim": int(self._kwargs["output_dim"]),
            "dense_rows": vocab,
        }

    def wire_rows(self, ids) -> int:
        """Rows a step's gradient actually moves: the count of UNIQUE
        ids in the batch (the row-sparse wire format carries each
        touched row once, however often the batch repeats it)."""
        arr = _np.asarray(getattr(ids, "asnumpy", lambda: ids)())
        return int(_np.unique(arr.astype(_np.int64)).size)
