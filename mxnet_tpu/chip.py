"""Chip identification: the package's table of published peaks.

Keyed by the exact PJRT `device_kind` string
(`jax.devices()[0].device_kind`), each row with its source.  A device
that is not in the table is an error, not a default: a utilisation
computed against a guessed peak is worse than none.  Add a row (with its
source) when the system is brought up on another chip.  Its one reader is
`observability/introspect.peak_flops`.

`chipbench/peaks.json` is the benchmark's own copy of the same row: the
benchmark may not import what it measures, or a change to this file
would move the yardstick.  The FLOPs a model needs are stated once, in
its configuration's `chipbench/configs/<name>/flops.py`; nothing here
counts them.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

from .base import MXNetError


class Peaks(NamedTuple):
    """Published per-chip peaks."""
    bf16_flops: float        # dense bf16 matmul, FLOP/s
    hbm_bytes_per_s: float   # HBM bandwidth, bytes/s
    hbm_bytes: float         # HBM capacity, bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         'Google Cloud documentation, "TPU v5e"'),
}


def device_kind() -> str:
    """`device_kind` of device 0, as JAX reports it."""
    import jax
    return jax.devices()[0].device_kind


def peaks(kind: Optional[str] = None) -> Peaks:
    """The table row for `kind` (default: device 0); raises MXNetError
    for a device that is not in the table."""
    k = device_kind() if kind is None else kind
    try:
        return PEAKS[k]
    except KeyError:
        raise MXNetError(
            f"no published peaks for device_kind {k!r} in "
            f"mxnet_tpu/chip.py (known: {sorted(PEAKS)}); add a row "
            "with its source rather than guessing") from None
