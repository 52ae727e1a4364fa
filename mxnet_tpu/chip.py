"""Chip identification and MFU accounting.

The one peaks table of the repo, keyed by the exact PJRT `device_kind`
string (`jax.devices()[0].device_kind`), each row with its source.  A
device that is not in the table is an error, not a default: an MFU
computed against a guessed peak is worse than no MFU.  Add a row (with
its source) when the system is brought up on another chip.
`observability/introspect.peak_flops` reads this table too.
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

# Model FLOPs per trained image, ResNet-50 v1 @ 224^2: 4.1 GMAC forward
# = 8.2 GFLOP; backward ~= 2x forward; 24.6 GFLOP/img for fwd+bwd.
RESNET50_TRAIN_FLOPS_PER_IMG = 24.6e9
RESNET50_INFER_FLOPS_PER_IMG = 8.2e9


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


def mfu(img_per_s: float, flops_per_img: float = RESNET50_TRAIN_FLOPS_PER_IMG,
        kind: Optional[str] = None) -> dict:
    """{"chip", "peak_bf16_tflops", "mfu"} for a measured throughput on
    a chip of `kind` (default: device 0).  Raises on an unknown kind."""
    k = device_kind() if kind is None else kind
    peak = peaks(k).bf16_flops
    return {"chip": k, "peak_bf16_tflops": peak / 1e12,
            "mfu": round(img_per_s * flops_per_img / peak, 4)}
