"""Internal compute layout for spatial ops (SURVEY.md §7: NCHW→NHWC).

User-facing semantics stay NCHW everywhere (reference parity:
src/operator/nn/convolution.cc defaults; every symbol/gluon shape
contract in this package is channels-first).  When the internal layout
is NHWC, Convolution/Deconvolution/Pooling/BatchNorm transpose
activations to channels-last at their boundaries and run the
MXU/VPU-native channels-last form: the TPU's (8, 128) vector tiles want
the contiguous minor dimension to be the channel axis, and XLA's conv
emitter tiles NHWC convs onto the MXU without the internal
transpose-pairs it inserts around NCHW ones.

Adjacent boundary transposes cancel in XLA's algebraic simplifier
(transpose∘transpose = id, and transposes commute through elementwise
ops), so a conv→BN→relu→conv chain stays channels-last end to end; only
the graph's true entry/exit pay a real data movement.

Default off (NCHW): no cell of BENCHMARK.json has measured the NHWC
pass on the chip yet (ROADMAP.md S2 names it as a lever); select with
``mxnet_tpu.layout.set_conv_layout("NHWC")`` or
``MXNET_TPU_CONV_LAYOUT=NHWC``.  Flip the flag BEFORE building
executors/CachedOps — compiled plans trace the flag at build time.
"""
from __future__ import annotations

import os

from .base import MXNetError

_VALID = ("NCHW", "NHWC")
_LAYOUT = os.environ.get("MXNET_TPU_CONV_LAYOUT", "NCHW").upper()
if _LAYOUT not in _VALID:
    raise MXNetError(
        f"MXNET_TPU_CONV_LAYOUT must be one of {_VALID}, got {_LAYOUT}")


def conv_layout() -> str:
    """The internal spatial-op layout ('NCHW' or 'NHWC' = channels-last)."""
    return _LAYOUT


def set_conv_layout(layout: str) -> str:
    """Set the internal layout; returns the previous value.  Affects ops
    traced AFTER the call — rebuild executors/CachedOps when flipping."""
    global _LAYOUT
    layout = layout.upper()
    if layout not in _VALID:
        raise MXNetError(f"layout must be one of {_VALID}, got {layout}")
    prev, _LAYOUT = _LAYOUT, layout
    return prev


def channels_last() -> bool:
    return _LAYOUT == "NHWC"


def whole_graph() -> bool:
    """Whether NHWC mode uses the GraphPlan-level propagation pass
    (transposes only at true graph edges — VERDICT r4 #1b) instead of
    per-op boundary transposes.  Default on; MXNET_TPU_CL_WHOLEGRAPH=0
    pins the old per-op mode for A/B runs."""
    return os.environ.get("MXNET_TPU_CL_WHOLEGRAPH", "1") != "0"


def to_cl(x):
    """NC[spatial] → N[spatial]C (no-op for rank<3)."""
    if x.ndim < 3:
        return x
    return x.transpose((0,) + tuple(range(2, x.ndim)) + (1,))


def from_cl(x):
    """N[spatial]C → NC[spatial] (no-op for rank<3)."""
    if x.ndim < 3:
        return x
    return x.transpose((0, x.ndim - 1) + tuple(range(1, x.ndim - 1)))
