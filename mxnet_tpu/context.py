"""Device contexts: mx.cpu()/mx.gpu()/mx.tpu() mapped onto JAX devices.

Reference parity: `python/mxnet/context.py` (Context class, with-stack,
default ctx).  TPU-native: a Context resolves to a concrete `jax.Device`;
`mx.tpu(i)` is first-class (the BASELINE.json north star).  `mx.gpu(i)` is
accepted and maps to the i-th accelerator so reference scripts run unmodified
on TPU hosts.
"""
from __future__ import annotations

from typing import Optional

import jax

from .base import MXNetError, _ThreadLocalStack

_DEVTYPE2STR = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "tpu"}
_DEVSTR2TYPE = {v: k for k, v in _DEVTYPE2STR.items()}


class Context:
    """A device context. Comparable/hashable; usable as a with-scope."""

    _stack = _ThreadLocalStack()
    default_ctx: "Context"

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in _DEVSTR2TYPE:
            raise MXNetError(f"unknown device type {device_type}")
        self.device_type = device_type
        self.device_id = device_id

    @property
    def device_typeid(self) -> int:
        return _DEVSTR2TYPE[self.device_type]

    # -- jax mapping --------------------------------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device.

        'tpu'/'gpu' both mean "accelerator i" — on a TPU host, mx.gpu(0) from
        a reference script lands on TPU chip 0 (no GPU in the loop);
        without an accelerator they raise rather than run on the host.
        'cpu'/'cpu_pinned' resolve to host CPU devices.
        """
        if self.device_type in ("cpu", "cpu_pinned"):
            devs = _local("cpu") if _has_platform("cpu") else _local(None)
            return devs[min(self.device_id, len(devs) - 1)]
        accels = _accelerators()
        if not accels:
            raise MXNetError(
                f"{self}: no accelerator visible to JAX (devices: "
                f"{[d.platform for d in _local(None)]}); use mx.cpu() "
                "to run on the host")
        if self.device_id >= len(accels):
            raise MXNetError(
                f"{self} out of range: {len(accels)} accelerator(s) visible")
        return accels[self.device_id]

    # -- scope --------------------------------------------------------------
    def __enter__(self):
        Context._stack.push(self)
        return self

    def __exit__(self, *exc):
        Context._stack.pop()

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()


def _has_platform(name: str) -> bool:
    try:
        jax.devices(name)
        return True
    except RuntimeError:
        return False


def _local(platform):
    """Process-local devices only: under multi-process jax.distributed,
    jax.devices() lists GLOBAL devices and device 0 may live on another
    host — contexts must resolve to addressable ones (parity: each ps-lite
    worker owned its own GPUs)."""
    devs = jax.local_devices() if platform is None else [
        d for d in jax.local_devices() if d.platform == platform]
    return devs if devs else (jax.devices() if platform is None
                              else jax.devices(platform))


def _accelerators():
    for plat in ("tpu", "gpu", "cuda", "rocm"):
        if _has_platform(plat):
            return _local(plat)
    return []


Context.default_ctx = Context("cpu", 0)


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def _accelerator(device_type: str, device_id: int) -> Context:
    ctx = Context(device_type, device_id)
    ctx.jax_device()  # no such device: raise here, not at the first array
    return ctx


def gpu(device_id: int = 0) -> Context:
    return _accelerator("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """First-class TPU context (north star: BASELINE.json).  Raises
    MXNetError when JAX sees no accelerator: there is no CPU fallback."""
    return _accelerator("tpu", device_id)


def num_gpus() -> int:
    """Number of visible accelerators (parity: mx.context.num_gpus)."""
    return len(_accelerators())


def num_tpus() -> int:
    return len(_accelerators())


def current_context() -> Context:
    return Context._stack.top() or Context.default_ctx
