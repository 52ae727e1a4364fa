"""Core shared definitions: errors, dtype tables, registries, small utils.

Reference parity: plays the role of `python/mxnet/base.py` plus the
dmlc-core capabilities mxnet consumed (`dmlc::Parameter` declarative config,
`dmlc::Registry`, env-var access — SURVEY.md §2.1 "empty-submodule
capabilities").  No ctypes FFI is needed: the "C API" boundary of the
reference (src/c_api/) is replaced by JAX/XLA python-native calls.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as _np

import jax as _jax

# float64 NDArrays are part of the reference API surface (mx.nd.array keeps
# numpy float64); TPU code paths stay f32/bf16 — x64 only widens CPU-side use.
_jax.config.update("jax_enable_x64", True)


def _maybe_init_distributed():
    """Join the jax.distributed cluster when launched by tools/launch.py
    (MXT_COORDINATOR / MXT_NUM_PROC / MXT_PROC_ID env contract — the
    redesign of ps-lite's DMLC_* tracker env, SURVEY.md §2.3).  Must run
    at import, before any backend is created."""
    coord = os.environ.get("MXT_COORDINATOR")
    nproc = int(os.environ.get("MXT_NUM_PROC", "1") or 1)
    if not coord or nproc <= 1:
        return
    pid = os.environ.get("MXT_PROC_ID")
    if pid is None:
        # mpirun placement (tools/launch.py --launcher mpi): the rank
        # comes from the MPI runtime's own env.  No rank var at all is
        # a misconfiguration — every process would claim rank 0 and the
        # coordinator would wait forever; fail fast instead.
        pid = (os.environ.get("OMPI_COMM_WORLD_RANK")
               or os.environ.get("PMI_RANK")
               or os.environ.get("PMIX_RANK"))
        if pid is None:
            raise MXNetError(
                "MXT_NUM_PROC=%d but no process rank found: set "
                "MXT_PROC_ID (tools/launch.py does) or launch under "
                "mpirun (OMPI_COMM_WORLD_RANK/PMI_RANK/PMIX_RANK)"
                % nproc)
    pid = int(pid)
    try:
        _jax.distributed.initialize(coord, nproc, pid)
    except RuntimeError as e:
        # tolerate ONLY double-init (e.g. the TPU pod runtime already
        # joined); an unreachable coordinator must fail fast — swallowing
        # it would silently degrade to un-synchronized workers
        if "already initialized" in str(e).lower():
            return
        raise MXNetError(
            f"jax.distributed.initialize(coordinator={coord}, "
            f"num_processes={nproc}, process_id={pid}) failed: {e}") from e


_maybe_init_distributed()


def _init_crash_handler():
    """Library init (parity: src/initialize.cc:33-50 — SIGSEGV backtrace
    handler + dmlc logging init): a crash in any thread (native engine
    workers included) dumps python tracebacks for every thread.  Disable
    with MXNET_USE_SIGNAL_HANDLER=0."""
    if os.environ.get("MXNET_USE_SIGNAL_HANDLER", "1") == "0":
        return
    import faulthandler
    try:
        faulthandler.enable(all_threads=True)
    except Exception:
        pass  # non-main-thread import or closed stderr


_init_crash_handler()


class MXNetError(RuntimeError):
    """Error raised by the framework (parity: mxnet.base.MXNetError)."""


# ---------------------------------------------------------------------------
# dtype tables (parity: python/mxnet/base.py _DTYPE_NP_TO_MX / _DTYPE_MX_TO_NP)
# TPU-native addition: bfloat16 is first-class (the MXU native dtype).
# ---------------------------------------------------------------------------
try:
    import ml_dtypes as _mld
    bfloat16 = _np.dtype(_mld.bfloat16)
except ImportError:  # pragma: no cover
    bfloat16 = None

_DTYPE_NP_TO_MX: Dict[Any, int] = {
    None: -1,
    _np.dtype(_np.float32): 0,
    _np.dtype(_np.float64): 1,
    _np.dtype(_np.float16): 2,
    _np.dtype(_np.uint8): 3,
    _np.dtype(_np.int32): 4,
    _np.dtype(_np.int8): 5,
    _np.dtype(_np.int64): 6,
    _np.dtype(_np.bool_): 7,
}
if bfloat16 is not None:
    _DTYPE_NP_TO_MX[bfloat16] = 12

_DTYPE_MX_TO_NP: Dict[int, Any] = {v: k for k, v in _DTYPE_NP_TO_MX.items()}

_STORAGE_TYPE_STR_TO_ID = {"undefined": -1, "default": 0, "row_sparse": 1, "csr": 2}
_STORAGE_TYPE_ID_TO_STR = {v: k for k, v in _STORAGE_TYPE_STR_TO_ID.items()}


def np_dtype(dtype) -> _np.dtype:
    """Canonicalize a user-supplied dtype (str/np.dtype/type) to np.dtype."""
    if dtype is None:
        return _np.dtype(_np.float32)
    if isinstance(dtype, str) and dtype == "bfloat16":
        if bfloat16 is None:
            raise MXNetError("bfloat16 requires ml_dtypes")
        return bfloat16
    return _np.dtype(dtype)


def getenv(name: str, default):
    """Typed env lookup (parity: dmlc::GetEnv). MXNET_* envs keep their names."""
    val = os.environ.get(name)
    if val is None:
        return default
    ty = type(default)
    if ty is bool:
        return val not in ("0", "false", "False", "")
    return ty(val)


_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where JAX's persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the fixed
    ``<checkout>/.jax_cache``.  The path is part of the cache key, so it
    must never move between runs (no temporary name, pid or clock in it)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def enable_compile_cache(default_to_checkout: bool = False) -> Optional[str]:
    """Turn on JAX's persistent compilation cache; returns its directory,
    or None when it stays off.

    The library calls this lazily at executor / serving construction and
    acts only when ``JAX_COMPILATION_CACHE_DIR`` is set (jax reads that
    variable itself; this adds the thresholds).  Entry-point scripts
    (chip_smoke.py, chipbench/run.py) pass ``default_to_checkout=True`` so
    that a second run in one checkout loads executables instead of
    recompiling.
    No other path is ever handed to ``jax_compilation_cache_dir``."""
    if not (default_to_checkout
            or os.environ.get("JAX_COMPILATION_CACHE_DIR")):
        return None
    cache_dir = compile_cache_dir()
    if _jax.config.jax_compilation_cache_dir != cache_dir:
        _jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax latches "no cache" at the first compile; a directory that
        # arrives after one takes effect only once that latch is cleared
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    # default thresholds skip "cheap" compiles — serving buckets are
    # exactly the small programs the restart win comes from, so
    # persist everything
    for knob, val in (("jax_persistent_cache_min_compile_time_secs", 0),
                      ("jax_persistent_cache_min_entry_size_bytes", -1)):
        if getattr(_jax.config, knob) != val:  # called per bind: stay cheap
            _jax.config.update(knob, val)
    return cache_dir


def compile_cache_active() -> bool:
    """Whether compiles in this process land in a persistent cache."""
    return bool(_jax.config.jax_compilation_cache_dir)


def atomic_write(fname: str, data) -> None:
    """Crash-atomic small-file write: temp file in the SAME directory,
    then one ``os.replace`` — a crash mid-write never corrupts an
    existing file at ``fname``.  str writes text, bytes writes binary.
    (The checkpoint subsystem's directory-level commit lives in
    mxnet_tpu/checkpoint/layout.py; this is the single-file variant
    shared by symbol/params/states writers.)"""
    tmp = f"{fname}.tmp-{os.getpid()}"
    mode = "w" if isinstance(data, str) else "wb"
    with open(tmp, mode) as f:
        f.write(data)
    os.replace(tmp, fname)


def flight_dir() -> str:
    """Where the flight recorder's dumps and the post-mortem reports
    land: ``MXNET_FLIGHT_DIR``, else ``<tempfile.gettempdir()>/mxnet_flight``
    — never the working directory, which may be a checkout that a run
    measures.  Created on demand."""
    import tempfile
    d = os.environ.get("MXNET_FLIGHT_DIR") or os.path.join(
        tempfile.gettempdir(), "mxnet_flight")
    os.makedirs(d, exist_ok=True)
    return d


def unique_path(directory: str, stem: str, ext: str, clock=None) -> str:
    """Collision-free timestamped file path — the ONE filename policy
    every dump writer (``profiler.dump_profile`` autosnapshots,
    ``observability.flight.dump``) shares:
    ``<dir>/<stem>-<UTC stamp>-<pid>[.N]<ext>``.

    ``clock`` is the injectable epoch-seconds source (default
    ``time.time``) so tests exercise the collision suffix
    deterministically instead of racing ambient wall-clock."""
    import time as _time
    t = (clock or _time.time)()
    stamp = _time.strftime("%Y%m%d-%H%M%S", _time.gmtime(t))
    base_name = f"{stem}-{stamp}-{os.getpid()}"
    path = os.path.join(directory, base_name + ext)
    n = 1
    while os.path.exists(path):
        path = os.path.join(directory, f"{base_name}.{n}{ext}")
        n += 1
    return path


# ---------------------------------------------------------------------------
# Generic registry (parity: dmlc::Registry / python/mxnet/registry.py)
# ---------------------------------------------------------------------------
class Registry:
    """Name → object registry with alias support."""

    def __init__(self, kind: str):
        self.kind = kind
        self._map: Dict[str, Any] = {}

    def register(self, obj=None, name: Optional[str] = None):
        def _do(o):
            key = (name or getattr(o, "__name__", None) or o.name).lower()
            self._map[key] = o
            return o
        return _do(obj) if obj is not None else _do

    def get(self, name: str):
        key = name.lower()
        if key not in self._map:
            raise MXNetError(
                f"{self.kind} '{name}' is not registered; known: {sorted(self._map)}")
        return self._map[key]

    def find(self, name: str):
        return self._map.get(name.lower())

    def create(self, name_or_obj, *args, **kwargs):
        if isinstance(name_or_obj, str):
            return self.get(name_or_obj)(*args, **kwargs)
        return name_or_obj

    def list(self) -> List[str]:
        return sorted(self._map)


# ---------------------------------------------------------------------------
# Declarative op/iterator parameter schema
# (parity: dmlc::Parameter<T> — DMLC_DECLARE_PARAMETER structs that every
#  reference op uses, e.g. src/kvstore/gradient_compression.h:43-48)
# ---------------------------------------------------------------------------
@dataclass
class Arg:
    name: str
    type: Callable = float
    default: Any = None
    required: bool = False
    doc: str = ""


class ParamSchema:
    """Validates/normalizes kwargs for an op into a canonical hashable tuple.

    `open_schema=True` passes unknown kwargs through as strings — the
    `Custom` op forwards them to the user's CustomOpProp constructor
    (parity: custom.cc keeps all kwargs as char** for the python callback).
    """

    def __init__(self, args: List[Arg], open_schema: bool = False):
        self.args = {a.name: a for a in args}
        self.open_schema = open_schema

    @staticmethod
    def _canon(ty, v):
        if v is None:
            return None
        if ty in (tuple, "shape"):
            if isinstance(v, str):
                v = eval(v, {"__builtins__": {}})  # "(2, 2)" from string configs
            if isinstance(v, (int, _np.integer)):
                return (int(v),)
            # None entries stay None (open-ended slice bounds, e.g.
            # _slice_assign begin=(None, 1))
            return tuple(None if x is None else int(x) for x in v)
        if ty == "floats":  # float tuple (anchor sizes/ratios, variances)
            if isinstance(v, str):
                v = eval(v, {"__builtins__": {}})
            if isinstance(v, (int, float, _np.integer, _np.floating)):
                return (float(v),)
            return tuple(float(x) for x in v)
        if ty is bool:
            if isinstance(v, str):
                return v.lower() in ("1", "true", "yes")
            return bool(v)
        if ty is int:
            return int(v)
        if ty is float:
            return float(v)
        if ty is str:
            return str(v)
        return ty(v)

    def normalize(self, kwargs: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
        out = {}
        for k, v in kwargs.items():
            if k not in self.args:
                if self.open_schema:
                    out[k] = str(v)
                    continue
                raise MXNetError(f"unknown argument '{k}'; expected {sorted(self.args)}")
            out[k] = self._canon(self.args[k].type, v)
        for a in self.args.values():
            if a.name not in out:
                if a.required:
                    raise MXNetError(f"required argument '{a.name}' missing")
                out[a.name] = self._canon(a.type, a.default) if a.default is not None else a.default
        return tuple(sorted(out.items()))


class _ThreadLocalStack(threading.local):
    """Per-thread stack used by with-scopes (Context, AttrScope, NameManager)."""

    def __init__(self):
        self.stack: List[Any] = []

    def top(self):
        return self.stack[-1] if self.stack else None

    def push(self, v):
        self.stack.append(v)

    def pop(self):
        return self.stack.pop()
