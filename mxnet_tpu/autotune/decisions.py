"""Persisted autotune decisions — measured once, reloaded forever.

The sweep tuner (``autotune/sweep.py``) is the TVM observation (arxiv
1802.04799) applied to this graft's knobs: the constants the docs tell
users to hand-tune — superstep K, ``MXNET_BUCKET_SIZE_MB``, the serving
bucket lattice, the ``MicroBatcher`` hold window — are *measurable* on
the actual (model, platform), so measure them once and persist the
answer exactly like AOT programs persist in the compile cache: paid on
the first run, reloaded with zero re-sweep afterwards.

One JSON file per (signature, platform) under ``decisions_dir()``
(``MXNET_AUTOTUNE_DIR``, else ``autotune-decisions/`` next to the
persistent compile cache — the same siting rule as the perf-regression
baselines).  Writes are crash-atomic (``base.atomic_write``).  A
signature is a content hash of what the decision depends on
(``model_signature`` for training knobs; serving knobs key on the
bucket-spec shapes), so a model change simply misses the cache and
re-tunes rather than applying a stale decision.

Precedence per knob (``KNOB_ENV``): an explicitly-set env var ALWAYS
wins — consumers check their own env first and only then consult
``knob()`` — so a user pin survives any decision file.  The whole
subsystem gates on ``MXNET_AUTOTUNE`` (default off): disabled, every
hook is one module-global boolean test.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from typing import Any, Dict, Optional

from ..base import atomic_write, getenv

logger = logging.getLogger("mxnet_tpu.autotune")

#: the MXNET_AUTOTUNE kill-switch (gate-hygiene contract: off = one
#: module-global boolean test in every consumer hook)
ENABLED: bool = bool(getenv("MXNET_AUTOTUNE", False))

_SCHEMA = 1

#: knob name -> the env var that overrides it (the pre-existing manual
#: pins; an explicitly-set env always beats a persisted decision)
KNOB_ENV = {
    "superstep_k": "MXNET_SUPERSTEP_K",
    "bucket_size_mb": "MXNET_BUCKET_SIZE_MB",
    "serve_buckets": "MXNET_SERVE_BUCKETS",
    "serve_max_wait_ms": "MXNET_SERVE_MAX_WAIT_MS",
    "prefetch_depth": "MXNET_PREFETCH_DEPTH",
}

#: in-process parse cache: (signature, platform) -> record | None.
#: Decisions are immutable once written (store() repopulates), so a
#: plain dict is safe; reset_cache() drops it for tests.
_cache: Dict[tuple, Optional[dict]] = {}


def enabled() -> bool:
    return ENABLED


def enable() -> None:
    global ENABLED
    ENABLED = True


def disable() -> None:
    global ENABLED
    ENABLED = False


def reset_cache() -> None:
    _cache.clear()


def decisions_dir() -> Optional[str]:
    """Where decisions persist: ``MXNET_AUTOTUNE_DIR``.  Unset,
    persistence is off — the tuner still runs, its answer just dies
    with the process.  (Never derived from the compile cache, which
    entry points always have: a stale decision must not arm itself.)"""
    return os.environ.get("MXNET_AUTOTUNE_DIR") or None


def _platform() -> str:
    import jax
    try:
        return jax.default_backend()
    except Exception:  # noqa: BLE001 — a dead backend must not kill tuning
        return "unknown"


def model_signature(sig, extra=()) -> str:
    """Content hash of a parameter signature (the ``built["sig"]`` /
    ``Trainer._ensure_bucketer`` tuple of (shape, dtype) pairs) plus
    any extra decision-relevant config — the training-knob decision
    key.  A model/batch change hashes differently and misses the
    decision cache instead of inheriting a stale K."""
    return hashlib.sha1(
        repr((tuple(sig), tuple(extra))).encode()).hexdigest()[:16]


def decision_path(signature: str, platform: Optional[str] = None) \
        -> Optional[str]:
    d = decisions_dir()
    if d is None:
        return None
    return os.path.join(
        d, f"autotune-{signature}-{platform or _platform()}.json")


def load(signature: str, platform: Optional[str] = None) \
        -> Optional[dict]:
    """The persisted decision record for (signature, platform), schema-
    checked; None on miss or corruption (corrupt files warn once and
    are treated as a miss — the tuner just re-sweeps)."""
    plat = platform or _platform()
    ck = (signature, plat)
    if ck in _cache:
        return _cache[ck]
    path = decision_path(signature, plat)
    rec = None
    if path is not None and os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
            if not isinstance(data, dict) or data.get("schema") != _SCHEMA \
                    or not isinstance(data.get("knobs"), dict):
                raise ValueError("missing/invalid required fields")
            rec = data
        except Exception as e:  # noqa: BLE001 — reject loudly, never crash
            logger.warning(
                "autotune: decision file %s is corrupt (%s) — ignored; "
                "the next tune() rewrites it", path, e)
    _cache[ck] = rec
    return rec


def store(signature: str, knobs: Dict[str, Any], evidence=None,
          platform: Optional[str] = None) -> Optional[str]:
    """Atomically persist a decision record; returns the path (None
    when no decisions dir is configured)."""
    plat = platform or _platform()
    path = decision_path(signature, plat)
    rec = {
        "schema": _SCHEMA,
        "signature": signature,
        "platform": plat,
        "knobs": dict(knobs),
        "evidence": dict(evidence or {}),
        "written_at": time.time(),
    }
    _cache[(signature, plat)] = rec
    if path is None:
        return None
    os.makedirs(os.path.dirname(path), exist_ok=True)
    atomic_write(path, json.dumps(rec, indent=1, sort_keys=True))
    logger.info("autotune: wrote decision %s (knobs %s)", path,
                sorted(knobs))
    return path


def knob(signature: str, name: str, default=None,
         platform: Optional[str] = None):
    """The persisted value of one knob, or ``default``.  Consumers must
    check their own env var FIRST (``KNOB_ENV[name]``) — an explicit
    env pin always beats the decision file — and call this only when
    the env is unset."""
    if not ENABLED:
        return default
    rec = load(signature, platform)
    if rec is None:
        return default
    return rec["knobs"].get(name, default)
