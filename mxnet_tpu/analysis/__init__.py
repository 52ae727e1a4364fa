"""graft-lint: repo-specific static analysis, compiled-program contract
audit, and runtime sanitizer (ISSUEs 7 + 15; docs/static_analysis.md).

Static side — ``analysis.run(checkers, paths) -> [Finding]`` with nine
repo-specific rules: the PR 7 set (thread-safety, host-sync,
atomic-write, env-sync, metrics-hygiene, memory-hygiene) plus the
jit/program-boundary tier (use-after-donate — a def-use dataflow pass
over donated call positions, ``analysis/dataflow.py``; retrace-hazard;
gate-hygiene).  Per-finding ``# graft-lint: disable=<rule>``
suppression and a checked-in ``baseline.json`` for
grandfathered findings.  ``make lint-graft`` / ``python -m
mxnet_tpu.analysis`` is the CI gate; tests/test_analysis.py pins it in
tier-1.

Program side — ``analysis.audit_programs()`` verifies each captured
compiled program (``observability.introspect``) against the contract
its compile chokepoint declared: donation really became input-output
aliasing, AMP left no f32 dot/conv, zero host callbacks in whole-step
programs, collective count matches the bucketer's plan
(``analysis/program_audit.py``; the CLI's ``--audit-programs`` leg).

Runtime side — ``MXNET_SANITIZE=1`` arms lock-order tracking on every
package lock (deadlock detector), ``no_sync()`` regions that raise on
device→host syncs, and donated-buffer poisoning: a failed donated
dispatch (wholestep / fused-update / serving) marks its wrappers so
any later access raises a typed ``DonatedBufferError`` instead of
jax's opaque deleted-array error; results surface in
``observability.snapshot()["analysis"]``.

This module stays import-light: the whole package imports it for
``hot_path`` / lock factories, so the ast machinery loads lazily.
"""
from __future__ import annotations

from . import sanitizer
from .sanitizer import (DonatedBufferError, LockOrderError, SyncViolation,
                        check_sync, hot_path, make_condition, make_lock,
                        make_rlock, no_sync, sanitized)

__all__ = ["run", "run_detailed", "Finding", "Baseline", "ALL_RULES",
           "hot_path", "no_sync", "sanitizer", "sanitized",
           "make_lock", "make_rlock", "make_condition", "check_sync",
           "LockOrderError", "SyncViolation", "DonatedBufferError",
           "audit_programs", "audit_program"]

_LAZY = {"run": "core", "run_detailed": "core", "Finding": "core",
         "Baseline": "core", "DEFAULT_BASELINE": "core",
         "ALL_RULES": "checkers", "registry": "checkers",
         "audit_programs": "program_audit",
         "audit_program": "program_audit",
         "self_audit": "program_audit"}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib
    return getattr(importlib.import_module(f".{mod}", __name__), name)
