"""Framework-global PRNG key stream (parity: python/mxnet/random.py + the
per-device ResourceManager kRandom resource, src/resource.cc:85-147).

Functional JAX keys replace stateful per-device generators: `seed(n)` resets
the root key; every eager random op consumes one split.  Graph executors fold
a per-run key by node id instead (trace-safe).
"""
from __future__ import annotations

import threading

import jax

_state = threading.local()
_DEFAULT_SEED = 0


def _get():
    if not hasattr(_state, "key"):
        _state.key = jax.random.PRNGKey(_DEFAULT_SEED)
    return _state.key


# host-side RandomState for initializers (the reference's initializers
# draw from the engine RNG that mx.random.seed controls; ours draw host-
# side, so the framework owns its own stream — never numpy's global one)
import numpy as _np

from .observability.tracing import span  # noqa: E402

host_rng = _np.random.RandomState(0)


def seed(seed_state: int) -> None:
    """Seed the framework RNG (parity: mx.random.seed / MXRandomSeed) —
    both the jax key stream and the host RNG that initializers use."""
    _state.key = jax.random.PRNGKey(int(seed_state))
    host_rng.seed(int(seed_state) % (2 ** 32))


def next_key():
    # two launches a call (split, unstack): a span of its own
    with span("mx.rng.next_key", cat="rng"):
        key = _get()
        _state.key, sub = jax.random.split(key)
        return sub


# nd-level sampling functions are attached in ndarray.random (autogen);
# keep module-level aliases for mx.random.uniform(...) etc.
def __getattr__(name):
    from . import ndarray
    fn = getattr(ndarray.random, name, None)
    if fn is None:
        raise AttributeError(name)
    return fn
