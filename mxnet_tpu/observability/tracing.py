"""The one span primitive: ``with span("mx.executor.launch", cat=...):``.

A span lands in up to three places, each behind the switch that already
existed for it:

  - the **profiler's host plane**, always: a
    ``jax.profiler.TraceAnnotation(name)``.  With no profiler session it
    is the same atomic test JAX pays around every jitted call; with one
    (``mx.profiler.set_state('run')`` *or* a plain
    ``jax.profiler.start_trace``) the span is written to the same
    ``*.xplane.pb`` as the device's operations: one file, one clock.
  - the **flight ring** while ``flight.ENABLED``: one record
    ``(name, cat, t0, t1, step, trace_id, labels, parent)``.  ``parent``
    is the name of the span open on this thread when this one opened;
    ``step`` is inherited from the enclosing span where not given, else
    from the thread's current step (``set_step``).
  - the profiler's Chrome ``_events`` (what ``mx.profiler.dump_profile()``
    writes) while ``mx.profiler.is_recording()``.

Spans of category ``"step"`` cover a whole step or more (``mx.step``, the
whole-step programs).  They go to the ring and the Chrome mirror only, not
to the host plane: a reader of the profiler's trace that labels a device
idle gap by the host span overlapping it most (chipbench/trace_reduce.py
``label_gap``) would give every gap to the enclosing step span.

``trace_span`` and ``flight.phase_span`` are names of this one function.
Span names are literals (the metrics-hygiene lint rejects built names);
the ``mx.`` names of the two training step paths are listed in
``SPAN_NAMES``.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

from ..analysis.sanitizer import make_lock as _make_lock

#: every ``mx.`` span and ring-record name the program emits
SPAN_NAMES = (
    # Module.fit (module/base_module.py, module/module.py, executor.py)
    "mx.fit.epoch", "mx.step",
    "mx.module.forward_backward",
    "mx.executor.gather", "mx.executor.launch", "mx.executor.deposit",
    "mx.module.update", "mx.kvstore.pushpull", "mx.optimizer.update_all",
    "mx.fit.data_fetch", "mx.module.prepare", "mx.module.update_metric",
    "mx.fit.callbacks", "mx.fit.epoch_end",
    # Gluon (gluon/block.py, autograd.py, gluon/trainer.py)
    "mx.cachedop.forward", "mx.autograd.backward", "mx.cachedop.backward",
    "mx.trainer.step", "mx.trainer.allreduce",
    # both (random.py, ndarray/ndarray.py, the jax.monitoring listener)
    "mx.rng.next_key", "mx.sync.read", "mx.program.load",
)

_tls = threading.local()
_tid_lock = _make_lock("tracing.tid")
_tid_map: dict = {}
# bound on the first span: flight imports this module (phase_span is
# span), and profiler imports the package
_flight = _profiler = None


def _bind():
    global _flight, _profiler
    from . import flight
    from .. import profiler
    _flight, _profiler = flight, profiler
    return flight


def _tid() -> int:
    """Small stable per-thread id (Chrome trace tids are more readable
    than 140-bit thread idents).  Shared with the flight recorder so
    merged dumps line threads up."""
    t = getattr(_tls, "tid", None)
    if t is None:
        with _tid_lock:
            t = _tid_map.setdefault(threading.get_ident(), len(_tid_map))
        _tls.tid = t
    return t


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def _depth() -> int:
    """Spans open on this thread."""
    return len(_stack())


def set_step(step) -> None:
    """This thread's current step: what a span with no ``step`` of its
    own and no enclosing span records.  ``Trainer.step`` sets it on
    return, so a Gluon step runs from one return to the next."""
    _tls.step = step


def context():
    """(name of the innermost span open on this thread, the step a span
    opened now would record): for ring records written without a span
    (``flight.record``)."""
    stack = _stack()
    if stack:
        return stack[-1].name, stack[-1].step
    return None, getattr(_tls, "step", None)


class span:
    """Time the body under ``name`` (see the module docstring).

    ``watch=True`` feeds the flight recorder's slow-phase watchdog;
    ``mem=True`` samples the HBM ledger at entry and exit and labels the
    ring record with ``mem_delta_bytes`` / ``mem_live_bytes``;
    ``trace_id`` / ``labels`` go into the ring record (``trace_id``
    defaults to the thread's ``flight.trace_scope``; a ``labels`` dict
    may be filled until the body ends).  After the body, ``seconds`` is
    what it took: callers that also feed a histogram read this clock
    pair, not one of their own."""

    __slots__ = ("name", "cat", "step", "trace_id", "labels", "watch",
                 "mem", "parent", "seconds", "_ann", "_t0", "_m0",
                 "_ring", "_prof")

    def __init__(self, name: str, cat: str = "runtime", step=None,
                 trace_id=None, labels=None, watch: bool = False,
                 mem: bool = False):
        self.name = name
        self.cat = cat
        self.step = step
        self.trace_id = trace_id
        self.labels = labels
        self.watch = watch
        self.mem = mem

    def __enter__(self):
        flight = _flight or _bind()
        stack = _stack()
        if stack:
            up = stack[-1]
            self.parent = up.name
            if self.step is None:
                self.step = up.step
        else:
            self.parent = None
            if self.step is None:
                self.step = getattr(_tls, "step", None)
        stack.append(self)
        self._ring = flight.ENABLED
        self._prof = _profiler.is_recording()
        self._m0 = flight._mem_live() if self.mem and self._ring else None
        self._t0 = time.perf_counter() * 1e6
        if self.cat != "step":
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        return self

    def __exit__(self, etype, exc, tb):
        try:
            if self._ann is not None:
                # exactly the exception unwinding through the span body
                self._ann.__exit__(etype, exc, tb)
        finally:
            t1 = time.perf_counter() * 1e6
            self.seconds = (t1 - self._t0) / 1e6
            stack = _stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:
                stack.remove(self)
            if self._ring or self._prof:
                self._record(t1, len(stack))
        return False

    def _record(self, t1, depth):
        flight, profiler = _flight, _profiler
        if self._prof:
            args = {"depth": depth}
            if self.step is not None:
                args["step"] = self.step
            profiler.record_event(self.name, self._t0, t1, cat=self.cat,
                                  tid=_tid(), args=args)
        if self._ring:
            labels = self.labels
            if self._m0 is not None:
                m1 = flight._mem_live()
                if m1 is not None:
                    labels = dict(labels) if labels else {}
                    labels["mem_delta_bytes"] = int(m1 - self._m0)
                    labels["mem_live_bytes"] = int(m1)
            flight.record(self.name, self.cat, self._t0, t1,
                          step=self.step, trace_id=self.trace_id,
                          labels=labels, watch=self.watch,
                          parent=self.parent)


trace_span = span


def step_span(step_num: int, name: str = "train"):
    """A step-level span ``<name>_step`` carrying ``step_num``, for loops
    outside this package (``Module.fit`` opens ``mx.step`` itself)."""
    # bounded by construction: callers pass literal stream names
    rec_name = name + "_step"
    return span(rec_name, cat="step", step=step_num, watch=True)
