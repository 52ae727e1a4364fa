"""Continuous batching for generative decode: paged KV serving with
per-step join/leave.

The coalescing tier (``MicroBatcher`` / ``ResilientServer``) batches at
*request* granularity — correct for classifiers, a throughput cliff for
generation: one long sequence pins its whole coalesced group for its
full output length (the ``rnn/`` + ``BucketingModule`` hostage path the
roadmap names).  ``DecodeEngine`` is the jax-native answer, the MXNet
bucketing-executor story (arxiv 1512.01274) crossed with TF's
dataflow-level dynamic batching (arxiv 1605.08695):

  * **ONE donated XLA dispatch per decode step** over the whole
    in-flight slot set.  Sequences join and leave *between* steps —
    a join is three host-array writes (token, position, slot), never a
    new program, so churn cannot change the dispatch count
    (``make decode-smoke`` pins dispatches == steps).
  * **paged KV on a pow2 bucket lattice** — decode state leaves carry a
    slot axis and a capacity axis sized ``pages x
    MXNET_DECODE_PAGE_TOKENS``; the (slots, pages) key routes through a
    stock ``buckets.BucketSpec`` (``buckets.page_lattice``), so mixed
    length sequences share ONE precompiled lattice and growth across a
    page boundary re-routes to the neighbouring precompiled key —
    ``SERVE_COMPILES`` stays flat under traffic, the serving tier's
    standing contract.
  * **KV pages are a first-class, evictable HBM resource** — the whole
    decode state registers in the PR 9 ledger under a dedicated
    ``serve_kv_pages`` tag; growth asks ``memory.ensure_headroom``
    FIRST (the PR 14 ask-first discipline), and under pressure the
    registry's LRU arbiter reclaims cold sequences' pages *before* any
    model weights (``ModelRegistry._make_room`` phase 0) — an evicted
    sequence fails with a typed ``SequenceEvicted`` carrying
    ``retry_after_s``, never a silent hang.
  * **EDF shedding at decode-step granularity** — admission sheds a
    sequence whose deadline the remaining-tokens x step-EWMA estimate
    (``resilience.StepEDF``) already cannot meet; between steps the
    engine expires passed deadlines and, when admitted work is waiting,
    preempts actives whose deadlines became unmeetable — typed
    ``DeadlineExceeded``, the slot goes to the earliest-deadline
    waiter.
  * **house invariants** — the step's donation is declared via
    ``note_program`` contracts and verified by
    ``analysis.audit_programs()``; every observability hook is one
    boolean test when its subsystem is off; failures at the
    ``serving.decode_step`` chaos site degrade typed with sequence
    state consistent across a retry.

``ToyLM`` (self-contained) and ``CellModel`` (any steppable
``rnn.BaseRNNCell`` via its one-step Symbol -> ``GraphPlan``) plug into
the engine's model protocol; ``BucketingModule.generate`` routes here.
docs/decode_serving.md is the guide.
"""
from __future__ import annotations

import argparse
import json
import logging
import sys
import threading
import time
import weakref
from concurrent.futures import Future
from typing import Dict, List, Optional

import numpy as _np

from ..analysis import hot_path, sanitizer as _san
from ..base import MXNetError, getenv
from ..faultinject import fire as _fi_fire
from ..observability import flight as _flight
from ..observability import goodput as _goodput
from ..observability import introspect as _introspect
from ..observability import memory as _memory
from ..observability import metrics as _metrics
from .batcher import GenerativeRouteError
from .buckets import bucket_label, page_lattice
from .resilience import DeadlineExceeded, Overloaded, StepEDF

log = logging.getLogger(__name__)

__all__ = ["DecodeEngine", "ToyLM", "CellModel", "SequenceEvicted",
           "GenerativeRouteError", "reclaim_kv_pages", "live_engines"]

#: ledger tag for paged decode state — alongside serve_weights /
#: serve_host_params in the multi-model cost model, and the CHEAPEST
#: victim tier (a shed sequence retries; weights must re-upload)
KV_TAG = "serve_kv_pages"


class SequenceEvicted(Overloaded):
    """This sequence's KV pages were reclaimed under HBM pressure (the
    budget arbiter preferred them over model weights).  Typed
    reject-with-backpressure: ``retry_after_s`` estimates when decode
    capacity frees — resubmit the prompt; nothing was silently lost
    because nothing was silently kept."""


class DecodeClosedError(MXNetError):
    """The engine was closed before this sequence finished (or before
    it could be submitted)."""


class _Seq:
    __slots__ = ("sid", "prompt", "max_new", "deadline", "priority",
                 "tenant", "future", "generated", "pos", "slot", "t0",
                 "trace_id", "eos")

    def __init__(self, sid: int, prompt, max_new: int,
                 deadline: Optional[float], priority: int, tenant: str,
                 eos: Optional[int]):
        self.sid = sid
        self.prompt = [int(t) for t in prompt]
        self.max_new = int(max_new)
        self.deadline = deadline  # absolute perf_counter time, or None
        self.priority = int(priority)
        self.tenant = str(tenant)
        self.future: Future = Future()
        self.generated: List[int] = []
        self.pos = 0          # next position to be written (tokens consumed)
        self.slot: Optional[int] = None
        self.t0 = time.perf_counter()
        self.trace_id = _flight.new_trace_id() if _flight.ENABLED else None
        self.eos = eos

    def remaining(self) -> int:
        """Decode steps left: unconsumed prompt + ungenerated tokens."""
        return max(0, len(self.prompt) - 1 - self.pos) \
            + max(0, self.max_new - len(self.generated))


class _PageTable:
    """Ledger-visible holder for one engine's paged decode state.  The
    state leaves themselves rotate every donated step; this stable
    object carries their byte total so the ``serve_kv_pages`` tag has
    one long-lived registrant per engine (weakref death on engine
    close returns the bytes — the leak gate pins it)."""
    __slots__ = ("__weakref__",)


# live engines, for the registry's phase-0 KV reclaim (and operators)
_engines_lock = _san.make_lock("serving.decode.engines")
_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


def live_engines() -> list:
    with _engines_lock:
        return list(_ENGINES)


def reclaim_kv_pages(deficit: float, why: str = "") -> float:
    """Process-wide KV-page reclaim: ask every live engine to shed its
    coldest sequences' pages until ~``deficit`` ledger bytes freed.
    ``ModelRegistry._make_room`` runs this as phase 0 — KV pages are
    cheaper victims than bucket executables or model weights.  Returns
    bytes freed (measured from the ledger, not trusted estimates)."""
    freed = 0.0
    for eng in live_engines():
        if freed >= deficit:
            break
        try:
            freed += eng.release_kv_pages(deficit - freed, why=why)
        except Exception as e:  # noqa: BLE001 — reclaim is best-effort
            log.warning("decode KV reclaim on %r failed (%s): %s",
                        getattr(eng, "name", "?"), why, str(e))
    return freed


# ---------------------------------------------------------------------------
# models
# ---------------------------------------------------------------------------
class ToyLM:
    """Self-contained decode model for tests/bench/smoke: embedding ->
    tanh recurrence -> sliding-window attention over the paged KV log
    -> vocab projection, greedy argmax.

    Two properties the engine's correctness gates lean on:

      * **slot independence** — row ``i`` of every op reads only row
        ``i`` of state/tokens (matmuls are row-wise) — so continuous
        batching is bitwise equal to a solo run in the same slot
        bucket, join/leave churn included;
      * **capacity independence** — the KV read is a fixed ``window``
        of positions ``<= pos`` (clamped gather, invalid lanes masked
        to exact zeros), so routing to a larger pages bucket changes
        where the log is STORED, never the values read — growth across
        page boundaries is bitwise-stable too.
    """

    def __init__(self, vocab: int = 32, dim: int = 16, window: int = 8):
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.window = int(window)
        if min(self.vocab, self.dim, self.window) < 1:
            raise MXNetError("ToyLM needs vocab/dim/window >= 1")

    #: state leaves with a pages-backed capacity axis (axis index)
    state_capacity_axes = {"kv": 1}

    def init_params(self, seed: int = 0) -> Dict[str, _np.ndarray]:
        rng = _np.random.RandomState(seed)
        s = 0.2
        return {
            "emb": rng.uniform(-s, s, (self.vocab, self.dim))
            .astype(_np.float32),
            "wx": rng.uniform(-s, s, (self.dim, self.dim))
            .astype(_np.float32),
            "wh": rng.uniform(-s, s, (self.dim, self.dim))
            .astype(_np.float32),
            "out": rng.uniform(-s, s, (self.dim, self.vocab))
            .astype(_np.float32),
        }

    def state_shapes(self, slots: int, capacity: int) -> Dict[str, tuple]:
        return {"h": ((slots, self.dim), _np.float32),
                "kv": ((slots, capacity, self.dim), _np.float32)}

    def step(self, params, state, tokens, pos):
        import jax.numpy as jnp
        x = params["emb"][tokens]                              # (S, D)
        h = jnp.tanh(x @ params["wx"] + state["h"] @ params["wh"])
        kv = state["kv"]
        cap = kv.shape[1]
        write = (jnp.arange(cap)[None, :] == pos[:, None])     # (S, C)
        kv = jnp.where(write[..., None], h[:, None, :], kv)
        # fixed-width window over positions [pos-window+1, pos]:
        # clamped gather + exact-zero masking keeps the read identical
        # across capacity buckets (see class docstring)
        offs = jnp.arange(self.window)                         # (W,)
        idx = pos[:, None] - offs[None, :]                     # (S, W)
        valid = (idx >= 0).astype(kv.dtype)
        got = jnp.take_along_axis(
            kv, jnp.clip(idx, 0, cap - 1)[..., None], axis=1)  # (S, W, D)
        r = (got * valid[..., None]).sum(axis=1) \
            / valid.sum(axis=1, keepdims=True)
        logits = (h + r) @ params["out"]                       # (S, V)
        return logits, {"h": h, "kv": kv}


class CellModel:
    """Adapt a *steppable* ``rnn.BaseRNNCell`` into the engine's model
    protocol: the cell's one-step Symbol (``cell(x, states)``) becomes
    a ``GraphPlan`` executed inside the donated decode step (the same
    jax-traceable plan the serving predictor compiles), wrapped with a
    token embedding, a paged KV log of the cell outputs, and a vocab
    projection.  This is how ``rnn/`` + ``BucketingModule`` generation
    routes through continuous batching instead of holding a coalesced
    micro-batch hostage.

    Non-steppable cells (``FusedRNNCell``, ``BidirectionalCell``) are
    rejected with a typed ``GenerativeRouteError`` — ``unfuse()`` a
    fused stack first."""

    def __init__(self, cell, vocab: int, seed: int = 0):
        if not getattr(cell, "steppable", False):
            raise GenerativeRouteError(
                f"{type(cell).__name__} cannot emit a one-token decode "
                f"step (fused/bidirectional cells consume whole "
                f"sequences) — unfuse() it, or build the engine on a "
                f"steppable cell (serving.decode.CellModel, "
                f"docs/decode_serving.md)")
        from .. import symbol as _symbol
        from ..symbol.graph import GraphPlan
        self.vocab = int(vocab)
        self._infos = list(cell.state_info)
        x = _symbol.Variable("decode_x")
        states = [_symbol.Variable(f"decode_state{i}")
                  for i in range(len(self._infos))]
        out, new_states = cell(x, states)
        self._plan = GraphPlan(_symbol.Group([out] + list(new_states)))
        self._state_names = [f"decode_state{i}"
                             for i in range(len(self._infos))]
        # one-step shape inference at batch 1 sizes every cell param
        # (and the cell's output width, which the KV log and the vocab
        # projection both ride)
        dim = self._infos[0]["shape"][-1]
        self.dim = int(dim)
        known = {"decode_x": (1, self.dim)}
        for n, info in zip(self._state_names, self._infos):
            known[n] = (1,) + tuple(info["shape"][1:])
        arg_shapes, out_shapes, _aux = self._plan.symbol.infer_shape(**known)
        self._arg_shapes = dict(zip(self._plan.symbol.list_arguments(),
                                    arg_shapes))
        self.out_dim = int(out_shapes[0][-1])
        self._seed = int(seed)

    @property
    def state_capacity_axes(self):
        return {"kv": 1}

    def init_params(self, seed: Optional[int] = None):
        rng = _np.random.RandomState(self._seed if seed is None else seed)
        s = 0.2
        params = {
            "decode_emb": rng.uniform(-s, s, (self.vocab, self.dim))
            .astype(_np.float32),
            "decode_out": rng.uniform(-s, s, (self.out_dim, self.vocab))
            .astype(_np.float32),
        }
        skip = {"decode_x"} | set(self._state_names)
        for name, shp in self._arg_shapes.items():
            if name in skip:
                continue
            if name.endswith("_bias"):
                params[name] = _np.zeros(shp, dtype=_np.float32)
            else:
                params[name] = rng.uniform(-s, s, shp).astype(_np.float32)
        return params

    def state_shapes(self, slots: int, capacity: int) -> Dict[str, tuple]:
        out = {"kv": ((slots, capacity, self.out_dim), _np.float32)}
        for n, info in zip(self._state_names, self._infos):
            out[n] = ((slots,) + tuple(info["shape"][1:]), _np.float32)
        return out

    def step(self, params, state, tokens, pos):
        import jax
        import jax.numpy as jnp
        x = params["decode_emb"][tokens]                       # (S, D)
        args = {n: v for n, v in params.items()
                if n not in ("decode_emb", "decode_out")}
        args["decode_x"] = x
        for n in self._state_names:
            args[n] = state[n]
        # fixed key: one decode step consumes no randomness in stock
        # cells; determinism across identical requests is the contract
        outs, _aux = self._plan.run(args, {}, jax.random.PRNGKey(0),
                                    is_train=False)
        cell_out, new_states = outs[0], outs[1:]
        kv = state["kv"]
        cap = kv.shape[1]
        write = (jnp.arange(cap)[None, :] == pos[:, None])
        kv = jnp.where(write[..., None], cell_out[:, None, :], kv)
        logits = cell_out @ params["decode_out"]
        new_state = {"kv": kv}
        for n, ns in zip(self._state_names, new_states):
            new_state[n] = ns
        return logits, new_state


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class DecodeEngine:
    """Continuous-batching decode server over one model.

    Parameters
    ----------
    model
        Anything with the decode-model protocol: ``init_params(seed)``,
        ``state_shapes(slots, capacity) -> {name: (shape, dtype)}``
        (every leaf slot-major; pages-backed leaves named in
        ``state_capacity_axes``), and ``step(params, state, tokens,
        pos) -> (logits, new_state)`` with row ``i`` depending only on
        slot ``i`` (the join/leave-bitwise contract).  ``ToyLM`` and
        ``CellModel`` ship in this module.
    params : dict, optional
        Host parameter arrays (default ``model.init_params()``).
        Uploaded once, ledger-tagged ``serve_weights``.
    slots / page_tokens / max_pages : int, optional
        Lattice geometry: at most ``slots`` concurrent sequences
        (``MXNET_DECODE_SLOTS``, 8), KV paged in
        ``MXNET_DECODE_PAGE_TOKENS``-token pages (16), capacity
        ``page_tokens * max_pages`` tokens per sequence
        (``MXNET_DECODE_MAX_PAGES``, 8).
    max_queue : int, optional
        Bound on waiting (admitted, slotless) sequences — past it
        ``submit`` sheds with a typed ``Overloaded``
        (``MXNET_SERVE_MAX_QUEUE``).
    shed_policy : str, optional
        ``"deadline"`` (default, ``MXNET_SERVE_SHED_POLICY``) arms EDF
        shedding over remaining-token estimates; ``"depth"`` sheds on
        the queue bound only.
    """

    def __init__(self, model, params: Optional[dict] = None,
                 slots: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 max_pages: Optional[int] = None,
                 slot_buckets=None, page_buckets=None,
                 max_queue: Optional[int] = None,
                 shed_policy: Optional[str] = None,
                 eos: Optional[int] = None,
                 name: str = "decode", warmup: bool = True):
        import jax
        self.model = model
        self.name = str(name)
        self.max_slots = int(getenv("MXNET_DECODE_SLOTS", 8)) \
            if slots is None else int(slots)
        self.page_tokens = int(getenv("MXNET_DECODE_PAGE_TOKENS", 16)) \
            if page_tokens is None else int(page_tokens)
        self.max_pages = int(getenv("MXNET_DECODE_MAX_PAGES", 8)) \
            if max_pages is None else int(max_pages)
        if min(self.max_slots, self.page_tokens, self.max_pages) < 1:
            raise MXNetError("DecodeEngine needs slots/page_tokens/"
                             "max_pages >= 1")
        self.max_queue = int(getenv("MXNET_SERVE_MAX_QUEUE", 64)) \
            if max_queue is None else int(max_queue)
        policy = shed_policy or getenv("MXNET_SERVE_SHED_POLICY",
                                       "deadline")
        if policy not in ("depth", "deadline"):
            raise MXNetError(f"shed_policy must be 'depth' or "
                             f"'deadline', got {policy!r}")
        self.shed_policy = policy
        self.eos = eos
        self.spec = page_lattice(self.max_slots, self.max_pages,
                                 slot_buckets=slot_buckets,
                                 page_buckets=page_buckets)
        self.capacity = self.page_tokens * self.max_pages
        # reentrant: step() -> KV growth -> ensure_headroom -> arbiter
        # -> release_kv_pages re-enters on the same thread
        self._lock = _san.make_rlock("serving.decode.engine")
        self._closed = False
        self._seq_no = 0
        self._waiting: List[_Seq] = []
        self._slots: List[Optional[_Seq]] = []
        self._key: Optional[tuple] = None
        self._state = None          # device pytree, or None (no KV live)
        self._kv_holder = _PageTable()
        self._kv_bytes = 0
        self._edf = StepEDF()
        self._steps = 0
        self._admitted = 0
        self._completed = 0
        self._evicted = 0
        self._shed = 0
        self._expired = 0
        self._tokens_out = 0
        self._compiled: Dict[tuple, object] = {}
        self._ever_compiled: set = set()

        host = dict(params) if params is not None else model.init_params()
        pbytes = sum(int(_np.asarray(v).nbytes) for v in host.values())
        # ask-first (the PR 14 admission discipline): give the budget
        # arbiter a chance to evict colder victims before the upload;
        # past a hard budget the ledger's register() raises typed
        _memory.ensure_headroom(pbytes, why=f"decode.admit:{self.name}")

        def _to_dev(v):
            arr = jax.device_put(_np.asarray(v))
            return _memory.register(arr, tag="serve_weights")

        self._params = {k: _to_dev(v) for k, v in host.items()}
        self._jit = jax.jit(self._step_impl, donate_argnums=(0,))
        with _engines_lock:
            _ENGINES.add(self)
        if warmup:
            self.warmup()

    # -- compiled lattice ----------------------------------------------------
    def _step_impl(self, state, fresh, tokens, pos, params):
        import jax.numpy as jnp
        # slot reuse hygiene INSIDE the one dispatch: a slot whose
        # previous occupant retired since the last key transition still
        # holds its state rows — zero every freshly-joined slot's rows
        # (fresh[i] <=> sequence i has never been dispatched) so churn
        # stays bitwise-equal to solo decoding without an extra launch
        state = {n: jnp.where(
            jnp.reshape(fresh, (-1,) + (1,) * (leaf.ndim - 1)),
            jnp.zeros((), dtype=leaf.dtype), leaf)
            for n, leaf in state.items()}
        logits, new_state = self.model.step(params, state, tokens, pos)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return new_state, nxt

    def _state_shapes(self, key: tuple) -> Dict[str, tuple]:
        slots_b, pages_b = key
        return self.model.state_shapes(slots_b,
                                       pages_b * self.page_tokens)

    def _state_bytes(self, key: tuple) -> int:
        return sum(int(_np.prod(shp)) * _np.dtype(dt).itemsize
                   for shp, dt in self._state_shapes(key).values())

    def precompile(self, key: tuple):
        """AOT-build the donated step for one (slots, pages) key — the
        predictor's ``SERVE_COMPILES`` discipline verbatim: a fresh
        compile counts once, a rebuild of an evicted key counts as a
        readmission, and after ``warmup()`` traffic compiles nothing."""
        import jax
        key = tuple(key)
        with self._lock:
            compiled = self._compiled.get(key)
            if compiled is not None:
                return compiled
            shapes = self._state_shapes(key)
            state_avals = {n: jax.ShapeDtypeStruct(shp, dt)
                           for n, (shp, dt) in shapes.items()}
            iv = jax.ShapeDtypeStruct((key[0],), _np.int32)
            fv = jax.ShapeDtypeStruct((key[0],), _np.bool_)
            pv = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                  for n, v in self._params.items()}
            t0 = time.perf_counter()
            compiled = self._jit.lower(state_avals, fv, iv, iv,
                                       pv).compile()
            if _goodput.ENABLED:
                _goodput.attribute("recompile",
                                   time.perf_counter() - t0)
            from .. import base as _base
            readmission = (key in self._ever_compiled
                           and _base.compile_cache_active())
            if _metrics.ENABLED:
                if readmission:
                    _metrics.SERVE_READMITS.inc(kind="bucket")
                else:
                    _metrics.SERVE_COMPILES.inc()
                    if key in self._ever_compiled:
                        _metrics.SERVE_READMITS.inc(kind="bucket")
            self._ever_compiled.add(key)
            try:
                _introspect.note_program(
                    "decode_step", compiled=compiled,
                    label=bucket_label(key),
                    contracts={
                        "donate_argnums": (0,),
                        "donated_leaves": len(shapes),
                        "host_callbacks": 0,
                        "collectives": 0,
                    })
            except Exception as e:  # noqa: BLE001 — stats best-effort
                log.debug("decode_step note_program failed: %s", str(e))
            self._compiled[key] = compiled
            return compiled

    def warmup(self, keys=None) -> int:
        """Compile the whole lattice before traffic.  After this,
        per-step join/leave and page-boundary growth route between
        already-compiled keys — zero hot-path compiles."""
        done = 0
        for key in (keys if keys is not None else self.spec.all_keys()):
            self.precompile(tuple(key))
            done += 1
        return done

    # -- admission -----------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int,
               deadline_ms: Optional[float] = None, priority: int = 0,
               tenant: str = "default") -> Future:
        """Admit one sequence; resolves to its generated token list.
        Sheds typed (``Overloaded`` with retry-after) on a full waiting
        queue, on an over-capacity request, or — policy ``deadline`` —
        when the EDF estimate already cannot meet ``deadline_ms``."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("decode submit needs a non-empty prompt")
        total = len(prompt) + int(max_new_tokens)
        if total > self.capacity:
            raise MXNetError(
                f"sequence needs {total} tokens > engine capacity "
                f"{self.capacity} (MXNET_DECODE_PAGE_TOKENS x "
                f"MXNET_DECODE_MAX_PAGES)")
        with self._lock:
            if self._closed:
                raise DecodeClosedError("DecodeEngine is closed")
            deadline = None if deadline_ms is None \
                else time.perf_counter() + float(deadline_ms) / 1e3
            seq = _Seq(self._seq_no, prompt, max_new_tokens, deadline,
                       priority, tenant, self.eos)
            self._seq_no += 1
            if len(self._waiting) >= self.max_queue:
                self._count_shed(tenant, "queue_full")
                retry = self._edf.eta_s(self._queued_tokens(),
                                        self._free_slots() or 1)
                raise Overloaded(
                    f"decode waiting queue full ({self.max_queue}, "
                    f"MXNET_SERVE_MAX_QUEUE); retry after "
                    f"~{retry:.2f}s", retry_after_s=retry)
            if self.shed_policy == "deadline" and deadline is not None:
                eta = self._edf.eta_s(
                    seq.remaining() + self._queued_tokens(),
                    max(1, self.max_slots))
                if time.perf_counter() + eta > deadline:
                    self._count_shed(tenant, "deadline_unmeetable")
                    raise Overloaded(
                        f"deadline {deadline_ms}ms unmeetable: EDF "
                        f"estimate ~{eta * 1e3:.1f}ms for "
                        f"{seq.remaining()} decode steps behind "
                        f"{self._queued_tokens()} queued tokens",
                        retry_after_s=eta)
            self._admitted += 1
            if _metrics.ENABLED:
                _metrics.SERVE_ADMITTED.inc(tenant=tenant)
            self._waiting.append(seq)
            # EDF order: priority first, earliest deadline within it
            self._waiting.sort(key=lambda s: (
                -s.priority,
                s.deadline if s.deadline is not None else float("inf"),
                s.sid))
            return seq.future

    def generate(self, prompt, max_new_tokens: int, **kw) -> List[int]:
        """Blocking convenience: submit + drive the engine until this
        sequence resolves (single-threaded tests and scripts)."""
        fut = self.submit(prompt, max_new_tokens, **kw)
        while not fut.done():
            if self.step() == 0 and not fut.done():
                break
        return fut.result()

    def _count_shed(self, tenant: str, reason: str) -> None:
        self._shed += 1
        if _metrics.ENABLED:
            _metrics.SERVE_SHED.inc(tenant=tenant, reason=reason)

    def _queued_tokens(self) -> int:
        return sum(s.remaining() for s in self._waiting)

    def _free_slots(self) -> int:
        return self.max_slots - sum(1 for s in self._slots
                                    if s is not None)

    # -- the decode step -----------------------------------------------------
    def _retire(self, seq: _Seq, exc: Optional[Exception] = None) -> None:
        """Free the sequence's slot and resolve its future (caller
        holds the lock)."""
        if seq.slot is not None and seq.slot < len(self._slots) \
                and self._slots[seq.slot] is seq:
            self._slots[seq.slot] = None
        seq.slot = None
        if seq.future.done():
            return
        if exc is not None:
            seq.future.set_exception(exc)
            return
        self._completed += 1
        seq.future.set_result(list(seq.generated))
        if _goodput.ENABLED:
            _goodput.serve_latency_sample(
                (time.perf_counter() - seq.t0) * 1e3)
        if _flight.ENABLED:
            _flight.record("decode_seq", "serving", seq.t0 * 1e6,
                           _flight.now_us(), trace_id=seq.trace_id)

    def _shed_and_expire(self, now: float) -> None:
        """Decode-step-granularity EDF: expire passed deadlines; when
        admitted work is waiting, preempt actives whose deadlines the
        remaining-tokens estimate can no longer meet (the slot goes to
        the earliest-deadline waiter on the admit pass that follows)."""
        for seq in [s for s in self._slots if s is not None]:
            if seq.deadline is None:
                continue
            if now > seq.deadline:
                self._expired += 1
                if _metrics.ENABLED:
                    _metrics.SERVE_EXPIRED.inc(tenant=seq.tenant)
                self._retire(seq, DeadlineExceeded(
                    f"sequence {seq.sid} deadline passed after "
                    f"{len(seq.generated)} generated token(s)"))
            elif self.shed_policy == "deadline" and self._waiting \
                    and self._edf.unmeetable(seq.deadline, now,
                                             seq.remaining()):
                self._count_shed(seq.tenant, "deadline_unmeetable")
                self._retire(seq, DeadlineExceeded(
                    f"sequence {seq.sid} preempted at decode-step "
                    f"granularity: {seq.remaining()} steps x "
                    f"~{self._edf.step_s() * 1e3:.1f}ms cannot meet "
                    f"its deadline and admitted work is waiting"))
        # drop waiters that already expired too — never dispatch them
        for seq in [s for s in self._waiting
                    if s.deadline is not None and now > s.deadline]:
            self._waiting.remove(seq)
            self._expired += 1
            if _metrics.ENABLED:
                _metrics.SERVE_EXPIRED.inc(tenant=seq.tenant)
            if not seq.future.done():
                seq.future.set_exception(DeadlineExceeded(
                    f"sequence {seq.sid} deadline passed in queue"))

    def _admit_waiting(self) -> None:
        """Fill free slots in EDF order (caller holds the lock)."""
        if not self._waiting:
            return
        if len(self._slots) < self.max_slots:
            self._slots.extend(
                [None] * (self.max_slots - len(self._slots)))
        for i in range(self.max_slots):
            if not self._waiting:
                break
            if self._slots[i] is None:
                seq = self._waiting.pop(0)
                seq.slot = i
                self._slots[i] = seq

    def _needed_key(self, compact: bool = False) -> Optional[tuple]:
        """Smallest lattice key covering the in-flight set.  Steady
        state routes on the highest OCCUPIED slot index (holes from
        retirements cost nothing until the bucket boundary, so no
        transition launches on every leave); ``compact=True`` routes on
        the live COUNT instead — what the set would need after a
        ``_transition`` compaction — which is what eviction must use,
        or reclaiming low slots could never shrink the buffers."""
        hi = -1
        live = 0
        max_pos = 0
        for i, s in enumerate(self._slots):
            if s is not None:
                hi = i
                live += 1
                max_pos = max(max_pos, s.pos + 1)
        if hi < 0:
            return None
        pages = -(-max_pos // self.page_tokens)  # ceil
        slots_need = live if compact else hi + 1
        return self.spec.route({"kv": (slots_need, pages)})

    def _transition(self, new_key: tuple) -> None:
        """Move live decode state onto ``new_key``'s buffers: compact
        occupied slots to the low indices, then pad/slice every leaf
        eagerly on device (a handful of launches on the RARE
        bucket-boundary crossing — steady-state steps stay at one).
        Growth asks the budget first; on refusal the longest actives
        are evicted typed until the remainder fits."""
        import jax.numpy as jnp
        new_bytes = self._state_bytes(new_key)
        grow = new_bytes - self._kv_bytes
        if grow > 0:
            if not _memory.ensure_headroom(
                    grow, why=f"decode.kv_grow:{self.name}"):
                self._evict_for_fit()
                new_key = self._needed_key(compact=True)
                if new_key is None:
                    self._drop_state()
                    return
                new_bytes = self._state_bytes(new_key)
        # compact: occupied slots move to 0..n-1 in slot order
        live = [s for s in self._slots if s is not None]
        if self._state is not None and live:
            perm = jnp.asarray([s.slot for s in live], dtype=jnp.int32)
            cap_axes = getattr(self.model, "state_capacity_axes", {})
            shapes = self._state_shapes(new_key)
            new_state = {}
            for n, leaf in self._state.items():
                taken = jnp.take(leaf, perm, axis=0)
                tgt, dt = shapes[n]
                pads = []
                for ax, d in enumerate(tgt):
                    have = taken.shape[ax]
                    if d < have:  # capacity shrink: keep the low side
                        taken = jnp.take(
                            taken, jnp.arange(d), axis=ax)
                        have = d
                    pads.append((0, d - have))
                new_state[n] = jnp.pad(taken, pads)
                del cap_axes  # capacity axis handled by shape math
                cap_axes = getattr(self.model, "state_capacity_axes", {})
            self._state = new_state
        else:
            shapes = self._state_shapes(new_key)
            self._state = {n: jnp.zeros(shp, dtype=dt)
                           for n, (shp, dt) in shapes.items()}
        for i, s in enumerate(live):
            s.slot = i
        self._slots = live + [None] * (self.max_slots - len(live))
        self._key = new_key
        self._register_kv(new_bytes)

    def _register_kv(self, nbytes: int) -> None:
        self._kv_bytes = int(nbytes)
        _memory.register(self._kv_holder, tag=KV_TAG,
                         nbytes=self._kv_bytes)

    def _drop_state(self) -> None:
        self._state = None
        self._key = None
        self._register_kv(0)

    def _evict_for_fit(self) -> None:
        """Budget refused KV growth: evict the longest actives (they
        force the page growth) typed until what remains fits the
        current buffers."""
        victims = sorted((s for s in self._slots if s is not None),
                         key=lambda s: -s.pos)
        for seq in victims:
            need = self._needed_key(compact=True)
            if need is None or (self._key is not None
                                and self._state_bytes(need)
                                <= self._kv_bytes):
                return
            self._evict_seq(seq, why="kv_grow")

    def _evict_seq(self, seq: _Seq, why: str) -> None:
        self._evicted += 1
        if _metrics.ENABLED:
            _metrics.SERVE_EVICTIONS.inc(kind="kv_pages",
                                         model=self.name)
            _metrics.DECODE_KV_EVICTIONS.inc()
        retry = self._edf.eta_s(self._queued_tokens() + seq.remaining(),
                                max(1, self.max_slots))
        self._retire(seq, SequenceEvicted(
            f"sequence {seq.sid} KV pages reclaimed under HBM "
            f"pressure ({why}); resubmit after ~{retry:.2f}s",
            retry_after_s=max(0.05, retry)))

    def release_kv_pages(self, deficit: float, why: str = "") -> float:
        """Reclaim ~``deficit`` ledger bytes of paged decode state —
        the ``serve_kv_pages`` arbiter hook (registry ``_make_room``
        phase 0).  Coldest first: waiting sequences hold no pages, so
        victims are actives with the *latest* deadlines / lowest
        priority / most work left; each fails typed with retry-after.
        Shrinks onto the smaller lattice key (or drops the buffers
        outright) so the freed bytes are REAL, then reports the
        measured ledger delta.

        Best-effort by contract: a busy engine lock (another thread
        mid-step) returns 0 instead of blocking — the arbiter moves on
        to cold buckets/models, and no registry-lock → engine-lock
        ordering edge can ever deadlock against an engine asking the
        budget for growth."""
        if not self._lock.acquire(blocking=False):
            return 0.0
        try:
            if self._state is None:
                return 0.0
            before = self._kv_bytes
            with _flight.phase_span("serve_evict", cat="serving",
                                    mem=True,
                                    labels={"model": self.name}):
                _fi_fire("serving.evict", model=self.name,
                         kind="kv_pages", why=why)
                victims = sorted(
                    (s for s in self._slots if s is not None),
                    key=lambda s: (
                        s.priority,
                        -(s.deadline if s.deadline is not None
                          else float("inf")),
                        -s.remaining()))
                for seq in victims:
                    if before - self._state_bytes_now() >= deficit:
                        break
                    self._evict_seq(seq, why=why or "arbiter")
                    need = self._needed_key(compact=True)
                    if need is None:
                        self._drop_state()
                    elif need != self._key:
                        self._transition(need)
            return float(before - self._kv_bytes)
        finally:
            self._lock.release()

    def _state_bytes_now(self) -> int:
        return self._kv_bytes if self._state is not None else 0

    @hot_path
    def step(self) -> int:
        """ONE decode step over the whole in-flight set: expire/shed
        (EDF), admit waiters into free slots, route the lattice key,
        then ONE donated dispatch — join/leave churn never changes the
        dispatch count.  Returns the number of active sequences
        advanced (0 = idle)."""
        with self._lock:
            if self._closed:
                raise DecodeClosedError("DecodeEngine is closed")
            now = time.perf_counter()
            self._shed_and_expire(now)
            self._admit_waiting()
            key = self._needed_key()
            if key is None:
                if self._state is not None:
                    self._drop_state()
                self._refresh_gauges()
                return 0
            if key != self._key or self._state is None:
                self._transition(key)
                key = self._key
                if key is None:
                    self._refresh_gauges()
                    return 0
            compiled = self.precompile(key)
            slots_b = key[0]
            tokens = _np.zeros((slots_b,), dtype=_np.int32)
            pos = _np.zeros((slots_b,), dtype=_np.int32)
            fresh = _np.zeros((slots_b,), dtype=_np.bool_)
            active = []
            for i in range(slots_b):
                s = self._slots[i]
                if s is None:
                    continue
                active.append(s)
                tokens[i] = s.prompt[s.pos] if s.pos < len(s.prompt) \
                    else s.generated[-1]
                pos[i] = s.pos
                # never dispatched: the slot's state rows may be a
                # retired predecessor's — the compiled step zeroes them
                fresh[i] = s.pos == 0
            t0 = time.perf_counter()
            with _flight.phase_span("decode_step", cat="serving",
                                    mem=True,
                                    labels={"bucket":
                                            bucket_label(key)}), \
                    _memory.oom_guard("serving.decode_step"):
                # chaos site BEFORE the dispatch: a raise rule models a
                # failed step with sequence state fully intact — the
                # caller retries step() and decode resumes bitwise
                # (tests/test_decode.py pins it); a delay rule is a
                # slow step feeding the EDF EWMA
                _fi_fire("serving.decode_step", step=self._steps,
                         active=len(active))
                if _metrics.ENABLED:
                    _metrics.XLA_LAUNCHES.inc(kind="decode")
                    _metrics.DECODE_STEPS.inc()
                state = self._state
                self._state = None  # donated: never reuse on failure
                try:
                    new_state, nxt = compiled(state, fresh, tokens,
                                              pos, self._params)
                except BaseException as e:
                    # the donated state may be consumed — poison the
                    # old mapping (typed DonatedBufferError on reuse
                    # under MXNET_SANITIZE) and fail every active
                    # sequence typed; waiting sequences survive
                    if _san.ENABLED:
                        _san.poison_mapping("decode_step", state)
                    self._drop_state()
                    err = MXNetError(
                        f"decode step failed mid-generation: "
                        f"{type(e).__name__}: {e}")
                    for s in active:
                        self._retire(s, err)
                    raise
                self._state = new_state
            # the per-step host sync is the decode CONTRACT, not an
            # accident: the sampled token is next step's input and the
            # join/leave scheduler's retire signal, so serving reads it
            # every step by design (continuous batching's irreducible
            # sync; the training hot paths this rule protects have no
            # such data dependence)
            # graft-lint: disable=host-sync
            nxt = _np.asarray(nxt)
            self._steps += 1
            step_s = time.perf_counter() - t0
            self._edf.observe(step_s)
            gen = 0
            for s in active:
                emitting = s.pos >= len(s.prompt) - 1
                s.pos += 1
                if emitting:
                    # host read of an already-synced numpy row (the
                    # asarray above); same justification
                    tok = int(nxt[s.slot])  # graft-lint: disable=host-sync
                    s.generated.append(tok)
                    gen += 1
                    done = len(s.generated) >= s.max_new or (
                        s.eos is not None and tok == s.eos)
                    if done:
                        self._retire(s)
            self._tokens_out += gen
            if _metrics.ENABLED:
                if gen:
                    _metrics.DECODE_TOKENS.inc(gen)
                if step_s > 0:
                    _metrics.DECODE_TOKENS_PER_S.set(
                        len(active) / max(step_s, 1e-9))
            self._refresh_gauges()
            if _flight.ENABLED:
                _flight.note("decode_step", step_s)
            return len(active)

    def drain(self, max_steps: int = 100000) -> int:
        """Step until idle (everything retired); returns steps run."""
        n = 0
        while n < max_steps:
            if self.step() == 0:
                break
            n += 1
        return n

    def _refresh_gauges(self) -> None:
        if not _metrics.ENABLED:
            return
        inflight = sum(1 for s in self._slots if s is not None)
        _metrics.DECODE_INFLIGHT.set(float(inflight))
        if self._key is not None and self._state is not None:
            slots_b, pages_b = self._key
            cap = slots_b * pages_b * self.page_tokens
            used = sum(s.pos + 1 for s in self._slots if s is not None)
            _metrics.DECODE_KV_OCCUPANCY.set(used / cap if cap else 0.0)
        else:
            _metrics.DECODE_KV_OCCUPANCY.set(0.0)

    # -- introspection / lifecycle -------------------------------------------
    def pending(self) -> int:
        with self._lock:
            return len(self._waiting) + sum(
                1 for s in self._slots if s is not None)

    def stats(self) -> dict:
        with self._lock:
            return {
                "steps": self._steps,
                "tokens": self._tokens_out,
                "admitted": self._admitted,
                "completed": self._completed,
                "evicted": self._evicted,
                "shed": self._shed,
                "expired": self._expired,
                "inflight": sum(1 for s in self._slots
                                if s is not None),
                "waiting": len(self._waiting),
                "key": self._key,
                "kv_bytes": self._kv_bytes,
                "step_ewma_s": self._edf.step_s(),
                "goodput": (self._completed / self._admitted)
                if self._admitted else 1.0,
            }

    def memory_stats(self) -> dict:
        with self._lock:
            return {
                "weights_bytes": sum(int(v.nbytes)
                                     for v in self._params.values()),
                "kv_bytes": self._kv_bytes,
            }

    def close(self) -> None:
        """Fail everything in flight typed, drop the compiled lattice,
        weights, and KV pages.  After close + the caller dropping its
        references, every ``serve_kv_pages`` ledger byte is back to
        baseline (the leak gate in tests/test_decode.py pins it)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            err = DecodeClosedError(
                "DecodeEngine closed before this sequence finished")
            for s in list(self._waiting):
                if not s.future.done():
                    s.future.set_exception(err)
            self._waiting.clear()
            for s in list(self._slots):
                if s is not None:
                    self._retire(s, err)
            self._slots = []
            self._drop_state()
            self._compiled.clear()
            self._params = {}
            if _metrics.ENABLED:
                _metrics.DECODE_INFLIGHT.set(0.0)
                _metrics.DECODE_KV_OCCUPANCY.set(0.0)
        with _engines_lock:
            _ENGINES.discard(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ---------------------------------------------------------------------------
# smoke gate: `python -m mxnet_tpu.serving.decode --smoke`
# ---------------------------------------------------------------------------
def _smoke() -> int:
    """The decode-smoke acceptance (< 60s, CPU): mixed-length traffic
    with per-step join/leave over a warmed lattice must hold exactly
    ONE dispatch per decode step and ZERO post-warmup compiles, and
    every admitted sequence must finish."""
    model = ToyLM(vocab=32, dim=8, window=4)
    eng = DecodeEngine(model, slots=4, page_tokens=4, max_pages=4,
                       name="smoke")
    try:
        compiles0 = _metrics.SERVE_COMPILES.value
        launches0 = _metrics.XLA_LAUNCHES.get(kind="decode")
        rng = _np.random.RandomState(0)
        futs = []
        # staggered mixed-length admission: the in-flight set churns
        # every few steps
        pending = [([int(t) for t in rng.randint(0, 32, size=n)], m)
                   for n, m in [(2, 3), (5, 8), (1, 12), (3, 2),
                                (7, 5), (2, 9), (4, 4), (1, 6)]]
        steps = 0
        while pending or eng.pending():
            for _ in range(2):
                if pending:
                    p, m = pending.pop(0)
                    futs.append(eng.submit(p, m))
            if eng.step() > 0:
                steps += 1
        outs = [f.result(timeout=5) for f in futs]
        launches = _metrics.XLA_LAUNCHES.get(kind="decode") - launches0
        compiles = _metrics.SERVE_COMPILES.value - compiles0
        ok = (launches == steps and compiles == 0
              and all(len(o) > 0 for o in outs)
              and eng.stats()["completed"] == len(futs))
        print(json.dumps({
            "decode_smoke": bool(ok),
            "steps": steps,
            "dispatches": launches,
            "post_warmup_compiles": compiles,
            "sequences": len(outs),
            "tokens": sum(len(o) for o in outs),
        }))
        if not ok:
            print("decode-smoke FAILED: dispatches != steps, a "
                  "post-warmup compile, or an unfinished sequence",
                  file=sys.stderr)
            return 1
        return 0
    finally:
        eng.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="mxnet_tpu.serving.decode")
    ap.add_argument("--smoke", action="store_true",
                    help="run the decode-smoke acceptance gate")
    args = ap.parse_args(argv)
    if args.smoke:
        return _smoke()
    ap.print_help()
    return 0


if __name__ == "__main__":
    sys.exit(main())
