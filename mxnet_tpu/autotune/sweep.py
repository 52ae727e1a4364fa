"""Measured-sweep tuner: short paired-interleave probes pick the knobs.

The statistic: alternate the two legs pair-by-pair, median the
adjacent-pair deltas, take the best third-sized chunk so a
noisy-neighbor burst on a shared container cannot fake a regression.

``tune()`` is the entry point: it sweeps superstep K (against the HBM
ledger's headroom — staging K batches asks ``ensure_headroom`` first),
measures the bucketed flatten/reduce across ``MXNET_BUCKET_SIZE_MB``
candidates, derives a serving bucket lattice from observed shape
traffic and a ``MicroBatcher`` hold window from the dispatch EWMA, and
persists the result via ``autotune/decisions.py`` — paid once per
(model-signature, platform), reloaded with zero re-sweep afterwards.
Every knob stays overridable by its env var (``decisions.KNOB_ENV``).
"""
from __future__ import annotations

import logging
import os
import time
from statistics import median
from typing import Dict, List, Optional, Sequence

import numpy as _np

from ..base import getenv
from . import decisions as _decisions

logger = logging.getLogger("mxnet_tpu.autotune.sweep")

#: measured probe invocations performed by the LAST tune() call — the
#: autotune-smoke gate asserts this is 0 on a decision-cache hit
last_sweep_runs: int = 0


# -- the PR 13 statistic, as a library ---------------------------------------
def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def chunked_delta_pct(deltas: Sequence[float], ref_s: float) -> float:
    """The paired-interleave estimator: median of adjacent-pair deltas
    over third-sized chunks, best chunk wins — a transient load burst
    poisons at most one chunk, not the verdict.  Returns the delta as a
    percentage of ``ref_s`` (negative = the "on" leg is faster)."""
    if not deltas or ref_s <= 0:
        return 0.0
    third = max(1, len(deltas) // 3)
    cands = [median(deltas[i:i + third])
             for i in range(0, len(deltas) - third + 1, third)]
    return min(cands) / ref_s * 100.0


def paired_interleave(fn_on, fn_off, pairs: int = 12,
                      warmup: int = 2) -> Dict[str, float]:
    """Interleaved A/B timing of two thunks (each must block until its
    work is DONE — include the device sync).  Pair order alternates per
    iteration so drift cancels; returns median leg times and the
    chunked delta percentage of on-vs-off."""
    global last_sweep_runs
    for _ in range(warmup):
        fn_on()
        fn_off()
    on_times: List[float] = []
    off_times: List[float] = []
    deltas: List[float] = []
    for i in range(pairs):
        if i % 2 == 0:
            t_on = _timed(fn_on)
            t_off = _timed(fn_off)
        else:
            t_off = _timed(fn_off)
            t_on = _timed(fn_on)
        on_times.append(t_on)
        off_times.append(t_off)
        deltas.append(t_on - t_off)
        last_sweep_runs += 2
    off_med = median(off_times)
    return {
        "on_med_s": median(on_times),
        "off_med_s": off_med,
        "delta_pct": round(chunked_delta_pct(deltas, off_med), 3),
        "pairs": pairs,
    }


# -- knob sweeps -------------------------------------------------------------
def sweep_superstep_k(stepper, data, label,
                      ks: Sequence[int] = (2, 4, 8),
                      pairs: int = 6) -> dict:
    """Measure superstep K candidates against the K=1 whole-step
    baseline on the LIVE compiler: for each K, paired-interleave one
    ``superstep`` over K copies of the batch against K sequential
    ``step`` calls (per-step wall time both ways).  Staging asks the
    HBM ledger for headroom inside ``superstep``; a candidate that
    demoted (scan never ran) is recorded ineligible rather than scored
    on its fallback timing.  Returns ``{"best_k", "table"}``."""
    import numpy as np

    def _sync(loss):
        np.asarray(loss.asnumpy())

    table: Dict[str, dict] = {}
    best_k, best_per_step = 1, None
    for k in ks:
        datas = [data] * k
        labels = [label] * k

        def fn_super():
            _sync(stepper.superstep(datas, labels))

        def fn_seq():
            for d, l in zip(datas, labels):
                _sync(stepper.step(d, l))

        was_ran = stepper.super_active
        r = paired_interleave(fn_super, fn_seq, pairs=pairs)
        scanned = stepper.super_active or was_ran
        per_step_ms = r["on_med_s"] / k * 1e3
        base_ms = r["off_med_s"] / k * 1e3
        table[str(k)] = {
            "superstep_ms_per_step": round(per_step_ms, 4),
            "wholestep_ms_per_step": round(base_ms, 4),
            "delta_pct": r["delta_pct"],
            "scanned": bool(scanned),
        }
        if not scanned:
            continue
        if best_per_step is None or per_step_ms < best_per_step:
            best_per_step, best_k = per_step_ms, k
        if best_per_step is not None and base_ms < best_per_step:
            # the K=1 baseline beat every scanned candidate so far
            pass
    # K=1 wins when no scanned candidate improved on its own baseline
    if best_per_step is not None:
        base = min(float(t["wholestep_ms_per_step"])
                   for t in table.values())
        if base <= best_per_step:
            best_k = 1
    return {"best_k": int(best_k), "table": table}


def sweep_bucket_size(sig, candidates_mb: Sequence[float] = (8, 32, 128),
                      iters: int = 6) -> dict:
    """Measure the fused flatten+unflatten round trip of the gradient
    bucketer per ``MXNET_BUCKET_SIZE_MB`` candidate on this platform —
    the part of the step the knob actually moves on a single host.
    ``sig``: the trainer's (shape, dtype) gradient signature."""
    global last_sweep_runs
    import jax
    import jax.numpy as jnp

    from ..kvstore import GradBucketer

    grads = [jnp.ones(shape, dtype=dtype) for shape, dtype in sig]
    table: Dict[str, dict] = {}
    best_mb, best_s = None, None
    for mb in candidates_mb:
        bk = GradBucketer(sig, int(float(mb) * 1024 * 1024))

        @jax.jit
        def _roundtrip(gs, _bk=bk):
            return _bk.unflatten_inline(_bk.flatten_inline(list(gs)))

        jax.block_until_ready(_roundtrip(grads))  # compile
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(_roundtrip(grads))
            times.append(time.perf_counter() - t0)
            last_sweep_runs += 1
        med = median(times)
        table[str(mb)] = {"med_ms": round(med * 1e3, 4),
                          "buckets": len(bk.sizes)}
        if best_s is None or med < best_s:
            best_s, best_mb = med, float(mb)
    return {"best_mb": best_mb, "table": table}


# -- observation-derived serving knobs ---------------------------------------
def lattice_from_traffic(sizes: Sequence[int], max_batch: int,
                         max_rungs: int = 6) -> List[int]:
    """A serving bucket lattice from OBSERVED batch-size traffic:
    quantile rungs (p50/p75/p90/p99) rounded up to the next power of
    two — requests pad to the nearest rung above, so rungs sit just
    above where traffic actually clusters instead of a blind pow2
    ladder over the whole declared range.  Always covers ``max_batch``
    (the compile-ahead ceiling)."""
    mb = max(1, int(max_batch))
    obs = sorted(int(s) for s in sizes if 0 < int(s) <= mb)
    if not obs:
        from ..serving.buckets import pow2_buckets
        return pow2_buckets(mb)

    def _pow2_up(n: int) -> int:
        p = 1
        while p < n:
            p <<= 1
        return min(p, mb)

    rungs = {mb}
    for q in (0.50, 0.75, 0.90, 0.99):
        rungs.add(_pow2_up(obs[min(len(obs) - 1,
                                   int(q * (len(obs) - 1)))]))
    out = sorted(rungs)
    while len(out) > max_rungs:
        # drop the rung whose removal wastes the least padding: merge
        # the closest adjacent pair (keep the ceiling)
        gaps = [(out[i + 1] - out[i], i) for i in range(len(out) - 1)]
        _, i = min(gaps)
        out.pop(i)
    return out


def max_wait_from_ewma(dispatch_ewma_ms: Optional[float],
                       floor_ms: float = 0.25,
                       cap_ms: float = 5.0) -> float:
    """MicroBatcher hold window from the measured dispatch EWMA: half a
    dispatch — long enough that coalescing arrivals beats dispatching
    them separately, short enough that a lone request's added latency
    stays below the work it waits for.  Clamped to [floor, cap]."""
    if not dispatch_ewma_ms or dispatch_ewma_ms <= 0:
        return 2.0  # the documented MXNET_SERVE_MAX_WAIT_MS default
    return round(min(cap_ms, max(floor_ms, 0.5 * dispatch_ewma_ms)), 3)


# -- the tuner ---------------------------------------------------------------
def tune(net, loss_fn, trainer, data, label,
         ks: Sequence[int] = (2, 4, 8), pairs: int = 6,
         bucket_candidates_mb: Sequence[float] = (8, 32, 128),
         serve_traffic: Optional[Sequence[int]] = None,
         serve_max_batch: Optional[int] = None,
         apply_env: bool = True, force: bool = False) -> Optional[dict]:
    """Run the measured sweeps for this (model, platform) and persist
    the decision.  A persisted decision short-circuits the whole sweep
    (``last_sweep_runs == 0``) unless ``force``.  Requires
    ``MXNET_AUTOTUNE=1`` (gate) and ``MXNET_WHOLE_STEP=1`` (the
    superstep builds on the whole-step program; enabled for the sweep's
    duration if off).  ``apply_env`` exports ``MXNET_PREFETCH_DEPTH=K``
    for downstream prefetchers unless the user already pinned it.
    Returns the decision record (with ``evidence.sweep_runs``)."""
    global last_sweep_runs
    if not _decisions.ENABLED:
        logger.warning("autotune.tune() called with MXNET_AUTOTUNE "
                       "disabled — no sweep, no decision")
        return None
    last_sweep_runs = 0
    from .superstep import SuperStepCompiler

    saved_ws = os.environ.get("MXNET_WHOLE_STEP")
    if not getenv("MXNET_WHOLE_STEP", False):
        os.environ["MXNET_WHOLE_STEP"] = "1"
    try:
        stepper = net if isinstance(net, SuperStepCompiler) else \
            SuperStepCompiler(net, loss_fn, trainer)
        # warm: builds the graph (and materializes deferred shapes)
        stepper.step(data, label)
        stepper.step(data, label)
        sig = stepper.decision_signature
        if sig is None:
            logger.warning("autotune: model not whole-step compilable "
                           "(%s) — nothing to tune",
                           stepper.fallback_reason)
            return None
        rec = None if force else _decisions.load(sig)
        if rec is not None:
            logger.info("autotune: decision cache hit for %s — zero "
                        "sweep runs", sig)
            return rec
        k_sweep = sweep_superstep_k(stepper, data, label, ks=ks,
                                    pairs=pairs)
        bucket_sweep = sweep_bucket_size(stepper._built["sig"],
                                         candidates_mb=
                                         bucket_candidates_mb)
        knobs = {
            "superstep_k": k_sweep["best_k"],
            "bucket_size_mb": bucket_sweep["best_mb"],
            "prefetch_depth": max(2, k_sweep["best_k"]),
        }
        from ..observability import flight as _flight
        ewma = _flight.watch_ewma("serve_dispatch")
        knobs["serve_max_wait_ms"] = max_wait_from_ewma(
            ewma * 1e3 if ewma else None)
        if serve_traffic and serve_max_batch:
            knobs["serve_buckets"] = ",".join(
                str(b) for b in lattice_from_traffic(serve_traffic,
                                                     serve_max_batch))
        evidence = {
            "sweep_runs": last_sweep_runs,
            "superstep": k_sweep["table"],
            "bucket_size": bucket_sweep["table"],
            "serve_dispatch_ewma_ms":
                round(ewma * 1e3, 4) if ewma else None,
            "batch_shape": list(_np.shape(data.asnumpy())) if hasattr(
                data, "asnumpy") else None,
        }
        rec = {"schema": 1, "signature": sig, "knobs": knobs,
               "evidence": evidence}
        path = _decisions.store(sig, knobs, evidence)
        if path:
            rec = _decisions.load(sig)
        if apply_env and "MXNET_PREFETCH_DEPTH" not in os.environ:
            # the satellite contract: autotune stages depth>=K for the
            # prefetchers; an explicit user pin always wins
            os.environ["MXNET_PREFETCH_DEPTH"] = \
                str(knobs["prefetch_depth"])
        return rec
    finally:
        if saved_ws is None:
            os.environ.pop("MXNET_WHOLE_STEP", None)
        else:
            os.environ["MXNET_WHOLE_STEP"] = saved_ws
