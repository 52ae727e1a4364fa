"""Flash attention as a Pallas TPU kernel.

The hot-op showcase for the Pallas path (`/opt/skills/guides/pallas_guide.md`):
blocked online-softmax attention that never materializes the (T, T) score
matrix.  The grid is (batch*heads, q_blocks, k_blocks) with the k dimension
sequential: each program sees one (blk_q, D) query block and one (blk_k, D)
key/value block in VMEM and walks the key block in `sub`-wide sub-tiles,
carrying running max/sum/accumulator scratch across sub-tiles and k steps.

What the forward kernel does with a call (`_fa_tiles`, `_fa_kernel`):

- The tiles come from the shapes: the largest (blk_q, sub) scores tile and
  then the largest key/value block that a VMEM budget holds, the whole key
  length where it fits (k and v are then fetched once a head).  At T 2048
  that is 512 x 512 under a resident key for head sizes 64 and 256 alike.
- q, k and v go to the MXU in the dtype they came in (bfloat16 stays
  bfloat16, float32 stays float32) and the exponentials go to the second
  product in v's dtype; scores, running max and sum, the exponentials and
  the accumulator are float32.
- Under a causal mask the sub-tiles wholly above the diagonal are neither
  fetched nor computed, and only those the diagonal crosses are masked.
  With a `window` W (query t sees keys t - W < s <= t) the sub-tiles wholly
  left of the band are skipped the same way, and those its edge crosses
  are masked.
- Keys and values may have fewer heads than the queries (grouped-query
  attention): with H query and Hkv key/value heads, query head j reads
  key/value head j // (H / Hkv), by the block index map alone; the
  backward pass sums a group's query heads into one key and value gradient.

`mxnet_flash_fwd_blocks` / `mxnet_flash_fwd_tile` (observability/metrics.py)
hold the grid, the computed share and the tiles of the call traced last;
`mxnet_flash_fwd_tiles_total` adds up, over every call traced, the tiles
the kernel visits and the tiles the mask leaves something of.
Composes with `parallel.sequence_parallel.ring_attention`, which rotates
K/V shards across chips while this kernel handles the on-chip block math.

The backward pass is two Pallas kernels behind a custom VJP (`_fa_bwd`,
`_bwd_kernels`), fed by what the forward kept:

- Where gradients are recorded the forward kernel has a second output, the
  rows' log-sum-exp `m + log l` in float32, stored compact (B * H * Tq
  floats: one (1, blk_q) row a query block; the 128-lane replication of
  the scratch never leaves VMEM) and marked `RESIDUAL_NAME` like the
  output, so a recorded CachedOp call's backward program is handed both
  and runs no forward kernel.  Outside a recording the forward program has
  the one output it always had.
- `flash_bwd_dkv` (`_fa_dkv_kernel`): a (blk_k, D) key / value block a grid
  step; the sequential grid axes run over the `group` query heads that
  read it and over their (blk_q, D) blocks of q and dO, walked in
  sub-tiles, and dK and dV are summed in float32 VMEM scratch over all of
  them.  The scores are computed transposed (keys down the rows), so
  every product is a plain or a b^T product and the statistics broadcast
  as the rows they are stored as.
- `flash_bwd_dq` (`_fa_dq_kernel`): the forward kernel's grid and loop
  bounds with dS K summed in scratch.  Seven products for the five the
  mathematics needs, no atomics.
- Both follow the forward kernel's rules: operands reach the MXU in the
  dtype they came in, P and dS are cast to it for the second products,
  scores, exponentials, `delta = rowsum(dO * O)` (one XLA fusion outside
  the kernels) and the accumulators are float32; blocks wholly above the
  diagonal or wholly outside a window's band are neither fetched nor
  computed, by position (`_key_ranges`, `_query_ranges`, clamped index
  maps), and only blocks an edge crosses are masked; the tiles come from
  the shapes under a VMEM budget of the backward's own (`_fa_bwd_tiles`).
- Each `pallas_call` sits directly inside a `jax.named_scope` of its own
  (`flash_bwd_dkv`, `flash_bwd_dq`): its device events carry that name and
  not the layer's, so a reader of forward events never counts them.
  `mxnet_flash_bwd_total{path}` counts the calls traced by path and
  `mxnet_flash_bwd_tiles_total{kind}` the tiles visited and needed.
- Shapes the tiles cannot cover, for which the forward answered with
  `_dense_reference` and kept no statistics, take `_bwd_banded`, the plain
  float32 pass in XLA that the tests hold the kernels against.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from ..base import Arg
from .registry import RESIDUAL_NAME, register

NEG_INF = -1e30


def _lanes(x, n):
    """A lane-replicated (rows, 128) value at n lanes."""
    if n % 128 == 0:
        return jnp.tile(x, (1, n // 128))
    if n < 128:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _count(n, sub, n_sub):
    """How many whole `sub`-wide sub-tiles lie before position offset n,
    kept inside [0, n_sub].  `lax.div`, not `//`, on traced integers: it
    rounds toward zero, which below zero clamps to 0 all the same, and `//`
    costs every lowering of a kernel a traced helper for each sign.  On
    plain integers (the counters' books) it is the same number."""
    if isinstance(n, int):
        return min(max(n // sub, 0), n_sub)
    return jnp.minimum(jnp.maximum(jax.lax.div(n, sub), 0), n_sub)


def _clip(x, lo, hi):
    if all(isinstance(a, int) for a in (x, lo, hi)):
        return min(max(x, lo), hi)
    return jnp.clip(x, lo, hi)


def _key_ranges(first_q, first_k, blk_q, sub, n_sub, causal, window):
    """[(lo, hi, masked)]: the runs of `sub`-wide key sub-tiles, counted
    from column first_k, that a query block of blk_q rows from row first_q
    meets, and whether the mask cuts them.  Under a causal mask: sub-tiles
    wholly under the diagonal (last column <= first row) need no mask;
    those the diagonal crosses are masked; those wholly above it (first
    column > last row) are not visited.  Under a window those wholly left
    of the band (last column <= first row - window) are not visited either,
    and those its edge crosses (first column <= last row - window) are
    masked.  The forward kernel, the dQ kernel and the counters' books
    (plain integers) all run on these bounds."""
    if not causal:
        return [(0, n_sub, False)]
    count = functools.partial(_count, sub=sub, n_sub=n_sub)
    under = count(first_q - first_k + 1)
    seen = count(first_q + blk_q - 1 - first_k + sub)
    if window is None:
        return [(0, under, False), (under, seen, True)]
    # a row whose keys all lie further right meets only masked columns
    # first, and what it summed over them is wiped when its first real
    # score arrives (NEG_INF is finite: the correction is
    # exp(-1e30 - m) = 0)
    start = count(first_q - window + 1 - first_k)
    inside = _clip(count(first_q + blk_q - 1 - window - first_k + sub),
                   start, seen)
    under = _clip(under, inside, seen)
    return [(start, inside, True), (inside, under, False),
            (under, seen, True)]


def _query_ranges(first_k, first_q, blk_k, sub, n_sub, causal, window):
    """[(lo, hi, masked)]: the runs of `sub`-tall query sub-tiles, counted
    from row first_q, that a key block of blk_k columns from column first_k
    is seen by: `_key_ranges` with the roles exchanged (the dK/dV kernel's
    loop).  Sub-tiles wholly above the diagonal (last row < first column)
    come first and are not visited; then those the diagonal crosses
    (masked), those wholly under it and inside the band, those the band's
    edge crosses (last row - first column >= window; masked), and those
    wholly past it (first row - last column >= window; not visited)."""
    if not causal:
        return [(0, n_sub, False)]
    count = functools.partial(_count, sub=sub, n_sub=n_sub)
    ahead = first_k - first_q
    lo = count(ahead)
    under = count(ahead + blk_k - 1 + sub - 1)
    if window is None:
        under = _clip(under, lo, n_sub)
        return [(lo, under, True), (under, n_sub, False)]
    hi = _clip(count(ahead + window + blk_k - 1 + sub - 1), lo, n_sub)
    under = _clip(under, lo, hi)
    edge = _clip(count(ahead + window), under, hi)
    return [(lo, under, True), (under, edge, False), (edge, hi, True)]


def _seen(rows, cols, ahead, window, transposed=False):
    """The mask of a (rows, cols) scores tile whose corner key lies `ahead`
    positions past its corner query; `transposed`: keys down the rows."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    # query position - key position is behind - ahead
    behind = col - row if transposed else row - col
    seen = behind >= ahead
    if window is not None:
        seen &= behind < ahead + window
    return seen


def _row_of(x):
    """A lane-replicated (rows, 128) value as one (1, rows) row: column j
    of each 128-row piece is picked out on the diagonal and summed down the
    sublanes, which Mosaic lowers without a transpose."""
    pieces = []
    for i in range(0, x.shape[0], 128):
        n = min(128, x.shape[0] - i)
        eye = jax.lax.broadcasted_iota(jnp.int32, (n, 128), 0) == \
            jax.lax.broadcasted_iota(jnp.int32, (n, 128), 1)
        pieces.append(jnp.sum(jnp.where(eye, x[i:i + n], 0.0), axis=0,
                              keepdims=True)[:, :n])
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


def _lse_row(m, l):
    """The rows' log-sum-exp `m + log l` as a (1, rows) row, from the
    lane-replicated (rows, 128) running max and sum.  Whole vregs of eight
    rows, all 128-row pieces at once: row 8k + s of a piece is selected
    into lane 8k + s of sublane s of the piece's (8, 128) value (m over
    zeros, l over ones), so the logarithm runs over one vreg a piece and
    not sixteen, every lane's other sublanes read 0 + log 1, and a sum down
    the sublanes leaves the row.  The forward kernel pays this once a query
    block: at 512 rows 2.3% of the call at T 2,048 and D 64, where
    `_row_of` of the finished sum cost 3.9% (chip runs, PR 32).  The chain
    is sixteen selects whatever the rows: a kernel's lowering, which every
    process pays for every layer, grows with the operations traced."""
    rows = m.shape[0]
    if rows % 128:
        return _row_of(m + jnp.log(l))
    pieces = rows // 128
    m, l = (a.reshape(pieces, 128, 128) for a in (m, l))
    d = jax.lax.broadcasted_iota(jnp.int32, (pieces, 8, 128), 2) - \
        jax.lax.broadcasted_iota(jnp.int32, (pieces, 8, 128), 1)
    row_m = jnp.zeros((pieces, 8, 128), jnp.float32)
    row_l = jnp.ones((pieces, 8, 128), jnp.float32)
    for k in range(0, 128, 8):
        here = d == k
        row_m = jnp.where(here, m[:, k:k + 8], row_m)
        row_l = jnp.where(here, l[:, k:k + 8], row_l)
    row = jnp.sum(row_m + jnp.log(row_l), axis=1, keepdims=True)
    return jnp.concatenate([row[i] for i in range(pieces)], axis=1)


def _column_of(row):
    """A (1, rows) row as a (rows, 128) lane-replicated value, the way back
    from `_lse_row`."""
    return jnp.broadcast_to(jnp.expand_dims(row[0], -1),
                            (row.shape[1], 128))


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *refs, scale, scale_q, causal,
               window, blk_q, blk_k, sub):
    """Grid (BH, nq, nk); nk is sequential — scratch carries the online
    softmax state across k steps, and within a step across the `sub`-wide
    sub-tiles of the (blk_k, D) key / value block.  The running max and
    sum are (blk_q, 1) values replicated over a 128-lane scratch row:
    Mosaic has no layout for rank-1 vectors, and whole vregs go in and out
    of the scratch without a lane broadcast.

    q, k and v reach the MXU in the dtype they came in; scores, softmax
    state and the accumulator are float32.  `scale_q` says the scale may
    go on q (the product is exact or q is float32); else it goes on the
    float32 scores.

    `refs` is the scratch (acc, m, l), after a second output where the
    call keeps the row statistics for the backward kernels: the rows'
    log-sum-exp `m + log l` as one (1, blk_q) row, so that B*H*Tq floats
    reach HBM and the 128-lane replication never leaves VMEM."""
    lse_ref = refs[0] if len(refs) == 4 else None
    acc_ref, m_ref, l_ref = refs[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    n_sub = blk_k // sub

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # positions of this step's corner: row first_q of q, column first_k of
    # k (the tiles are unequal, so everything is compared by position)
    first_q, first_k = qi * blk_q, ki * blk_k
    q = q_ref[...]                                           # (blk_q, D)
    if scale_q:
        q = q * scale

    def _update(c, masked):
        """Sub-tile c of the key block: columns first_k + c * sub on."""
        rows = slice(None) if n_sub == 1 else \
            pl.ds(pl.multiple_of(c * sub, sub), sub)
        # q @ k^T as a contraction over D of both operands (no transpose)
        s = jax.lax.dot_general(q, k_ref[rows, :], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if not scale_q:
            s = s * scale
        if masked:
            s = jnp.where(_seen(blk_q, sub, first_k + c * sub - first_q,
                                window), s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, sub))
        corr = jnp.exp(m_prev - m_new)
        v = v_ref[rows, :]
        acc_ref[...] = acc_ref[...] * _lanes(corr, acc_ref.shape[1]) \
            + jnp.dot(p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new

    # a key block that holds no sub-tile to visit is not fetched
    # (`kv_index_map`)
    for lo, hi, masked in _key_ranges(first_q, first_k, blk_q, sub, n_sub,
                                      causal, window):
        jax.lax.fori_loop(lo, hi, lambda c, _, m=masked: _update(c, m), None)

    @pl.when(ki == nk - 1)
    def _finish():
        acc, l = acc_ref[...], jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc / _lanes(l, acc.shape[1])).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = _lse_row(m_ref[...], l)


def _dense_reference(q, k, v, scale, causal, window=None):
    group = q.shape[1] // k.shape[1]
    if group > 1:  # query head j reads key / value head j // group
        k, v = (jnp.repeat(a, group, axis=1) for a in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        ahead = jnp.arange(Tq)[:, None] - jnp.arange(Tk)[None, :]
        mask = ahead >= 0
        if window is not None:
            mask &= ahead < window
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


# What one grid step may hold in VMEM by `_fa_vmem_bytes`, under the 16 MiB
# that Mosaic grants a kernel on v5e without being asked; the scores tile
# may take a third of it.
VMEM_BUDGET = 12 * 2 ** 20


def _fa_scores_bytes(blk_q, sub, itemsize):
    """The scores tile and its exponentials in float32, and the
    exponentials again in v's dtype for the second product."""
    return blk_q * sub * (4 + 4 + itemsize)


def _fa_vmem_bytes(blk_q, blk_k, sub, D, itemsize):
    """VMEM of one grid step: the q, output, k and v blocks (each twice,
    the pipeline's two buffers), the float32 scratch, the scores."""
    blocks = 2 * (2 * blk_q + 2 * blk_k) * D * itemsize
    scratch = blk_q * (D + 2 * 128) * 4
    return blocks + scratch + _fa_scores_bytes(blk_q, sub, itemsize)


def _choose_tiles(T, T_walked, D, dtype, budget, scores_bytes, vmem_bytes):
    """(blk, major, sub), or None where nothing fits: `blk` rows of T stay
    a grid step; the other side's T_walked rows come in blocks of `major`
    and are walked `sub` at a time.  blk and sub give the largest scores
    tile within a third of the budget (the squarer on a tie: what a causal
    mask wastes grows with the longer side); major is the largest block of
    whole sub-tiles that the rest of the budget holds, the whole of
    T_walked where it can: it is then fetched once a head and no grid step
    is spent on what the mask empties.  Tiles divide their lengths and are
    multiples of 128, or the whole length."""
    itemsize = jnp.dtype(dtype).itemsize

    def sizes(n):
        return [d for d in range(128, n, 128) if n % d == 0] + [n]

    fits = [(b * s, -abs(b - s), b, s)
            for b in sizes(T) for s in sizes(T_walked)
            if scores_bytes(b, s, itemsize) <= budget // 3]
    if not fits:
        return None
    _, _, blk, sub = max(fits)
    major = max([d for d in range(sub, T_walked + 1, sub)
                 if T_walked % d == 0 and
                 vmem_bytes(blk, d, sub, D, itemsize) <= budget],
                default=sub)
    return blk, major, sub


def _fa_tiles(Tq, Tk, D, dtype, budget=VMEM_BUDGET):
    """(blk_q, blk_k, sub) of the forward kernel for a call, or None: a
    query tile, the key / value block and its sub-tile."""
    return _choose_tiles(Tq, Tk, D, dtype, budget, _fa_scores_bytes,
                         _fa_vmem_bytes)


def _fa_blocks(Tq, Tk, blk_q, sub, causal, window=None):
    """(grid, computed): the (query tile, key sub-tile) pairs of one head
    and those the forward kernel (and the dQ kernel) visits: under a causal
    mask none above the diagonal, under a window none wholly left of the
    band either (`_key_ranges`, the kernels' own loop bounds, on plain
    integers)."""
    nq, nk = Tq // blk_q, Tk // sub
    visited = sum(hi - lo for i in range(nq) for lo, hi, _ in _key_ranges(
        i * blk_q, 0, blk_q, sub, nk, causal, window))
    return nq * nk, visited


def _fa_needed(Tq, Tk, blk_q, sub, causal, window=None):
    """The (query tile, key sub-tile) pairs of one head in which the mask
    leaves at least one (query, key) pair: counted from the mask, not
    from the kernel's loops."""
    if not causal:
        return (Tq // blk_q) * (Tk // sub)
    needed = 0
    for i in range(Tq // blk_q):
        first, last = i * blk_q, (i + 1) * blk_q - 1      # query rows
        for j in range(Tk // sub):
            lo, hi = j * sub, (j + 1) * sub - 1            # key columns
            # some row t in [first, last] sees some column s in [lo, hi]:
            # s <= t, and s > t - window
            if lo <= last and (window is None or hi > first - window):
                needed += 1
    return needed


def _fa_call(q, k, v, scale, causal, blk_q, blk_k, window, stats):
    """(o, lse): the forward kernel over q (B, H, Tq, D) and k, v (B, Hkv,
    Tk, D).  `stats`: a second output, the rows' log-sum-exp (B * H, 1, Tq)
    in float32, for the backward kernels; without it the program is the
    forward-only one.  lse is None without `stats` and where the shapes
    cannot be tiled (the dense reference answers)."""
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if H % Hkv or v.shape[1] != Hkv:
        raise ValueError(f"flash attention: {H} query heads over {Hkv} key "
                         f"and {v.shape[1]} value heads")
    if window is not None and (not causal or window < 1):
        raise ValueError("flash attention: a window needs causal=True and "
                         f"at least one key, got window={window}")
    group = H // Hkv
    if blk_q is None or blk_k is None:
        tiles = _fa_tiles(Tq, Tk, D, q.dtype)
    else:
        tiles = None if Tq % blk_q or Tk % blk_k else (blk_q, blk_k, blk_k)
    if tiles is None:
        # shapes the blocking cannot tile (not an escape from compile
        # trouble: on TPU the kernel below compiles or raises)
        return _dense_reference(q, k, v, scale, causal, window), None
    blk_q, blk_k, sub = tiles
    from jax.experimental.pallas import tpu as pltpu
    from ..observability import metrics as _metrics
    grid, visited = _fa_blocks(Tq, Tk, blk_q, sub, causal, window)
    _metrics.FLASH_FWD_BLOCKS.set(B * H * grid, kind="grid")
    _metrics.FLASH_FWD_BLOCKS.set(B * H * visited, kind="computed")
    _metrics.FLASH_FWD_TILES.inc(B * H * visited, kind="visited")
    _metrics.FLASH_FWD_TILES.inc(
        B * H * _fa_needed(Tq, Tk, blk_q, sub, causal, window),
        kind="needed")
    _metrics.FLASH_FWD_TILE.set(blk_q, dim="q")
    _metrics.FLASH_FWD_TILE.set(sub, dim="k")
    kernel = functools.partial(_fa_kernel, scale=scale,
                               scale_q=_scale_on_q(q.dtype, scale),
                               causal=causal, window=window, blk_q=blk_q,
                               blk_k=blk_k, sub=sub)
    kv_index_map = _kv_index_map(blk_q, blk_k, group, causal, window)
    q_spec = pl.BlockSpec((None, blk_q, D), lambda b, i, j: (b, i, 0))
    out_specs, out_shape = q_spec, jax.ShapeDtypeStruct((B * H, Tq, D),
                                                        q.dtype)
    if stats:
        out_specs = [out_specs, pl.BlockSpec((None, 1, blk_q),
                                             lambda b, i, j: (b, 0, i))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((B * H, 1, Tq), jnp.float32)]
    # mxnet_tpu runs with jax_enable_x64 on, under which Python scalars
    # and the index maps' literals trace as f64/i64; Mosaic has neither,
    # so the kernel is traced with 32-bit defaults
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=(B * H, Tq // blk_q, Tk // blk_k),
            in_specs=[
                q_spec,
                pl.BlockSpec((None, blk_k, D), kv_index_map),
                pl.BlockSpec((None, blk_k, D), kv_index_map),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((blk_q, D), jnp.float32),    # acc
                pltpu.VMEM((blk_q, 128), jnp.float32),  # running max
                pltpu.VMEM((blk_q, 128), jnp.float32),  # running sum
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            # the interpreter is how the CPU runs the kernel in tests; on
            # TPU Mosaic compiles it, and a compile error is an error
            interpret=jax.default_backend() == "cpu",
        )(q.reshape(B * H, Tq, D), k.reshape(B * Hkv, Tk, D),
          v.reshape(B * Hkv, Tk, D))
    o, lse = out if stats else (out, None)
    return o.reshape(B, H, Tq, D), lse


def _scale_on_q(dtype, scale):
    """The scale goes on q where that is exact (a power of two) or float32
    arithmetic already; else on the float32 scores."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


def _kv_index_map(blk_q, blk_k, group, causal, window):
    """The key / value block of grid step (b, i, j) over (query heads, query
    blocks, key blocks), forward and dQ kernels alike."""
    def kv_index_map(b, i, j):
        if causal:
            # past the last key block that holds a position this query
            # block may see the map stays on that block, and the pipeline
            # issues no copy for a block it already holds
            j = jnp.minimum(j, jax.lax.div((i + 1) * blk_q - 1, blk_k))
        if window is not None:  # nor for one wholly left of the band
            j = jnp.maximum(j, jax.lax.div(
                jnp.maximum(i * blk_q - window + 1, 0), blk_k))
        if group > 1:  # the group's query heads read one key / value head
            b = jax.lax.div(b, group)
        return b, j, 0
    return kv_index_map


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, scale, causal, blk_q=None, blk_k=None,
                     window=None):
    """The forward kernel.  q (B, H, Tq, D); k, v (B, Hkv, Tk, D) with H a
    multiple of Hkv.  blk_q / blk_k None: `_fa_tiles` chooses from the
    shapes; given, they are the scores tile and the key block both, and the
    backward kernels' tiles too.  window W (with `causal`): query t sees
    keys t - W < s <= t."""
    return _fa_call(q, k, v, scale, causal, blk_q, blk_k, window, False)[0]


def _fa_fwd(q, k, v, scale, causal, blk_q, blk_k, window):
    o, lse = _fa_call(q, k, v, scale, causal, blk_q, blk_k, window, True)
    # without the marks a recorded CachedOp call's backward program would
    # run the kernel again for `o` and the statistics
    # (registry.RESIDUAL_NAME)
    o = checkpoint_name(o, RESIDUAL_NAME)
    if lse is not None:
        lse = checkpoint_name(lse, RESIDUAL_NAME)
    return o, (q, k, v, o, lse)


# What one grid step of a backward kernel may hold in VMEM by
# `_fa_bwd_vmem_bytes`, and what the kernels ask Mosaic for (the v5e has
# 128 MiB; 16 are granted without asking).
BWD_VMEM_BUDGET = 24 * 2 ** 20
BWD_VMEM_LIMIT = 48 * 2 ** 20
# The plain float32 pass's query block.
BWD_BLOCK = 128


def _fa_bwd_scores_bytes(blk, sub, itemsize):
    """A backward scores tile: scores, exponentials, dO V^T and dS in
    float32, and P and dS again in the operands' dtype for the second
    products."""
    return blk * sub * (4 * 4 + 2 * itemsize)


def _fa_bwd_vmem_bytes(blk, major, sub, D, itemsize):
    """VMEM of one grid step of a backward kernel: the blocks that stay for
    a tile (two in, two out at the most: k, v, dK, dV) and the two that are
    walked in sub-tiles (q and dO, or k and v), each twice for the
    pipeline's two buffers; the float32 accumulators and statistics; the
    scores tile."""
    blocks = 2 * (4 * blk + 2 * major) * D * itemsize
    scratch = 2 * blk * (D + 128) * 4 + 2 * 2 * 8 * major * 4
    return blocks + scratch + _fa_bwd_scores_bytes(blk, sub, itemsize)


def _fa_bwd_tiles(T, T_walked, D, dtype, budget=BWD_VMEM_BUDGET):
    """(blk, major, sub) of one backward kernel, or None: query rows against
    walked keys in the dQ kernel, keys against walked query rows in the
    dK/dV kernel."""
    return _choose_tiles(T, T_walked, D, dtype, budget,
                         _fa_bwd_scores_bytes, _fa_bwd_vmem_bytes)


def _fa_bwd_visited(Tq, Tk, dq_tiles, dkv_tiles, causal, window=None):
    """(visited, needed): the scores tiles of one query head that the two
    backward kernels' loops run (the kernels' own bounds, on plain
    integers) and those in which the mask leaves a query a key, counted
    from the mask."""
    blk_q, _, sub_k = dq_tiles
    blk_k, major_q, sub_q = dkv_tiles
    visited = _fa_blocks(Tq, Tk, blk_q, sub_k, causal, window)[1] + sum(
        hi - lo for first_k in range(0, Tk, blk_k)
        for first_q in range(0, Tq, major_q)
        for lo, hi, _ in _query_ranges(first_k, first_q, blk_k, sub_q,
                                       major_q // sub_q, causal, window))
    return visited, _fa_needed(Tq, Tk, blk_q, sub_k, causal, window) \
        + _fa_needed(Tq, Tk, sub_q, blk_k, causal, window)


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  acc_ref, lse_col, delta_col, *, scale, scale_q, causal,
                  window, blk_q, blk_k, sub):
    """dQ of one (blk_q, D) query block.  Grid (BH, nq, nk), the forward
    kernel's, with nk sequential: the key / value block (blk_k, D) is
    walked in `sub`-wide sub-tiles on the forward kernel's own bounds, and
    dS K is summed in float32 scratch.  The rows' statistics come as
    (1, blk_q) rows and are turned into lane-replicated columns once a
    query block."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_sub = blk_k // sub

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lse_col[...] = _column_of(lse_ref[...])
        delta_col[...] = _column_of(delta_ref[...])

    first_q, first_k = qi * blk_q, ki * blk_k
    q = q_ref[...]
    if scale_q:
        q = q * scale
    do = do_ref[...]
    nt = (((1,), (1,)), ((), ()))    # a @ b^T, no transpose

    def _update(c, masked):
        rows = slice(None) if n_sub == 1 else \
            pl.ds(pl.multiple_of(c * sub, sub), sub)
        k, v = k_ref[rows, :], v_ref[rows, :]
        s = jax.lax.dot_general(q, k, nt, preferred_element_type=jnp.float32)
        if not scale_q:
            s = s * scale
        if masked:
            s = jnp.where(_seen(blk_q, sub, first_k + c * sub - first_q,
                                window), s, NEG_INF)
        p = jnp.exp(s - _lanes(lse_col[...], sub))
        dp = jax.lax.dot_general(do, v, nt,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(delta_col[...], sub))
        acc_ref[...] += jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

    for lo, hi, masked in _key_ranges(first_q, first_k, blk_q, sub, n_sub,
                                      causal, window):
        jax.lax.fori_loop(lo, hi, lambda c, _, m=masked: _update(c, m), None)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                   dv_ref, dk_acc, dv_acc, *, scale, scale_q, causal, window,
                   blk_k, blk_q, sub):
    """dK and dV of one (blk_k, D) key / value block.  Grid (B * Hkv, nk,
    group, nq) with the last two sequential: the `group` query heads that
    read this key / value head, and each head's (blk_q, D) blocks of q and
    dO, walked in `sub`-tall sub-tiles; both gradients are summed in
    float32 scratch over all of them, so a group's sum never reaches HBM.
    The scores are computed TRANSPOSED, keys down the rows (k q^T, v dO^T):
    P^T dO and dS^T q are then plain products, and the rows' statistics
    broadcast down the sublanes as the (1, sub) rows they are stored as."""
    ki = pl.program_id(1)
    g = pl.program_id(2)
    qi = pl.program_id(3)
    n_sub = blk_q // sub

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    first_k, first_q = ki * blk_k, qi * blk_q
    k, v = k_ref[...], v_ref[...]
    nt = (((1,), (1,)), ((), ()))

    def _update(c, masked):
        if n_sub == 1:
            rows = cols = slice(None)
        else:
            rows = cols = pl.ds(pl.multiple_of(c * sub, sub), sub)
        q, do = q_ref[rows, :], do_ref[rows, :]
        s = jax.lax.dot_general(k, q * scale if scale_q else q, nt,
                                preferred_element_type=jnp.float32)
        if not scale_q:
            s = s * scale
        if masked:
            s = jnp.where(_seen(blk_k, sub, first_k - first_q - c * sub,
                                window, transposed=True), s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, cols])                    # (blk_k, sub)
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, nt,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[:, cols])
        dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    for lo, hi, masked in _query_ranges(first_k, first_q, blk_k, sub, n_sub,
                                        causal, window):
        jax.lax.fori_loop(lo, hi, lambda c, _, m=masked: _update(c, m), None)

    @pl.when((g == pl.num_programs(2) - 1) & (qi == pl.num_programs(3) - 1))
    def _finish():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# Both backward calls are `jit`s of their own: the layers of a model that
# make the same call share one trace and one lowering of each kernel (a
# called function in the step's program), where every layer's own copy
# cost the start of every process a kernel lowering more.
_BWD_STATIC = dict(static_argnums=(0, 1),
                   static_argnames=("scale", "scale_q", "causal", "window"))


@functools.partial(jax.jit, **_BWD_STATIC)
def _dkv_call(tiles, group, q, k, v, do, lse, delta, **how):
    """dK, dV (B * Hkv, Tk, D) from q, dO (B * H, Tq, D), k, v and the
    rows' statistics (B * H, 1, Tq)."""
    from jax.experimental.pallas import tpu as pltpu
    blk_k, blk_q, sub = tiles
    Tq, (N, Tk, D) = q.shape[1], k.shape
    causal, window = how["causal"], how["window"]

    def q_block(j, i):
        if causal:  # query blocks wholly above the diagonal are not fetched
            i = jnp.maximum(i, jax.lax.div(j * blk_k, blk_q))
        if window is not None:  # nor those wholly past the band
            i = jnp.minimum(i, jax.lax.div(
                (j + 1) * blk_k - 1 + window - 1, blk_q))
        return i
    rows_spec = pl.BlockSpec(
        (None, blk_q, D), lambda n, j, h, i: (n * group + h, q_block(j, i), 0))
    stat_spec = pl.BlockSpec(
        (None, 1, blk_q), lambda n, j, h, i: (n * group + h, 0, q_block(j, i)))
    kv_spec = pl.BlockSpec((None, blk_k, D), lambda n, j, h, i: (n, j, 0))
    with jax.enable_x64(False), jax.named_scope("flash_bwd_dkv"):
        return pl.pallas_call(
            functools.partial(_fa_dkv_kernel, blk_k=blk_k, blk_q=blk_q,
                              sub=sub, **how),
            grid=(N, Tk // blk_k, group, Tq // blk_q),
            in_specs=[rows_spec, kv_spec, kv_spec, rows_spec, stat_spec,
                      stat_spec],
            out_specs=[kv_spec, kv_spec],
            out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch_shapes=[pltpu.VMEM((blk_k, D), jnp.float32),
                            pltpu.VMEM((blk_k, D), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary",
                                     "arbitrary"),
                vmem_limit_bytes=BWD_VMEM_LIMIT),
            interpret=jax.default_backend() == "cpu",
        )(q, k, v, do, lse, delta)


@functools.partial(jax.jit, **_BWD_STATIC)
def _dq_call(tiles, group, q, k, v, do, lse, delta, **how):
    """dQ (B * H, Tq, D), from the same."""
    from jax.experimental.pallas import tpu as pltpu
    blk_q, blk_k, sub = tiles
    (BH, Tq, D), Tk = q.shape, k.shape[1]
    rows_spec = pl.BlockSpec((None, blk_q, D), lambda b, i, j: (b, i, 0))
    stat_spec = pl.BlockSpec((None, 1, blk_q), lambda b, i, j: (b, 0, i))
    kv_spec = pl.BlockSpec((None, blk_k, D), _kv_index_map(
        blk_q, blk_k, group, how["causal"], how["window"]))
    with jax.enable_x64(False), jax.named_scope("flash_bwd_dq"):
        return pl.pallas_call(
            functools.partial(_fa_dq_kernel, blk_q=blk_q, blk_k=blk_k,
                              sub=sub, **how),
            grid=(BH, Tq // blk_q, Tk // blk_k),
            in_specs=[rows_spec, kv_spec, kv_spec, rows_spec, stat_spec,
                      stat_spec],
            out_specs=rows_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            scratch_shapes=[pltpu.VMEM((blk_q, D), jnp.float32),
                            pltpu.VMEM((blk_q, 128), jnp.float32),
                            pltpu.VMEM((blk_q, 128), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=BWD_VMEM_LIMIT),
            interpret=jax.default_backend() == "cpu",
        )(q, k, v, do, lse, delta)


def _bwd_kernels(scale, causal, window, dq_tiles, dkv_tiles, res, g):
    """The backward pass as two Pallas kernels (seven products for the five
    required, no atomics): dK and dV by key block, dQ by query block, each
    `pallas_call` directly inside a `jax.named_scope` of its own, so that
    its device events carry that name and not the layer's."""
    q, k, v, o, lse = res
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32), axis=-1
                    ).reshape(B * H, 1, Tq)
    args = (H // Hkv, q.reshape(B * H, Tq, D), k.reshape(B * Hkv, Tk, D),
            v.reshape(B * Hkv, Tk, D), g.reshape(B * H, Tq, D), lse, delta)
    how = dict(scale=scale, scale_q=_scale_on_q(q.dtype, scale),
               causal=causal, window=window)
    dk, dv = _dkv_call(dkv_tiles, *args, **how)
    dq = _dq_call(dq_tiles, *args, **how)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def _fa_bwd(scale, causal, blk_q, blk_k, window, res, g):
    """The backward pass, from the forward's residuals (q, k, v, o and the
    rows' log-sum-exp L) and the output's gradient.

    Flash backward identities (FlashAttention paper, §B):
      P = exp(S - L);  D_i = rowsum(dO ∘ O)
      dV = Pᵀ dO;  dS = P ∘ (dO Vᵀ − D_i);  dQ = dS K · scale;  dK = dSᵀ Q · scale

    One path: `_bwd_kernels`, whose tiles depend on what the call shows
    (Tq, Tk, D, dtype; the caller's blk_q / blk_k where the forward got
    them).  Shapes the tiles cannot cover, for which the forward kept no
    statistics either, take the plain float32 pass `_bwd_banded`, as the
    forward takes `_dense_reference`; `mxnet_flash_bwd_total{path}` counts
    both."""
    from ..observability import metrics as _metrics
    q, k, v, o, lse = res
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    dq_tiles = dkv_tiles = None
    if lse is not None and blk_q is not None and blk_k is not None:
        dq_tiles, dkv_tiles = (blk_q, blk_k, blk_k), (blk_k, blk_q, blk_q)
    elif lse is not None:
        dq_tiles = _fa_bwd_tiles(Tq, Tk, D, q.dtype)
        dkv_tiles = _fa_bwd_tiles(Tk, Tq, D, q.dtype)
    if dq_tiles is None or dkv_tiles is None:
        _metrics.FLASH_BWD.inc(path="reference")
        with jax.named_scope("flash_attention_bwd"):
            return _bwd_banded(scale, causal, window, (q, k, v, o), g)
    _metrics.FLASH_BWD.inc(path="kernel")
    visited, needed = _fa_bwd_visited(Tq, Tk, dq_tiles, dkv_tiles, causal,
                                      window)
    _metrics.FLASH_BWD_TILES.inc(B * H * visited, kind="visited")
    _metrics.FLASH_BWD_TILES.inc(B * H * needed, kind="needed")
    return _bwd_kernels(scale, causal, window, dq_tiles, dkv_tiles, res, g)


def _bwd_banded(scale, causal, window, res, g):
    """The backward pass for grouped heads or a window.  One loop over the
    query blocks: the block's rows of all `group` query heads that read a
    key/value head go through the products together (group x blk rows), so
    their key and value gradients are summed by the products themselves.  Under a window the block meets only a slice of `span`
    keys, the fewest whole multiples of 128 that hold every key its rows
    can see, ending where the block ends; without one it meets them all.
    The key and value gradients are summed in the loop's carry, in place
    over the slice."""
    q, k, v, o = res
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    group = H // Hkv
    blk = BWD_BLOCK if Tq % BWD_BLOCK == 0 else Tq
    nq = Tq // blk
    span = Tk if window is None else \
        min(Tk, -(-(window + blk - 1) // 128) * 128)
    f32 = lambda a: a.astype(jnp.float32)
    # (N, group, Tq, D) queries, outputs and their gradients of the query
    # heads that read each of the N = B * Hkv heads' (N, Tk, D) keys and
    # values.  The batch axis is written out (no `vmap`): the slices below
    # then stay slices at a scalar offset, where `vmap` would make them
    # gathers and scatters
    by_kv = lambda a: f32(a).reshape(B * Hkv, group, Tq, D)
    flat = lambda a: f32(a).reshape(B * Hkv, Tk, D)
    qg, og, gg = by_kv(q), by_kv(o), by_kv(g)
    kf, vf = flat(k), flat(v)
    delta = jnp.sum(gg * og, axis=-1)                     # (N, group, Tq)

    def q_block(acc, i):
        rows = lambda a: jax.lax.dynamic_slice_in_dim(
            a, i * blk, blk, axis=2).reshape((B * Hkv, group * blk)
                                             + a.shape[3:])
        qs, gs, ds = rows(qg), rows(gg), rows(delta)
        # the last `span` keys up to the block's own end, kept inside
        first = jnp.clip((i + 1) * blk - span, 0, Tk - span)
        ks = jax.lax.dynamic_slice_in_dim(kf, first, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vf, first, span, axis=1)
        s = jnp.einsum("nqd,nkd->nqk", qs, ks) * scale
        if causal:
            q_pos = jnp.tile(i * blk + jnp.arange(blk), group)
            ahead = q_pos[:, None] - (first + jnp.arange(span))[None, :]
            mask = ahead >= 0
            if window is not None:
                mask &= ahead < window
            s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        dsoft = p * (jnp.einsum("nqd,nkd->nqk", gs, vs) - ds[..., None])
        dq = jnp.einsum("nqk,nkd->nqd", dsoft, ks) * scale
        dk = jnp.einsum("nqk,nqd->nkd", dsoft, qs) * scale    # (N, span, D)
        dv = jnp.einsum("nqk,nqd->nkd", p, gs)
        add = lambda a, d: jax.lax.dynamic_update_slice_in_dim(
            a, jax.lax.dynamic_slice_in_dim(a, first, span, axis=1) + d,
            first, axis=1)
        return (add(acc[0], dk), add(acc[1], dv)), \
            dq.reshape(B * Hkv, group, blk, D)

    (dk, dv), dqs = jax.lax.scan(
        q_block, (jnp.zeros_like(kf), jnp.zeros_like(vf)), jnp.arange(nq))
    # (nq, N, group, blk, D) -> (N, group, Tq, D)
    dq = dqs.transpose(1, 2, 0, 3, 4)
    return (dq.reshape(q.shape).astype(q.dtype),
            dk.reshape(k.shape).astype(k.dtype),
            dv.reshape(v.shape).astype(v.dtype))


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


@register("_contrib_flash_attention", input_names=("q", "k", "v"),
          aliases=("flash_attention",),
          args=[Arg("causal", bool, False), Arg("scale", float, -1.0),
                Arg("block_q", int, -1), Arg("block_k", int, -1),
                Arg("window", int, -1)])
def _flash_attention_op(p, q, k, v):
    """Memory-efficient attention: q (B, H, T, D), k/v (B, Hkv, T, D) with
    H a multiple of Hkv (query head j reads key/value head j // (H / Hkv))
    → (B, H, T, D).

    block_q / block_k: the scores tile of the forward kernel; left at -1
    (both or either) the kernel chooses its tiles from the shapes.
    window W > 0 (with causal): a query sees its own key and the W - 1
    before it; left at -1, every key up to its own."""
    scale = p["scale"] if p["scale"] > 0 else q.shape[-1] ** -0.5
    window = p["window"] if p["window"] > 0 else None
    if p["block_q"] <= 0 or p["block_k"] <= 0:
        return _flash_attention(q, k, v, float(scale), bool(p["causal"]),
                                None, None, window)
    return _flash_attention(q, k, v, float(scale), bool(p["causal"]),
                            min(p["block_q"], q.shape[2]),
                            min(p["block_k"], k.shape[2]), window)


@register("_contrib_mha_decode_step",
          input_names=("qkv", "k_cache", "v_cache", "pos"),
          aliases=("mha_decode_step",), f32_inputs=(3,),
          args=[Arg("num_heads", int, required=True),
                Arg("scale", float, -1.0), Arg("impl", str, "dense")],
          num_outputs=3, differentiable=False,
          sp_impls=("ring", "ulysses"))
def _mha_decode_step_op(p, qkv, kc, vc, pos):
    """One autoregressive attention step over a KV cache (inference).

    qkv: (B, 1, 3*D) — the current token's fused projections;
    k_cache/v_cache: (B, H, Tmax, dh) rolling caches; pos: (1,) the
    current position t.  Writes this token's K/V at column t
    (lax.dynamic_update_slice — the position is DATA, so one compiled
    program serves every step) and attends over columns <= t.  Returns
    (out (B, 1, D), new_k_cache, new_v_cache).  O(Tmax*D) per token vs
    the full re-forward's O(Tmax^2*D) — the long-context decode path
    the 2017 reference never needed (its RNNs carry state natively;
    for attention the cache IS that recurrent state).
    """
    B, _, D3 = qkv.shape
    H = p["num_heads"]
    D = D3 // 3
    dh = D // H
    x = qkv.reshape(B, 3, H, dh)                    # T=1 folded away
    q, k, v = x[:, 0], x[:, 1], x[:, 2]             # (B, H, dh)
    if p["impl"] not in ("dense", "ring", "ulysses"):
        raise ValueError(
            f"mha_decode_step impl={p['impl']!r}: choose 'dense', "
            "'ring' (sequence-sharded caches) or 'ulysses' "
            "(head-sharded caches)")
    if p["impl"] in ("ring", "ulysses"):
        # sharded caches over the ambient sp mesh: the cache never
        # leaves its shard.  ring = sequence-sharded columns with a
        # pmax/psum distributed softmax; ulysses = head-sharded
        # full-length caches with purely local attention per head
        from ..parallel import sequence_parallel as _sp
        mesh, axis = _sp.current_sp_scope()
        scale = p["scale"] if p["scale"] > 0 else dh ** -0.5
        cache_spec = ((None, None, axis, None) if p["impl"] == "ring"
                      else (None, axis, None, None))
        step_fn = (_sp.ring_decode_step_sharded if p["impl"] == "ring"
                   else _sp.ulysses_decode_step_sharded)
        eager = not isinstance(qkv, jax.core.Tracer)
        orig_dev = None
        if eager:
            orig_dev = _sp.single_device_of(qkv)
            q, k, v, pos = _sp.place_on_mesh(mesh, (q, k, v, pos))
            kc, vc = _sp.place_on_mesh(mesh, (kc, vc), spec=cache_spec)
        out, kc, vc = step_fn(q, k, v, kc, vc, pos, mesh,
                              axis_name=axis, scale=float(scale))
        if eager and orig_dev is not None:
            # only the attention OUTPUT returns to the caller's device
            # (it feeds single-device eager neighbors); the caches stay
            # SHARDED — they are the recurrent state of the decode
            # loop, and gathering them back each step would both defeat
            # the memory scaling and pay O(cache) transfers per token
            out = jax.device_put(out, orig_dev)  # graft-lint: disable=memory-hygiene
        return out.reshape(B, 1, D).astype(qkv.dtype), kc, vc
    t = pos.astype(jnp.int32).reshape(())
    zero = jnp.zeros((), jnp.int32)
    kc = jax.lax.dynamic_update_slice(
        kc, k[:, :, None, :].astype(kc.dtype), (zero, zero, t, zero))
    vc = jax.lax.dynamic_update_slice(
        vc, v[:, :, None, :].astype(vc.dtype), (zero, zero, t, zero))
    scale = p["scale"] if p["scale"] > 0 else dh ** -0.5
    # scores + softmax in f32 like every other attention path (the
    # flash kernel and the dense reference): bf16 near-ties must not
    # flip the greedy argmax vs the training forward
    s = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32) * scale,
                   kc.astype(jnp.float32))
    s = jnp.where(jnp.arange(kc.shape[2])[None, None, :] <= t, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,bhtd->bhd", w, vc.astype(jnp.float32))
    return out.reshape(B, 1, D).astype(qkv.dtype), kc, vc


@register("_contrib_multihead_attention", input_names=("qkv",),
          aliases=("multihead_attention",),
          args=[Arg("num_heads", int, required=True),
                Arg("causal", bool, True), Arg("impl", str, "dense"),
                Arg("scale", float, -1.0)],
          sp_impls=("ring", "ulysses"))
def _multihead_attention_op(p, qkv):
    """Fused causal multi-head self-attention over packed projections.

    qkv: (B, T, 3*D) — the output of one Dense QKV projection; returns
    (B, T, D).  Registered as an op (not python in the gluon block) so the
    shape-dependent reshapes/masks live where shapes are always concrete —
    usable from symbol graphs and hybridized blocks.  impl='flash' routes
    to the Pallas kernel (tiles chosen from T, the head size and the
    dtype; q, k and v multiplied in the dtype they have, softmax and
    accumulation in float32; under `causal` the blocks above the diagonal
    are skipped); 'dense' materializes scores (XLA fuses the softmax
    chain).
    """
    B, T, D3 = qkv.shape
    H = p["num_heads"]
    D = D3 // 3
    dh = D // H
    x = qkv.reshape(B, T, 3, H, dh).transpose(2, 0, 3, 1, 4)  # (3,B,H,T,dh)
    q, k, v = x[0], x[1], x[2]
    scale = p["scale"] if p["scale"] > 0 else dh ** -0.5
    if p["impl"] == "flash":
        out = _flash_attention(q, k, v, float(scale), bool(p["causal"]))
    elif p["impl"] in ("ring", "ulysses"):
        # sequence parallelism as a first-class impl: the mesh comes
        # from the ambient parallel.sp_scope (captured at trace time);
        # K/V rotate over ICI (ring) or heads re-shard via all-to-all
        # (ulysses) — SURVEY.md §5's "exposed through the same
        # Module/Gluon APIs" leg
        from ..parallel import sequence_parallel as _sp
        mesh, axis = _sp.current_sp_scope()
        eager = not isinstance(q, jax.core.Tracer)
        orig_dev = None
        if eager:
            # eager arrays arrive committed to one device; place them
            # sequence-sharded on the scope's mesh for shard_map, and
            # bring the result back so downstream single-device eager
            # ops compose (a jitted sp model runs fully on the mesh)
            orig_dev = _sp.single_device_of(q)
            q, k, v = _sp.place_on_mesh(
                mesh, (q, k, v), spec=(None, None, axis, None))
        fn = (_sp.ring_attention_sharded if p["impl"] == "ring"
              else _sp.ulysses_attention_sharded)
        out = fn(q, k, v, mesh, axis_name=axis, causal=bool(p["causal"]),
                 scale=float(scale))
        if eager and orig_dev is not None:
            # transient D2D return-to-caller move (see ops/registry)
            out = jax.device_put(out, orig_dev)  # graft-lint: disable=memory-hygiene
    else:
        out = _dense_reference(q, k, v, float(scale), bool(p["causal"]))
    return out.transpose(0, 2, 1, 3).reshape(B, T, D)


@register("_contrib_arange_like", input_names=("data",),
          aliases=("arange_like",), differentiable=False,
          args=[Arg("axis", int, None), Arg("start", float, 0.0),
                Arg("step", float, 1.0)])
def _arange_like(p, x):
    """Parity: _contrib_arange_like — a [start, start+step, ...] ramp
    shaped like `data` along `axis` (or flat over all elements)."""
    if p["axis"] is None:
        n = 1
        for d in x.shape:
            n *= d
        return (p["start"] + p["step"] * jnp.arange(n)).reshape(x.shape)
    n = x.shape[p["axis"]]
    return p["start"] + p["step"] * jnp.arange(n, dtype=jnp.float32)
