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

Backward is a custom VJP that recomputes scores blockwise (a loop over
q-blocks of its own size, `BWD_BLOCK`): peak extra memory O(blk · Tk) per
(batch, head) — linear in sequence length, the standard flash recompute
trade.  With one head count and no window it multiplies every query block
against all the keys (`_bwd_whole_keys`, two ways of summing the key and
value gradients); grouped heads or a window go through `_bwd_banded`,
which takes a group's query heads together and, under a window, slices
the band of keys a query block can see.
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


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               scale, scale_q, causal, window, blk_q, blk_k, sub):
    """Grid (BH, nq, nk); nk is sequential — scratch carries the online
    softmax state across k steps, and within a step across the `sub`-wide
    sub-tiles of the (blk_k, D) key / value block.  The running max and
    sum are (blk_q, 1) values replicated over a 128-lane scratch row:
    Mosaic has no layout for rank-1 vectors, and whole vregs go in and out
    of the scratch without a lane broadcast.

    q, k and v reach the MXU in the dtype they came in; scores, softmax
    state and the accumulator are float32.  `scale_q` says the scale may
    go on q (the product is exact or q is float32); else it goes on the
    float32 scores."""
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
            row = jax.lax.broadcasted_iota(jnp.int32, (blk_q, sub), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (blk_q, sub), 1)
            # query position - key position is behind - ahead
            behind = row - col
            ahead = first_k + c * sub - first_q
            seen_ = behind >= ahead
            if window is not None:
                seen_ &= behind < ahead + window
            s = jnp.where(seen_, s, NEG_INF)
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

    def _over(lo, hi, masked):
        jax.lax.fori_loop(lo, hi, lambda c, _: _update(c, masked), None)

    if causal:
        # sub-tiles wholly under the diagonal (last column <= first row)
        # need no mask; those the diagonal crosses are masked; those
        # wholly above it (first column > last row) are not visited, and a
        # key block that holds no other is not fetched (`kv_index_map`).
        # `lax.div`, not `//`: it rounds toward zero, which below zero
        # clamps to 0 all the same, and `//` on traced integers costs
        # every lowering of the kernel a traced helper for each sign
        def count(n):
            return jnp.minimum(jnp.maximum(jax.lax.div(n, sub), 0), n_sub)
        under = count(first_q - first_k + 1)
        seen = count(first_q + blk_q - 1 - first_k + sub)
        if window is None:
            _over(0, under, False)
            _over(under, seen, True)
        else:
            # sub-tiles wholly left of the band (last column <= first row
            # - window) are not visited; those the band's edge crosses
            # (first column <= last row - window) are masked, and so are
            # those on the diagonal; a row whose keys all lie further
            # right meets only masked columns first, and what it summed
            # over them is wiped when its first real score arrives
            # (NEG_INF is finite: the correction is exp(-1e30 - m) = 0)
            start = count(first_q - window + 1 - first_k)
            inside = jnp.clip(
                count(first_q + blk_q - 1 - window - first_k + sub),
                start, seen)
            under = jnp.clip(under, inside, seen)
            _over(start, inside, True)
            _over(inside, under, False)
            _over(under, seen, True)
    else:
        _over(0, n_sub, False)

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / _lanes(
            jnp.maximum(l_ref[...], 1e-30), acc_ref.shape[1])
                      ).astype(o_ref.dtype)


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


def _fa_tiles(Tq, Tk, D, dtype, budget=VMEM_BUDGET):
    """(blk_q, blk_k, sub) for a call, or None where nothing fits: the
    query tile and the key sub-tile that give the largest scores tile
    within a third of the budget (the squarer on a tie: what a causal mask
    wastes grows with the longer side), then the largest key / value block
    of whole sub-tiles that the rest of the budget holds, the whole of Tk
    where it can: k and v are then fetched once a head and no grid step is
    spent above the diagonal.  Tiles divide Tq and Tk and are multiples of
    128, or the whole length."""
    itemsize = jnp.dtype(dtype).itemsize

    def sizes(T):
        return [d for d in range(128, T, 128) if T % d == 0] + [T]

    fits = [(bq * bs, -abs(bq - bs), bq, bs)
            for bq in sizes(Tq) for bs in sizes(Tk)
            if _fa_scores_bytes(bq, bs, itemsize) <= budget // 3]
    if not fits:
        return None
    _, _, blk_q, sub = max(fits)
    blk_k = max([d for d in range(sub, Tk + 1, sub) if Tk % d == 0 and
                 _fa_vmem_bytes(blk_q, d, sub, D, itemsize) <= budget],
                default=sub)
    return blk_q, blk_k, sub


def _fa_blocks(Tq, Tk, blk_q, sub, causal, window=None):
    """(grid, computed): the (query tile, key sub-tile) pairs of one head
    and those the kernel visits: under a causal mask none above the
    diagonal, under a window none wholly left of the band either (the
    kernel's own loop bounds, in plain integers)."""
    nq, nk = Tq // blk_q, Tk // sub
    if not causal:
        return nq * nk, nq * nk
    visited = 0
    for i in range(nq):
        seen = min(nk, ((i + 1) * blk_q - 1) // sub + 1)
        start = 0 if window is None else \
            min(max((i * blk_q - window + 1) // sub, 0), seen)
        visited += seen - start
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, scale, causal, blk_q=None, blk_k=None,
                     window=None):
    """The forward kernel.  q (B, H, Tq, D); k, v (B, Hkv, Tk, D) with H a
    multiple of Hkv.  blk_q / blk_k None: `_fa_tiles` chooses from the
    shapes; given, they are the scores tile and the key block both.
    window W (with `causal`): query t sees keys t - W < s <= t."""
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
        return _dense_reference(q, k, v, scale, causal, window)
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
    # the scale goes on q where that is exact (a power of two) or float32
    # arithmetic already; else on the float32 scores
    scale_q = q.dtype == jnp.float32 or math.frexp(scale)[0] == 0.5
    kernel = functools.partial(_fa_kernel, scale=scale, scale_q=scale_q,
                               causal=causal, window=window, blk_q=blk_q,
                               blk_k=blk_k, sub=sub)

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
    # mxnet_tpu runs with jax_enable_x64 on, under which Python scalars
    # and the index maps' literals trace as f64/i64; Mosaic has neither,
    # so the kernel is traced with 32-bit defaults
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            grid=(B * H, Tq // blk_q, Tk // blk_k),
            in_specs=[
                pl.BlockSpec((None, blk_q, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((None, blk_k, D), kv_index_map),
                pl.BlockSpec((None, blk_k, D), kv_index_map),
            ],
            out_specs=pl.BlockSpec((None, blk_q, D),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
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
    return out.reshape(B, H, Tq, D)


def _fa_fwd(q, k, v, scale, causal, blk_q, blk_k, window):
    o = _flash_attention(q, k, v, scale, causal, blk_q, blk_k, window)
    # without the mark a recorded CachedOp call's backward program would run
    # the kernel again for `o` (registry.RESIDUAL_NAME)
    o = checkpoint_name(o, RESIDUAL_NAME)
    return o, (q, k, v, o)


# The backward pass's per-block key and value gradients, stacked over the
# query blocks before they are summed, are B*H x (Tq / blk) x Tk x D floats
# each.  Past this many bytes a stack is not built: the blocks' gradients
# are summed in the loop's carry instead.  At B*H 64, T 2048, D 64 a stack
# is 0.5 GiB and that program stays as it was; at B*H 40, D 256 it would be
# 1.25 GiB twice over, 2.9 GB of a backward program's temporaries (4.62 GB
# stacked, 1.65 GB carried: v5e compiles, PR 27).
STACK_BYTES_MAX = 2 ** 30
# The backward pass's query block: its own, whatever tiles the forward
# kernel chose, so its loops and their score temporaries stay as they were.
BWD_BLOCK = 128


def _fa_bwd(scale, causal, blk_q, blk_k, window, res, g):
    """Blockwise recompute backward: a loop over q blocks keeps peak
    score memory at O(blk_q · Tk) per (batch, head).

    Flash backward identities (FlashAttention paper, §B):
      P = softmax(S);  D_i = rowsum(dO ∘ O)
      dV = Pᵀ dO;  dS = P ∘ (dO Vᵀ − D_i);  dQ = dS K · scale;  dK = dSᵀ Q · scale

    PROVISIONAL dispatch (PR 31): one head count and no window take
    `_bwd_whole_keys`, the pass the accepted cells were measured on, whose
    program text a test pins; everything else takes `_bwd_banded`.  With
    group 1 and no window `_bwd_banded` is `_bwd_whole_keys`' carried-sums
    path written once more, so three paths (stacked, carried, banded) do one
    thing.  ROADMAP S6's first step measures the accepted cells under
    `_bwd_banded` and keeps one path; until then a change to the backward
    pass is measured on all three.
    """
    if res[0].shape[1] == res[1].shape[1] and window is None:
        return _bwd_whole_keys(scale, causal, res, g)
    with jax.named_scope("flash_attention_bwd"):
        return _bwd_banded(scale, causal, window, res, g)


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


def _bwd_whole_keys(scale, causal, res, g):
    """The backward pass for one head count and no window: every query
    block against all the keys, a (batch, head) at a time."""
    q, k, v, o = res
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    blk = BWD_BLOCK if Tq % BWD_BLOCK == 0 else Tq
    nq = Tq // blk
    carry_kv = B * H * nq * Tk * D * 4 > STACK_BYTES_MAX

    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    of = o.astype(jnp.float32)
    gf = g.astype(jnp.float32)

    def per_head(q1, k1, v1, o1, g1):
        # (Tq,D),(Tk,D),... for one (batch,head)
        delta = jnp.sum(g1 * o1, axis=-1)                     # (Tq,)

        def q_block(i):
            qs = jax.lax.dynamic_slice_in_dim(q1, i * blk, blk)
            gs = jax.lax.dynamic_slice_in_dim(g1, i * blk, blk)
            ds = jax.lax.dynamic_slice_in_dim(delta, i * blk, blk)
            s = qs @ k1.T * scale                             # (blk, Tk)
            if causal:
                q_pos = i * blk + jnp.arange(blk)
                mask = q_pos[:, None] >= jnp.arange(Tk)[None, :]
                s = jnp.where(mask, s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            dp = gs @ v1.T                                    # (blk, Tk)
            dsoft = p * (dp - ds[:, None])
            dq = dsoft @ k1 * scale                           # (blk, D)
            dk = dsoft.T @ qs * scale                         # (Tk, D)
            dv = p.T @ gs                                     # (Tk, D)
            return dq, dk, dv

        if carry_kv:
            def step(acc, i):
                dq, dk, dv = q_block(i)
                return (acc[0] + dk, acc[1] + dv), dq

            (dk, dv), dqs = jax.lax.scan(
                step, (jnp.zeros_like(k1), jnp.zeros_like(v1)),
                jnp.arange(nq))
            return dqs.reshape(Tq, D), dk, dv
        dqs, dks, dvs = jax.lax.map(q_block, jnp.arange(nq))
        return dqs.reshape(Tq, D), dks.sum(0), dvs.sum(0)

    flat = lambda a: a.reshape(B * H, a.shape[2], a.shape[3])
    dq, dk, dv = jax.vmap(per_head)(flat(qf), flat(kf), flat(vf),
                                    flat(of), flat(gf))
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
