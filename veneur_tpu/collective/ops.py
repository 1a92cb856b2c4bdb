"""Reusable named-axis merge collectives for sketch state.

The cross-replica merge that `parallel/sharded.py` runs at flush time is
a composition of five independent sketch merges, each tied to a metric
family's algebra (SURVEY §3.4; t-digests arxiv 1902.04023, HLL register
merge arxiv 2005.13332):

- two-float pair totals for counters and digest scalars (`psum` would
  round the ~48-bit pairs back to 24 bits, so it is an all-gather +
  error-free TwoSum fold),
- unpack → register max → `pmax` → repack for 6-bit packed HLL,
- stamp-argmax last-write-wins for gauges/status,
- all-gather + re-compress for t-digest centroids,
- `pmin`/`pmax` for histogram extremes.

This module generalizes them out of the sharded backend into functions
parameterized by the collective axis name, so the collective global tier
(collective/tier.py) and any future mesh program merge over whichever
axis carries replica-tier state. Every function expects the shard_map
block layout: a leading local-replica dim (the collapsed-mesh tile dim)
followed by [s_local, ...] table dims, and reduces BOTH the local dim
and the named axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from veneur_tpu.aggregation.state import DeviceState, TableSpec
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest as td

REPLICA_AXIS = "replica"
SHARD_AXIS = "shard"

shard_map = jax.shard_map


def twofloat_axis_sum(hi, lo, acc, axis: str = REPLICA_AXIS):
    """Sum two-float pairs across the local leading dim AND `axis`
    without collapsing to f32 (a plain psum of hi+lo rounds the ~48-bit
    pairs back to 24 bits — the same boundary bug combine_flush_scalars
    fixes on the host). Gather every participant's pair and fold
    sequentially with error-free TwoSum merges; the global counter merge
    then matches the reference's exact int64 adds (importsrv ->
    Counter.Merge)."""
    from veneur_tpu.utils.numerics import twofloat_add, twofloat_merge
    hi, lo = twofloat_add(hi, lo, acc)   # absorb any unfolded acc
    hs = jax.lax.all_gather(hi, axis)    # [Rg, r_local, s, K]
    ls = jax.lax.all_gather(lo, axis)
    hs = hs.reshape((-1,) + hs.shape[2:])
    ls = ls.reshape((-1,) + ls.shape[2:])

    def body(carry, x):
        return twofloat_merge(carry[0], carry[1], x[0], x[1]), None

    (h, l), _ = jax.lax.scan(body, (hs[0], ls[0]), (hs[1:], ls[1:]))
    return h, l


def hll_axis_max(packed, axis: str = REPLICA_AXIS, *, precision: int):
    """Register-wise HLL union across the local leading dim and `axis`
    (reference Set.Merge, samplers/samplers.go:461). The resident layout
    is 6-bit packed i32 words; componentwise max of packed WORDS is not
    register max (a high register field dominates the word compare
    regardless of the low fields), so unpack to dense u8 registers, max
    locally and across the collective, repack. The dense form is
    transient — it never lands in state or HBM-resident buffers."""
    dense = hll_ops.unpack_registers(packed, precision=precision)
    dense = jax.lax.pmax(dense.max(axis=0), axis)
    return hll_ops.pack_registers(dense, precision=precision)


def lww_axis_merge(val, stamp, axis: str = REPLICA_AXIS):
    """Last-write-wins merge with canonical order = highest global
    participant index that wrote (reference Gauge.Merge overwrites,
    :297). Returns (merged values, written-mask u8)."""
    r_local = val.shape[0]
    ridx = jax.lax.axis_index(axis) * r_local + jnp.arange(r_local)
    ridx = ridx.reshape((r_local,) + (1,) * (val.ndim - 1))
    prio = jnp.where(stamp > 0, ridx + 1, 0)
    vals = jax.lax.all_gather(val, axis)          # [Rg, r_local, s, K]
    prios = jax.lax.all_gather(prio, axis)
    vals = vals.reshape((-1,) + vals.shape[2:])
    prios = prios.reshape((-1,) + prios.shape[2:])
    win = jnp.argmax(prios, axis=0)
    merged = jnp.take_along_axis(vals, win[None], axis=0)[0]
    written = prios.max(axis=0) > 0
    return merged, written.astype(jnp.uint8)


def digest_axis_merge(wm, w, axis: str = REPLICA_AXIS, *,
                      spec: TableSpec):
    """t-digest merge: gather every participant's centroids for the key,
    concatenate along the centroid axis, re-compress to canonical cells
    (the fixed-shape analogue of Histo.Merge digest re-add,
    samplers/samplers.go:726). Returns (h_wm, h_w) in the state's
    column layout (TableSpec.stored_cells wide) with the temp cells
    emptied."""
    wm = jax.lax.all_gather(wm, axis)   # [Rg, r_local, s, K, C]
    w = jax.lax.all_gather(w, axis)
    wm = jnp.moveaxis(wm.reshape((-1,) + wm.shape[2:]), 0, -2)  # [s,K,R,C]
    w = jnp.moveaxis(w.reshape((-1,) + w.shape[2:]), 0, -2)
    s_l, k, r, c = w.shape
    mean = wm / jnp.maximum(w, 1e-30)
    mean = mean.reshape(s_l, k, r * c)
    w = w.reshape(s_l, k, r * c)
    m2, w2 = td.compress_rows(mean, w, compression=spec.compression,
                              cells_per_k=spec.cells_per_k,
                              out_c=spec.centroids,
                              exact_extremes=spec.exact_extremes)
    pad = jnp.zeros(w2.shape[:-1] + (c - spec.centroids,), w2.dtype)
    w2 = jnp.concatenate([w2, pad], axis=-1)
    wm2 = jnp.concatenate([m2 * w2[..., :spec.centroids], pad], axis=-1)
    return wm2, w2


def extremes_axis_merge(h_min, h_max, axis: str = REPLICA_AXIS):
    return (jax.lax.pmin(h_min.min(axis=0), axis),
            jax.lax.pmax(h_max.max(axis=0), axis))


def merge_replica_block(state: DeviceState, spec: TableSpec,
                        axis: str = REPLICA_AXIS) -> DeviceState:
    """Inside shard_map: merge a [r_local, s_local, ...] block over the
    full `axis` (local reduce + named-axis collective). Returns arrays
    with the replica dims reduced away — one merged table per shard
    tile."""
    counters = twofloat_axis_sum(state.counter_hi, state.counter_lo,
                                 state.counter_acc, axis)
    h_count = twofloat_axis_sum(state.h_count_hi, state.h_count_lo,
                                state.h_count_acc, axis)
    h_sum = twofloat_axis_sum(state.h_sum_hi, state.h_sum_lo,
                              state.h_sum_acc, axis)
    h_recip = twofloat_axis_sum(state.h_recip_hi, state.h_recip_lo,
                                state.h_recip_acc, axis)

    hll = hll_axis_max(state.hll, axis, precision=spec.hll_precision)

    gauge, gauge_stamp = lww_axis_merge(state.gauge, state.gauge_stamp,
                                        axis)
    status, status_stamp = lww_axis_merge(state.status,
                                          state.status_stamp, axis)

    wm2, w2 = digest_axis_merge(state.h_wm, state.h_w, axis, spec=spec)
    h_min, h_max = extremes_axis_merge(state.h_min, state.h_max, axis)

    z = jnp.zeros_like
    return DeviceState(
        counter_acc=z(counters[0]), counter_hi=counters[0],
        counter_lo=counters[1],
        gauge=gauge, gauge_stamp=gauge_stamp,
        status=status, status_stamp=status_stamp,
        hll=hll,
        h_wm=wm2, h_w=w2,
        h_temp_n=jnp.zeros(w2.shape[:-1], jnp.int32),
        h_min=h_min, h_max=h_max,
        h_count_acc=z(h_count[0]), h_count_hi=h_count[0],
        h_count_lo=h_count[1],
        h_sum_acc=z(h_sum[0]), h_sum_hi=h_sum[0], h_sum_lo=h_sum[1],
        h_recip_acc=z(h_recip[0]), h_recip_hi=h_recip[0],
        h_recip_lo=h_recip[1],
    )
