"""Device-side aggregation state: the metric-key table.

The reference keeps 13 scope-split Go maps of sampler objects per worker
(reference worker.go:60-84) whose values are heap objects (int64 counters,
float64 gauges, HLL sketches, t-digests). Here the equivalent state is a
fixed-capacity struct-of-arrays, one slot per live MetricKey, assigned by the
host key dictionary (host.py). Strings never reach the device; scope and
name/tag metadata stay host-side.

Numeric representation notes:

- Counters (reference samplers/samplers.go:129: int64) are kept as a
  two-float f32 accumulator (utils/numerics.py) plus a plain f32 scatter
  target ``counter_acc`` that absorbs the per-batch scatter-adds; the host
  folds acc into (hi, lo) inside every ingest step, bounding
  rounding error to ~1e-6 relative while keeping the hot path a single
  scatter-add.
- Histogram digests are stored as (weight*mean, weight) rather than
  (mean, weight) so the ingest step is two scatter-adds with no dense
  mean recomputation; means materialize only during compaction/flush.
- Gauges are last-write-wins (reference samplers.go:225); batches are
  in arrival order, so per-batch "last sample per slot" + scatter-set
  preserves the semantics.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from veneur_tpu.ops import tdigest as td
from veneur_tpu.ops import hll


@dataclasses.dataclass(frozen=True)
class TableSpec:
    """Static capacities and sketch parameters of one key table (one shard's
    worth when sharded; see parallel/)."""
    counter_capacity: int = 1 << 16
    gauge_capacity: int = 1 << 14
    status_capacity: int = 1 << 10
    set_capacity: int = 1 << 10
    histo_capacity: int = 1 << 14
    compression: float = td.DEFAULT_COMPRESSION
    cells_per_k: int = td.DEFAULT_CELLS_PER_K
    exact_extremes: int = td.DEFAULT_EXACT_EXTREMES
    # 192 raw cells + 280 centroids = 472 columns — inside the 512 the
    # Pallas quantile kernel pads to anyway; temp feeds the per-batch
    # extremeness-priority allocation in step._histo_update
    temp_cells: int = 192
    hll_precision: int = hll.DEFAULT_PRECISION

    @property
    def centroids(self) -> int:
        return td.centroid_capacity(self.compression, self.cells_per_k,
                                    self.exact_extremes)

    @property
    def interior_cells(self) -> int:
        """k-cell columns between the 2·exact_extremes protected slots
        (see ops/tdigest.py DEFAULT_EXACT_EXTREMES)."""
        return self.centroids - 2 * self.exact_extremes

    @property
    def total_cells(self) -> int:
        """Centroid columns per digest row: C canonical k-cells plus T raw
        temp cells (the fixed-shape analogue of the reference digest's temp
        buffer, merging_digest.go:105-111). A key's first T samples land
        verbatim in temp cells — exact until compaction — so cold keys never
        suffer estimate-based cell assignment while their digest is still
        unformed."""
        return self.centroids + self.temp_cells

    @property
    def stored_cells(self) -> int:
        """Columns a digest row is STORED in: total_cells rounded up to
        whole 128-lane tiles (472 -> 512). Columns [total_cells,
        stored_cells) are never written and hold zero weight, which every
        reader of a row already takes for an empty cell.

        The width decides which way the table lies on a TPU: of the two
        (8, 128)-tiled layouts the device's default is the one that pads
        less, and f32[Kh, 472] pads 472 to 512 lanes in rows but nothing
        in columns, so by default it lay column-major between programs
        while everything in the step wants rows (the plan's row gather,
        the fused kernel's row blocks, the compaction's row copies): every
        ingest step turned both tables into rows and back, four passes
        over a whole table, 3.08 of 9.24 ms at 131072 rows (PERF.md,
        PR 33). At a multiple of 128 rows pad nothing, the default is
        row-major and the copies are gone; the row occupies the 512
        lanes it occupied in rows before."""
        return -(-self.total_cells // 128) * 128

    @property
    def registers(self) -> int:
        return hll.num_registers(self.hll_precision)

    @property
    def hll_words(self) -> int:
        """int32 words per set row in the resident 6-bit packed HLL layout
        (ops/hll.py §packed); 3/8 of the register count — 12288 B/key vs
        16384 B dense u8 (and vs 65536 B for the i32-materialized registers
        an XLA scatter chain works over) at p=14."""
        return hll.packed_words(self.hll_precision)


class DeviceState(NamedTuple):
    """One flush interval's aggregation state. All arrays are per-slot;
    slot indices beyond a type's live count are simply zero/empty."""
    # counters
    counter_acc: jax.Array   # f32[Kc] unfolded scatter target
    counter_hi: jax.Array    # f32[Kc] two-float accumulator
    counter_lo: jax.Array
    # gauges / status checks (value part; message is host-side).  The stamp
    # arrays mark slots written this interval so the cross-replica merge has
    # a well-defined last-write winner (the reference's Gauge.Merge simply
    # overwrites in import order, samplers/samplers.go:297; our canonical
    # order is "highest replica index that wrote wins").
    gauge: jax.Array         # f32[Kg]
    gauge_stamp: jax.Array   # u8[Kg] 1 if written this interval
    status: jax.Array        # f32[Kst]
    status_stamp: jax.Array  # u8[Kst]
    # sets: 6-bit packed registers, register r at bit 6r little-endian
    # (ops/hll.py pack_registers; dense u8 exists only at host
    # boundaries and in the import-row merge)
    hll: jax.Array           # i32[Ks, W] where W = ceil(R*6/32)
    # histograms / timers: digest as (wm, w) + exact scalar aggregates.
    # Columns [0, C) are canonical k-cells; columns [C, C+T) are raw temp
    # cells holding individual samples since the last compaction; columns
    # [C+T, W) are the pad up to TableSpec.stored_cells, never written.
    h_wm: jax.Array          # f32[Kh, W]  sum of weight*mean per cell
    h_w: jax.Array           # f32[Kh, W]
    # h_temp_n is the dirty mark too: a row's first sample of a cycle always
    # lands in a temp cell (step._histo_plan), so h_temp_n > 0 exactly on
    # the rows that took a sample since the last compaction, and those are
    # the rows step.compact_core compresses.
    h_temp_n: jax.Array      # i32[Kh] temp cells used since last compact
    h_min: jax.Array         # f32[Kh]
    h_max: jax.Array         # f32[Kh]
    h_count_acc: jax.Array   # f32[Kh] + two-float, like counters
    h_count_hi: jax.Array
    h_count_lo: jax.Array
    h_sum_acc: jax.Array
    h_sum_hi: jax.Array
    h_sum_lo: jax.Array
    h_recip_acc: jax.Array   # sum of weight/value — harmonic mean support
    h_recip_hi: jax.Array    # (reference samplers/samplers.go:481,493)
    h_recip_lo: jax.Array


def empty_state_compiled(spec: TableSpec) -> DeviceState:
    """ONE compiled program materializing the whole empty state, where
    the eager version dispatches ~20 distinct fill executables (one per
    array shape) on every per-interval swap."""
    return _empty_state_jit(spec=spec)


def empty_state(spec: TableSpec) -> DeviceState:
    f = jnp.float32
    kc, kg, kst = spec.counter_capacity, spec.gauge_capacity, spec.status_capacity
    ks, kh, c = spec.set_capacity, spec.histo_capacity, spec.stored_cells
    z = jnp.zeros
    return DeviceState(
        counter_acc=z((kc,), f), counter_hi=z((kc,), f), counter_lo=z((kc,), f),
        gauge=z((kg,), f), gauge_stamp=z((kg,), jnp.uint8),
        status=z((kst,), f), status_stamp=z((kst,), jnp.uint8),
        hll=jnp.zeros((ks, spec.hll_words), jnp.int32),
        h_wm=z((kh, c), f), h_w=z((kh, c), f),
        h_temp_n=z((kh,), jnp.int32),
        h_min=jnp.full((kh,), jnp.inf, f),
        h_max=jnp.full((kh,), -jnp.inf, f),
        h_count_acc=z((kh,), f), h_count_hi=z((kh,), f), h_count_lo=z((kh,), f),
        h_sum_acc=z((kh,), f), h_sum_hi=z((kh,), f), h_sum_lo=z((kh,), f),
        h_recip_acc=z((kh,), f), h_recip_hi=z((kh,), f), h_recip_lo=z((kh,), f),
    )


_empty_state_jit = jax.jit(empty_state, static_argnames=("spec",))
