"""The jitted ingest step and flush computations.

This is the hot core replacing the reference's Worker.ProcessMetric switch
(reference worker.go:344) and the samplers' Sample methods
(reference samplers/samplers.go:142/225/375/484). One call processes a whole
padded batch of parsed samples of every type with a handful of scatter ops;
state is donated so updates are in-place on device.

Histogram ingestion is the interesting part. The reference buffers samples
into a temp array and runs a sequential greedy merge (reference
tdigest/merging_digest.go:115,140). Here every sample is assigned a k-cell
directly: its quantile midpoint is estimated from (a) the current digest's
mass below the sample value (a [B, C] gather + compare against the row's
centroids) and (b) the mass of earlier batch samples in the same key segment
(sort by (slot, value) + segmented cumsum). The sample's (weight, weight*value)
is then scatter-added into its (slot, cell). Cell assignments drift as the
distribution evolves, so the host periodically re-compresses rows
(``compact``), which re-bins all mass at once — the fixed-shape analogue of
the reference's amortized mergeAllTemps.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from veneur_tpu.aggregation.state import DeviceState, TableSpec
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest as td
from veneur_tpu.utils.numerics import twofloat_add


class Batch(NamedTuple):
    """A padded batch of parsed samples. Padding rows carry slot == capacity
    (out of range) so their scatters drop. All arrays are fixed-size per
    configuration, so one compiled program serves every step."""
    counter_slot: jax.Array   # i32[Bc]
    counter_inc: jax.Array    # f32[Bc]  value * (1/sample_rate), reference samplers.go:142
    gauge_slot: jax.Array     # i32[Bg]
    gauge_val: jax.Array      # f32[Bg]
    status_slot: jax.Array    # i32[Bst]
    status_val: jax.Array     # f32[Bst]
    set_slot: jax.Array       # i32[Bs]
    set_reg: jax.Array        # i32[Bs]
    set_rho: jax.Array        # u8[Bs]
    histo_slot: jax.Array     # i32[Bh]
    histo_val: jax.Array      # f32[Bh]
    histo_wt: jax.Array       # f32[Bh]  1/sample_rate, reference samplers.go:484
    # import-side digest scalars (global tier merge, worker.go:438
    # ImportMetricGRPC): per imported digest, its exact min/max/reciprocalSum
    # ride these lanes instead of being lossily re-derived from centroids.
    # None on pure-ingest batches (the common case).
    histo_stat_slot: jax.Array = None   # i32[Bm]
    histo_stat_min: jax.Array = None    # f32[Bm]
    histo_stat_max: jax.Array = None    # f32[Bm]
    histo_stat_recip: jax.Array = None  # f32[Bm]


def _last_per_slot_set(target, stamp, slot, val, capacity):
    """Scatter-set the LAST batch value per slot (gauge semantics,
    reference samplers/samplers.go:225 last-write-wins) and mark the slot's
    write stamp."""
    idx = jnp.arange(slot.shape[0], dtype=jnp.int32)
    order = jnp.lexsort((idx, slot))
    s = slot[order]
    v = val[order]
    is_last = jnp.concatenate([s[:-1] != s[1:], jnp.ones((1,), bool)])
    tgt = jnp.where(is_last & (s >= 0) & (s < capacity), s, capacity)
    return (target.at[tgt].set(v, mode="drop"),
            stamp.at[tgt].set(jnp.uint8(1), mode="drop"))


def _histo_plan(state: DeviceState, slot, val, wt, spec: TableSpec):
    """The estimate/temp cell-assignment math of `_histo_update`, factored
    out so the fused Pallas ingest kernel (ops/pallas_ingest.py) consumes
    the EXACT same sorted streams the scatter chain does — byte parity by
    construction. Returns (s, cell, v, w, tadd): batch sorted by
    (slot, value) with invalid rows mapped to slot==histo_capacity, the
    target cell column per row, the value/weight streams, and the
    temp-slot consumption (0/1) per row."""
    c = spec.centroids
    t = spec.temp_cells
    kh = spec.histo_capacity
    valid = (slot >= 0) & (slot < kh) & (wt > 0)
    slot = jnp.where(valid, slot, kh)
    # sort batch by (slot, value) so each key's samples are a contiguous,
    # value-ordered segment
    order = jnp.lexsort((val, slot))
    s = slot[order]
    v = jnp.where(valid[order], val[order], 0.0)
    w = jnp.where(valid[order], wt[order], 0.0)
    ok = valid[order]

    # segment bookkeeping: start flags, ids, within-segment rank
    idx = jnp.arange(s.shape[0], dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]])
    seg_id = jnp.cumsum(seg_start.astype(jnp.int32)) - 1
    rank = idx - jax.lax.cummax(jnp.where(seg_start, idx, 0))

    # T samples per compaction cycle land verbatim in a key's temp cells
    # (exact — no estimate involved), the fixed-shape analogue of the
    # reference digest's temp buffer (merging_digest.go:105-140). Temp
    # PRIORITY within each batch segment goes to the segment's most
    # EXTREME samples, alternating bottom/top (ext_order is a
    # permutation of 0..seg_len-1: bottom-0, top-0, bottom-1, top-1, …),
    # so when a hot key overflows temp, it's the MID-RANGE samples that
    # fall back to estimate-based k-cells — where cells are
    # statistically thick and merging is harmless — while the tail
    # samples that decide p99 stay raw until compaction's exact-extreme
    # protection (ops/tdigest.py) takes them over. First-come order
    # instead (the pre-r05 behavior) let tail samples of hot keys merge
    # in narrow estimate cells, the dominant per-key p99 error term.
    seg_cnt = jax.ops.segment_sum(
        jnp.where(ok, 1, 0).astype(jnp.int32), seg_id,
        num_segments=s.shape[0], indices_are_sorted=True)[seg_id]
    r_top = seg_cnt - 1 - rank
    ext_order = 2 * jnp.minimum(rank, r_top) + (rank > r_top)
    # Temp budget per batch: half of what's left (with a small floor),
    # so one big batch can't starve the rest of the compaction cycle —
    # every batch in the cycle keeps at least its ±8-rank neighborhood
    # of the tail queries exact. h_temp_n counts USED slots only (see
    # below), so unused budget rolls over to the next batch.
    avail = t - state.h_temp_n[jnp.minimum(s, kh - 1)]
    allowed = jnp.maximum(avail // 2, jnp.minimum(avail, 16))
    use_temp = ok & (ext_order < allowed)
    temp_idx = state.h_temp_n[jnp.minimum(s, kh - 1)] + ext_order

    # mass of the current digest below each sample value (temp cells
    # participate: their "means" are raw sample values)
    sc = jnp.minimum(s, kh - 1)
    row_w = state.h_w[sc]                     # f32[B, W]
    row_wm = state.h_wm[sc]
    row_mean = row_wm / jnp.maximum(row_w, 1e-30)
    w_main = jnp.sum(row_w, axis=-1)
    below = (jnp.sum(row_w * (row_mean < v[:, None]), axis=-1)
             + 0.5 * jnp.sum(row_w * (row_mean == v[:, None]), axis=-1))

    # mass of earlier batch samples in the same segment
    cum_excl = jnp.cumsum(w) - w
    base = jax.lax.cummax(jnp.where(seg_start, cum_excl, 0.0))
    cum_seg = cum_excl - base
    seg_tot = jax.ops.segment_sum(w, seg_id, num_segments=s.shape[0],
                                  indices_are_sorted=True)[seg_id]

    q_mid = (below + cum_seg + 0.5 * w) / jnp.maximum(w_main + seg_tot, 1e-30)
    k0 = -spec.compression / 4.0
    cell = jnp.floor((td._k1(q_mid, spec.compression) - k0)
                     * spec.cells_per_k).astype(jnp.int32)
    # estimate-based scatter lands in the k-cell INTERIOR only — the
    # protected extreme columns [0,E) and [C-E,C) are written exclusively
    # by compaction, which owns rank order (ops/tdigest.py compress_rows)
    cell = spec.exact_extremes + jnp.clip(cell, 0, spec.interior_cells - 1)
    cell = jnp.where(use_temp, c + jnp.minimum(temp_idx, t - 1), cell)
    return s, cell, v, w, jnp.where(use_temp, 1, 0).astype(jnp.int32)


def _histo_update(state: DeviceState, slot, val, wt, spec: TableSpec):
    s, cell, v, w, tadd = _histo_plan(state, slot, val, wt, spec)
    h_w = state.h_w.at[s, cell].add(w, mode="drop")
    h_wm = state.h_wm.at[s, cell].add(w * v, mode="drop")
    # count USED temp slots (samples that overflowed to estimate cells
    # don't consume budget — their slots stay available to later batches
    # in the cycle)
    h_temp_n = state.h_temp_n.at[s].add(tadd, mode="drop")
    h_min = state.h_min.at[s].min(jnp.where(w > 0, v, jnp.inf), mode="drop")
    h_max = state.h_max.at[s].max(jnp.where(w > 0, v, -jnp.inf), mode="drop")
    h_count = state.h_count_acc.at[s].add(w, mode="drop")
    h_sum = state.h_sum_acc.at[s].add(w * v, mode="drop")
    # Go float64 division by zero yields +Inf; match (harmonic mean of a
    # stream containing 0 is 0 downstream).
    h_recip = state.h_recip_acc.at[s].add(
        jnp.where(w > 0, w / v, 0.0), mode="drop")
    return state._replace(h_w=h_w, h_wm=h_wm, h_temp_n=h_temp_n,
                          h_min=h_min, h_max=h_max,
                          h_count_acc=h_count, h_sum_acc=h_sum,
                          h_recip_acc=h_recip)


def ingest_core(state: DeviceState, batch: Batch, *, spec: TableSpec,
                allow_pallas: bool = True) -> DeviceState:
    """Apply one padded batch to the table. The whole reference hot loop
    below the worker channel (reference server.go:984 -> worker.go:344 ->
    samplers Sample) becomes this one compiled program. Pure function —
    `ingest_step` is the donating jit wrapper; parallel/sharded.py wraps it
    in shard_map/vmap instead (with allow_pallas=False: the per-tile body
    runs under vmap, where the fused kernel's scalar-prefetch grid does
    not apply).

    When the fused Pallas ingest kernel is active (ops/pallas_ingest.py
    `active`: a TPU backend plus the module constant, or the
    `pallas_ingest_enabled` config override; byte parity pinned by
    tests/test_pallas_ingest.py), the scatter chain below is replaced by
    ONE kernel over VMEM-tiled state blocks; the XLA chain is what runs
    everywhere else, and the parity oracle."""
    from veneur_tpu.ops import pallas_ingest
    if allow_pallas and pallas_ingest.active():
        with jax.named_scope("ingest.fused"):
            state = pallas_ingest.fused_ingest_core(
                state, batch, spec=spec,
                interpret=pallas_ingest.interpret_mode())
    else:
        # one named scope per kind: the profiler's device ops and the
        # lowered program carry these names, which survive a refactor
        # where HLO instruction numbers do not
        with jax.named_scope("ingest.counter"):
            counter_acc = state.counter_acc.at[batch.counter_slot].add(
                batch.counter_inc, mode="drop")
        with jax.named_scope("ingest.gauge"):
            gauge, gauge_stamp = _last_per_slot_set(
                state.gauge, state.gauge_stamp, batch.gauge_slot,
                batch.gauge_val, spec.gauge_capacity)
        with jax.named_scope("ingest.status"):
            status, status_stamp = _last_per_slot_set(
                state.status, state.status_stamp, batch.status_slot,
                batch.status_val, spec.status_capacity)
        with jax.named_scope("ingest.set"):
            hll = hll_ops.insert_batch_packed(
                state.hll, batch.set_slot, batch.set_reg, batch.set_rho,
                precision=spec.hll_precision)
        state = state._replace(counter_acc=counter_acc,
                               gauge=gauge, gauge_stamp=gauge_stamp,
                               status=status, status_stamp=status_stamp,
                               hll=hll)
        with jax.named_scope("ingest.histo"):
            state = _histo_update(state, batch.histo_slot, batch.histo_val,
                                  batch.histo_wt, spec)
    if batch.histo_stat_slot is not None:
        s = batch.histo_stat_slot
        with jax.named_scope("ingest.histo_stat"):
            state = state._replace(
                h_min=state.h_min.at[s].min(batch.histo_stat_min,
                                            mode="drop"),
                h_max=state.h_max.at[s].max(batch.histo_stat_max,
                                            mode="drop"),
                h_recip_acc=state.h_recip_acc.at[s].add(
                    batch.histo_stat_recip, mode="drop"))
    # Fold the batch's scatter accumulators into the two-float pairs
    # INSIDE the ingest program: XLA fuses the elementwise fold into the
    # scatter dispatch (no extra launch), the f32 accumulator never
    # carries more than one batch, and the pair absorbs each batch via
    # error-free TwoSum — so counters match the reference's int64 for
    # any realistic interval (e.g. a lone :1|c arriving after 2^32 no
    # longer rounds away, which a 64-batch fold cadence allowed).
    with jax.named_scope("fold"):
        return _fold_core(state)


ingest_step = partial(jax.jit, static_argnames=("spec", "allow_pallas"),
                      donate_argnames=("state",))(ingest_core)


# -- packed batch transfer ---------------------------------------------------
# A 16-lane Batch is 16 host->device transfers per step. Mirroring the
# flush direction (flush_live_in_packed), the whole batch ships as ONE flat
# i32 buffer and the lanes are rebuilt with static slices + bitcasts inside
# the compiled program. i32 is the carrier because integer transfers are
# bit-exact (an f32 carrier could canonicalize NaN payloads in i32 lanes).

_U8_LANES = frozenset({"set_rho"})
_F32_LANES = frozenset({
    "counter_inc", "gauge_val", "status_val", "histo_val", "histo_wt",
    "histo_stat_min", "histo_stat_max", "histo_stat_recip"})


def batch_sizes(batch: Batch) -> tuple:
    """Static lane lengths of a batch (the packed program's compile key,
    alongside spec). None lanes (the optional histo_stat_* import-scalar
    lanes) encode as 0 and round-trip back to None."""
    return tuple(0 if a is None else int(a.size) for a in batch)


def packed_layout(sizes: tuple):
    """Word layout of the pack_batch buffer for the given lane sizes:
    ({lane_name: (word_off, n, words)}, total_words). Word 0 is the
    control word; lanes follow in Batch._fields order, u8 lanes padded
    to word multiples, 0-size (None) lanes absent. This is the one
    definition of the wire<->device layout — pack_batch writes it, the
    native engine's vt_emit_packed is handed these offsets, and
    unpack_batch walks the same order inside jit."""
    layout = {}
    off = 1
    for name, n in zip(Batch._fields, sizes):
        if n == 0:
            continue
        words = (n + 3) // 4 if name in _U8_LANES else n
        layout[name] = (off, n, words)
        off += words
    return layout, off


def pack_batch(batch: Batch, do_compact: bool = False, out=None):
    """Host side: one contiguous i32 buffer holding every lane (f32 lanes
    bit-viewed, u8 lanes padded to word multiples, None lanes skipped),
    preceded by one control word (the in-band compact flag — a separate
    scalar argument would be a second transfer). Each lane is written
    straight into its packed_layout slice — no intermediate parts list or
    concatenation — so hot-path callers pass a persistent zero-initialized
    `out` (aggregator.py double-buffers two; sharded packs into rows of
    one [1, S, W] array) and the pack costs one pass with zero
    allocations. Without `out` a fresh zeroed buffer is returned. A
    reused `out` must have been zero-initialized once at allocation: u8
    pad bytes are never rewritten, and every non-pad word is overwritten
    on every pack, so the buffer stays bit-identical to a fresh pack."""
    import numpy as np
    layout, words = packed_layout(batch_sizes(batch))
    if out is None:
        out = np.zeros(words, np.int32)
    out[0] = 1 if do_compact else 0
    for name, a in zip(Batch._fields, batch):
        if a is None:
            continue
        off, n, w = layout[name]
        if name in _U8_LANES:
            out[off:off + w].view(np.uint8)[:n] = a
        elif name in _F32_LANES:
            out[off:off + n].view(np.float32)[:] = a
        else:
            out[off:off + n] = a
    return out


def unpack_batch(flat, sizes: tuple) -> Batch:
    """Device side (inside jit): static slices + bitcasts back into lanes.
    A 0 size restores the lane to None (ingest_core's optional-lane
    contract, see Batch docstring)."""
    out = []
    off = 0
    for name, n in zip(Batch._fields, sizes):
        if n == 0:
            out.append(None)
            continue
        if name in _U8_LANES:
            words = (n + 3) // 4
            a = jax.lax.bitcast_convert_type(
                flat[off:off + words], jnp.uint8).reshape(-1)[:n]
            off += words
        elif name in _F32_LANES:
            a = jax.lax.bitcast_convert_type(flat[off:off + n], jnp.float32)
            off += n
        else:
            a = flat[off:off + n]
            off += n
        out.append(a)
    return Batch(*out)


def packed_step_core(state: DeviceState, flat, *, spec: TableSpec,
                     sizes: tuple):
    """The un-jitted production step: ingest one packed batch; when the
    control word is set, re-compress the digest rows that took samples
    in the SAME program (lax.cond — only the taken branch executes).
    Folding compaction in keeps the steady-state hot loop at ONE resident
    executable and one dispatch per batch. Returns (state, rows): rows
    is i32[], the digest rows this step compressed, 0 where the control
    word is clear — the device's own count behind `compact_rows`, and
    the small array by which the host knows the step finished. Shared by
    ingest_step_packed and the driver entry (__graft_entry__.entry)."""
    with jax.named_scope("unpack"):
        batch = unpack_batch(flat[1:], sizes)
    state = ingest_core(state, batch, spec=spec)
    # Stable names where the profile had `%cond.8`, an HLO instruction
    # number the next edit renumbers: the conditional itself is
    # `maybe_compact`, the ops of its taken branch `compact`. (The other
    # branch is the identity and has no op to name.)
    with jax.named_scope("maybe_compact"):
        do_compact = flat[0] != 0
        rows = jnp.where(do_compact, dirty_rows(state), 0)
        return jax.lax.cond(
            do_compact,
            jax.named_scope("compact")(partial(compact_core, spec=spec)),
            lambda s: s, state), rows


ingest_step_packed = partial(
    jax.jit, static_argnames=("spec", "sizes"),
    donate_argnames=("state",))(packed_step_core)


def packed_rings_core(state: DeviceState, arena, *, spec: TableSpec,
                      sizes: tuple):
    """Multi-ring step: `arena` is i32[R, words] — one packed row per
    reader ring, all shipped in ONE host->device transfer (the multi-ring
    pipeline's whole point: R rings cost one RTT, not R). The loop is
    unrolled at trace time (R is static via the arena shape), so XLA sees
    R back-to-back packed steps in a single program — same executable
    residency story as ingest_step_packed, and the fused Pallas ingest
    kernel (when active inside ingest_core) runs per row against its
    scalar-prefetch windows unchanged. Idle rings ride as sentinel-only
    rows whose scatters all drop; the host skips the step entirely when
    every ring emitted zero rows. Only row 0 carries the compact control
    word — one compaction per step, exactly like the single-ring path,
    and its count of rows is the step's (packed_step_core)."""
    n_rings = arena.shape[0]
    state, rows = packed_step_core(state, arena[0], spec=spec, sizes=sizes)
    for r in range(1, n_rings):
        with jax.named_scope("unpack"):
            batch = unpack_batch(arena[r][1:], sizes)
        state = ingest_core(state, batch, spec=spec)
    return state, rows


ingest_step_packed_rings = partial(
    jax.jit, static_argnames=("spec", "sizes"),
    donate_argnames=("state",))(packed_rings_core)


def _fold_core(state: DeviceState) -> DeviceState:
    ch, cl = twofloat_add(state.counter_hi, state.counter_lo, state.counter_acc)
    hch, hcl = twofloat_add(state.h_count_hi, state.h_count_lo, state.h_count_acc)
    hsh, hsl = twofloat_add(state.h_sum_hi, state.h_sum_lo, state.h_sum_acc)
    hrh, hrl = twofloat_add(state.h_recip_hi, state.h_recip_lo, state.h_recip_acc)
    z = jnp.zeros_like
    return state._replace(
        counter_acc=z(state.counter_acc), counter_hi=ch, counter_lo=cl,
        h_count_acc=z(state.h_count_acc), h_count_hi=hch, h_count_lo=hcl,
        h_sum_acc=z(state.h_sum_acc), h_sum_hi=hsh, h_sum_lo=hsl,
        h_recip_acc=z(state.h_recip_acc), h_recip_hi=hrh, h_recip_lo=hrl)


# Standalone fold kept for flush-time finalization (a last partial batch
# staged through non-ingest paths) and the host fold cadence, which is now
# a harmless no-op on already-folded state.
fold_scalars = jax.jit(_fold_core)


def dirty_rows(state: DeviceState) -> jax.Array:
    """i32[]: the digest rows that took a sample since the last
    compaction, which are the rows the next one compresses."""
    return jnp.sum(state.h_temp_n > 0, dtype=jnp.int32)


# Dirty rows one trip of compact_core's loop takes: it gathers that many
# whole rows of both digest tables, compresses them and writes them back.
# Chosen on the v5e at 131072 x 472 (PERF.md, PR 31): XLA's row scatter
# costs a pass over the table it writes, 0.8 ms a call however few the
# rows, so few large trips beat many small ones (22,400 dirty rows: 153 ms
# in blocks of 256, 50 in 1024, 27 in 4096, 21 in 8192, 27 in 16384, where
# the last block's idle rows cost more than a trip saves).
COMPACT_ROW_BLOCK = 8192


def compact_core(state: DeviceState, *, spec: TableSpec) -> DeviceState:
    """Re-compress the digest rows that took a sample since the last
    compaction (canonical k-cells AND raw temp cells into canonical
    k-cells, emptying temp) and leave every other row's bytes as they
    are: a row that took none is in canonical form already, and
    compressing it again merges nothing new. Amortized analogue of the
    reference's mergeAllTemps (merging_digest.go:140), which likewise
    returns at once on an empty temp buffer.

    The dirty rows are those with h_temp_n > 0 (see DeviceState). Their
    ids are sorted to the front and a loop of ceil(n / COMPACT_ROW_BLOCK)
    trips works through them, so the work follows the rows that took
    samples and not the table's height; no dirty row, no trip. Under
    vmap (the sharded step) the loop runs to the largest shard's count."""
    kh = state.h_w.shape[0]
    r = min(COMPACT_ROW_BLOCK, kh)
    n = dirty_rows(state)
    # Row ids, dirty ones first and ascending. A clean row sorts behind
    # them under an id beyond the table, as does the pad that rounds the
    # length up to whole blocks: the read clips such an id and the write
    # drops it, and every block's ids are unique and ascending.
    row = jnp.arange(kh, dtype=jnp.int32)
    ids = jnp.concatenate([
        jnp.sort(jnp.where(state.h_temp_n > 0, row, kh + row)),
        2 * kh + jnp.arange(-kh % r, dtype=jnp.int32)])
    pad = jnp.zeros((r, state.h_w.shape[-1] - spec.centroids),
                    state.h_w.dtype)
    take = dict(mode="clip", unique_indices=True, indices_are_sorted=True)
    put = dict(take, mode="drop")

    def block(i, tables):
        h_w, h_wm = tables
        rows = jax.lax.dynamic_slice(ids, (i * r,), (r,))
        w = h_w.at[rows].get(**take)
        wm = h_wm.at[rows].get(**take)
        m2, w2 = td.compress_rows(
            wm / jnp.maximum(w, 1e-30), w, compression=spec.compression,
            cells_per_k=spec.cells_per_k, out_c=spec.centroids,
            exact_extremes=spec.exact_extremes)
        return (h_w.at[rows].set(jnp.concatenate([w2, pad], axis=-1), **put),
                h_wm.at[rows].set(jnp.concatenate([m2 * w2, pad], axis=-1),
                                  **put))

    h_w, h_wm = jax.lax.fori_loop(0, (n + r - 1) // r, block,
                                  (state.h_w, state.h_wm))
    return state._replace(h_wm=h_wm, h_w=h_w,
                          h_temp_n=jnp.zeros_like(state.h_temp_n))


compact = partial(jax.jit, static_argnames=("spec",),
                  donate_argnames=("state",))(compact_core)


def quantiles_with_median(table, qs):
    """ONE quantile pass for (requested quantiles, median): the median
    rides as an extra column instead of a second full per-row sort+cumsum
    over the digest table — the flush program's dominant compute, which
    XLA does not reliably CSE. Returns (quantiles[..., Q], median[...])."""
    all_q = td.quantiles(
        table, jnp.concatenate([qs, jnp.asarray([0.5], jnp.float32)]))
    return all_q[..., :-1], all_q[..., -1]


def flush_core(state: DeviceState, qs: jax.Array, *, spec: TableSpec):
    """Produce the final per-slot values the flusher turns into InterMetrics
    (reference flusher.go:225 generateInterMetrics), dense over capacity.
    No fold/compact prerequisite: ingest folds accumulators in-program and
    the quantile kernel argsorts cells per row, so unmerged temp cells are
    just extra exact centroids. The production path uses the live-slot
    variants below; this dense form serves kernels/benchmarks/tests."""
    mean = state.h_wm / jnp.maximum(state.h_w, 1e-30)
    table = td.TDigestTable(
        mean=mean, weight=state.h_w, min=state.h_min, max=state.h_max,
        count_hi=state.h_count_hi, count_lo=state.h_count_lo,
        sum_hi=state.h_sum_hi, sum_lo=state.h_sum_lo,
        recip_hi=state.h_recip_hi, recip_lo=state.h_recip_lo)
    # Scalar totals leave the device as UNCOLLAPSED two-float pairs:
    # hi + lo in f32 would round the ~48-bit accumulator back to 24 bits
    # at the very boundary the pair exists to protect (a 2^32+1 counter
    # interval would flush as 2^32). The host combines them in float64
    # (combine_flush_scalars) — device f64 is unavailable without
    # jax_enable_x64.
    with jax.named_scope("flush.quantiles"):
        hq, hmed = quantiles_with_median(table, qs)
    with jax.named_scope("flush.hll_estimate"):
        set_estimate = hll_ops.estimate(state.hll,
                                        precision=spec.hll_precision)
    return {
        "counter_hi": state.counter_hi,
        "counter_lo": state.counter_lo,
        "gauge": state.gauge,
        "status": state.status,
        "set_estimate": set_estimate,
        "histo_quantiles": hq,
        "histo_min": state.h_min,
        "histo_max": state.h_max,
        "histo_count_hi": state.h_count_hi,
        "histo_count_lo": state.h_count_lo,
        "histo_sum_hi": state.h_sum_hi,
        "histo_sum_lo": state.h_sum_lo,
        "histo_recip_hi": state.h_recip_hi,
        "histo_recip_lo": state.h_recip_lo,
        "histo_median": hmed,
    }


flush_compute = partial(jax.jit, static_argnames=("spec",))(flush_core)


def _take(a, idx):
    return jnp.take(a, idx, axis=0, mode="clip")


def flush_live_core(state: DeviceState, qs: jax.Array, cidx, gidx, stidx,
                    setidx, hidx, *, spec: TableSpec, want_raw: bool = False):
    """flush_core restricted to LIVE slots: gather each kind's occupied
    rows (idx arrays padded to a size bucket) before any flush math, so
    (a) the quantile/estimate compute runs on O(live) rows instead of
    O(capacity), and (b) only O(live) bytes cross the device→host
    boundary. Output arrays are indexed by POSITION: row i corresponds
    to table.get_meta(kind)[i]."""
    with jax.named_scope("flush.gather"):
        wm = _take(state.h_wm, hidx)
        w = _take(state.h_w, hidx)
        mn = _take(state.h_min, hidx)
        mx = _take(state.h_max, hidx)
        chi = _take(state.h_count_hi, hidx)
        clo = _take(state.h_count_lo, hidx)
        shi, slo = _take(state.h_sum_hi, hidx), _take(state.h_sum_lo, hidx)
        rhi = _take(state.h_recip_hi, hidx)
        rlo = _take(state.h_recip_lo, hidx)
        hll_rows = _take(state.hll, setidx)
        out = {
            "counter_hi": _take(state.counter_hi, cidx),
            "counter_lo": _take(state.counter_lo, cidx),
            "gauge": _take(state.gauge, gidx),
            "status": _take(state.status, stidx),
            "histo_min": mn,
            "histo_max": mx,
            "histo_count_hi": chi, "histo_count_lo": clo,
            "histo_sum_hi": shi, "histo_sum_lo": slo,
            "histo_recip_hi": rhi, "histo_recip_lo": rlo,
        }
    with jax.named_scope("flush.quantiles"):
        mean = wm / jnp.maximum(w, 1e-30)
        table = td.TDigestTable(
            mean=mean, weight=w, min=mn, max=mx,
            count_hi=chi, count_lo=clo, sum_hi=shi, sum_lo=slo,
            recip_hi=rhi, recip_lo=rlo)
        out["histo_quantiles"], out["histo_median"] = quantiles_with_median(
            table, qs)
    with jax.named_scope("flush.hll_estimate"):
        out["set_estimate"] = hll_ops.estimate(
            hll_rows, precision=spec.hll_precision)
    if want_raw:
        # forwarding needs the mergeable sketch state of live rows
        out["raw_hll"] = hll_rows
        # the digest rows leave with their total_cells columns, without
        # the stored row's pad (TableSpec.stored_cells)
        out["raw_h_mean"] = mean[:, :spec.total_cells]
        out["raw_h_weight"] = w[:, :spec.total_cells]
    return out


def _pack_outputs(out: dict):
    parts = []
    for k in sorted(out):
        a = out[k]
        if a.dtype == jnp.uint8:
            a = jax.lax.bitcast_convert_type(a.reshape((-1, 4)),
                                             jnp.float32)
        elif a.dtype == jnp.int32:
            # packed HLL rows (raw_hll) ride the f32 carrier bit-cast.
            # Safe: a 6-bit register never exceeds 64-p+1 <= 61, so the
            # longest run of set bits across packed field boundaries is 5
            # — an f32 NaN/Inf needs 8 consecutive exponent ones, which
            # the carrier therefore can never form (no canonicalization
            # hazard on the way back to the host).
            a = jax.lax.bitcast_convert_type(a, jnp.float32)
        parts.append(a.reshape(-1).astype(jnp.float32))
    return jnp.concatenate(parts)


def pack_flush_inputs(perc, idx_arrays):
    """Host side: quantile list + the five live-index buckets as ONE i32
    buffer (f32 quantiles bit-viewed), the H2D mirror of the packed
    output — 6 transfers per flush become 1."""
    import numpy as np
    qs = np.asarray(perc, np.float32).view(np.int32)
    return np.concatenate([qs] + [np.asarray(i, np.int32).ravel()
                                  for i in idx_arrays])


def pack_query_inputs(spec, need, union_qs):
    """Host side: the query tier's gather plan -> the flush program's
    packed input buffer + static shape args (n_q, buckets, qcol).

    Same wire layout as `pack_flush_inputs`, but shaped for ad-hoc
    reads instead of a full-table flush: quantiles pad to the next
    power of two (min 4) so arbitrary per-query quantile vectors hit a
    handful of `flush_live_in_packed` specializations instead of
    recompiling per distinct count, and each kind's slot gather pads
    with `pad_bucket` exactly like the flush tiling — which is what
    keeps query reads running the flush's own jitted program (and
    therefore value-exact against the next flush's exports).

    `need` maps table name -> live slot list in flush-table order
    (counter, gauge, status, set, histo); `union_qs` is the batch's
    union quantile set. Returns (inputs, n_q, buckets, qcol) where
    qcol maps quantile value -> column in the padded vector.
    """
    import numpy as np
    caps = (spec.counter_capacity, spec.gauge_capacity,
            spec.status_capacity, spec.set_capacity, spec.histo_capacity)
    qs = sorted(union_qs) or [0.5]
    n_q = 4
    while n_q < len(qs):
        n_q <<= 1
    qcol = {v: i for i, v in enumerate(qs)}
    qs_padded = qs + [0.5] * (n_q - len(qs))
    buckets, idx_arrays = [], []
    for slots, cap in zip(need, caps):
        b = min(pad_bucket(len(slots), cap), FLUSH_BLOCK_ROWS)
        if len(slots) > b:
            raise ValueError("query gather exceeds one flush block")
        arr = np.zeros(b, np.int32)
        arr[:len(slots)] = slots
        buckets.append(b)
        idx_arrays.append(arr)
    return (pack_flush_inputs(qs_padded, idx_arrays), n_q,
            tuple(buckets), qcol)


def _flush_live_in_packed_core(state, flat, *, spec, n_q: int,
                               buckets: tuple, want_raw: bool = False):
    qs = jax.lax.bitcast_convert_type(flat[:n_q], jnp.float32)
    idx, off = [], n_q
    for n in buckets:
        idx.append(flat[off:off + n])
        off += n
    out = flush_live_core(state, qs, *idx, spec=spec, want_raw=want_raw)
    with jax.named_scope("flush.pack"):
        return _pack_outputs(out)


flush_live_in_packed = partial(
    jax.jit, static_argnames=("spec", "n_q", "buckets", "want_raw"))(
        _flush_live_in_packed_core)


def _flush_live_hist_packed_core(state, flat, hist, hflat, *, spec,
                                 hspec, n_q: int, buckets: tuple,
                                 want_raw: bool = False,
                                 clear: bool = False):
    """The flush program WITH the history tier's fused window write:
    identical flush math and packed output wire as
    _flush_live_in_packed_core, plus one extra scatter of the interval's
    values into ring column `col` — no second launch, no extra host
    traffic (ISSUE 18 tentpole). `hflat` carries the per-kind ring-row
    destinations (same bucket sizes as the flush's live-index buckets,
    sentinel rows drop) followed by the column scalar; the ring is
    DONATED and returned alongside the packed outputs.

    The write itself is history/device.write_window_core — the same
    function the host-fed backends and the replay oracle jit standalone
    — so both paths store bit-identical window bytes."""
    from veneur_tpu.history.device import write_window_core
    qs = jax.lax.bitcast_convert_type(flat[:n_q], jnp.float32)
    idx, off = [], n_q
    for n in buckets:
        idx.append(flat[off:off + n])
        off += n
    out = flush_live_core(state, qs, *idx, spec=spec, want_raw=True)
    dests, hoff = [], 0
    for n in buckets:
        dests.append(hflat[hoff:hoff + n])
        hoff += n
    col = hflat[hoff]
    vals = {
        "counter_hi": out["counter_hi"], "counter_lo": out["counter_lo"],
        "gauge": out["gauge"], "status": out["status"],
        "hll": out["raw_hll"],
        "h_mean": out["raw_h_mean"], "h_weight": out["raw_h_weight"],
        "h_min": out["histo_min"], "h_max": out["histo_max"],
        "h_count_hi": out["histo_count_hi"],
        "h_count_lo": out["histo_count_lo"],
        "h_sum_hi": out["histo_sum_hi"], "h_sum_lo": out["histo_sum_lo"],
    }
    new_hist = write_window_core(hist, vals, tuple(dests), col,
                                 hspec=hspec, clear=clear)
    if not want_raw:
        out = {k: v for k, v in out.items() if not k.startswith("raw_")}
    with jax.named_scope("flush.pack"):
        return _pack_outputs(out), new_hist


flush_live_hist_packed = partial(
    jax.jit,
    static_argnames=("spec", "hspec", "n_q", "buckets", "want_raw",
                     "clear"),
    donate_argnames=("hist",))(_flush_live_hist_packed_core)


def unpack_flush(packed, shapes: dict) -> dict:
    """Host-side inverse of the device packing: slice the flat f32 array
    back into named arrays. `shapes` maps key -> (shape, dtype); keys are
    consumed in sorted order, matching the packer."""
    import numpy as np
    out = {}
    off = 0
    for k in sorted(shapes):
        shape, dtype = shapes[k]
        n = int(np.prod(shape))
        if np.dtype(dtype) == np.uint8:
            words = n // 4
            out[k] = np.frombuffer(
                packed[off:off + words].tobytes(), np.uint8).reshape(shape)
            off += words
        elif np.dtype(dtype) == np.int32:
            out[k] = np.frombuffer(
                packed[off:off + n].tobytes(), np.int32).reshape(shape)
            off += n
        else:
            out[k] = packed[off:off + n].reshape(shape)
            off += n
    return out


def flush_live_shapes(spec, n_c, n_g, n_st, n_set, n_h, n_q,
                      want_raw: bool = False) -> dict:
    """The packer's output layout for given live-bucket sizes."""
    f32 = "float32"
    shapes = {
        "counter_hi": ((n_c,), f32), "counter_lo": ((n_c,), f32),
        "gauge": ((n_g,), f32), "status": ((n_st,), f32),
        "set_estimate": ((n_set,), f32),
        "histo_quantiles": ((n_h, n_q), f32),
        "histo_min": ((n_h,), f32), "histo_max": ((n_h,), f32),
        "histo_count_hi": ((n_h,), f32), "histo_count_lo": ((n_h,), f32),
        "histo_sum_hi": ((n_h,), f32), "histo_sum_lo": ((n_h,), f32),
        "histo_recip_hi": ((n_h,), f32), "histo_recip_lo": ((n_h,), f32),
        "histo_median": ((n_h,), f32),
    }
    if want_raw:
        cells = spec.centroids + spec.temp_cells
        shapes["raw_hll"] = ((n_set, spec.hll_words), "int32")
        shapes["raw_h_mean"] = ((n_h, cells), f32)
        shapes["raw_h_weight"] = ((n_h, cells), f32)
    return shapes


# Which live-index bucket each flush output key rides (0=counter,
# 1=gauge, 2=status, 3=set, 4=histo) — the tiled flush uses this to trim
# each block's padded rows back to the kind's real length.
FLUSH_KEY_KIND = {
    "counter_hi": 0, "counter_lo": 0, "gauge": 1, "status": 2,
    "set_estimate": 3, "raw_hll": 3,
    "histo_quantiles": 4, "histo_min": 4, "histo_max": 4,
    "histo_count_hi": 4, "histo_count_lo": 4, "histo_sum_hi": 4,
    "histo_sum_lo": 4, "histo_recip_hi": 4, "histo_recip_lo": 4,
    "histo_median": 4, "raw_h_mean": 4, "raw_h_weight": 4,
}

# Row-block size for the tiled flush: a flush whose live buckets exceed
# this compiles ONE block-shaped executable and loops over blocks on the
# host instead of minting a multi-million-row program, whose compile
# time grows with its row count (the reference streams flushes in fixed
# chunks too, flusher.go:169-298).
FLUSH_BLOCK_ROWS = 1 << 17


def live_slots(table, kind: str):
    """UNPADDED int32 slot-index array for a kind, in get_meta order:
    the table's own slot column (host.KeyColumns), which a native
    interval holds as the array its engine handed over and a Python
    KeyTable makes from its list."""
    return table.columns(kind).slots


def pack_bucket_chunks(slots, buckets, block_i: int, fill: int = 0):
    """Block `block_i`'s per-kind index chunk, padded to each
    kind's STATIC bucket size (the tiled flush's executable-shape
    contract: every block invocation has identical bucket shapes).
    `fill` is the pad value: 0 for gather indices (clipped, outputs
    trimmed), an out-of-range sentinel for the history tier's scatter
    destinations (mode="drop" discards pads)."""
    import numpy as np
    out = []
    for sarr, b in zip(slots, buckets):
        c = sarr[block_i * b:(block_i + 1) * b]
        buf = np.full(b, fill, np.int32)
        buf[:len(c)] = c
        out.append(buf)
    return out





def pad_bucket(n: int, cap: int) -> int:
    """Size bucket for live-slot index arrays: next power of two (min 64),
    clamped to capacity — bounds compiled variants to ~log2(capacity).
    The 64 floor keeps small kinds (self-telemetry counters/gauges grow a
    little between the first and second flush) inside ONE bucket, so a
    steady server re-uses a single compiled flush program instead of
    minting a variant per flush — which both avoids recompiles and keeps
    the resident-executable count at two (see ingest_step_packed)."""
    p = 64
    while p < n:
        p <<= 1
    return min(p, max(cap, 1))


def live_indices(table, kind: str, cap: int):
    """Padded int32 slot-index array for a kind, in get_meta order (the
    positional contract flush_live's outputs follow). Pad-of-live_slots:
    ONE copy of the slot-extraction loop."""
    import numpy as np
    raw = live_slots(table, kind)
    idx = np.zeros(pad_bucket(len(raw), cap), np.int32)
    idx[:len(raw)] = raw
    return idx


def combine_flush_scalars(result: dict) -> dict:
    """Host-side finish of flush_core's output: collapse each two-float
    pair in FLOAT64 (exact for the pair's ~48 significand bits — the
    reference's int64 counters and float64 histo scalars,
    samplers/samplers.go:131,477-481, stay exact through here) and derive
    count/sum/avg/hmean. Works on any leading batch shape; the input dict
    is left untouched."""
    import numpy as np

    def f64(key):
        return (np.asarray(result[key + "_hi"], np.float64)
                + np.asarray(result[key + "_lo"], np.float64))

    out = {k: v for k, v in result.items()
           if not (k.endswith("_hi") or k.endswith("_lo"))}
    out["counter"] = f64("counter")
    count = f64("histo_count")
    total = f64("histo_sum")
    recip = f64("histo_recip")
    out["histo_count"] = count
    out["histo_sum"] = total
    out["histo_avg"] = total / np.maximum(count, 1e-30)
    out["histo_hmean"] = count / np.maximum(recip, 1e-30)
    return out


def finish_flush(out) -> dict:
    """Device flush output -> host numpy dict with pairs combined; the
    one boundary every flush consumer (server aggregators, tests, the
    multichip dryrun) goes through."""
    import numpy as np
    return combine_flush_scalars({k: np.asarray(v) for k, v in out.items()})
