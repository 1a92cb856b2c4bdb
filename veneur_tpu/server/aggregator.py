"""Single-process aggregation backend: key table + batcher + device state.

The glue between parsed UDPMetrics and the jitted ingest step — the role of
the reference's Worker goroutines (worker.go:265 Work / :344 ProcessMetric),
with N workers replaced by one device table (logical shards assigned by
digest, host.py). Flush performs the map-swap double-buffering of
worker.go:498: the live table/state are detached and replaced, then the
flush math runs on the detached state while new samples accumulate.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, Tuple

import numpy as np

from veneur_tpu.aggregation.host import Batcher, BatchSpec, KeyTable
from veneur_tpu.aggregation.state import (TableSpec, empty_state_compiled)
from veneur_tpu.aggregation.step import (
    batch_sizes, ingest_step_packed, pack_batch, packed_layout)
from veneur_tpu.observability import hostspans, jaxruntime
from veneur_tpu.samplers.parser import UDPMetric
from veneur_tpu.utils.hashing import fnv1a_64, splitmix64


def set_member_bytes(value) -> bytes:
    """The ONE place the set-member encoding policy lives (used by the
    single-process and sharded process_metric paths): surrogateescape
    round-trips NON-UTF-8 member bytes back to the original wire bytes —
    the parser decoded them that way, a plain encode() raises
    UnicodeEncodeError (which would kill the pipeline thread: one
    corrupt datagram = DoS, found by differential fuzz), and the
    restored bytes hash identically to the C++ engine's raw-byte
    MetroHash."""
    return value if isinstance(value, bytes) else str(value).encode(
        "utf-8", "surrogateescape")


# sampled device-sync cadence for step_ns (_sampled_sync); every backend's
# dispatch shares it
_SYNC_EVERY = 64

# Ingest steps the host may have dispatched without having seen them
# finish. Where the device is the slower side (a compaction of a 131072-row
# digest table is 103 ms on the v5e against ~8 ms to parse a batch) the host
# otherwise runs ahead until the runtime's own queue is full, ~30 steps or
# 0.55 s of device work: the 64th-step sync then stops the pipeline thread
# for all of it, parsing nothing, and a tick waits for whatever part of it
# it finds queued (PERF.md, PR 30). Four keeps the device fed across a
# compaction (it needs two) and a tick's wait under one compaction.
_MAX_STEPS_IN_FLIGHT = 4


class Aggregator:
    def __init__(self, spec: TableSpec, bspec: BatchSpec = BatchSpec(),
                 n_shards: int = 1, compact_every: int = 8):
        self.spec = spec
        self.bspec = bspec
        self.n_shards = n_shards
        self.compact_every = compact_every
        self.table = KeyTable(spec, n_shards)
        self.batcher = Batcher(spec, bspec, on_batch=self._on_batch)
        self.state = empty_state_compiled(spec)
        self._init_step_site()

    def _init_step_site(self) -> None:
        """The host side of an ingest step, which this class owns for every
        backend: each constructor calls this once (the sharded backends
        build their own state and do not run Aggregator.__init__). A step
        site asks _step_buffer for the host buffer to pack into and hands
        it to _dispatch_step; the accounting, the steps in flight and the
        buffers live here.

        The invariant the buffers keep: a packed host buffer is not
        written again until the step that read it has been settled in
        _settle_step. A runtime may read a host array in place for as long
        as the step is queued (the CPU client does, for 64-byte-aligned
        arrays), so a buffer written earlier hands a queued step a later
        step's words, the compaction control word among them. While step n
        is packed, steps n-_MAX_STEPS_IN_FLIGHT .. n-1 may be unsettled
        (_dispatch_step settles the oldest only once step n is ready to
        go), so a ring of one more buffer than that is the fewest that
        keeps it."""
        self._steps = 0
        # staged HLL import rows (merged via ops.hll.merge_rows)
        self._hll_slots: list = []
        self._hll_rows: list = []
        # checkpoint-restore residuals: (batcher, slot, lo) counter tails
        # applied in a SECOND ingest step (restore_flush)
        self._restore_residuals: list = []
        # optional tables.pressure.TablePressure shared across intervals
        self._pressure = None
        # stats (reference self-telemetry counters)
        self.processed = 0
        self.dropped_capacity = 0
        self.h2d_bytes = 0  # packed ingest bytes shipped to the device
        # device-step accounting for /metrics (observability/):
        # dispatch_ns is host time inside the jitted call (XLA execution
        # is async, so this is NOT device time: enqueue cost, plus the
        # wait for a slot once the device queue is full); step_ns sums
        # the sampled syncs, every _SYNC_EVERY steps and at swap() via
        # jaxruntime.sync_and_time: each waits for EVERYTHING queued, so
        # it reads the queue's drain, not one step (_dispatch_step).
        # steps_total is monotonic (_steps resets every swap);
        # steps_synced counts the samples behind step_ns.
        self.step_ns = 0
        self.dispatch_ns = 0
        self.steps_total = 0
        self.steps_synced = 0
        # steps that carried the in-band compaction (_count_step) and the
        # digest rows those compactions compressed, as the device counted
        # them (_settle_step), monotonic like steps_total
        self.compactions = 0
        self.compact_rows = 0
        # flushes computed (compute_flush), the block-shaped calls of the
        # flush program they dispatched and the live rows they flushed,
        # written by the flush worker alone: blocks a flush and rows a
        # block are read from their ratios
        self.flushes_computed = 0
        self.flush_blocks = 0
        self.flush_rows = 0
        # rows the frames built from those flushes emitted, and of them
        # the rows whose name was taken from a column kept with the key
        # and not built (flusher.MetricFrame.labels_reused); the flush
        # worker's too
        self.frame_rows = 0
        self.frame_labels_reused = 0
        self._steps_in_flight = collections.deque()
        # shape key -> ring of host buffers, next to be packed first
        self._step_bufs: dict = {}
        self._init_degrade()

    def _init_degrade(self) -> None:
        """Degraded-aggregation state (reliability/overload.py).

        Under SHEDDING+ the OverloadController pushes these knobs; the
        defaults (1.0 / 0) are branch-predicted no-ops on the hot path.
        Timers: admit a fraction p of samples and scale the recorded
        sample_rate by p — staged weight becomes 1/(rate·p), so the
        correction is exact in expectation and needs no latch."""
        self.degraded_timer_rate = 1.0
        self._degrade_seq = 0
        # Sets: admit a member iff the low k bits of fnv1a_64(member)
        # are zero (rate 2^-k, deterministic per member so repeats stay
        # idempotent) and multiply the flushed estimate by 2^k. The
        # shift LATCHES at swap — pending applies from the next interval
        # and last_set_shift is the shift that governed the interval
        # just detached (the flush worker reads it for the correction);
        # a mid-interval change would make the 2^k correction wrong for
        # members admitted before the change.
        self.pending_set_shift = 0
        self.active_set_shift = 0
        self.last_set_shift = 0
        # degradation drop accounting (veneur.overload.degraded_samples
        # _total): samples represented statistically, not lost rows
        self.degraded_timer_skipped = 0
        self.degraded_set_skipped = 0

    def extra_parse_errors(self) -> int:
        """Parse errors counted below the Python layer (native engine)."""
        return 0

    def set_pressure(self, pressure) -> None:
        """Install a tables.pressure.TablePressure: the live table and
        every subsequent interval's fresh KeyTable (swap) get it
        attached. Python key tables only — the native engine's C++ maps
        keep exact counted drops instead (absorbed by the next grow)."""
        self._pressure = pressure
        if pressure is not None:
            pressure.attach(self.table)

    # -- degraded aggregation (shared by the sharded backend) ---------------
    def _histo_admit(self, sample_rate: float):
        """Effective sample rate for one timer/histogram sample under
        degradation, or None when the sample is skipped. The roll is a
        deterministic splitmix64 counter sequence (reproducible tests,
        no RNG state), and the admitted samples carry rate·p so the
        flushed count/percentile weights stay unbiased."""
        p = self.degraded_timer_rate
        if p >= 1.0:
            return sample_rate
        self._degrade_seq += 1
        if (splitmix64(self._degrade_seq) >> 11) * (1.0 / (1 << 53)) >= p:
            self.degraded_timer_skipped += 1
            return None
        return sample_rate * p

    def _set_admit(self, member: bytes) -> bool:
        """Hash-prefix member subsample at rate 2^-active_set_shift."""
        k = self.active_set_shift
        if k <= 0:
            return True
        if fnv1a_64(member) & ((1 << k) - 1):
            self.degraded_set_skipped += 1
            return False
        return True

    def _latch_degrade(self) -> None:
        """Interval boundary: promote the pending set shift and expose
        the one that governed the detached interval. Called from every
        backend's swap() ON the pipeline thread, before new samples
        land in the fresh table."""
        self.last_set_shift = self.active_set_shift
        self.active_set_shift = self.pending_set_shift

    # -- ingest -------------------------------------------------------------
    def _on_batch(self, batch):
        # one packed H2D transfer per step; compaction rides the same
        # program via the control word (step.py pack_batch rationale)
        compacts = self._count_step()
        sizes = batch_sizes(batch)
        flat = self._step_buffer(
            sizes, lambda: np.zeros(packed_layout(sizes)[1], np.int32))
        pack_batch(batch, compacts, out=flat)
        self._dispatch_step(ingest_step_packed, flat, sizes, spec=self.spec,
                            sizes=sizes)

    def _count_step(self, force_compact: bool = False) -> bool:
        """Count one more ingest step, and say whether it carries the
        in-band compaction: every compact_every-th step of the interval
        does, and one a caller forces. What it compresses is the digest
        rows that took a sample since the last one (step.compact_core);
        the device counts those and _settle_step adds them up."""
        self._steps += 1
        self.steps_total += 1
        compacts = force_compact or self._steps % self.compact_every == 0
        if compacts:
            self.compactions += 1
        return compacts

    def _step_buffer(self, key, make):
        """The host buffer the next step of shape `key` is packed into
        (the lane-size signature, the native engine's packed buffer or
        its (rings, words) arena, the mesh's [R, S, W] row): whatever
        `make` builds, one buffer with what belongs to it, built
        _MAX_STEPS_IN_FLIGHT + 1 times on first use. The same one comes
        back until _dispatch_step has sent a step from it, so a site that
        finds nothing to send has used up nothing. The ring's length is
        the invariant of _init_step_site."""
        ring = self._step_bufs.get(key)
        if ring is None:
            ring = self._step_bufs[key] = collections.deque(
                make() for _ in range(_MAX_STEPS_IN_FLIGHT + 1))
        return ring[0]

    def _dispatch_step(self, step, flat, key, **static) -> None:
        """The one ingest dispatch every backend's step site goes through
        (here, the native packed and ring emits, the mesh row of the
        sharded backends and the collective tier), for the buffer `flat`
        that _step_buffer(key) handed out:
        `self.state, rows = step(self.state, flat, **static)` under the
        `pipeline.dispatch` span, its host time summed into dispatch_ns
        (with _MAX_STEPS_IN_FLIGHT steps already queued it first waits
        for the oldest to finish, so this is queue wait as much as
        enqueue), then the sampled sync. `rows`, the digest rows the
        step's compaction compressed, is a step's completion too: a
        small array of its own, which survives the state's donation to
        the next step."""
        in_flight = self._steps_in_flight
        self.h2d_bytes += flat.nbytes
        with hostspans.span("pipeline.dispatch"):
            t0 = time.perf_counter_ns()
            if len(in_flight) == _MAX_STEPS_IN_FLIGHT:
                self._settle_step()
            self.state, rows = step(self.state, flat, **static)
            # the control word, as the program reads it
            in_flight.append((rows, flat.flat[0] != 0))
            dispatch_dt = time.perf_counter_ns() - t0
        self._step_bufs[key].rotate(-1)
        self.dispatch_ns += dispatch_dt
        self._sampled_sync(dispatch_dt)

    def _sampled_sync(self, dispatch_dt: int) -> None:
        """Every _SYNC_EVERY-th step, wait for the device under
        `pipeline.sampled_sync`. The wait is for everything queued, so
        step_ns reads the queue's drain, not one step's device time."""
        if self.steps_total % _SYNC_EVERY == 0:
            with hostspans.span("pipeline.sampled_sync"):
                self.step_ns += dispatch_dt + jaxruntime.sync_and_time(
                    self.state)
            self.steps_synced += 1

    def _settle_step(self) -> None:
        """Wait for the oldest step in flight and, where it compacted,
        add the rows it compressed to compact_rows: four bytes read from
        a step that has finished."""
        rows, compacted = self._steps_in_flight.popleft()
        jaxruntime.sync_and_time(rows)
        if compacted:
            self.compact_rows += int(np.asarray(rows).sum())

    def _await_steps(self) -> None:
        """The interval boundary's sync, shared by every backend's swap:
        wait for every step still queued on the device, so that step_ns
        is never 0 after a flush that ingested even when _SYNC_EVERY
        never fired, then settle them all, which makes compact_rows
        exact at each tick."""
        if self._steps:
            with hostspans.span("swap.device_wait"):
                self.step_ns += jaxruntime.sync_and_time(self.state)
            self.steps_synced += 1
        while self._steps_in_flight:
            self._settle_step()

    def process_metric(self, m: UDPMetric) -> None:
        """reference worker.go:344 ProcessMetric: switch on type+scope,
        upsert, sample."""
        kind = m.type
        slot = self.table.slot_for(kind, m.name, m.tags, m.scope, m.digest,
                                   hostname=m.hostname,
                                   joined_tags=m.joined_tags)
        if slot is None:
            self.dropped_capacity += 1
            return
        if kind in ("histogram", "timer"):
            self.table.sampled_directly(kind, slot)
        if kind == "counter":
            self.batcher.add_counter(slot, float(m.value), m.sample_rate)
        elif kind == "gauge":
            self.batcher.add_gauge(slot, float(m.value))
        elif kind == "status":
            self.batcher.add_status(slot, float(m.value))
            # keep the latest message on the slot metadata (O(1);
            # reference StatusCheck.Sample keeps last message,
            # samplers.go:312)
            mt = self.table.meta_for_slot("status", slot)
            if mt is not None:
                mt.message = m.message
        elif kind == "set":
            member = set_member_bytes(m.value)
            if self._set_admit(member):
                self.batcher.add_set(slot, member)
        elif kind in ("histogram", "timer"):
            # self-metric timers are exempt from degraded sampling: the
            # admission layer never sheds veneur.*, and blurring the
            # operator's own latency telemetry during an incident
            # defeats the point of bounded degradation. (Sets get no
            # such exemption — their 2^shift correction is applied
            # per-interval to every set row at flush, so a row staged
            # unsubsampled would be over-corrected.)
            if m.name.startswith("veneur."):
                rate = m.sample_rate
            else:
                rate = self._histo_admit(m.sample_rate)
            if rate is not None:
                self.batcher.add_histo(slot, float(m.value), rate)
        self.processed += 1

    # -- import path (global tier) ------------------------------------------
    def import_metric(self, kind: str, name: str, tags: tuple, scope: int,
                      digest: int, payload: dict) -> None:
        """Merge one forwarded metric's sketch state (the reference's
        Worker.ImportMetricGRPC switch, worker.go:438-495). payload keys by
        kind: counter/gauge 'value'; set 'registers' (np.uint8[R]);
        histogram/timer 'means','weights' (+ optional 'min','max','recip')."""
        slot = self.table.slot_for(kind, name, tags, scope, digest,
                                   imported=True)
        if slot is None:
            self.dropped_capacity += 1
            return
        if kind == "counter":
            self.batcher.add_counter(slot, float(payload["value"]), 1.0)
        elif kind == "gauge":
            self.batcher.add_gauge(slot, float(payload["value"]))
        elif kind == "set":
            regs = payload["registers"]
            if regs.shape[0] != self.spec.registers:
                # peer configured with a different hll_precision; sketch
                # registers don't interoperate across precisions
                raise ValueError(
                    f"imported HLL has {regs.shape[0]} registers, "
                    f"table expects {self.spec.registers}")
            self._hll_slots.append(slot)
            self._hll_rows.append(regs)
            if len(self._hll_slots) >= 128:
                self._flush_hll_imports()
        elif kind in ("histogram", "timer"):
            means = np.asarray(payload["means"], np.float32)
            weights = np.asarray(payload["weights"], np.float32)
            # digest merge = re-add centroids (samplers.go:726 -> tdigest
            # Merge), with the wire's exact min/max/reciprocalSum replacing
            # the re-add's approximation: the stats lane carries the
            # imported recip minus what the centroid re-add will add.
            live = weights > 0
            means, weights = means[live], weights[live]
            # bulk-stage the centroid re-add: a per-centroid Python call
            # costs ~230 calls per imported digest and dominated the
            # global tier's import throughput (BASELINE config 4)
            self.batcher.add_histos_bulk(
                np.full(len(means), slot, np.int32), means, weights)
            mn = float(payload.get("min", np.inf))
            mx = float(payload.get("max", -np.inf))
            recip = payload.get("recip")
            recip_corr = 0.0
            if recip is not None and np.all(means != 0.0):
                recip_corr = float(recip) - float(np.sum(weights / means))
            self.batcher.add_histo_stats(slot, mn, mx, recip_corr)
        self.processed += 1

    # -- checkpoint restore (persistence/restore.py) ------------------------
    def _restore_lane(self, kind: str, slot: int):
        """(batcher, staging slot) for a restored key; the sharded
        backend overrides with its per-shard routing."""
        return self.batcher, slot

    def _restore_hll(self, slot: int, regs) -> None:
        """Stage restored HLL registers for max-merge, same as the
        import path."""
        self._hll_slots.append(slot)
        self._hll_rows.append(regs)
        if len(self._hll_slots) >= 128:
            self._flush_hll_imports()

    def _restore_emit(self) -> None:
        self.batcher.emit()

    def restore_metric(self, kind: str, name: str, tags: tuple, scope: int,
                       digest: int, payload: dict, hostname: str = "",
                       message: str = "", imported_only: bool = False,
                       joined_tags=None) -> None:
        """Fold one checkpointed key back in through the merge lanes
        (never by overwriting state): counter add, gauge/status
        last-write-wins, HLL max, digest centroid re-add — the
        import_metric machinery plus the host-side metadata
        (hostname/message/joined_tags) a snapshot preserves and a
        forwarded metric does not. Callers finish with restore_flush()."""
        slot = self.table.slot_for(kind, name, tags, scope, digest,
                                   hostname=hostname,
                                   imported=imported_only,
                                   joined_tags=joined_tags)
        if slot is None:
            self.dropped_capacity += 1
            return
        b, local = self._restore_lane(kind, slot)
        if kind == "counter":
            # two-float split: the staging lane is f32, but the
            # checkpointed count is the f64 hi+lo fold. Stage hi now and
            # defer lo to restore_flush's second ingest step — a
            # same-batch scatter-add would re-round hi+lo to f32 and
            # lose exactly the bits the split carries.
            value = float(payload["value"])
            hi = float(np.float32(value))
            b.add_counter(local, hi, 1.0)
            lo = value - hi
            if lo != 0.0:
                self._restore_residuals.append((b, local, lo))
        elif kind == "gauge":
            b.add_gauge(local, float(payload["value"]))
        elif kind == "status":
            b.add_status(local, float(payload["value"]))
            mt = self.table.meta_for_slot("status", slot)
            if mt is not None:
                mt.message = message
        elif kind == "set":
            regs = np.asarray(payload["registers"], np.uint8)
            if regs.shape[0] != self.spec.registers:
                raise ValueError(
                    f"restored HLL has {regs.shape[0]} registers, table "
                    f"expects {self.spec.registers}")
            self._restore_hll(slot, regs)
        elif kind in ("histogram", "timer"):
            # identical merge math to import_metric: re-add live
            # centroids, exact min/max/recip via the stats lane
            means = np.asarray(payload["means"], np.float32)
            weights = np.asarray(payload["weights"], np.float32)
            live = weights > 0
            means, weights = means[live], weights[live]
            b.add_histos_bulk(
                np.full(len(means), local, np.int32), means, weights)
            mn = float(payload.get("min", np.inf))
            mx = float(payload.get("max", -np.inf))
            recip = payload.get("recip")
            recip_corr = 0.0
            if recip is not None and len(means) and np.all(means != 0.0):
                recip_corr = float(recip) - float(np.sum(weights / means))
            b.add_histo_stats(local, mn, mx, recip_corr)
        self.processed += 1

    def restore_flush(self) -> None:
        """Materialize a fold_snapshot pass: emit the hi-part batches,
        then the counter lo residuals in a separate step (see the split
        rationale in restore_metric), then drain staged HLL rows."""
        self._restore_emit()
        if self._restore_residuals:
            for b, local, lo in self._restore_residuals:
                b.add_counter(local, lo, 1.0)
            self._restore_residuals = []
            self._restore_emit()
        self._restore_drain_hll()

    def _restore_drain_hll(self) -> None:
        while self._hll_slots:
            self._flush_hll_imports()

    def _flush_hll_imports(self):
        if not self._hll_slots:
            return
        from veneur_tpu.ops.hll import merge_rows_packed
        import jax.numpy as jnp
        b = 128
        slots = np.full(b, self.spec.set_capacity, np.int32)
        rows = np.zeros((b, self.spec.registers), np.uint8)
        n = min(len(self._hll_slots), b)
        slots[:n] = self._hll_slots[:n]
        rows[:n] = np.stack(self._hll_rows[:n])
        self.state = self.state._replace(
            hll=merge_rows_packed(self.state.hll, jnp.asarray(slots),
                                  jnp.asarray(rows),
                                  precision=self.spec.hll_precision))
        self._hll_slots, self._hll_rows = (self._hll_slots[b:],
                                           self._hll_rows[b:])

    # -- flush --------------------------------------------------------------
    def swap(self):
        """Map-swap (worker.go:498): detach live state+table, reset fresh.
        This is the ONLY flush work that must run on the pipeline thread;
        everything downstream operates on the detached (immutable) interval
        and can run on a flush thread while new samples accumulate."""
        with hostspans.span("swap.emit_staged"):
            self.batcher.emit()
            while self._hll_slots:
                self._flush_hll_imports()
        self._await_steps()
        with hostspans.span("swap.reset"):
            state, table = self.state, self.table
            self.state = empty_state_compiled(self.spec)
            self.table = KeyTable(self.spec, self.n_shards)
            if self._pressure is not None:
                self._pressure.attach(self.table)
            self._steps = 0
            self._latch_degrade()
        return state, table

    # -- query tier ---------------------------------------------------------
    def query_snapshot(self):
        """Pipeline-thread-only: a coherent read view of the LIVE
        interval for the query tier (veneur_tpu/query/) — swap()'s
        staging drain (batcher emit + packed-HLL import fold) WITHOUT
        the detach. Every sample admitted before this call is folded
        into the returned state; JAX immutability makes the returned
        reference a frozen snapshot while ingest keeps replacing
        self.state underneath. Returns (state, table, active_set_shift)
        — the LIVE shift, because the latched-shift correction the
        flush applies has not happened yet for this interval."""
        self.batcher.emit()
        while self._hll_slots:
            self._flush_hll_imports()
        return self.state, self.table, self.active_set_shift

    def query_flat_state(self, state):
        """Query-tier state view with flat [rows, ...] leading dims;
        the single-device layout already is one."""
        return state

    def _count_flush(self, blocks: int, rows: int) -> None:
        self.flushes_computed += 1
        self.flush_blocks += blocks
        self.flush_rows += rows

    def count_frame(self, rows: int, labels_reused: int) -> None:
        self.frame_rows += rows
        self.frame_labels_reused += labels_reused

    def compute_flush(self, state, table, percentiles: List[float],
                      want_raw: bool = False, history=None
                      ) -> Tuple[Dict[str, np.ndarray], KeyTable]:
        """Flush math on a detached interval (safe off the pipeline thread:
        JAX arrays are immutable and dispatch is thread-safe). Output
        arrays are COMPACT: row i pairs with table.get_meta(kind)[i]
        (flush_live gathers live rows on device, so only O(live) bytes
        cross the host boundary). With want_raw, also returns the live
        rows' mergeable sketch state (numpy) for forwarding.

        With `history` (a history.HistoryWriter), each block runs the
        FUSED flush+history program instead: the interval's values land
        in their ring column inside the flush launch itself — same
        packed outputs, zero extra launches (ISSUE 18 tentpole). The
        ring is donated through the blocks and committed back to the
        writer with the interval's window metadata."""
        from veneur_tpu.aggregation.step import (
            FLUSH_BLOCK_ROWS, FLUSH_KEY_KIND, combine_flush_scalars,
            flush_live_hist_packed, flush_live_in_packed,
            flush_live_shapes, live_slots, pack_bucket_chunks,
            pack_flush_inputs, pad_bucket, unpack_flush)

        # No fold/compact pass here: ingest folds accumulators in-program
        # (step.py ingest_core), and the quantile kernel argsorts cells
        # per row (ops/tdigest.py _quantiles_one), so unmerged temp cells
        # are just extra exact centroids — compacting the FULL table
        # before flush cost ~2s of device time per interval at 2^17
        # capacity for no accuracy gain (temps unmerged are strictly more
        # precise; forwarding re-adds centroids either way).
        perc = percentiles or [0.5]
        spec = self.spec
        caps = [spec.counter_capacity, spec.gauge_capacity,
                spec.status_capacity, spec.set_capacity,
                spec.histo_capacity]
        # the host's part before anything is dispatched: the live slots,
        # the block count and bucket sizes, every block's packed input
        with hostspans.span("flush_plan"):
            slots = [live_slots(table, k) for k in
                     ("counter", "gauge", "status", "set", "histogram")]
            lens = [len(s) for s in slots]
            n_blocks = max(1, max(
                -(-n // min(pad_bucket(n, cap), FLUSH_BLOCK_ROWS))
                for n, cap in zip(lens, caps)))
            # Per-kind buckets sized to SPREAD each kind's rows evenly over
            # all n_blocks invocations (ceil(n/n_blocks), padded): a kind
            # smaller than the block-count driver never runs full-padding
            # garbage blocks — e.g. 7M counters + 1M timers tiles as 57
            # blocks of 128k counters x 18k timers, not 57 x 128k timers of
            # which 49 are pure waste on the expensive quantile kernel.
            buckets = tuple(min(pad_bucket(-(-n // n_blocks), cap),
                                FLUSH_BLOCK_ROWS)
                            for n, cap in zip(lens, caps))
            shapes = flush_live_shapes(spec, *buckets, len(perc),
                                       want_raw=want_raw)
            flats = [pack_flush_inputs(
                perc, pack_bucket_chunks(slots, buckets, i))
                for i in range(n_blocks)]
            if history is not None:
                from veneur_tpu.history.writer import SENTINEL
                plan = history.plan_flush(table)
                hflats = [np.concatenate(
                    pack_bucket_chunks(plan.dests, buckets, i, fill=SENTINEL)
                    + [np.asarray([plan.col], np.int32)])
                    for i in range(n_blocks)]
        self._count_flush(n_blocks, sum(lens))
        # Tiled flush: every invocation reuses ONE block-shaped
        # executable, so that compile time and the program's working set
        # are bounded by the block (FLUSH_BLOCK_ROWS rows a kind), never
        # by live cardinality: a million live names flush as five calls
        # of the program a hundred thousand flush with, not as a program
        # of their own. n_blocks == 1 is the steady small-table case. All
        # blocks are dispatched before any is materialized, so the device
        # pipelines them. The benchmark's cell agent-1m-names measures it
        # (PERF.md sections 4 and 5).
        with hostspans.span("flush_dispatch"):
            if history is not None:
                hist = history.begin_flush(plan)
                try:
                    packs = []
                    for flat, hflat in zip(flats, hflats):
                        p, hist = flush_live_hist_packed(
                            state, flat, hist, hflat, spec=spec,
                            hspec=history.spec, n_q=len(perc),
                            buckets=buckets, want_raw=want_raw,
                            clear=not packs)
                        packs.append(p)
                except BaseException:
                    history.abort_flush()
                    raise
                history.commit_flush(plan, hist)
            else:
                packs = [
                    flush_live_in_packed(
                        state, flat, spec=spec, n_q=len(perc),
                        buckets=buckets, want_raw=want_raw)
                    for flat in flats]
        # the host's wait for the flush program, which queues on the
        # device behind every ingest step dispatched since the swap,
        # plus the transfer
        with hostspans.span("flush_d2h"):
            pieces = [unpack_flush(np.asarray(p), shapes) for p in packs]
        out = {}
        for key, kind_i in ((k, FLUSH_KEY_KIND[k]) for k in pieces[0]):
            b, n = buckets[kind_i], lens[kind_i]
            rows = [p[key][:min(b, n - i * b)]
                    for i, p in enumerate(pieces) if n - i * b > 0]
            out[key] = (np.concatenate(rows) if rows
                        else pieces[0][key][:0])
        result = combine_flush_scalars(out)
        if want_raw:
            raw = {
                "counter": result["counter"],
                "gauge": result["gauge"],
                "hll": result.pop("raw_hll"),
                "h_mean": result.pop("raw_h_mean"),
                "h_weight": result.pop("raw_h_weight"),
                "h_min": result["histo_min"],
                "h_max": result["histo_max"],
                "h_recip": np.asarray(out["histo_recip_hi"], np.float64)
                + np.asarray(out["histo_recip_lo"], np.float64),
            }
            return result, table, raw
        return result, table

    def flush(self, percentiles: List[float], want_raw: bool = False
              ) -> Tuple[Dict[str, np.ndarray], KeyTable]:
        """swap + compute in one call (single-threaded callers, tests)."""
        state, table = self.swap()
        return self.compute_flush(state, table, percentiles, want_raw)
