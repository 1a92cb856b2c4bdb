"""Native-ingest aggregation backend.

Wire packets are parsed, keyed, and staged entirely in C++
(veneur_tpu/native/dogstatsd.cpp); the Python side only moves completed
batches to the device. Python-originated samples (imports, span-extracted
metrics, service checks) share the same slot space through vt_slot_for and
stage through the ordinary Python Batcher — both batch streams feed the
same jitted ingest step.

The engine's key table outlives the flush interval: a key keeps its slot
from interval to interval, and its SlotMeta (flush labeling), built once
from the engine's new-key record, lives with the feed (_SlotMetas). What
an interval owns is its live list, the slots touched in it in
first-arrival order, which the engine hands over as arrays; a swap
therefore pays for the keys that are new, not for every live key. What
is emitted is what a flush-scoped table (aggregation/host.py KeyTable,
the reference's worker maps) emits, row for row. Status checks keep a
pure-Python table per interval (they never ride the native wire path's
kinds).

Known imprecisions, documented:

- A histo slot whose first arrival in an interval came by the import path
  and that native wire samples hit later in it keeps imported_only=True
  for that interval (the native path doesn't report per-slot direct-hit
  sets), so its aggregates are suppressed on a global tier — strictly
  conservative (percentiles still flush).
- Gauge last-write-wins is per-stream: when the same gauge key arrives
  both over the wire (native staging) and via Python-side paths
  (span-extracted/imported) in one interval, the flush order is
  deterministic (native batch first, Python batch second → Python-side
  write wins) but not arrival-ordered across the two streams. The
  single-stream case — by far the common one — is exactly ordered.
- A corrupt MetricList tail is a PARTIAL apply: import_pb_bytes stages
  incrementally, so metrics decoded before the undecodable boundary are
  already merged when the tail is dropped-and-counted, where the Python
  path's whole-request deserialize would reject ALL of them. Every
  intact metric is preserved either way; the difference is only which
  side of a mid-request corruption survives. (PARITY.md pins this with
  the other native-path deviations.)
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import List

import numpy as np

from veneur_tpu.aggregation.host import (
    Batcher, BatchSpec, KeyColumns, KeyTable, SlotMeta, _KindTable,
    first_byte)
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.aggregation.step import (
    ingest_step_packed, ingest_step_packed_rings, packed_layout)
from veneur_tpu.native import IMPORTED_BIT, LIVE_TABLES, NativeIngest
from veneur_tpu.observability import hostspans
from veneur_tpu.server.aggregator import Aggregator
from veneur_tpu.server.sharded_aggregator import ShardedAggregator

log = logging.getLogger("veneur_tpu.server.native_aggregator")


def _lane(flat, layout, name, f32=False):
    """One lane of a packed buffer (step.packed_layout), as a view: f32
    lanes bit-viewed."""
    off, n, _ = layout[name]
    view = flat[off:off + n]
    return view.view(np.float32) if f32 else view


class _KindLabels:
    """One kind-table's flush labels by slot (_SlotMetas)."""

    __slots__ = ("meta", "name", "first", "stamp", "compound")

    def __init__(self, capacity: int):
        self.meta = np.empty(capacity, object)     # SlotMeta
        self.name = np.empty(capacity, object)     # its name
        self.first = np.zeros(capacity, np.uint8)  # its first_byte
        # the interval (_SlotMetas.epoch) a slot was last written in
        self.stamp = np.zeros(capacity, np.uint32)
        # suffix -> (object column name + suffix, bool column: filled),
        # the compound names a timer emits (`name.min`, ...). The flush
        # worker alone fills them, for a slot the first time a flush
        # emits it, and they stay with the key; a write to the slot
        # unfills them. The suffixes are the server's aggregates and
        # percentiles, so a process holds as many columns as it emits
        # rows a timer.
        self.compound: dict = {}

    def compound_column(self, suffix: str) -> tuple:
        got = self.compound.get(suffix)
        if got is None:
            n = len(self.name)
            got = self.compound[suffix] = (np.empty(n, object),
                                           np.zeros(n, np.bool_))
        return got


class _SlotMetas:
    """Flush labels by slot for every key the engine's tables hold: the
    Python half of the persistent key table. It lives with the feed, not
    with the interval. The pipeline thread alone writes a slot (put),
    when the engine allocates it or its key returns with another scope
    or import standing; a SlotMeta is never changed once an interval's
    view may hold it, the slot gets a new one.

    The flush worker reads the name columns by slot while the next
    interval may already be writing slots it reuses, with no lock: put
    stamps the slot with `epoch` BEFORE it writes a label, detach hands
    the view its epoch and starts the next, and the worker reads the
    stamps AFTER the labels (_SlotColumns.names): a stamp above the
    view's says the label may be a later key's, and the row is labelled
    from the view's own SlotMeta."""

    def __init__(self, spec: TableSpec):
        caps = (spec.counter_capacity, spec.gauge_capacity,
                spec.set_capacity, spec.histo_capacity)
        self.tables = {t: _KindLabels(c) for t, c in zip(LIVE_TABLES, caps)}
        self.epoch = 0

    def put(self, table: str, slot: int, meta: SlotMeta) -> None:
        lab = self.tables[table]
        lab.stamp[slot] = self.epoch
        lab.meta[slot] = meta
        lab.name[slot] = meta.name
        lab.first[slot] = first_byte(meta)
        # a list: the flush worker may add a column meanwhile (one it
        # adds after this finds the stamp already written)
        for names, filled in list(lab.compound.values()):
            filled[slot] = False
            names[slot] = None

    def columns(self, table: str, slots, first) -> "_SlotColumns":
        """An interval's live keys (NativeIngest.live_keys) as columns,
        by lookup: nothing is built for a key whose scope and import
        standing are what they were when it was last emitted."""
        lab = self.tables[table]
        for i in np.flatnonzero(lab.first[slots] != first).tolist():
            slot, f = int(slots[i]), int(first[i])
            self.put(table, slot, dataclasses.replace(
                lab.meta[slot], scope=f & ~IMPORTED_BIT,
                imported_only=bool(f & IMPORTED_BIT)))
        return _SlotColumns(slots, first, lab.meta[slots], lab, self.epoch)


class _SlotColumns:
    """host.KeyColumns of a native interval: the engine's `slots` and
    `first` arrays as it handed them over and the SlotMeta column taken
    at that moment, which are the interval's own and frozen; names are
    read from the feed's columns by slot when a frame is built."""

    __slots__ = ("slots", "first", "metas", "_labels", "_epoch")

    def __init__(self, slots, first, metas, labels: _KindLabels,
                 epoch: int):
        self.slots, self.first, self.metas = slots, first, metas
        self._labels, self._epoch = labels, epoch

    def __len__(self):
        return len(self.slots)

    def names(self, sel=None, suffix: str = ""):
        """KeyColumns.names: one take from the kept column; with a
        suffix, the compound column of that suffix, built and kept for
        the rows no flush has emitted yet. Reused are the rows whose
        label was in its column before this interval began."""
        lab, epoch = self._labels, self._epoch
        slots = self.slots if sel is None else self.slots[sel]
        if suffix:
            column, filled = lab.compound_column(suffix)
            out = column[slots]
            kept = filled[slots]
            todo = np.flatnonzero(~kept)
            if len(todo):
                at = slots[todo]
                out[todo] = column[at] = lab.name[at] + suffix
                filled[at] = True
        else:
            out = lab.name[slots]
        stamps = lab.stamp[slots]          # after the labels: _SlotMetas
        late = np.flatnonzero(stamps > epoch)
        if len(late):
            if suffix:
                # what this flush filled there may lie over the new key's
                filled[slots[late]] = False
            rows = late if sel is None else sel[late]
            out[late] = [m.name + suffix for m in self.metas[rows]]
        reused = (kept & (stamps <= epoch)) if suffix else stamps < epoch
        return out, int(np.count_nonzero(reused))

    def pairs(self) -> list:
        return list(zip(self.slots.tolist(), self.metas.tolist()))

    def meta_for_slot(self, slot: int):
        at = np.flatnonzero(self.slots == slot)
        return self.metas[at[0]] if len(at) else None


class _IntervalKeys:
    """A detached interval's keys, as KeyTable states them to the flush
    worker: frozen when the swap made it. The next interval's allocations
    and evictions, which reuse slots while the worker still reads, do not
    reach it. It holds columns (_SlotColumns), which is all the tick
    reads; the [(slot, SlotMeta)] list of get_meta is made for the
    readers that ask for it (forward, checkpoint, history, watch, query),
    once a kind."""

    def __init__(self, cols: dict, status: _KindTable):
        self.cols = cols          # kind-table name -> _SlotColumns
        self.status = status
        self._pairs: dict = {}
        self._pairs_lock = threading.Lock()

    def columns(self, kind: str):
        if kind == "status":
            return KeyColumns(self.status.meta)
        return self.cols[KeyTable._table_name(kind)]

    def get_meta(self, kind: str):
        if kind == "status":
            return self.status.meta
        table = KeyTable._table_name(kind)
        # forward and checkpoint ask from threads of their own
        with self._pairs_lock:
            pairs = self._pairs.get(table)
            if pairs is None:
                pairs = self._pairs[table] = self.cols[table].pairs()
        return pairs

    def meta_for_slot(self, kind: str, slot: int):
        if kind == "status":
            return self.status.by_slot.get(slot)
        return self.columns(kind).meta_for_slot(slot)


class NativeKeyTable:
    """KeyTable facade over the live interval: the C++ slot maps, the
    feed's _SlotMetas and a Python status table of the interval's own."""

    def __init__(self, spec: TableSpec, eng: NativeIngest, n_shards: int,
                 metas: _SlotMetas):
        self.spec = spec
        self.eng = eng
        self.n_shards = n_shards
        self.metas = metas
        self.status = _KindTable(spec.status_capacity, n_shards)

    _TABLE = staticmethod(KeyTable._table_name)

    def _absorb_new_keys(self):
        for kind, slot, scope, name, joined, imported in \
                self.eng.drain_new_keys():
            # flush labels use the FIRST arrival's tags, matching the
            # reference's one-sampler-per-MetricKey semantics. Deliberate
            # deviation: an empty tag SECTION (`|#`) and no section both
            # serialize to joined == "" in the C++ key record, so the
            # label here is () where the reference would keep [""] when
            # the empty section arrived first — a cosmetic empty tag on
            # a pathological packet shape; the key identity (and the
            # digest) agree with the reference either way.
            self.metas.put(self._TABLE(kind), slot, SlotMeta(
                name=name, tags=tuple(joined.split(",")) if joined else (),
                scope=scope, kind=kind, joined_tags=joined,
                imported_only=imported))

    def slot_for(self, kind: str, name: str, tags: tuple, scope: int,
                 digest: int, hostname: str = "", imported: bool = False,
                 joined_tags=None):
        if kind == "status":
            # joined-string identity, same as host.py KeyTable and the
            # C++ engine's keybuf (reference MetricKey.JoinedTags)
            key = (kind, name, joined_tags if joined_tags is not None
                   else ",".join(tags))
            slot = self.status.by_key.get(key)
            if slot is not None:
                return slot
            return self.status.alloc(key, digest, name, tags, scope, kind,
                                     hostname=hostname)
        joined = joined_tags if joined_tags is not None else ",".join(tags)
        slot, was_new = self.eng.slot_for(kind, name, joined, scope, digest,
                                          imported)
        if slot is not None and was_new:
            # register the exact tuple — tags from SSF maps may contain
            # commas, which a joined-string round-trip would corrupt —
            # over the allocation's own record, absorbed first
            self._absorb_new_keys()
            self.metas.put(self._TABLE(kind), slot, SlotMeta(
                name=name, tags=tags, scope=scope, kind=kind,
                hostname=hostname, imported_only=imported,
                joined_tags=joined))
        return slot

    def sampled_directly(self, kind: str, slot: int) -> None:
        self.eng.sampled_directly(slot)

    def _columns(self, table: str) -> _SlotColumns:
        # the list first: a ring worker may allocate between the two
        # calls, and every slot of the list must have its record absorbed
        slots, first = self.eng.live_keys(table)
        self._absorb_new_keys()
        return self.metas.columns(table, slots, first)

    def columns(self, kind: str):
        """The interval so far as columns; pipeline thread only."""
        if kind == "status":
            return KeyColumns(self.status.meta)
        return self._columns(self._TABLE(kind))

    def get_meta(self, kind: str):
        """[(slot, SlotMeta)] of the interval so far, in first-arrival
        order; pipeline thread only, a new list at every call."""
        if kind == "status":
            return self.status.meta
        return self._columns(self._TABLE(kind)).pairs()

    def meta_for_slot(self, kind: str, slot: int):
        if kind == "status":
            return self.status.by_slot.get(slot)
        return self._columns(self._TABLE(kind)).meta_for_slot(slot)

    def dropped(self) -> int:
        return self.eng.stats()["dropped"] + self.status.dropped

    def detach(self) -> _IntervalKeys:
        """The interval's keys for the flush worker, taken before the
        engine's reset starts the next interval: a copy of the live list
        and one take of the SlotMeta column a kind."""
        view = _IntervalKeys({t: self._columns(t) for t in LIVE_TABLES},
                             self.status)
        self.metas.epoch += 1
        return view


class _NativeFeed:
    """What a backend fed by the C++ parse/key/stage engine does whatever
    device backend it stands on: both native backends inherit this beside
    theirs (Aggregator, ShardedAggregator) and define _emit_native and
    _emit_rings, which move the engine's staged rows into device steps."""

    def _init_native(self, engine) -> None:
        # live resharding passes the OLD aggregator's engine: the C++
        # reader rings/sockets keep feeding the same handle across the
        # rebuild (its staged shard map was applied by the reset inside
        # the drain swap), so ingest never restarts
        self.eng = engine if engine is not None \
            else NativeIngest(self.spec, self.bspec, self.n_shards)
        # an engine handed over has had its tables emptied (the reset
        # that applied the staged map or capacity), so every key it holds
        # from here on leaves its record with this feed
        self._slot_metas = _SlotMetas(self.spec)
        self.table = NativeKeyTable(self.spec, self.eng, self.n_shards,
                                    self._slot_metas)
        # the gRPC import path (import_pb_bytes), added up on the pipeline
        # thread: requests folded, engine rows they staged, lane stops,
        # the device steps dispatched while folding them and, of those,
        # the steps a full stats lane dispatched
        self.import_rpcs = 0
        self.import_rows = 0
        self.import_lane_stops = 0
        self.import_steps = 0
        self.import_stat_steps = 0

    # -- wire path -----------------------------------------------------------
    def feed(self, data: bytes) -> List[bytes]:
        """Parse a packet buffer natively; returns escalated event/service-
        check lines for the caller to handle via the Python parser. A
        lane-full stop resumes at the consumed offset — the buffer is
        never re-sliced (NativeIngest.feed offset contract)."""
        full, off = self.eng.feed(data)
        while full:
            self._emit_native()
            full, off = self.eng.feed(data, off)
        return self.eng.drain_specials()

    def extra_parse_errors(self) -> int:
        return self.eng.stats()["parse_errors"]

    # -- native UDP reader group ---------------------------------------------
    def readers_start(self, fds, max_len: int = 65536,
                      ring_cap: int = 65536, n_rings: int = 1,
                      pin_cores=None, force_rings: bool = False) -> None:
        """Start the native readers. n_rings == 1 keeps the proven
        single-ring vr_* engine (N reader threads -> one ring -> this
        thread's pump); n_rings > 1 starts the multi-ring vrm_* engine:
        one ring + parser + packed arena row per reader core, fds
        distributed round-robin across rings (each SO_REUSEPORT fd owns
        its ring), optional sched_affinity pinning per ring.
        force_rings routes even a 1-ring config through the vrm engine —
        tenant fairness lives only there (the vr_* path stays
        tenant-blind), so a tenancy-enabled server must set it."""
        if n_rings <= 1 and not force_rings:
            self.eng.readers_start(fds, max_len=max_len, ring_cap=ring_cap)
            return
        # every fd must own a ring (vrm readers are 1:1 with rings) — a
        # multi-address bind with more sockets than configured rings
        # grows the ring count rather than orphaning listeners
        n_rings = max(n_rings, len(fds) if fds else 0)
        self.rings_start(n_rings, fds=fds, max_len=max_len,
                         ring_cap=ring_cap, pin_cores=pin_cores)

    def rings_start(self, n_rings: int, fds=None, max_len: int = 65536,
                    ring_cap: int = 65536, pin_cores=None) -> None:
        """Multi-ring engine start (fd-less rings accept rings_inject only
        — bench/test entry)."""
        self.eng.rings_start(n_rings, fds=fds, max_len=max_len,
                             ring_cap=ring_cap, pin_cores=pin_cores)

    def pump(self, max_wait_ms: int, max_emits: int = 8) -> List[bytes]:
        """Drain the C++ datagram ring(s) into staging (GIL released while
        idle), emitting device batches whenever a lane fills. Bounded:
        under sustained overload an unbounded drain would never return to
        the pipeline dispatch loop and flush requests (which ride
        packet_queue) would starve — exactly when operators most need the
        flush. Returns escalated event/service-check lines."""
        if self.eng.n_rings:
            self._pump(max_wait_ms)
            for _ in range(max_emits):
                if not self._emit_rings():
                    break
            return self.eng.drain_specials()
        full = self._pump(max_wait_ms)
        for _ in range(max_emits):
            if not full:
                break
            self._emit_native()
            full = self._pump(0)
        if full:
            # leave staging drained so the next call ingests immediately
            self._emit_native()
        return self.eng.drain_specials()

    def _pump(self, max_wait_ms: int) -> bool:
        """One vr_pump call (waiting for datagrams and parsing them, GIL
        released) or, with rings, one wait for their workers, counted
        into the thread's `pipeline.pump` run: one record for however
        many consecutive calls. True when a staging lane filled."""
        hostspans.run_call("pipeline.pump")
        if self.eng.n_rings:
            self.eng.rings_wait(max_wait_ms)
            full = False
        else:
            full, _st = self.eng.pump(max_wait_ms)
        hostspans.run_returned()
        return full

    def reader_counters(self) -> dict:
        return self.eng.reader_counters()

    def ring_stats(self) -> dict:
        """Deep ring/emit telemetry (vr_stats): depth, high-water, pump
        batches/stalls, emit_packed call/ns totals. Any thread. In
        multi-ring mode this is the EXACT cross-ring aggregate (sums;
        high-water is the per-ring max). With them the two counts of the
        steps those emits fed that the engine does not keep: compactions
        (Aggregator._count_step) and the digest rows they compressed, as
        the device counted them (Aggregator._settle_step: exact at each
        swap, up to _MAX_STEPS_IN_FLIGHT steps behind between two); the
        flushes computed, their blocks and their live rows
        (Aggregator._count_flush); the rows their frames emitted and how
        many of those took their name from a kept column
        (Aggregator.count_frame); how often the key table's
        persistence engaged in the intervals swapped so far
        (NativeIngest.key_counters): the keys they held, of them the ones
        a swap paid for (new) and did not (reused), and the keys evicted
        to make room; and the gRPC import path's counts (import_pb_bytes).
        """
        keys = self.eng.key_counters()
        return {**self.eng.ring_stats(), "compactions": self.compactions,
                "compact_rows": self.compact_rows,
                "flushes": self.flushes_computed,
                "flush_blocks": self.flush_blocks,
                "flush_rows": self.flush_rows,
                "frame_rows": self.frame_rows,
                "frame_labels_reused": self.frame_labels_reused, **keys,
                "keys_reused": keys["keys_live"] - keys["keys_new"],
                "import_rpcs": self.import_rpcs,
                "import_rows": self.import_rows,
                "import_lane_stops": self.import_lane_stops,
                "import_steps": self.import_steps,
                "import_stat_steps": self.import_stat_steps}

    def ring_stats_per_ring(self) -> List[dict]:
        """Per-ring telemetry rows ([] outside multi-ring mode) — the
        `ring=<i>`-labeled collector family reads these."""
        return self.eng.ring_stats_per_ring()

    def admission_set(self, enabled: bool, state: int, rate: float,
                      burst: float, high_tags) -> None:
        """Push OverloadController statsd-admission knobs into the C++
        reader ring (tentpole (c): shedding runs in-engine, off-GIL)."""
        self.eng.admission_set(enabled, state, rate, burst, high_tags)

    def admission_drain(self) -> dict:
        """Exact per-class {admitted, shed} deltas since the last drain."""
        return self.eng.admission_drain()

    # -- tenant fairness/quarantine push-down (reliability/tenancy.py) -------
    def tenant_config(self, *a, **kw) -> None:
        """One-shot tenant-table creation; must land before rings start."""
        self.eng.tenant_config(*a, **kw)

    def tenant_params(self, base_rate: float, weights) -> None:
        self.eng.tenant_params(base_rate, weights)

    def tenant_table(self) -> dict:
        """Non-destructive {tenant: {demoted, key_est}} engine snapshot."""
        return self.eng.tenant_table()

    def tenant_restore(self, entries) -> int:
        return self.eng.tenant_restore(entries)

    def tenant_rows_drain(self) -> dict:
        return self.eng.tenant_rows_drain()

    def readers_stop(self) -> None:
        self.eng.readers_stop()

    # `processed` spans both ingest paths: the C++ engine's count plus the
    # Python-side samples (imports, extracted metrics, service checks).
    @property
    def processed(self):
        native = self.eng.stats()["processed"] if hasattr(self, "eng") else 0
        return self._py_processed + native

    @processed.setter
    def processed(self, v):
        native = self.eng.stats()["processed"] if hasattr(self, "eng") else 0
        self._py_processed = v - native

    # dropped spans both paths too: engine drops + python-side drops
    # (status-table capacity, import drops)
    @property
    def dropped_capacity(self):
        native = self.eng.stats()["dropped"] if hasattr(self, "eng") else 0
        return self._py_dropped + native

    @dropped_capacity.setter
    def dropped_capacity(self, v):
        native = self.eng.stats()["dropped"] if hasattr(self, "eng") else 0
        self._py_dropped = v - native

    # -- flush ---------------------------------------------------------------
    def swap(self):
        rings = bool(self.eng.n_rings)
        with hostspans.span("swap.emit_staged"):
            if rings:
                # quiesce: no ring worker parses between here and resume,
                # so staged rows can't race the table reset below.
                # Datagrams queued (or parked mid-parse on a lane stop)
                # during the pause are parsed after resume and land in
                # the NEXT interval — the same boundary semantics as the
                # single-ring pump queue.
                self.eng.rings_pause()
                self._emit_rings()
            self._emit_native()
        # the interval's keys, frozen for the flush worker: a SlotMeta is
        # built for the keys allocated in it (none, in a steady stream);
        # the rest is the engine's live list looked up in _slot_metas
        with hostspans.span("swap.finalize"):
            detached = self.table.detach()
        state, _ = super().swap()
        # super() replaced self.table with a fresh Python KeyTable; the
        # native engine keeps the keys and their slots and starts the
        # next interval's live list, so re-wrap it post-reset
        with hostspans.span("swap.reset"):
            self.eng.reset()
            self.table = NativeKeyTable(self.spec, self.eng, self.n_shards,
                                        self._slot_metas)
            if rings:
                self.eng.rings_resume()
        return state, detached

    def query_snapshot(self):
        """Live snapshot: emit natively staged rows first. Rings are NOT
        paused — nothing resets here, so datagrams parsed after this
        instant simply land after the snapshot (the ring-path analogue
        of packet-queue FIFO ordering)."""
        if self.eng.n_rings:
            self._emit_rings()
        self._emit_native()
        return super().query_snapshot()


class NativeAggregator(_NativeFeed, Aggregator):
    def __init__(self, spec: TableSpec, bspec: BatchSpec = BatchSpec(),
                 n_shards: int = 1, compact_every: int = 8, engine=None):
        super().__init__(spec, bspec, n_shards, compact_every)
        self._init_native(engine)
        # The native emit is zero-copy: C++ writes staged rows straight
        # into a host buffer in the exact pack_batch device layout
        # (_new_packed, or one row of _new_arena) and the buffer goes to
        # the ingest program as-is; no Batch pytree, no per-lane copies,
        # no Python repack. All 16 lanes are present at the Python
        # Batcher's sizes, in Batch._fields order, so the compile key
        # (spec, sizes) matches the Python path and ONE compiled ingest
        # program serves both. C++ never touches the status and
        # histo_stat lanes: status stays a Python-initialized constant
        # sentinel region, and the histo_stat region carries the Python
        # Batcher's staged digest stats when there are any (_carry_stats).
        b = bspec
        self._pk_sizes = (b.counter, b.counter, b.gauge, b.gauge,
                          b.status, b.status, b.set, b.set, b.set,
                          b.histo, b.histo, b.histo,
                          b.histo_stat, b.histo_stat, b.histo_stat,
                          b.histo_stat)
        self._pk_layout, self._pk_words = packed_layout(self._pk_sizes)
        # word offsets of the ten lanes the C++ engine stages, in
        # vt_emit_packed's argument order; the interleaved status and
        # histo_stat lanes are Python-owned
        self._pk_offs = np.asarray(
            [self._pk_layout[name][0] for name in (
                "counter_slot", "counter_inc", "gauge_slot", "gauge_val",
                "set_slot", "set_reg", "set_rho", "histo_slot",
                "histo_val", "histo_wt")], np.int32)

    def _new_packed(self):
        """One flat packed buffer, and beside it the staged-row counts of
        that buffer's previous emit — vt_emit_packed's incremental
        sentinel-restore bound — and the stats rows its previous step
        carried (_carry_stats' bound, a one-item list)."""
        flat = np.zeros(self._pk_words, np.int32)
        self._init_packed_sentinels(flat, self._pk_layout, self.spec)
        return flat, np.zeros(4, np.uint32), [0]

    def _new_arena(self):
        """One (rings, words) arena — a row per ring in the exact packed
        layout — and its per-row previous counts. Every ring's emit lands
        in its own row and the WHOLE arena crosses host->device as one
        donated transfer per step (ingest_step_packed_rings), so R rings
        cost one h2d RTT, not R."""
        n_rings = self.eng.n_rings
        arena = np.zeros((n_rings, self._pk_words), np.int32)
        for row in arena:
            self._init_packed_sentinels(row, self._pk_layout, self.spec)
        return arena, np.zeros((n_rings, 4), np.uint32)

    @staticmethod
    def _init_packed_sentinels(flat, layout, spec):
        """One-time sentinel fill of a fresh packed buffer: every slot
        lane at its table capacity (scatter mode='drop' padding), weight
        lanes 0, histo-stat min/max at +/-inf — the state Batcher.emit's
        partial reset maintains on the Python path. After this, the six
        C++-maintained lanes are kept in this state incrementally by
        vt_emit_packed, the histo_stat region by _carry_stats, and the
        status region is never written again."""

        def lane(name, value, f32=False):
            _lane(flat, layout, name, f32)[:] = value

        lane("counter_slot", spec.counter_capacity)
        lane("gauge_slot", spec.gauge_capacity)
        lane("set_slot", spec.set_capacity)
        lane("histo_slot", spec.histo_capacity)
        lane("status_slot", spec.status_capacity)
        lane("histo_stat_slot", spec.histo_capacity)
        lane("histo_stat_min", np.inf, f32=True)
        lane("histo_stat_max", -np.inf, f32=True)

    def _emit_native(self, compact: bool = False):
        flat, prev, carried = self._step_buffer("packed", self._new_packed)
        with hostspans.span("pipeline.emit"):
            nc, ng, ns, nh = self.eng.emit_packed(flat, self._pk_offs, prev)
        if nc + ng + ns + nh == 0:
            return
        if self.batcher.nhs or carried[0]:
            self._carry_stats(flat, carried)
        flat[0] = 1 if self._count_step(force_compact=compact) else 0
        self._dispatch_step(ingest_step_packed, flat, "packed",
                            spec=self.spec, sizes=self._pk_sizes)

    def _carry_stats(self, flat, carried) -> None:
        """Move the Python Batcher's staged digest stats into this step's
        histo_stat region, so that they ride a step the engine's rows
        make and dispatch none of their own, and put the rows this buffer
        carried last time past the new count back at their sentinels (as
        vt_emit_packed does for its lanes with `prev`). Scatter min, max
        and add do not care which step a row rides."""
        lay = self._pk_layout
        slot = _lane(flat, lay, "histo_stat_slot")
        mn = _lane(flat, lay, "histo_stat_min", f32=True)
        mx = _lane(flat, lay, "histo_stat_max", f32=True)
        recip = _lane(flat, lay, "histo_stat_recip", f32=True)
        n = self.batcher.move_histo_stats(slot, mn, mx, recip)
        old = carried[0]
        if old > n:
            slot[n:old] = self.spec.histo_capacity
            mn[n:old] = np.inf
            mx[n:old] = -np.inf
            recip[n:old] = 0.0
        carried[0] = n

    # -- native import path (global tier) ------------------------------
    def import_pb_bytes(self, data: bytes):
        """Decode + stage a serialized forwardrpc.MetricList with the
        C++ engine (VERDICT r04 #5: the gRPC decode→slot path batched
        the way wire ingest staging is; reference importsrv/server.go:97
        SendMetrics). Counters/gauges/digests stage natively; sets,
        valueless metrics, and oneof/type mismatches fall back to the
        Python import_into path so error accounting matches the
        reference's per-metric semantics. Returns (metrics, errors).

        Spanned inside the caller's `pipeline.item`: `import.decode` (each
        engine call: decode, key lookup, staging), `import.fallback` (the
        Python import of fallback metrics) and `import.stats` (the
        digests' scalar stats into the Python stats lane, which the next
        engine step carries: _carry_stats); the emits and dispatches keep
        their own spans. Counted: import_rpcs, import_rows (rows the
        engine staged: a digest's centroids, a counter's or gauge's
        value), import_lane_stops, import_steps (steps dispatched in
        here, the stats lane's included) and import_stat_steps (of them,
        the ones a full stats lane dispatched in `import.stats`)."""
        from veneur_tpu.forward.convert import import_into
        from veneur_tpu.proto import metricpb_pb2 as mpb
        eng = self.eng
        steps_before = self.steps_total
        total = 0
        errors = 0
        off = 0
        while off < len(data):
            with hostspans.span("import.decode"):
                pending = eng.pending()
                staged, new_off, spans, lane_full = \
                    eng.import_metriclist(data, off)
                self.import_rows += eng.pending() - pending
            total += staged + len(spans)
            if spans:
                with hostspans.span("import.fallback"):
                    for so, sl in spans:
                        try:
                            import_into(self, mpb.Metric.FromString(
                                data[so:so + sl]))
                        except Exception as e:
                            errors += 1
                            log.warning("bad imported metric (native "
                                        "path): %s", e)
            if new_off >= len(data):
                break
            if not lane_full and new_off == off and staged == 0 \
                    and not spans:
                # undecodable at a top-level boundary (NOT a lane stop):
                # the Python deserializer would reject the whole request
                # — count one error and drop the remainder
                errors += 1
                log.warning("undecodable MetricList tail at offset %d "
                            "(%d bytes dropped)", off, len(data) - off)
                break
            # staging filled (or the fallback buffer did): free the
            # lanes, then re-enter at the reported boundary. The step
            # compacts: it carries a full lane of imported centroids,
            # already merged and heavy, and what overflows a digest row's
            # temp cells before a compaction lands in its estimate cells
            # (step._histo_plan), whose error grows with every step left
            # uncompacted
            self.import_lane_stops += lane_full
            self._emit_native(compact=True)
            off = new_off
        # per-digest exact min/max/recip ride the Python stats lane until
        # an engine step carries them — scatter min/max/add are
        # order-independent vs the centroid re-add, so batch boundaries
        # don't matter; a step dispatched here is the lane overflowing
        with hostspans.span("import.stats"):
            slots, mns, mxs, rcs = eng.drain_import_stats()
            if len(slots):
                stat_steps_before = self.steps_total
                self.batcher.add_histo_stats_bulk(slots, mns, mxs, rcs)
                self.import_stat_steps += (self.steps_total
                                           - stat_steps_before)
        self.import_rpcs += 1
        self.import_steps += self.steps_total - steps_before
        return total, errors

    def _emit_rings(self) -> bool:
        """Drain every ring's staging into the current arena's rows and
        run ONE device step over the whole arena. Returns False (no step)
        when all rings were empty — the common idle poll. The compact
        control word rides row 0 only."""
        n_rings = self.eng.n_rings
        key = ("rings", n_rings)
        arena, prev = self._step_buffer(key, self._new_arena)
        total = 0
        t0 = time.monotonic_ns()
        for r in range(n_rings):
            counts = self.eng.rings_emit(r, arena[r], self._pk_offs,
                                         prev[r])
            total += counts[0] + counts[1] + counts[2] + counts[3]
        if total == 0:
            return False
        # stamped after the fact: an empty poll (one per pump call, far
        # more often than a step) must leave no record
        hostspans.record("pipeline.emit", t0, time.monotonic_ns())
        arena[0, 0] = 1 if self._count_step() else 0
        self._dispatch_step(ingest_step_packed_rings, arena, key,
                            spec=self.spec, sizes=self._pk_sizes)
        return True


class NativeShardedAggregator(_NativeFeed, ShardedAggregator):
    """Mesh-sharded backend fed by the C++ parse/key/stage engine.

    The engine's slot space is shard-aware (dogstatsd.cpp KindTable:
    slot = shard*per_shard + local, same rule as aggregation/host.py), so
    its emitted global slots split into (shard, local) with two vectorized
    numpy ops and bulk-copy into the per-shard staging batchers — the 30x
    C++ host path and the multi-device mesh compose instead of excluding
    each other."""

    def __init__(self, spec: TableSpec, bspec: BatchSpec = BatchSpec(),
                 n_shards: int = 2, compact_every: int = 8,
                 preshard: bool = False, engine=None):
        super().__init__(spec, bspec, n_shards, compact_every)
        self._init_native(engine)
        self.preshard = preshard
        self._ps_bounds = np.zeros(4 * (n_shards + 1), np.int32)
        self._alloc_emit_buffers()
        # the engine's rows staged into the shard batchers, in all and by
        # shard, added up on the pipeline thread (ring_stats)
        self.shard_rows_staged = 0
        self._shard_rows = np.zeros(n_shards, np.int64)

    def ring_stats(self) -> dict:
        """_NativeFeed.ring_stats with the sharded staging's counts: the
        rows the engine's emits staged into the shard batchers, the
        busiest shard's part of them, the lane rows the dispatched
        steps' tiles held (ShardedAggregator._dispatch_row) and the steps
        one shard's full lane dispatched with the other tiles as they
        stood (_on_shard_batch)."""
        return {**super().ring_stats(),
                "shard_rows_staged": self.shard_rows_staged,
                "shard_rows_max": int(self._shard_rows.max()),
                "shard_lane_rows_dispatched":
                    self.shard_lane_rows_dispatched,
                "shard_forced_emits": self.shard_forced_emits}

    def _count_staged(self, rows: int, at) -> None:
        """Count a kind's `rows` staged, split by the shard bounds `at`
        (S + 1 offsets into its lane)."""
        self.shard_rows_staged += rows
        self._shard_rows += at[1:]
        self._shard_rows -= at[:-1]

    def _alloc_emit_buffers(self):
        """Staging targets for emit_into — the sharded backend re-stages
        emitted rows into per-shard Python Batchers (the per-shard packed
        layout differs from the engine's global slot space), so it keeps
        the array-based emit rather than the single backend's direct
        packed emit. Only the ten native lanes are needed; slot lanes are
        re-sentineled per emit below."""
        b = self.bspec
        self._c_slot = np.empty(b.counter, np.int32)
        self._c_inc = np.zeros(b.counter, np.float32)
        self._g_slot = np.empty(b.gauge, np.int32)
        self._g_val = np.zeros(b.gauge, np.float32)
        self._s_slot = np.empty(b.set, np.int32)
        self._s_reg = np.zeros(b.set, np.int32)
        self._s_rho = np.zeros(b.set, np.uint8)
        self._h_slot = np.empty(b.histo, np.int32)
        self._h_val = np.zeros(b.histo, np.float32)
        self._h_wt = np.zeros(b.histo, np.float32)

    _PER_SHARD_FIELD = {"counter": "counter_capacity",
                        "gauge": "gauge_capacity",
                        "status": "status_capacity",
                        "set": "set_capacity",
                        "histo": "histo_capacity"}

    def _local(self, kind: str, slot: int):
        """global slot -> (shard, local). ShardedAggregator reads per-shard
        widths off its Python KeyTable; here the table is a NativeKeyTable
        (no .tables), but the widths are statically the per-shard spec's
        capacities — the C++ engine allocates with the identical
        shard*per_shard+local rule (dogstatsd.cpp KindTable)."""
        per = getattr(self.pspec,
                      self._PER_SHARD_FIELD[KeyTable._table_name(kind)])
        return slot // per, slot % per

    def _split_shards(self, global_slots, per_shard):
        """One-pass shard split of a staged slot lane: a stable argsort
        groups rows by shard (stability preserves arrival order within a
        shard — gauge last-write-wins depends on it), searchsorted finds
        the [start, end) bounds per shard. Replaces the per-shard
        boolean-mask loop, which scanned the whole lane n_shards times.
        Returns (order, local_slots_sorted, bounds)."""
        sh = global_slots // per_shard
        order = np.argsort(sh, kind="stable")
        lo = (global_slots - sh * per_shard).astype(np.int32, copy=False)
        bounds = np.searchsorted(sh, np.arange(self.n_shards + 1),
                                 sorter=order)
        return order, lo[order], bounds

    def _native_lanes(self):
        return (self._c_slot, self._c_inc, self._g_slot, self._g_val,
                self._s_slot, self._s_reg, self._s_rho, self._h_slot,
                self._h_val, self._h_wt)

    def _stage_presharded(self, nc, ng, ns, nh):
        """Bulk-copy a pre-sharded emit (vt_emit_sharded contract: rows
        grouped by owner shard, slots already shard-local, per-kind shard
        bounds in self._ps_bounds) into the per-shard batchers. Contiguous
        slices only — the argsort/searchsorted of _split_shards and the
        local-slot subtraction both happened in C++ during the one pass
        the emit copy already makes. Under the `pipeline.shard_split`
        span, as _emit_split's staging."""
        with hostspans.span("pipeline.shard_split"):
            self._stage_bounds(nc, ng, ns, nh)

    def _stage_bounds(self, nc, ng, ns, nh):
        b = self._ps_bounds
        S = self.n_shards
        for k, n in enumerate((nc, ng, ns, nh)):
            if n:
                self._count_staged(n, b[k * (S + 1):(k + 1) * (S + 1)])
        if nc:
            at = b[0:S + 1]
            for i in range(S):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_counters_bulk(
                        self._c_slot[at[i]:at[i + 1]],
                        self._c_inc[at[i]:at[i + 1]])
        if ng:
            at = b[S + 1:2 * (S + 1)]
            for i in range(S):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_gauges_bulk(
                        self._g_slot[at[i]:at[i + 1]],
                        self._g_val[at[i]:at[i + 1]])
        if ns:
            at = b[2 * (S + 1):3 * (S + 1)]
            for i in range(S):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_sets_bulk(
                        self._s_slot[at[i]:at[i + 1]],
                        self._s_reg[at[i]:at[i + 1]],
                        self._s_rho[at[i]:at[i + 1]])
        if nh:
            at = b[3 * (S + 1):4 * (S + 1)]
            for i in range(S):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_histos_bulk(
                        self._h_slot[at[i]:at[i + 1]],
                        self._h_val[at[i]:at[i + 1]],
                        self._h_wt[at[i]:at[i + 1]])

    def _emit_presharded(self):
        nc, ng, ns, nh = self.eng.emit_sharded(self._native_lanes(),
                                               self._ps_bounds)
        if nc + ng + ns + nh:
            self._stage_presharded(nc, ng, ns, nh)

    def _emit_native(self):
        # the staging below may fill a shard's batcher and dispatch a
        # step: that `pipeline.dispatch` is then this span's child
        with hostspans.span("pipeline.emit"):
            if self.preshard:
                self._emit_presharded()
            else:
                self._emit_split()

    def _emit_split(self):
        nc, ng, ns, nh = self.eng.emit_into(
            (self._c_slot, self._c_inc, self._g_slot, self._g_val,
             self._s_slot, self._s_reg, self._s_rho, self._h_slot,
             self._h_val, self._h_wt))
        if nc + ng + ns + nh == 0:
            return
        # the lanes' split by owner shard and their copy into the shard
        # batchers; a step a full lane dispatches is its child
        # (`pipeline.shard_pack`)
        with hostspans.span("pipeline.shard_split"):
            self._split_into_batchers(nc, ng, ns, nh)

    def _split_into_batchers(self, nc, ng, ns, nh):
        p = self.pspec
        if nc:
            order, lo, at = self._split_shards(self._c_slot[:nc],
                                               p.counter_capacity)
            self._count_staged(nc, at)
            inc = self._c_inc[:nc][order]
            for i in range(self.n_shards):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_counters_bulk(
                        lo[at[i]:at[i + 1]], inc[at[i]:at[i + 1]])
        if ng:
            order, lo, at = self._split_shards(self._g_slot[:ng],
                                               p.gauge_capacity)
            self._count_staged(ng, at)
            val = self._g_val[:ng][order]
            for i in range(self.n_shards):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_gauges_bulk(
                        lo[at[i]:at[i + 1]], val[at[i]:at[i + 1]])
        if ns:
            order, lo, at = self._split_shards(self._s_slot[:ns],
                                               p.set_capacity)
            self._count_staged(ns, at)
            reg = self._s_reg[:ns][order]
            rho = self._s_rho[:ns][order]
            for i in range(self.n_shards):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_sets_bulk(
                        lo[at[i]:at[i + 1]], reg[at[i]:at[i + 1]],
                        rho[at[i]:at[i + 1]])
        if nh:
            order, lo, at = self._split_shards(self._h_slot[:nh],
                                               p.histo_capacity)
            self._count_staged(nh, at)
            val = self._h_val[:nh][order]
            wt = self._h_wt[:nh][order]
            for i in range(self.n_shards):
                if at[i + 1] > at[i]:
                    self.batchers[i].add_histos_bulk(
                        lo[at[i]:at[i + 1]], val[at[i]:at[i + 1]],
                        wt[at[i]:at[i + 1]])

    # -- multi-ring reader group (sharded) -----------------------------------
    # Ring staging drains through the pre-sharded emit ONLY (vrm exposes
    # the packed and pre-sharded drains per ring; flush output is
    # byte-identical to the _split_shards path either way — pinned by
    # tests/test_native_preshard.py).
    def _emit_rings(self) -> bool:
        emitted = False
        for r in range(self.eng.n_rings):
            nc, ng, ns, nh = self.eng.rings_emit_sharded(
                r, self._native_lanes(), self._ps_bounds)
            if nc + ng + ns + nh:
                self._stage_presharded(nc, ng, ns, nh)
                emitted = True
        return emitted
