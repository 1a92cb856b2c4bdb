"""Host-side key dictionary and batch staging.

The reference resolves a MetricKey to a sampler object by Go map lookup inside
each worker (reference worker.go:108 Upsert). Here the host resolves
(name, type, joined_tags) to a dense slot index into the device arrays; the
device never sees strings. Slot metadata (name, tags, scope) stays host-side
for flush labeling, mirroring how the reference's MetricKey fields ride along
to InterMetric generation (reference samplers/samplers.go:147-158).

Slots are assigned shard-aware: slot = shard * per_shard + local index, where
shard = digest % n_shards and digest is the reference-compatible FNV-1a 32
(reference server.go:973,984 routes by Digest % numWorkers the same way).
This keeps every key's state resident on a single device when the table is
sharded over a mesh (parallel/), so ingest scatters never cross devices.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import numpy as np

from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.aggregation.step import Batch
from veneur_tpu.native import IMPORTED_BIT
from veneur_tpu.utils.hashing import hll_reg_rho

# metric type classes that own a table
KINDS = ("counter", "gauge", "status", "set", "histogram", "timer")

# scopes, mirroring reference samplers/parser.go:66-70 MetricScope
SCOPE_MIXED = 0
SCOPE_LOCAL = 1
SCOPE_GLOBAL = 2


@dataclasses.dataclass
class SlotMeta:
    name: str
    tags: tuple
    scope: int
    kind: str
    hostname: str = ""
    message: str = ""  # status checks only
    # True while a histo slot has only ever been fed by the import path;
    # drives the global tier's aggregate suppression for mixed-scope
    # histograms (reference flusher.go:61-77 "avoid double counting":
    # imported mixed histos have no local scalars, so only percentiles
    # flush). Cleared on the first directly-sampled value.
    imported_only: bool = False
    # the parser's precomputed MetricKey.JoinedTags, when the allocation
    # site had it; lets flush labeling test for routing tags with ONE
    # substring scan instead of per-tag startswith (None -> join lazily)
    joined_tags: Optional[str] = None
    # flusher.generate_intermetrics cache: (tags list, sink route,
    # hostname) computed once per key per interval. The tags list is
    # SHARED by every InterMetric of the key — sinks must derive
    # (tags + [...]) rather than mutate, which they all do.
    _emit_prep: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)


def object_column(items) -> np.ndarray:
    """A 1-D object array of `items`, whatever they are (np.asarray
    would look inside a tuple)."""
    col = np.empty(len(items), object)
    col[:] = items
    return col


def first_byte(meta: SlotMeta) -> int:
    """What a SlotMeta states of its key's standing in an interval, as
    the engine's one byte a key: the scope, IMPORTED_BIT set while it is
    imported_only (NativeIngest.live_keys)."""
    return meta.scope | IMPORTED_BIT if meta.imported_only else meta.scope


def scopes_of(first: np.ndarray) -> np.ndarray:
    """The scope column of a `first` column (KeyColumns)."""
    return first & np.uint8(0xFF & ~IMPORTED_BIT)


class KeyColumns:
    """One kind's keys of an interval as columns: what flush labeling
    reads, on every table type, through `table.columns(kind)`. Row i is
    row i of the kind's flush arrays and pair i of get_meta(kind).

    slots  int32: the device rows (step.live_slots).
    first  uint8: first_byte of each SlotMeta.
    metas  object: the SlotMeta.

    This one is made from a get_meta list when a column is asked for (a
    loop a column, what the list's readers paid before there were
    columns); the native tables hand over arrays they hold
    (native_aggregator._SlotColumns)."""

    def __init__(self, pairs: list):
        self.pairs = pairs

    def __len__(self):
        return len(self.pairs)

    @functools.cached_property
    def slots(self) -> np.ndarray:
        return np.fromiter((s for s, _m in self.pairs), np.int32,
                           len(self.pairs))

    @functools.cached_property
    def first(self) -> np.ndarray:
        return np.fromiter((first_byte(m) for _s, m in self.pairs),
                           np.uint8, len(self.pairs))

    @functools.cached_property
    def metas(self) -> np.ndarray:
        return object_column([m for _s, m in self.pairs])

    def names(self, sel=None, suffix: str = ""):
        """(names of rows `sel` (all when None) with `suffix` appended,
        as an object array; how many of them came out of a column kept
        with the key: none here)."""
        metas = self.metas if sel is None else self.metas[sel]
        return object_column([m.name + suffix for m in metas]), 0


class _KindTable:
    __slots__ = ("capacity", "n_shards", "per_shard", "by_key", "meta",
                 "by_slot", "next_free", "dropped")

    def __init__(self, capacity: int, n_shards: int):
        self.capacity = capacity
        self.n_shards = n_shards
        self.per_shard = capacity // n_shards
        self.by_key: dict = {}
        self.meta: list = []          # parallel to allocation order
        self.by_slot: dict = {}       # slot -> SlotMeta, O(1) mutation
        self.next_free = [0] * n_shards
        self.dropped = 0

    def alloc(self, key, digest: int, name: str, tags: tuple, scope: int,
              kind: str, hostname: str = "", imported: bool = False,
              joined_tags=None) -> Optional[int]:
        """Allocate a slot for a new key (callers check by_key first —
        KeyTable.slot_for owns the hit path). Takes the SlotMeta FIELDS
        so the capacity check runs before any construction: during a
        cardinality explosion every re-arrival of a never-admitted key
        lands here, and paying a dataclass build per dropped sample is
        a regression at exactly the wrong time."""
        shard = digest % self.n_shards
        nxt = self.next_free[shard]
        if nxt >= self.per_shard:
            self.dropped += 1
            return None
        meta = SlotMeta(name=name, tags=tags, scope=scope, kind=kind,
                        hostname=hostname, imported_only=imported,
                        joined_tags=joined_tags)
        self.next_free[shard] = nxt + 1
        slot = shard * self.per_shard + nxt
        self.by_key[key] = slot
        self.meta.append((slot, meta))
        self.by_slot[slot] = meta
        return slot

    def reset(self):
        self.by_key.clear()
        self.meta.clear()
        self.by_slot.clear()
        self.next_free = [0] * self.n_shards


class KeyTable:
    """name/type/tags -> slot assignment for one flush interval.

    Timers and histograms share the histo device table (same sampler math,
    reference samplers.go:467) but are distinct key namespaces, as in the
    reference's separate timers/histograms maps (worker.go:66-67); we prefix
    the dict key with the kind.
    """

    # optional tables.pressure.TablePressure — attached by the backend's
    # swap() when table pressure management is enabled; stays None (one
    # predicted-not-taken branch on the MISS path only) otherwise
    pressure = None

    def __init__(self, spec: TableSpec, n_shards: int = 1):
        self.spec = spec
        self.n_shards = n_shards
        self.tables = {
            "counter": _KindTable(spec.counter_capacity, n_shards),
            "gauge": _KindTable(spec.gauge_capacity, n_shards),
            "status": _KindTable(spec.status_capacity, n_shards),
            "set": _KindTable(spec.set_capacity, n_shards),
            "histo": _KindTable(spec.histo_capacity, n_shards),
        }

    @staticmethod
    def _table_name(kind: str) -> str:
        return "histo" if kind in ("histogram", "timer") else kind

    def slot_for(self, kind: str, name: str, tags: tuple, scope: int,
                 digest: int, hostname: str = "",
                 imported: bool = False,
                 joined_tags: Optional[str] = None) -> Optional[int]:
        t = self.tables[self._table_name(kind)]
        # key identity is the JOINED tag string, exactly the reference's
        # MetricKey.JoinedTags (samplers/parser.go:76,412): an empty tag
        # section (`|#` -> [""]) joins to "" and shares the no-tags key,
        # and the C++ engine keys the same way (dogstatsd.cpp keybuf).
        # Callers on the hot path pass the parser's precomputed
        # UDPMetric.joined_tags to skip the per-sample join.
        if joined_tags is None:
            joined_tags = ",".join(tags)
        key = (kind, name, joined_tags)
        # steady-state hit path: ONE dict probe and nothing else —
        # constructing the SlotMeta (or even a closure to defer it) per
        # call cost ~25% of the whole staging hot loop
        slot = t.by_key.get(key)
        if slot is not None:
            return slot
        if self.pressure is not None:
            # miss path only — the pressure ladder (tables/pressure.py)
            # may redirect the key to a rollup/merge slot or admit it
            return self.pressure.admit(t, key, digest, name, tags, scope,
                                       kind, hostname, imported, joined_tags)
        return t.alloc(key, digest, name, tags, scope, kind,
                       hostname=hostname, imported=imported,
                       joined_tags=joined_tags)

    def get_meta(self, kind: str):
        """[(slot, SlotMeta)] in allocation order for flush labeling."""
        return self.tables[self._table_name(kind)].meta

    def columns(self, kind: str) -> KeyColumns:
        """get_meta(kind) as columns, made from the list."""
        return KeyColumns(self.get_meta(kind))

    def meta_for_slot(self, kind: str, slot: int) -> Optional[SlotMeta]:
        return self.tables[self._table_name(kind)].by_slot.get(slot)

    def sampled_directly(self, kind: str, slot: int) -> None:
        """A histo slot took a directly-sampled value: it is no longer
        imported_only in this interval (SlotMeta.imported_only)."""
        mt = self.meta_for_slot(kind, slot)
        if mt is not None and mt.imported_only:
            mt.imported_only = False

    def dropped(self) -> int:
        return sum(t.dropped for t in self.tables.values())

    def reset(self):
        for t in self.tables.values():
            t.reset()


@dataclasses.dataclass(frozen=True)
class BatchSpec:
    """Fixed staging sizes — one compiled ingest program per configuration."""
    counter: int = 8192
    gauge: int = 2048
    status: int = 256
    set: int = 4096
    histo: int = 8192
    # imported-digest scalar lane (step.py); a server that takes imports
    # makes it as wide as `histo` (server.bspec_from_config)
    histo_stat: int = 256


class Batcher:
    """Stages parsed samples into numpy arrays and emits padded Batches.

    The reference's analogue is the PacketChan buffering between parser
    goroutines and workers (reference worker.go:31-55); here buffering is the
    staging arrays and "the worker" is the jitted ingest step.
    """

    def __init__(self, spec: TableSpec, bspec: BatchSpec = BatchSpec(),
                 on_batch: Optional[Callable[[Batch], None]] = None):
        self.spec = spec
        self.bspec = bspec
        self.on_batch = on_batch
        self._alloc()

    def _alloc(self):
        b = self.bspec
        self.c_slot = np.full(b.counter, self.spec.counter_capacity, np.int32)
        self.c_inc = np.zeros(b.counter, np.float32)
        self.g_slot = np.full(b.gauge, self.spec.gauge_capacity, np.int32)
        self.g_val = np.zeros(b.gauge, np.float32)
        self.st_slot = np.full(b.status, self.spec.status_capacity, np.int32)
        self.st_val = np.zeros(b.status, np.float32)
        self.s_slot = np.full(b.set, self.spec.set_capacity, np.int32)
        self.s_reg = np.zeros(b.set, np.int32)
        self.s_rho = np.zeros(b.set, np.uint8)
        self.h_slot = np.full(b.histo, self.spec.histo_capacity, np.int32)
        self.h_val = np.zeros(b.histo, np.float32)
        self.h_wt = np.zeros(b.histo, np.float32)
        self.hs_slot = np.full(b.histo_stat, self.spec.histo_capacity,
                               np.int32)
        self.hs_min = np.full(b.histo_stat, np.inf, np.float32)
        self.hs_max = np.full(b.histo_stat, -np.inf, np.float32)
        self.hs_recip = np.zeros(b.histo_stat, np.float32)
        self.nc = self.ng = self.nst = self.ns = self.nh = self.nhs = 0

    def _maybe_emit(self, n, cap):
        if n >= cap:
            self.emit()

    def add_counter(self, slot: int, value: float, rate: float):
        self.c_slot[self.nc] = slot
        self.c_inc[self.nc] = value * (1.0 / rate)
        self.nc += 1
        self._maybe_emit(self.nc, self.bspec.counter)

    def add_gauge(self, slot: int, value: float):
        self.g_slot[self.ng] = slot
        self.g_val[self.ng] = value
        self.ng += 1
        self._maybe_emit(self.ng, self.bspec.gauge)

    def add_status(self, slot: int, value: float):
        self.st_slot[self.nst] = slot
        self.st_val[self.nst] = value
        self.nst += 1
        self._maybe_emit(self.nst, self.bspec.status)

    def add_set(self, slot: int, member: bytes):
        reg, rho = hll_reg_rho(member, self.spec.hll_precision)
        self.s_slot[self.ns] = slot
        self.s_reg[self.ns] = reg
        self.s_rho[self.ns] = rho
        self.ns += 1
        self._maybe_emit(self.ns, self.bspec.set)

    def add_histo(self, slot: int, value: float, rate: float):
        self.h_slot[self.nh] = slot
        self.h_val[self.nh] = value
        self.h_wt[self.nh] = 1.0 / rate
        self.nh += 1
        self._maybe_emit(self.nh, self.bspec.histo)

    def add_histo_weighted(self, slot: int, value: float, weight: float):
        """Direct-weight variant for imported digest centroids (the
        global-tier re-add merge, reference samplers.go:726)."""
        self.h_slot[self.nh] = slot
        self.h_val[self.nh] = value
        self.h_wt[self.nh] = weight
        self.nh += 1
        self._maybe_emit(self.nh, self.bspec.histo)

    def add_histo_stats(self, slot: int, mn: float, mx: float,
                        recip: float):
        """Imported digest's exact min/max/reciprocalSum."""
        self.hs_slot[self.nhs] = slot
        self.hs_min[self.nhs] = mn
        self.hs_max[self.nhs] = mx
        self.hs_recip[self.nhs] = recip
        self.nhs += 1
        self._maybe_emit(self.nhs, self.bspec.histo_stat)

    # -- bulk staging (vectorized; the native engine's emit arrays are
    # split per shard and copied in slices, not per-sample Python calls) --
    def _bulk(self, dsts, srcs, n_attr: str, cap: int):
        n = len(srcs[0])
        i = 0
        while i < n:
            cur = getattr(self, n_attr)
            take = min(cap - cur, n - i)
            for dst, src in zip(dsts, srcs):
                dst[cur:cur + take] = src[i:i + take]
            setattr(self, n_attr, cur + take)
            i += take
            if getattr(self, n_attr) >= cap:
                self.emit()

    def add_counters_bulk(self, slots, incs):
        """incs already rate-weighted (the native stager applies 1/rate)."""
        self._bulk((self.c_slot, self.c_inc), (slots, incs), "nc",
                   self.bspec.counter)

    def add_gauges_bulk(self, slots, vals):
        self._bulk((self.g_slot, self.g_val), (slots, vals), "ng",
                   self.bspec.gauge)

    def add_sets_bulk(self, slots, regs, rhos):
        """(reg, rho) pre-hashed by the native engine."""
        self._bulk((self.s_slot, self.s_reg, self.s_rho),
                   (slots, regs, rhos), "ns", self.bspec.set)

    def add_histos_bulk(self, slots, vals, wts):
        self._bulk((self.h_slot, self.h_val, self.h_wt),
                   (slots, vals, wts), "nh", self.bspec.histo)

    def add_histo_stats_bulk(self, slots, mns, mxs, recips):
        """Imported-digest exact scalar stats, staged in slices (the
        native import decoder drains these per request)."""
        self._bulk((self.hs_slot, self.hs_min, self.hs_max,
                    self.hs_recip), (slots, mns, mxs, recips), "nhs",
                   self.bspec.histo_stat)

    def move_histo_stats(self, slot, mn, mx, recip) -> int:
        """Move the staged imported-digest stats into another step's
        stats lanes (arrays of this batcher's histo_stat width) and reset
        them here; returns the rows moved (NativeAggregator._emit_native
        carries them in its packed step)."""
        n = self.nhs
        slot[:n] = self.hs_slot[:n]
        mn[:n] = self.hs_min[:n]
        mx[:n] = self.hs_max[:n]
        recip[:n] = self.hs_recip[:n]
        self._clear_histo_stats()
        return n

    def _clear_histo_stats(self):
        n = self.nhs
        self.hs_slot[:n] = self.spec.histo_capacity
        self.hs_min[:n] = np.inf
        self.hs_max[:n] = -np.inf
        self.hs_recip[:n] = 0.0
        self.nhs = 0

    def pending(self) -> int:
        return (self.nc + self.ng + self.nst + self.ns + self.nh
                + self.nhs)

    def force_emit(self) -> Batch:
        """Emit unconditionally (possibly all-padding) WITHOUT notifying
        on_batch — for callers that stack per-shard batches themselves
        (server/sharded_aggregator.py)."""
        b = self.emit(notify=False)
        if b is None:
            b = Batch(
                counter_slot=self.c_slot.copy(), counter_inc=self.c_inc.copy(),
                gauge_slot=self.g_slot.copy(), gauge_val=self.g_val.copy(),
                status_slot=self.st_slot.copy(), status_val=self.st_val.copy(),
                set_slot=self.s_slot.copy(), set_reg=self.s_reg.copy(),
                set_rho=self.s_rho.copy(),
                histo_slot=self.h_slot.copy(), histo_val=self.h_val.copy(),
                histo_wt=self.h_wt.copy(),
                histo_stat_slot=self.hs_slot.copy(),
                histo_stat_min=self.hs_min.copy(),
                histo_stat_max=self.hs_max.copy(),
                histo_stat_recip=self.hs_recip.copy(),
            )
        return b

    def emit(self, notify: bool = True) -> Optional[Batch]:
        """Build a padded Batch from staged samples, reset staging, and pass
        it to on_batch (if set and notify). Returns the Batch (None if
        empty)."""
        if self.pending() == 0:
            return None
        batch = Batch(
            counter_slot=self.c_slot.copy(), counter_inc=self.c_inc.copy(),
            gauge_slot=self.g_slot.copy(), gauge_val=self.g_val.copy(),
            status_slot=self.st_slot.copy(), status_val=self.st_val.copy(),
            set_slot=self.s_slot.copy(), set_reg=self.s_reg.copy(),
            set_rho=self.s_rho.copy(),
            histo_slot=self.h_slot.copy(), histo_val=self.h_val.copy(),
            histo_wt=self.h_wt.copy(),
            histo_stat_slot=self.hs_slot.copy(),
            histo_stat_min=self.hs_min.copy(),
            histo_stat_max=self.hs_max.copy(),
            histo_stat_recip=self.hs_recip.copy(),
        )
        # reset padding sentinels for the next batch
        self.c_slot[:self.nc] = self.spec.counter_capacity
        self.g_slot[:self.ng] = self.spec.gauge_capacity
        self.st_slot[:self.nst] = self.spec.status_capacity
        self.s_slot[:self.ns] = self.spec.set_capacity
        self.h_slot[:self.nh] = self.spec.histo_capacity
        self._clear_histo_stats()
        self.c_inc[:self.nc] = 0.0
        self.h_wt[:self.nh] = 0.0
        self.nc = self.ng = self.nst = self.ns = self.nh = self.nhs = 0
        if notify and self.on_batch is not None:
            self.on_batch(batch)
        return batch
