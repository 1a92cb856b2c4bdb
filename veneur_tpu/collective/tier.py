"""CollectiveGlobalTier: the global aggregation tier as a mesh resident.

A ShardedAggregator whose mesh carries a real replica axis: co-located
local tiers hand their flush's raw sketch arrays straight to
`absorb_raw` (zero serialization — no protobuf, no gRPC, no wire
bytes), rows are staged into per-(replica row, source column, OWNER
shard) buckets using the hash-routed CollectiveKeyTable, and one
on-device `all_to_all` inside shard_map delivers every bucket to its
owner tile where the ordinary ingest scatter applies it
(collective/router.py). Flush time replica-merges the mesh with the
same named-axis sketch collectives the sharded backend uses
(collective/ops.py) — the 64-process gRPC merge becomes one collective
program over ICI.

The envelope/gRPC forward path stays authoritative for cross-host (DCN)
peers: a local tier with a dialed forward client keeps using it;
`collective_attach` only short-circuits the co-located case.

Participant rows spread over replica rows round-robin (participant p ->
replica p % R, staging column (p // R) % S), so N locals' absorbs
parallelize over the replica axis instead of serializing into row 0.
Absorb payloads are EXACTLY what the wire path would deliver —
iter_forwardable (forward/convert.py) is shared with export_metrics —
with one documented exception: HLL rows skip the axiomhq nibble
serialization, so where that format's tailcut would saturate a register
spread > 15 the absorbed union is lossless (strictly more accurate, and
byte-identical whenever the spread fits, i.e. in practice).
"""

from __future__ import annotations

import threading
import time
from functools import partial
from typing import Dict, Optional

import numpy as np

from veneur_tpu.aggregation.host import Batcher, BatchSpec
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.aggregation.step import Batch
from veneur_tpu.collective.keytable import CollectiveKeyTable
from veneur_tpu.observability import jaxruntime
from veneur_tpu.observability.registry import Timer
from veneur_tpu.server.sharded_aggregator import ShardedAggregator

# -- process-local tier registry -------------------------------------------
# Co-located servers living in one process (the deployment shape the
# collective tier exists for) find each other here; lookup by group name
# at flush time so start order does not matter.
_REGISTRY: Dict[str, "CollectiveGlobalTier"] = {}
_REGISTRY_LOCK = threading.Lock()


def register(group: str, tier: "CollectiveGlobalTier") -> None:
    with _REGISTRY_LOCK:
        _REGISTRY[group] = tier


def lookup(group: str) -> Optional["CollectiveGlobalTier"]:
    with _REGISTRY_LOCK:
        return _REGISTRY.get(group)


def unregister(group: str, tier: "CollectiveGlobalTier") -> None:
    with _REGISTRY_LOCK:
        if _REGISTRY.get(group) is tier:
            del _REGISTRY[group]


class CollectiveGlobalTier(ShardedAggregator):
    def __init__(self, spec: TableSpec, bspec: BatchSpec = BatchSpec(),
                 n_shards: int = 2, n_replicas: int = 1,
                 compact_every: int = 8):
        from veneur_tpu.collective.router import (
            make_merged_state, make_routed_ingest, shard_axis_is_physical)

        self.n_replicas = max(1, int(n_replicas))
        super().__init__(spec, bspec, n_shards, compact_every)
        self._merge = make_merged_state(self.mesh, self.pspec)
        # the collective tier routes by key identity
        self.table = CollectiveKeyTable(spec, n_shards)
        # absorb staging: one Batcher per (replica row, source column,
        # owner shard); the routed all_to_all delivers buckets to owners
        self._route_device = shard_axis_is_physical(self.mesh, n_shards)
        self._routed = (make_routed_ingest(self.mesh, self.pspec)
                        if self._route_device else None)
        self._stage_grid = self._make_stage_grid()
        self._absorb_lock = threading.Lock()
        self._next_participant = 0
        self._routed_steps = 0
        self.absorbed_rows = 0
        # always-on phase timers: a private Timer instance until a host
        # server injects its registry-owned one (set_phase_timer), so
        # phase durations accumulate with or without a Server around.
        # Phases: stage (absorb_raw host staging), all_to_all_route
        # (routed dispatch), replica_merge / flush (compute_flush).
        self._phase_timer = Timer(
            "veneur.collective.phase_duration_ns",
            help="collective tier phase wall time by phase (ns)",
            labelnames=("phase",))
        # cross-tier tracing: the last absorb's (trace_id, span_id) so
        # compute_flush's replica_merge span parents onto it, closing
        # the local->global span tree; the trace client rides along.
        self._last_absorb = None
        self._trace_client = None

    def set_phase_timer(self, timer) -> None:
        """Adopt a registry-owned phase-duration Timer (the host Server
        registers `veneur.collective.phase_duration_ns` and injects it
        here so phase observations reach its /metrics exposition)."""
        self._phase_timer = timer

    # -- absorb staging ------------------------------------------------------
    def _make_stage_grid(self):
        if not self._route_device:
            return None
        grid = []
        for r in range(self.n_replicas):
            row = []
            for j in range(self.n_shards):
                row.append([Batcher(self.pspec, self.bspec,
                                    on_batch=partial(self._on_stage_batch,
                                                     r, j, d))
                            for d in range(self.n_shards)])
            grid.append(row)
        return grid

    def _on_stage_batch(self, r: int, j: int, d: int, batch: Batch):
        """A stage bucket filled mid-absorb: emit the whole grid (the
        routed program is rectangular) with the filled bucket's batch in
        place, everyone else force-emitted — the _on_shard_batch pattern
        one level up."""
        self._dispatch_routed(
            lambda rr, jj, dd: batch if (rr, jj, dd) == (r, j, d)
            else self._stage_grid[rr][jj][dd].force_emit())

    def _dispatch_routed(self, get):
        """One routed step over the whole stage grid. The one step site
        outside _dispatch_step and its bound on steps in flight: the
        routed program returns the donated state and nothing else, so
        there is nothing to wait on for one step without another device
        op, and its batch is built anew each time, so no host buffer is
        reused under it. It shares the sampled sync."""
        nested = []
        for r in range(self.n_replicas):
            row = []
            for j in range(self.n_shards):
                dest = [get(r, j, d) for d in range(self.n_shards)]
                cols = list(zip(*dest))
                row.append(Batch(*[None if all(x is None for x in col)
                                   else np.stack(col) for col in cols]))
            nested.append(row)
        from veneur_tpu.parallel import stack_batches
        batch = stack_batches(nested, self.n_replicas, self.n_shards)
        self.h2d_bytes += sum(a.nbytes for a in batch if a is not None)
        t0 = time.perf_counter_ns()
        self.state = self._routed(self.state, batch)
        dispatch_dt = time.perf_counter_ns() - t0
        self.dispatch_ns += dispatch_dt
        self._phase_timer.observe(dispatch_dt, phase="all_to_all_route")
        self.steps_total += 1
        self._sampled_sync(dispatch_dt)
        # absorbed digest rows land in temp cells like any other ingest;
        # ride the packed program's in-band compact word at the same
        # cadence as direct traffic so they recompress
        self._routed_steps += 1
        if self._routed_steps % self.compact_every == 0:
            self._dispatch_row([b.force_emit() for b in self.batchers],
                               force_compact=True)

    def _emit_absorbed(self):
        if self._stage_grid is None:
            return
        if not any(b.pending() for row in self._stage_grid
                   for cell in row for b in cell):
            return
        self._dispatch_routed(
            lambda r, j, d: self._stage_grid[r][j][d].force_emit())

    # -- zero-serialization absorb -------------------------------------------
    def assign_participant(self) -> int:
        """Claim a stable participant id (-> replica row / staging
        column) for a co-located local tier."""
        with self._absorb_lock:
            p = self._next_participant
            self._next_participant += 1
            return p

    def absorb_raw(self, raw, table, participant: Optional[int] = None,
                   parent_span=None, trace_client=None) -> int:
        """Fold a co-located local tier's flush output (raw arrays + its
        detached KeyTable) into the collective state. Returns the number
        of rows absorbed. Thread-safe against concurrent absorbs and the
        tier's own swap. With parent_span (the local's flush.forward
        span), emits a collective.absorb child span carrying rows/bytes
        tags — the same tree shape the wire path's import span produces
        — and remembers its ids so compute_flush's replica_merge span
        parents onto this absorb."""
        from veneur_tpu.forward.convert import iter_forwardable
        span = None
        if parent_span is not None:
            span = parent_span.child("collective.absorb")
            span.set_tag("transport", "colocated")
        with self._absorb_lock:
            if participant is None:
                participant = self._next_participant
                self._next_participant += 1
            r = participant % self.n_replicas
            j = (participant // self.n_replicas) % self.n_shards
            n = 0
            t0 = time.perf_counter_ns()
            for kind, meta, scope, payload in iter_forwardable(
                    raw, table, self.spec.hll_precision):
                self._absorb_one(r, j, kind, meta, scope, payload)
                n += 1
            self._phase_timer.observe(time.perf_counter_ns() - t0,
                                      phase="stage")
            self.absorbed_rows += n
            if span is not None:
                span.set_tag("rows", str(n))
                try:
                    span.set_tag("bytes", str(sum(
                        a.nbytes for a in raw.values()
                        if hasattr(a, "nbytes"))))
                except AttributeError:
                    pass
                self._last_absorb = (span.trace_id, span.id)
                self._trace_client = trace_client
                span.client_finish(trace_client)
            return n

    def _absorb_one(self, r: int, j: int, kind: str, meta, scope: int,
                    payload: dict) -> None:
        slot = self.table.slot_for_routed(
            kind, meta.name, meta.tags, scope, hostname=meta.hostname,
            imported=True, joined_tags=meta.joined_tags)
        if slot is None:
            self.dropped_capacity += 1
            return
        shard, local = self._local(kind, slot)
        if self._stage_grid is not None:
            b = self._stage_grid[r][j][shard]
        else:
            # collapsed fallback mesh: owner-bucket on the host straight
            # into the direct batchers (semantically identical delivery)
            b = self.batchers[shard]
        if kind == "counter":
            b.add_counter(local, float(payload["value"]), 1.0)
        elif kind == "gauge":
            b.add_gauge(local, float(payload["value"]))
        elif kind == "set":
            # imported register rows can't ride the Batch member lanes;
            # they merge through the established (shard, local) host
            # fold -> on-device register max (order-free), replica row 0
            regs = payload["registers"]
            if regs.shape[0] != self.pspec.registers:
                raise ValueError("absorbed HLL register-count mismatch")
            self._hll_slots.append((shard, local))
            self._hll_rows.append(regs)
        elif kind in ("histogram", "timer"):
            means = np.asarray(payload["means"], np.float32)
            weights = np.asarray(payload["weights"], np.float32)
            live = weights > 0
            means, weights = means[live], weights[live]
            b.add_histos_bulk(np.full(len(means), local, np.int32),
                              means, weights)
            recip = payload.get("recip")
            recip_corr = 0.0
            if recip is not None and np.all(means != 0.0):
                recip_corr = float(recip) - float(np.sum(weights / means))
            b.add_histo_stats(local, float(payload.get("min", np.inf)),
                              float(payload.get("max", -np.inf)),
                              recip_corr)
        self.processed += 1

    # -- flush ---------------------------------------------------------------
    def swap(self):
        with self._absorb_lock:
            self._emit_absorbed()
            if self._routed_steps and not self._steps:
                # absorb-only interval: the inherited swap's boundary
                # sync keys off _steps, which routed dispatch bypasses
                self.step_ns += jaxruntime.sync_and_time(self.state)
                self.steps_synced += 1
            state, table = super().swap()
            # super() installed a plain KeyTable; the collective tier
            # routes by key identity
            self.table = CollectiveKeyTable(self.spec, self.n_shards)
            self._stage_grid = self._make_stage_grid()
            self._routed_steps = 0
            return state, table

    # -- query tier ---------------------------------------------------------
    def query_snapshot(self):
        """Absorb-staged routed rows are part of 'admitted before the
        snapshot' too: fold them under the absorb lock (the same mutual
        exclusion swap() takes against forwarding threads), then
        snapshot as a sharded backend."""
        with self._absorb_lock:
            self._emit_absorbed()
            return super().query_snapshot()

    def query_flat_state(self, state):
        """R > 1: replica-merge the mesh first (the flush's own ICI
        collectives — register max for HLL, the mergeable reductions
        elsewhere) so reads see the mesh-global sketches, then flatten
        the shard axis like the sharded backend."""
        if self.n_replicas == 1:
            return super().query_flat_state(state)
        import jax
        merged = self._merge(state)
        return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]),
                            merged)

    def compute_flush(self, state, table, percentiles,
                      want_raw: bool = False, history=None):
        t_flush = time.perf_counter_ns()
        try:
            return self._compute_flush_timed(state, table, percentiles,
                                             want_raw, history)
        finally:
            # implicitly synced: every return path host-materializes the
            # flush arrays (np.asarray), so this is true wall time
            # vtlint: disable=timer-sync -- callee's np.asarray is the sync
            self._phase_timer.observe(time.perf_counter_ns() - t_flush,
                                      phase="flush")

    def _compute_flush_timed(self, state, table, percentiles,
                             want_raw: bool = False, history=None):
        # the replica_merge span parents onto the most recent co-located
        # absorb and is emitted on EVERY flush path — on the plain path
        # the merge collectives run inside the compiled flush itself, so
        # the span covers the whole compute; either way the cross-tier
        # trace stays connected (local forward -> absorb -> merge)
        from veneur_tpu.trace.tracer import Span
        mspan = None
        if self._last_absorb is not None:
            tid, sid = self._last_absorb
            mspan = Span("collective.replica_merge", service="veneur",
                         trace_id=tid, parent_id=sid)
            mspan.set_tag("replicas", str(self.n_replicas))
        try:
            return self._compute_flush_inner(state, table, percentiles,
                                             want_raw, history)
        finally:
            if mspan is not None:
                mspan.client_finish(self._trace_client)
                self._last_absorb = None

    def _compute_flush_inner(self, state, table, percentiles,
                             want_raw: bool = False, history=None):
        if self.n_replicas == 1 or (not want_raw and history is None):
            # R == 1: the inherited raw gather reads the state verbatim,
            # byte-identical to the sharded backend by construction
            return super().compute_flush(state, table, percentiles,
                                         want_raw, history=history)
        import jax
        import jax.numpy as jnp
        from veneur_tpu.aggregation.step import live_indices, unpack_flush
        from veneur_tpu.server.sharded_aggregator import (
            _gather_sharded_raw, _sharded_raw_shapes)
        # R > 1: replica-merge the mesh first (same collectives as the
        # flush), then reuse the [1, S] raw gather on the merged state
        result, table = super().compute_flush(state, table, percentiles)
        setidx = jnp.asarray(
            live_indices(table, "set", self.spec.set_capacity))
        hidx = jnp.asarray(
            live_indices(table, "histogram", self.spec.histo_capacity))
        t0 = time.perf_counter_ns()
        merged = jax.tree.map(lambda x: x[None], self._merge(state))
        jaxruntime.sync_and_time(merged)
        merge_synced_dt = time.perf_counter_ns() - t0
        self._phase_timer.observe(merge_synced_dt, phase="replica_merge")
        r = unpack_flush(
            np.asarray(_gather_sharded_raw(
                merged, setidx, hidx, cells=self.pspec.total_cells)),
            _sharded_raw_shapes(self.pspec, len(setidx), len(hidx)))
        raw = {
            "counter": result["counter"],
            "gauge": result["gauge"],
            "hll": r["hll"],
            "h_mean": r["h_mean"],
            "h_weight": r["h_weight"],
            "h_min": r["h_min"],
            "h_max": r["h_max"],
            "h_recip": r["recip_hi"].astype(np.float64) + r["recip_lo"],
        }
        if history is not None:
            # replica-merged raw is the mesh-global frame — the one the
            # archive keeps — so the ring stores the same bytes a replay
            # of those frames would
            history.record_frame(table, result, raw)
        if want_raw:
            return result, table, raw
        return result, table
