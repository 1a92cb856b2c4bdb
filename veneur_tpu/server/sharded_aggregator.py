"""Multi-device aggregation backend: the key table sharded over a device
mesh (veneur_tpu/parallel/sharded.py) behind the same Aggregator interface
the Server uses.

The key space splits across `n_shards` mesh tiles by the reference's
`Digest % numWorkers` rule (host.py assigns slot = shard*per_shard+idx, so
the GLOBAL slot flattening of per-shard flush arrays lines up with the
KeyTable's slot numbers by construction). Each shard has its own staging
Batcher; batches emit for ALL shards together (stacked [1, S, ...]) so one
sharded ingest program serves every step, with each tile's scatters local
to its device.

Config: tpu_n_shards > 1 (or 0 = one shard per local device when several
devices are present). The native C++ engine feeds this backend through
native_aggregator.NativeShardedAggregator, which splits the engine's
staged lanes by owner shard into these per-shard Batchers.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np

from veneur_tpu.aggregation.host import Batcher, BatchSpec, KeyTable
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.aggregation.step import batch_sizes, pack_batch
from veneur_tpu.observability import hostspans
from veneur_tpu.server.aggregator import Aggregator, set_member_bytes


def per_shard_spec(spec: TableSpec, n_shards: int) -> TableSpec:
    import dataclasses
    for field in ("counter_capacity", "gauge_capacity", "status_capacity",
                  "set_capacity", "histo_capacity"):
        cap = getattr(spec, field)
        if cap % n_shards or cap < n_shards:
            raise ValueError(
                f"tpu_{field} ({cap}) must be a positive multiple of "
                f"tpu_n_shards ({n_shards})")
    return dataclasses.replace(
        spec,
        counter_capacity=spec.counter_capacity // n_shards,
        gauge_capacity=spec.gauge_capacity // n_shards,
        status_capacity=spec.status_capacity // n_shards,
        set_capacity=spec.set_capacity // n_shards,
        histo_capacity=spec.histo_capacity // n_shards)


def _gather_sharded_impl(out, cidx, gidx, stidx, setidx, hidx):
    """Live-row gather over the merged flush's [S, K_per] dense arrays
    (global KeyTable slots are flat indices by construction), packed into
    one flat f32 array — one device->host transfer per flush, same as
    the single-device flush_live_in_packed."""
    import jax.numpy as jnp
    which = {"counter_hi": cidx, "counter_lo": cidx, "gauge": gidx,
             "status": stidx, "set_estimate": setidx}

    def take(key, a):
        flat = a.reshape((-1,) + a.shape[2:])
        return jnp.take(flat, which.get(key, hidx), axis=0, mode="clip")

    return jnp.concatenate([take(k, out[k]).reshape(-1).astype(jnp.float32)
                            for k in sorted(out)])


def _gather_sharded_raw_impl(st, setidx, hidx, *, cells: int):
    """Raw sketch state of live rows, packed like the flush gather (one
    transfer; 6-bit packed i32 HLL rows ride as bitcast f32 words — safe
    for the same run-of-set-bits reason as step._pack_outputs). A digest
    row leaves with its `cells` = TableSpec.total_cells columns, without
    the stored row's pad."""
    import jax
    import jax.numpy as jnp

    def take(x, i):
        flat = x.reshape((-1,) + x.shape[3:])   # drop [R=1, S]
        return jnp.take(flat, i, axis=0, mode="clip")

    w = take(st.h_w, hidx)[:, :cells]
    out = {
        "hll": take(st.hll, setidx),
        "h_weight": w,
        "h_mean": take(st.h_wm, hidx)[:, :cells] / jnp.maximum(w, 1e-30),
        "h_min": take(st.h_min, hidx),
        "h_max": take(st.h_max, hidx),
        "recip_hi": take(st.h_recip_hi, hidx),
        "recip_lo": take(st.h_recip_lo, hidx) + take(st.h_recip_acc, hidx),
    }
    parts = []
    for k in sorted(out):
        a = out[k]
        if a.dtype == jnp.uint8:
            a = jax.lax.bitcast_convert_type(a.reshape((-1, 4)),
                                             jnp.float32)
        elif a.dtype == jnp.int32:
            a = jax.lax.bitcast_convert_type(a, jnp.float32)
        parts.append(a.reshape(-1).astype(jnp.float32))
    return jnp.concatenate(parts)


def _sharded_raw_shapes(pspec, n_set, n_h):
    cells = pspec.centroids + pspec.temp_cells
    f32 = "float32"
    return {"hll": ((n_set, pspec.hll_words), "int32"),
            "h_weight": ((n_h, cells), f32), "h_mean": ((n_h, cells), f32),
            "h_min": ((n_h,), f32), "h_max": ((n_h,), f32),
            "recip_hi": ((n_h,), f32), "recip_lo": ((n_h,), f32)}


import jax as _jax

_gather_sharded = _jax.jit(_gather_sharded_impl)
_gather_sharded_raw = _jax.jit(_gather_sharded_raw_impl,
                               static_argnames=("cells",))


class ShardedAggregator(Aggregator):
    # replica rows of the mesh: one here; the collective tier
    # (collective/tier.py), which is this backend over a mesh with a real
    # replica axis, sets its own before it runs this constructor
    n_replicas = 1

    def __init__(self, spec: TableSpec, bspec: BatchSpec = BatchSpec(),
                 n_shards: int = 2, compact_every: int = 8):
        from veneur_tpu.parallel import (
            make_mesh, make_merged_flush, make_sharded_ingest_packed,
            sharded_empty_state)

        self.spec = spec            # total capacities (KeyTable slot space)
        self.pspec = per_shard_spec(spec, n_shards)
        self.bspec = bspec
        self.n_shards = n_shards
        self.compact_every = compact_every

        self.mesh = make_mesh(self.n_replicas, n_shards)
        # packed ingest: each tile's batch ships as one i32 buffer with
        # the compact word in-band — mirrors the single-device backend
        # (one executable, one transfer per step per tile)
        self._sizes = batch_sizes(Batcher(self.pspec, bspec).force_emit())
        self._ingest = make_sharded_ingest_packed(self.mesh, self.pspec,
                                                  self._sizes)
        self._flush = make_merged_flush(self.mesh, self.pspec)
        self._empty = partial(sharded_empty_state, self.pspec,
                              self.n_replicas, n_shards, self.mesh)
        self.state = self._empty()
        self.table = KeyTable(spec, n_shards)
        # direct traffic (process_metric / import_metric / restore)
        # stages into replica row 0
        self.batchers = self._make_batchers()
        # (its _hll_slots hold (shard, local_slot) pairs in this backend)
        self._init_step_site()
        # how full the mesh's steps go out, added up on the pipeline
        # thread: the rows the dispatched tiles hold in the four lanes
        # the native engine stages (every tile's, padding included), and
        # the steps one shard's full lane dispatched with the other
        # tiles as they stood (_on_shard_batch)
        self._lane_rows = n_shards * (bspec.counter + bspec.gauge
                                      + bspec.set + bspec.histo)
        self.shard_lane_rows_dispatched = 0
        self.shard_forced_emits = 0

    # -- slot routing --------------------------------------------------------
    def _local(self, kind: str, slot: int) -> Tuple[int, int]:
        """global slot -> (shard, local slot); per-kind shard width."""
        per = self.table.tables[KeyTable._table_name(kind)].per_shard
        return slot // per, slot % per

    def process_metric(self, m) -> None:
        kind = m.type
        slot = self.table.slot_for(kind, m.name, m.tags, m.scope, m.digest,
                                   hostname=m.hostname,
                                   joined_tags=m.joined_tags)
        if slot is None:
            self.dropped_capacity += 1
            return
        if kind in ("histogram", "timer"):
            self.table.sampled_directly(kind, slot)
        shard, local = self._local(kind, slot)
        b = self.batchers[shard]
        if kind == "counter":
            b.add_counter(local, float(m.value), m.sample_rate)
        elif kind == "gauge":
            b.add_gauge(local, float(m.value))
        elif kind == "status":
            b.add_status(local, float(m.value))
            mt = self.table.meta_for_slot("status", slot)
            if mt is not None:
                mt.message = m.message
        elif kind == "set":
            member = set_member_bytes(m.value)
            if self._set_admit(member):
                b.add_set(local, member)
        elif kind in ("histogram", "timer"):
            # self-metric timers exempt from degraded sampling (see the
            # base Aggregator.process_metric rationale)
            if m.name.startswith("veneur."):
                rate = m.sample_rate
            else:
                rate = self._histo_admit(m.sample_rate)
            if rate is not None:
                b.add_histo(local, float(m.value), rate)
        self.processed += 1

    def import_metric(self, kind: str, name: str, tags: tuple, scope: int,
                      digest: int, payload: dict) -> None:
        slot = self.table.slot_for(kind, name, tags, scope, digest,
                                   imported=True)
        if slot is None:
            self.dropped_capacity += 1
            return
        shard, local = self._local(kind, slot)
        b = self.batchers[shard]
        if kind == "counter":
            b.add_counter(local, float(payload["value"]), 1.0)
        elif kind == "gauge":
            b.add_gauge(local, float(payload["value"]))
        elif kind == "set":
            regs = payload["registers"]
            if regs.shape[0] != self.pspec.registers:
                raise ValueError("imported HLL register-count mismatch")
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

    # -- checkpoint restore (hooks into Aggregator.restore_metric) ----------
    def _restore_lane(self, kind: str, slot: int):
        shard, local = self._local(kind, slot)
        return self.batchers[shard], local

    def _restore_hll(self, slot: int, regs) -> None:
        # staged as (shard, local) for _apply_hll_imports, same as the
        # sharded import path; drained by _restore_drain_hll / swap
        self._hll_slots.append(self._local("set", slot))
        self._hll_rows.append(regs)

    def _restore_emit(self) -> None:
        self._emit_all()

    def _restore_drain_hll(self) -> None:
        self._apply_hll_imports()

    # -- device steps --------------------------------------------------------
    def _make_batchers(self):
        """One staging Batcher per shard; when ANY shard's lane fills, every
        shard emits (padded) so the stacked [1, S] batch stays rectangular
        and one compiled program serves every step."""
        return [Batcher(self.pspec, self.bspec,
                        on_batch=partial(self._on_shard_batch, i))
                for i in range(self.n_shards)]

    def _new_row(self):
        """One [R, S, W] host buffer, every tile an all-padding packed
        batch. A step rewrites the tiles of replica row 0; rows 1..R-1
        of the collective tier's mesh stay padding (absorbed traffic
        reaches them through its routed path instead)."""
        pad = pack_batch(Batcher(self.pspec, self.bspec).force_emit())
        return np.broadcast_to(
            pad, (self.n_replicas, self.n_shards) + pad.shape).copy()

    def _dispatch_row(self, row, force_compact: bool = False):
        """Pack each shard's batch straight into its tile of a persistent
        [R, S, W] buffer (pack_batch `out`: no per-step allocation, no
        np.stack pass) and run the fused mesh step; compaction rides the
        in-band control word at the same cadence as the single-device
        backend (Aggregator._on_batch). Under the `pipeline.shard_pack`
        span, whose child is the step's `pipeline.dispatch`."""
        with hostspans.span("pipeline.shard_pack"):
            dc = self._count_step(force_compact)
            flat = self._step_buffer("row", self._new_row)
            for i, b in enumerate(row):
                pack_batch(b, dc, out=flat[0, i])
            self.shard_lane_rows_dispatched += self._lane_rows
            self._dispatch_step(self._ingest, flat, "row")

    def _on_shard_batch(self, shard: int, batch):
        """One shard's lane filled: every other tile goes out as it
        stands, partly full or empty."""
        if self.n_shards > 1:
            self.shard_forced_emits += 1
        self._dispatch_row([batch if i == shard else b.force_emit()
                            for i, b in enumerate(self.batchers)])

    def _emit_all(self):
        if not any(b.pending() for b in self.batchers):
            return
        self._dispatch_row([b.force_emit() for b in self.batchers])

    def _apply_hll_imports(self):
        """Imported HLL rows merge on-device (rare path: only a global
        tier with sharded state receives these). Runs on the pipeline
        thread out of swap(), so it must not materialize the
        [1, S, K, W] table on host — that blocks behind every queued
        ingest step. With the 6-bit packed resident layout the update is
        gather packed words -> unpack -> register max -> repack ->
        scatter-set; duplicate (shard, local) targets are folded on the
        host first (np.maximum.at — register max is order-free) because
        a scatter-SET with duplicate targets is ill-defined, unlike the
        old dense register scatter-max."""
        if not self._hll_slots:
            return
        import jax
        import jax.numpy as jnp
        from veneur_tpu.ops.hll import pack_registers, unpack_registers
        from veneur_tpu.parallel.sharded import state_sharding

        sh = np.array([s for s, _ in self._hll_slots], np.int64)
        loc = np.array([l for _, l in self._hll_slots], np.int64)
        rows = np.stack(self._hll_rows).astype(np.uint8)
        key = sh * (self.pspec.set_capacity + 1) + loc
        uniq, inv = np.unique(key, return_inverse=True)
        folded = np.zeros((len(uniq), rows.shape[1]), np.uint8)
        np.maximum.at(folded, inv, rows)
        sh_u = jnp.asarray((uniq // (self.pspec.set_capacity + 1))
                           .astype(np.int32))
        loc_u = jnp.asarray((uniq % (self.pspec.set_capacity + 1))
                            .astype(np.int32))
        p = self.pspec.hll_precision
        cur = unpack_registers(self.state.hll[0, sh_u, loc_u], precision=p)
        merged = pack_registers(jnp.maximum(cur, jnp.asarray(folded)),
                                precision=p)
        hll = self.state.hll.at[0, sh_u, loc_u].set(merged, mode="drop")
        self.state = self.state._replace(
            hll=jax.device_put(hll, state_sharding(self.mesh)))
        self._hll_slots, self._hll_rows = [], []

    # -- flush ---------------------------------------------------------------
    def swap(self):
        with hostspans.span("swap.emit_staged"):
            self._emit_all()
            self._apply_hll_imports()
        self._await_steps()
        with hostspans.span("swap.reset"):
            state, table = self.state, self.table
            self.state = self._empty()
            self.table = KeyTable(self.spec, self.n_shards)
            if self._pressure is not None:
                self._pressure.attach(self.table)
            self.batchers = self._make_batchers()
            self._steps = 0
            self._latch_degrade()
        return state, table

    # -- query tier ---------------------------------------------------------
    def query_snapshot(self):
        """Pipeline-thread-only live-interval snapshot (see
        Aggregator.query_snapshot): drain every shard's staging batcher
        and the packed-HLL import queue, then capture references."""
        self._emit_all()
        self._apply_hll_imports()
        return self.state, self.table, self.active_set_shift

    def query_flat_state(self, state):
        """[R=1, S, rows, ...] -> flat [S*rows, ...] views (free
        reshapes, no copy): the KeyTable's global slot numbers ARE flat
        indices into the shard-major layout by construction (slot =
        shard * per_shard + local), so a query gather addresses — and
        moves — only the owner shard's rows."""
        import jax
        return jax.tree.map(lambda x: x.reshape((-1,) + x.shape[3:]),
                            state)

    def compute_flush(self, state, table, percentiles,
                      want_raw: bool = False, history=None):
        import jax.numpy as jnp

        from veneur_tpu.aggregation.step import (
            combine_flush_scalars, flush_live_shapes, live_indices,
            unpack_flush)

        qs = jnp.asarray(percentiles or [0.5], jnp.float32)
        # live-slot gather AFTER the merged flush (same O(live) host
        # boundary as the single-device flush_live): the KeyTable's
        # global slot numbers ARE flat indices into the [S, K_per]
        # reshape by construction (slot = shard * per_shard + local)
        with hostspans.span("flush_plan"):
            idx = {kind: jnp.asarray(live_indices(table, kind, cap))
                   for kind, cap in (
                       ("counter", self.spec.counter_capacity),
                       ("gauge", self.spec.gauge_capacity),
                       ("status", self.spec.status_capacity),
                       ("set", self.spec.set_capacity),
                       ("histogram", self.spec.histo_capacity))}
        # the merged flush is one program over every shard: one block
        self._count_flush(1, sum(len(table.columns(k)) for k in idx))

        with hostspans.span("flush_dispatch"):
            gathered = _gather_sharded(
                self._flush(state, qs), idx["counter"], idx["gauge"],
                idx["status"], idx["set"], idx["histogram"])
        # the host's wait for the flush and gather programs, queued on
        # the device behind the ingest steps dispatched since the swap,
        # plus the transfer
        with hostspans.span("flush_d2h"):
            packed = np.asarray(gathered)
        out = unpack_flush(packed, flush_live_shapes(
            self.pspec, len(idx["counter"]), len(idx["gauge"]),
            len(idx["status"]), len(idx["set"]), len(idx["histogram"]),
            len(qs)))
        result = combine_flush_scalars(out)
        if want_raw or history is not None:
            from veneur_tpu.aggregation.step import unpack_flush as _unpack
            with hostspans.span("flush_dispatch"):
                gathered = _gather_sharded_raw(
                    state, idx["set"], idx["histogram"],
                    cells=self.pspec.total_cells)
            with hostspans.span("flush_d2h"):
                r = _unpack(np.asarray(gathered), _sharded_raw_shapes(
                    self.pspec, len(idx["set"]), len(idx["histogram"])))
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
                # Host-fed ring write: the sharded flush already
                # materializes result+raw, so the same frame the
                # forwarder/archive sees feeds the standalone
                # write_window jit — byte-identical window bytes to the
                # single-device fused path by construction.
                history.record_frame(table, result, raw)
            if want_raw:
                return result, table, raw
        return result, table
