"""Live shard-migration protocol: resize the mesh while ingest, flush,
forward, and the query tier keep running.

The protocol has four phases, each observable via /readyz's `phase`
field and the veneur.reshard.* instruments:

ANNOUNCE   the server enters the RESHARDING sub-state (ready-but-
           announcing: /readyz stays 200 so peers keep sending, but the
           machine-readable phase tells the proxy's prober and
           dashboards a move is underway).
DRAIN      one pipeline visit detaches the old interval at a flush
           boundary through the sanctioned swap-boundary helper
           (reshard/quiesce.py — the C++ rings re-learn the shard map
           inside the same quiesce, so no packed batch straddles two
           maps), then builds and installs the NEW aggregator: for
           native backends the same C++ engine is re-wrapped, so reader
           sockets, rings, and parse threads never restart. Ingest
           continues into the new mesh the moment the visit returns.
TRANSFER   a mover thread computes the drained interval's rows off the
           hot path (the same want_raw compute_flush the flush worker
           runs on detached state) and partitions them into per-
           destination-shard migration units (reshard/plan.py). Units
           replay through the pipeline queue in bounded waves
           (reshard_max_parallel_shards per visit), interleaving with
           packets, flushes, and queries. Each unit carries an
           exactly-once envelope (source_id, migration epoch, seq =
           destination shard): a crash mid-move replays the SAME seqs
           and the DedupWindow suppresses every unit that already
           folded. Rows fold through fold_snapshot — the restore path's
           merge machinery, not a duplicate.
CUTOVER    a flush that arrives mid-transfer completes the remaining
           folds synchronously on the pipeline thread before swapping
           (bounding the transition at one flush interval); otherwise
           the mover finishes and exits the announce state.

Crash matrix (what each phase loses on failure):
- announce/drain failure: nothing moved; the old aggregator keeps
  serving; failed_total increments.
- transfer fold fault: the whole epoch replays from seq 0; folded units
  return DUPLICATE and are skipped — exactly-once, no double-count.
- transfer timeout at a flush boundary: the flush proceeds with what
  has folded; the remainder of the drained interval is dropped with
  exact accounting (failed_total + log) rather than wedging the flush.
- full process crash: checkpoint restore (persistence/assembly.py)
  re-shards the newest snapshot onto whatever mesh restarts — the
  wholesale fallback this live path exists to avoid.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import List, Optional

from veneur_tpu.forward.envelope import DedupWindow, Envelope, FRESH, \
    mint_source_id
from veneur_tpu.query.snapshot import PipelineCall, PipelineRequest
from veneur_tpu.reliability.faults import FAULTS, RESHARD_FOLD
from veneur_tpu.reshard import quiesce
from veneur_tpu.reshard.plan import ReshardPlan, partition_units

log = logging.getLogger("veneur_tpu.reshard")

# replays of a faulted transfer before the move is declared failed
_MAX_REPLAYS = 3


class ReshardError(RuntimeError):
    """A resize that could not start or did not complete: feature off,
    another move in progress, invalid target shard count, or a transfer
    that failed/timed out."""


class _Transfer:
    """Shared state of one resize: the drained interval, the migration
    units, and the fold cursor. Units fold ONLY on the pipeline thread
    (via _BeginRequest-spawned PipelineCalls or the flush-boundary
    completion), so the cursor needs the lock only against the mover
    thread's progress reads."""

    def __init__(self, new_n: int, epoch: int):
        self.new_n = int(new_n)
        self.epoch = int(epoch)
        self.plan: Optional[ReshardPlan] = None
        self.lock = threading.Lock()
        self.units: List[dict] = []
        self.units_ready = threading.Event()
        self.next_i = 0
        self.replays = 0
        self.rows_folded = 0
        self.rows_moved = 0
        self.dup_suppressed = 0
        self.failed = False
        self.detail = ""
        self.done = threading.Event()
        self.t0_ns = 0
        self.duration_ns = 0
        # detached interval, held until the transfer finishes
        self.state = None
        self.table = None
        self.old_agg = None

    def fail(self, detail: str) -> None:
        with self.lock:
            self.failed = True
            self.detail = self.detail or detail

    def remaining(self) -> int:
        with self.lock:
            return max(0, len(self.units) - self.next_i)

    def summary(self) -> dict:
        return {"plan": self.plan.signature if self.plan else "",
                "epoch": self.epoch,
                "units": len(self.units),
                "rows_folded": self.rows_folded,
                "rows_moved": self.rows_moved,
                "dup_suppressed": self.dup_suppressed,
                "replays": self.replays,
                "failed": self.failed,
                "detail": self.detail,
                "duration_ns": self.duration_ns}


class _BeginRequest(PipelineRequest):
    """The DRAIN phase as one pipeline-queue visit: swap boundary,
    shard-map re-learn, aggregator rebuild, install."""

    __slots__ = ("coord", "transfer")

    def __init__(self, coord: "ReshardCoordinator", transfer: _Transfer):
        super().__init__()
        self.coord = coord
        self.transfer = transfer

    def run(self, aggregator) -> None:
        try:
            self.coord._begin_on_pipeline(self.transfer)
            self.ok = True
        except Exception as e:  # noqa: BLE001 — waiter must always wake
            self.detail = f"reshard begin failed: {e}"
            self.transfer.fail(self.detail)
        finally:
            self.done.set()


class ReshardCoordinator:
    """One per server. Public surface: resize() (any thread),
    complete_pending_folds() (pipeline thread, called by the flush
    handler), and `active` for the health phase / query stale marking."""

    def __init__(self, server, dedup_window: int = 256):
        self._server = server
        # migration units get their OWN exactly-once stream: a dedicated
        # source identity and one epoch per resize attempt, so a replay
        # after a mid-move crash re-presents the original seqs and the
        # window answers DUPLICATE (never FRESH) for anything folded
        self._source_id = mint_source_id()
        self._epoch = -1
        self.dedup = DedupWindow(dedup_window)
        self._lock = threading.Lock()
        self._transfer: Optional[_Transfer] = None
        self.moves_total = 0
        self.failed_total = 0

    @property
    def active(self) -> bool:
        t = self._transfer
        return t is not None and not t.done.is_set()

    # -- public API ----------------------------------------------------------
    def resize(self, new_n_shards: int, wait: bool = True,
               timeout_s: Optional[float] = None):
        """Resize the mesh to `new_n_shards`. With wait=True blocks until
        the transfer finished and returns its summary dict; with
        wait=False returns the live transfer handle."""
        srv = self._server
        cfg = srv.cfg
        if not getattr(cfg, "reshard_enabled", False):
            raise ReshardError("resharding is disabled "
                               "(reshard_enabled: false)")
        new_n = int(new_n_shards)
        if new_n < 1:
            raise ReshardError(f"bad target shard count {new_n}")
        if new_n > 1:
            # early capacity guard (re-checked on the pipeline thread):
            # the per-shard layout needs every capacity divisible
            from veneur_tpu.server.sharded_aggregator import per_shard_spec
            try:
                per_shard_spec(srv.aggregator.spec, new_n)
            except ValueError as e:
                raise ReshardError(str(e))
        # The cfg transfer timeout bounds individual fold waves (see
        # _run_transfer); the resize-level wait must also absorb the
        # one-off XLA compile of the new shard layout, which on a cold
        # process dwarfs the steady-state transfer.  Callers who want a
        # tight bound pass timeout_s explicitly.
        timeout = (float(timeout_s) if timeout_s is not None
                   else max(120.0, float(cfg.reshard_transfer_timeout_s)))
        with self._lock:
            if self.active:
                raise ReshardError("a reshard is already in progress")
            self._epoch += 1
            t = _Transfer(new_n, self._epoch)
            self._transfer = t
        begin = _BeginRequest(self, t)
        srv.packet_queue.put(begin)
        if not begin.wait(timeout):
            t.fail(f"drain visit timed out after {timeout:.1f}s")
            self._finalize(t)
            raise ReshardError(t.detail)
        if not begin.ok:
            self._finalize(t)
            raise ReshardError(begin.detail or "reshard begin failed")
        mover = threading.Thread(target=self._run_transfer, args=(t,),
                                 daemon=True, name="reshard-mover")
        mover.start()
        if not wait:
            return t
        if not t.done.wait(timeout):
            t.fail(f"transfer timed out after {timeout:.1f}s")
            raise ReshardError(t.detail)
        if t.failed:
            raise ReshardError(t.detail)
        return t.summary()

    def complete_pending_folds(self, aggregator,
                               timeout_s: float) -> bool:
        """Pipeline-thread hook, called by the flush handler BEFORE the
        swap: a flush that lands mid-transfer completes the remaining
        folds synchronously, so flush output always covers the whole
        drained interval and the transition is bounded at one flush
        boundary. Returns False only when the transfer had to be
        abandoned (units never became ready inside the timeout)."""
        t = self._transfer
        if t is None or t.done.is_set():
            return True
        if not t.units_ready.wait(timeout_s):
            t.fail(f"migration units not ready within {timeout_s:.1f}s "
                   "at a flush boundary; remainder dropped")
            self._finalize(t)
            return False
        self._fold_some(t, aggregator, limit=None)
        if t.remaining() == 0 or t.failed:
            self._finalize(t)
        return not t.failed

    # -- DRAIN (pipeline thread) --------------------------------------------
    def _begin_on_pipeline(self, t: _Transfer) -> None:
        srv = self._server
        old_agg = srv.aggregator
        old_n = int(getattr(old_agg, "n_shards", 1))
        if t.new_n == old_n:
            raise ReshardError(f"mesh already has {old_n} shards")
        t.plan = ReshardPlan(old_n, t.new_n)
        t.t0_ns = time.perf_counter_ns()
        log.info("reshard %s: announce (epoch=%d)", t.plan.signature,
                 t.epoch)
        # ANNOUNCE: ready-but-announcing — /readyz stays 200, phase flips
        srv._resharding = True
        ov = getattr(srv, "_overload", None)
        if ov is not None:
            ov.enter_resharding()
        try:
            # flush boundary + shard-map re-learn inside one quiesce
            state, table = quiesce.shard_map_swap(old_agg, t.new_n)
            t.state, t.table, t.old_agg = state, table, old_agg
            new_agg, native = srv._make_aggregator(
                t.new_n, engine=getattr(old_agg, "eng", None))
            # accounting continuity: processed/dropped/h2d are cumulative
            # server-lifetime counters, not per-aggregator ones
            new_agg.processed = old_agg.processed
            new_agg.dropped_capacity = old_agg.dropped_capacity
            new_agg.h2d_bytes = old_agg.h2d_bytes
            new_agg.last_set_shift = old_agg.last_set_shift
            srv.aggregator = new_agg
            srv._native = native
        except Exception:
            # nothing installed: leave the old aggregator serving and
            # exit the announce state
            srv._resharding = False
            if ov is not None:
                ov.exit_resharding()
            raise
        log.info("reshard %s: new mesh serving; transfer starting",
                 t.plan.signature)

    # -- TRANSFER (mover thread + pipeline folds) ---------------------------
    def _run_transfer(self, t: _Transfer) -> None:
        srv = self._server
        try:
            from veneur_tpu.persistence import build_snapshot
            flush_arrays, table, raw = t.old_agg.compute_flush(
                t.state, t.table, srv.cfg.percentiles, want_raw=True)
            snap = build_snapshot(
                t.old_agg.spec, table, flush_arrays, raw,
                agg_kind="sharded" if t.plan.old_n > 1 else "single",
                n_shards=t.plan.old_n, interval_ts=time.time(),
                hostname=srv.hostname)
            t.units = partition_units(snap, t.plan)
        except Exception as e:
            log.exception("reshard %s: unit build failed",
                          t.plan.signature)
            t.fail(f"unit build failed: {e}")
            t.units_ready.set()
            self._finalize(t)
            return
        t.units_ready.set()
        batch = max(1, int(getattr(srv.cfg, "reshard_max_parallel_shards",
                                   4)))
        wave_s = float(getattr(srv.cfg, "reshard_transfer_timeout_s", 10.0))
        # The budget bounds lack of PROGRESS, not total wall time: every
        # wave that folds at least one unit re-arms the clock, so the
        # one-off XLA compile of the new layout (which dwarfs wave_s on
        # a cold process) cannot fail an otherwise healthy transfer,
        # while a wedged pipeline still trips within one budget.  The
        # first wave carries the compile, so it gets a generous floor.
        deadline = time.monotonic() + max(wave_s, 120.0)
        while not t.done.is_set() and t.remaining() and not t.failed:
            if time.monotonic() > deadline:
                t.fail("transfer timed out; remainder dropped")
                break
            with t.lock:
                before = t.next_i
            call = PipelineCall(
                lambda agg, _t=t, _b=batch: self._fold_some(_t, agg, _b))
            srv.packet_queue.put(call)
            call.wait(max(0.1, deadline - time.monotonic()))
            with t.lock:
                progressed = t.next_i > before
            if progressed:
                deadline = time.monotonic() + wave_s
        self._finalize(t)

    def _fold_some(self, t: _Transfer, aggregator, limit) -> int:
        """Fold up to `limit` units (None = all) into the serving
        aggregator. Pipeline thread only. A fold fault replays the WHOLE
        epoch under the original seqs — the dedup window turns already-
        folded units into DUPLICATE skips, so replay cost is bounded and
        double-folding is impossible."""
        from veneur_tpu.persistence import fold_snapshot
        folded = 0
        while limit is None or folded < limit:
            with t.lock:
                if t.failed or t.next_i >= len(t.units):
                    break
                i = t.next_i
                t.next_i = i + 1
            u = t.units[i]
            env = Envelope(self._source_id, t.epoch, u["dest_shard"])
            verdict = self.dedup.observe(env)
            if verdict != FRESH:
                with t.lock:
                    t.dup_suppressed += 1
                folded += 1
                continue
            try:
                n = fold_snapshot(aggregator, u)
                # chaos hook: a fault HERE models the receiver dying
                # after the fold but before progress is recorded — the
                # canonical replay hazard exactly-once exists for
                FAULTS.inject(RESHARD_FOLD,
                              name=f"unit{u['dest_shard']}")
            except Exception as e:
                with t.lock:
                    t.replays += 1
                    replays = t.replays
                    t.next_i = 0   # replay the epoch from seq 0
                if replays > _MAX_REPLAYS:
                    t.fail(f"fold failed after {replays} replays: {e}")
                else:
                    log.warning("reshard %s: fold fault (%s); replaying "
                                "epoch %d (attempt %d)",
                                t.plan.signature, e, t.epoch, replays)
                break
            with t.lock:
                t.rows_folded += n
                t.rows_moved += int(u.get("rows_moved", 0))
            folded += 1
        return folded

    # -- CUTOVER -------------------------------------------------------------
    def _finalize(self, t: _Transfer) -> None:
        with t.lock:
            if t.done.is_set():
                return
            t.duration_ns = (time.perf_counter_ns() - t.t0_ns
                             if t.t0_ns else 0)
            # release the drained interval's device state
            t.state = t.table = t.old_agg = None
            t.done.set()
        srv = self._server
        srv._resharding = False
        ov = getattr(srv, "_overload", None)
        if ov is not None:
            ov.exit_resharding()
        sig = t.plan.signature if t.plan else f"->{t.new_n}"
        if t.failed:
            self.failed_total += 1
            c = getattr(srv, "_c_reshard_failed", None)
            if c is not None:
                c.inc()
            log.warning("reshard %s FAILED: %s", sig, t.detail)
        else:
            self.moves_total += 1
            c = getattr(srv, "_c_reshard_moves", None)
            if c is not None:
                c.inc()
            log.info("reshard %s complete: %d units, %d rows folded "
                     "(%d moved owner), %.1f ms", sig, len(t.units),
                     t.rows_folded, t.rows_moved, t.duration_ns / 1e6)
        rc = getattr(srv, "_c_reshard_rows_moved", None)
        if rc is not None and t.rows_moved:
            rc.inc(t.rows_moved)
        tm = getattr(srv, "_t_reshard", None)
        if tm is not None and t.duration_ns:
            tm.observe(t.duration_ns)
