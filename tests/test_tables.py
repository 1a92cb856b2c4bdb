"""Self-adjusting key tables (veneur_tpu/tables/, ISSUE 20): grow
planning and the swap-boundary grow on both backends, the pressure
ladder's exact accounting (demotion, SALSA merge cells, TTL eviction),
cross-capacity snapshot folds in both directions, query value-exactness
across a grow, shard-assignment stability of the C++ preshard emit
across a grow, and the rings_inject backpressure verdict pin."""

import time
from types import SimpleNamespace

import numpy as np
import pytest

from veneur_tpu.aggregation.host import BatchSpec, SCOPE_GLOBAL
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.server.aggregator import Aggregator
from veneur_tpu.tables import (TableManager, TablePressure,
                               adopt_capacities, grow_swap, grown_spec)
from veneur_tpu.tables.growth import spec_capacities
from veneur_tpu.tables.pressure import MERGE_CELL_NAME, ROLLUP_TAG
from veneur_tpu.utils.hashing import fnv1a_32

# same shapes as test_collective.py so the jit cache is shared in-process
SPEC = TableSpec(counter_capacity=64, gauge_capacity=32,
                 status_capacity=8, set_capacity=16, histo_capacity=32)
BSPEC = BatchSpec(counter=256, gauge=32, status=8, set=64, histo=512,
                  histo_stat=32)


def pm(agg, kind, name, value, scope=SCOPE_GLOBAL, tags=(), rate=1.0):
    m = SimpleNamespace(type=kind, name=name, tags=tuple(tags),
                        scope=scope, digest=fnv1a_32(name.encode()),
                        value=value, sample_rate=rate, hostname="",
                        message="", joined_tags=",".join(tags))
    agg.process_metric(m)


def counter_meta(table):
    """(slot, SlotMeta) pairs of a detached table's counter kind —
    Python KeyTable or a native interval's keys alike."""
    return list(table.get_meta("counter"))


def counter_values(state, table):
    """(name, joined_tags) -> folded counter value of a detached
    interval (acc + hi + lo compensated lanes, flat slot index)."""
    acc = (np.asarray(state.counter_acc).reshape(-1)
           + np.asarray(state.counter_hi).reshape(-1)
           + np.asarray(state.counter_lo).reshape(-1))
    return {(m.name, m.joined_tags): float(acc[slot])
            for slot, m in counter_meta(table)}


class _Srv:
    """The minimal server surface grow_swap/adopt_capacities touch."""

    def __init__(self, agg):
        self.aggregator = agg
        self._native = False

    def _make_aggregator(self, n_shards, engine=None, spec=None):
        return Aggregator(spec, BSPEC), False


# -- planning (TableManager) -------------------------------------------------

def test_grown_spec_changes_only_named_kinds():
    spec2 = grown_spec(SPEC, {"counter": 128})
    assert spec_capacities(spec2) == {"counter": 128, "gauge": 32,
                                      "set": 16, "histo": 32, "status": 8}
    assert grown_spec(SPEC, {"counter": 64}) is SPEC   # no-op is identity


def test_manager_plans_doubling_until_demand_fits():
    agg = Aggregator(SPEC, BSPEC)
    for i in range(100):           # 64 admitted + 36 exact counted drops
        pm(agg, "counter", f"pl.c{i}", 1)
    mgr = TableManager(SPEC)
    occ = mgr.occupancy(agg)
    assert occ["counter"] == (64, 36, 64)
    assert mgr.plan(agg) == {"counter": 128}    # 100 < 0.85 * 128


def test_manager_clamps_to_max_capacity_on_shard_multiple():
    agg = Aggregator(SPEC, BSPEC)
    for i in range(100):
        pm(agg, "counter", f"cl.c{i}", 1)
    mgr = TableManager(SPEC, n_shards=4, max_capacity=100)
    assert mgr.plan(agg) == {"counter": 100 - (100 % 4)}


def test_manager_force_validates_and_is_consumed_once():
    mgr = TableManager(SPEC, n_shards=4)
    with pytest.raises(ValueError):
        mgr.force({"bogus": 128})
    with pytest.raises(ValueError):
        mgr.force({"counter": 130})     # not divisible by n_shards
    with pytest.raises(ValueError):
        mgr.force({})
    mgr.force({"counter": 128})
    agg = Aggregator(SPEC, BSPEC)
    assert mgr.plan(agg) == {"counter": 128}
    assert mgr.plan(agg) is None        # consumed, occupancy is cold


def test_manager_shrinks_after_full_idle_window_never_below_baseline():
    fake = SimpleNamespace(table=SimpleNamespace(tables={
        "counter": SimpleNamespace(next_free=[3], dropped=0,
                                   capacity=256)}))
    mgr = TableManager(SPEC, shrink_window=3)
    assert mgr.plan(fake) is None       # window not full yet
    assert mgr.plan(fake) is None
    assert mgr.plan(fake) == {"counter": 128}   # 3 intervals < cap/4
    # at the baseline the halving stops even when idle
    fake.table.tables["counter"].capacity = 64
    for _ in range(4):
        assert mgr.plan(fake) is None


# -- the grow swap (Python backend) -------------------------------------------

def test_grow_swap_detaches_exact_interval_and_lifts_capacity():
    agg = Aggregator(SPEC, BSPEC)
    for i in range(100):
        pm(agg, "counter", f"gs.c{i}", 2)
    srv = _Srv(agg)
    state, table, old = grow_swap(srv, grown_spec(SPEC, {"counter": 128}))
    # the detached interval flushes at the OLD spec, value-exact
    vals = counter_values(state, table)
    assert len(vals) == 64
    assert all(v == 2.0 for v in vals.values())
    # lifetime counters carried across the rebuild
    assert srv.aggregator is not agg
    assert srv.aggregator.spec.counter_capacity == 128
    assert srv.aggregator.processed == agg.processed
    assert srv.aggregator.dropped_capacity == 36
    # the same population now fits without a single drop
    before = srv.aggregator.dropped_capacity
    for i in range(100):
        pm(srv.aggregator, "counter", f"gs.c{i}", 2)
    assert srv.aggregator.dropped_capacity == before
    state2, table2 = srv.aggregator.swap()
    assert len(counter_values(state2, table2)) == 100


def test_adopt_capacities_rejects_shard_indivisible_and_noop():
    agg = Aggregator(SPEC, BSPEC)
    agg.n_shards = 4
    srv = _Srv(agg)
    assert adopt_capacities(srv, spec_capacities(SPEC)) is False
    assert adopt_capacities(srv, {"counter": 130}) is False
    assert srv.aggregator is agg        # untouched on rejection
    assert adopt_capacities(srv, {"counter": 128}) is True
    assert srv.aggregator.spec.counter_capacity == 128


# -- pressure ladder ----------------------------------------------------------

def test_tag_explosion_demotes_to_rollup_row_exactly():
    agg = Aggregator(SPEC, BSPEC)
    pressure = TablePressure(demote_threshold=6)
    agg.set_pressure(pressure)
    for i in range(30):
        pm(agg, "counter", "exp.hot", 1, tags=(f"v:{i}",))
    # variants 1..6 allocate (the 6th trips the detector); 7..30 collapse
    assert pressure.demoted == {"counter": 24}
    assert agg.dropped_capacity == 0
    state, table = agg.swap()
    vals = counter_values(state, table)
    assert vals[("exp.hot", ROLLUP_TAG)] == 24.0
    assert sum(v for (n, _), v in vals.items() if n == "exp.hot") == 30.0
    # a demoted family stays demoted across the swap: the next interval's
    # brand-new variant goes straight to the rollup row
    pm(agg, "counter", "exp.hot", 1, tags=("v:fresh",))
    assert pressure.demoted == {"counter": 25}


def test_salsa_merge_cells_conserve_value_mass_exactly():
    agg = Aggregator(SPEC, BSPEC)
    pressure = TablePressure(salsa_enabled=True, salsa_cells=4)
    agg.set_pressure(pressure)
    for i in range(60):                 # cells take 4 slots; fill the rest
        pm(agg, "counter", f"sl.c{i}", 1)
    overflow = {f"sl.o{i}": float(i + 1) for i in range(30)}
    for name, v in overflow.items():
        pm(agg, "counter", name, v)
    assert pressure.merged == {"counter": 30}
    assert agg.dropped_capacity == 0    # rung 3 caught everything
    state, table = agg.swap()
    vals = counter_values(state, table)
    cell_total = sum(v for (n, _), v in vals.items()
                     if n == MERGE_CELL_NAME)
    # SALSA error bound: a cell is the EXACT sum of its members, so the
    # total overflow mass is conserved to the float
    assert cell_total == sum(overflow.values())
    # and any single member is over-reported by at most its cell total
    assert all(v <= cell_total for v in overflow.values())


def test_accounting_identity_merged_plus_resident_equals_sent():
    agg = Aggregator(SPEC, BSPEC)
    pressure = TablePressure(salsa_enabled=True, salsa_cells=4)
    agg.set_pressure(pressure)
    sent = 200
    for i in range(sent):
        pm(agg, "counter", f"id.c{i}", 1)
    own_slots = 64 - 4                  # capacity minus the cell block
    merged = pressure.merged.get("counter", 0)
    demoted = pressure.demoted.get("counter", 0)
    dropped = agg.dropped_capacity
    assert merged + demoted + dropped == sent - own_slots
    assert dropped == 0
    # no value lost either: total counter mass equals datagrams sent
    state, table = agg.swap()
    assert sum(counter_values(state, table).values()) == float(sent)


@pytest.mark.parametrize("backend", ["python", "native"])
def test_census_ttl_eviction_is_exact(backend):
    mgr = TableManager(SPEC, idle_ttl_s=50.0)
    if backend == "native":
        from veneur_tpu import native
        if not native.available():
            pytest.skip("native engine unavailable")
        from veneur_tpu.server.native_aggregator import NativeAggregator
        agg = NativeAggregator(SPEC, BSPEC)
    else:
        agg = Aggregator(SPEC, BSPEC)
    for i in range(10):
        pm(agg, "counter", f"ev.c{i}", 1)
    _state, table1 = agg.swap()
    mgr.census_flush(table1, now=1000.0)
    for i in range(3):                  # 3 of the 10 stay live
        pm(agg, "counter", f"ev.c{i}", 1)
    _state, table2 = agg.swap()
    mgr.census_flush(table2, now=1100.0)
    assert mgr.evicted == {"counter": 7}


# -- cross-capacity snapshot folds (both directions) --------------------------

def _interval_snapshot(spec, n_names):
    agg = Aggregator(spec, BSPEC)
    for i in range(n_names):
        pm(agg, "counter", f"xc.c{i}", 3)
    state, table = agg.swap()
    flush_arrays, table, raw = agg.compute_flush(
        state, table, [0.5], want_raw=True)
    from veneur_tpu.persistence import build_snapshot
    return build_snapshot(spec, table, flush_arrays, raw,
                          agg_kind="single", n_shards=1,
                          interval_ts=1, hostname="t")


def test_grown_snapshot_folds_into_smaller_tables_with_exact_drops():
    from veneur_tpu.persistence import fold_snapshot
    snap = _interval_snapshot(grown_spec(SPEC, {"counter": 128}), 100)
    small = Aggregator(SPEC, BSPEC)
    n = fold_snapshot(small, snap)
    assert n > 0
    state, table = small.swap()
    vals = counter_values(state, table)
    assert len(vals) == 64              # at capacity, never torn
    assert all(v == 3.0 for v in vals.values())
    assert small.dropped_capacity == 36  # the overflow is counted exactly


def test_small_snapshot_folds_into_grown_tables_value_exact():
    from veneur_tpu.persistence import fold_snapshot
    snap = _interval_snapshot(SPEC, 60)
    big = Aggregator(grown_spec(SPEC, {"counter": 128}), BSPEC)
    fold_snapshot(big, snap)
    state, table = big.swap()
    vals = counter_values(state, table)
    assert len(vals) == 60 and all(v == 3.0 for v in vals.values())
    assert big.dropped_capacity == 0


# -- server composition: query exactness across a grow ------------------------

def test_query_value_exact_across_grow():
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink
    from tests.test_server import (_send_udp, _wait_processed, by_name,
                                   small_config)
    sink = DebugMetricSink()
    srv = Server(small_config(http_address="127.0.0.1:0",
                              query_enabled=True, native_ingest=False,
                              table_grow_enabled=True),
                 metric_sinks=[sink])
    srv.start()
    try:
        from tests.test_query import _query
        _send_udp(srv.local_addr(), [b"qg.c%d:3|c" % i for i in range(20)])
        _wait_processed(srv, 20)
        out = _query(srv, {"name": "qg.c7", "kinds": ["counter"]})
        assert out["results"][0]["matches"][0]["value"] == 3.0
        # the forced grow rides a flush: the detached interval exports
        # at the old spec, the live spec doubles
        assert srv.trigger_table_grow({"counter": 512})
        assert srv.aggregator.spec.counter_capacity == 512
        assert srv.tables.grows == {"counter": 1}
        assert by_name(sink.flushed)["qg.c7"].value == 3.0
        _send_udp(srv.local_addr(), [b"qg.c%d:5|c" % i for i in range(20)])
        _wait_processed(srv, 40)
        out = _query(srv, {"name": "qg.c7", "kinds": ["counter"]})
        assert out["results"][0]["matches"][0]["value"] == 5.0
        sink.flushed.clear()
        assert srv.trigger_flush()
        assert by_name(sink.flushed)["qg.c7"].value == 5.0
    finally:
        srv.shutdown()


# -- native engine: preshard stability + backpressure verdict -----------------

from veneur_tpu import native  # noqa: E402

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native engine not buildable")


@needs_native
def test_preshard_shard_assignment_byte_stable_across_grow():
    """Fuzz pin for the grow/preshard contract: shard assignment is
    `route_digest % n_shards`, capacity-independent — the same corpus
    fed to preshard engines at capacity C and 2C lands every key on the
    SAME shard with the SAME folded value."""
    from veneur_tpu.server.native_aggregator import NativeShardedAggregator
    rng = np.random.default_rng(20)
    alpha = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789._-",
                          dtype="S1")
    names = {b"fz." + b"".join(rng.choice(alpha, rng.integers(3, 24)))
             for _ in range(40)}
    buf = b"\n".join(b"%s:1|c" % n for n in names)
    spec2 = grown_spec(SPEC, {"counter": 128})
    placements = []
    for spec in (SPEC, spec2):
        agg = NativeShardedAggregator(spec, BSPEC, n_shards=4,
                                      preshard=True)
        agg.feed(buf)
        state, table = agg.swap()
        per_shard = spec.counter_capacity // 4
        acc = (np.asarray(state.counter_acc).reshape(-1)
               + np.asarray(state.counter_hi).reshape(-1)
               + np.asarray(state.counter_lo).reshape(-1))
        placements.append({
            m.name: (slot // per_shard, float(acc[slot]))
            for slot, m in counter_meta(table)})
        # sized so nothing drops: the placement comparison is total
        assert len(placements[-1]) == len(names)
    assert placements[0] == placements[1]


@needs_native
def test_rings_inject_backpressure_uncounted_and_retry_exact():
    """The satellite-1 pin: INJECT_BACKPRESSURE (-1) counts NOTHING —
    a pace-and-retry loop lands the datagram exactly once, and the
    `datagrams == toolong + admitted + shed` identity holds over the
    whole run despite the retries."""
    from veneur_tpu.native import (INJECT_BACKPRESSURE, INJECT_OK,
                                   INJECT_REJECTED)
    from veneur_tpu.server.native_aggregator import NativeAggregator
    agg = NativeAggregator(SPEC, BSPEC)
    agg.rings_start(1, ring_cap=8)
    agg.admission_set(True, 0, 1e9, 1e9, [])
    try:
        agg.eng.rings_pause()           # parse stalled: the ring fills
        accepted = 0
        verdict = INJECT_OK
        while verdict == INJECT_OK:
            verdict = agg.eng.rings_inject(
                0, b"bp.k%d:1|c" % accepted)
            if verdict == INJECT_OK:
                accepted += 1
        assert verdict == INJECT_BACKPRESSURE and accepted > 0
        before = agg.eng.ring_counters_one(0)["datagrams"]
        for _ in range(5):              # hammer the full ring: all -1,
            assert agg.eng.rings_inject(0, b"bp.retry:1|c") \
                == INJECT_BACKPRESSURE  # nothing counted
        assert agg.eng.ring_counters_one(0)["datagrams"] == before
        agg.eng.rings_resume()
        deadline = time.time() + 30.0
        while agg.eng.rings_inject(0, b"bp.retry:1|c") \
                == INJECT_BACKPRESSURE:
            assert time.time() < deadline
            time.sleep(0.001)
        total = accepted + 1
        while agg.eng.stats()["processed"] < total:
            agg.pump(10)
            assert time.time() < deadline
        c = agg.eng.ring_counters_one(0)
        adm = agg.eng.ring_admission_drain_one(0)
        assert c["datagrams"] == total
        assert c["datagrams"] == (c["toolong"]
                                  + sum(adm["admitted"].values())
                                  + sum(adm["shed"].values()))
        state, table = agg.swap()
        vals = counter_values(state, table)
        assert sum(vals.values()) == float(total)
        assert vals[("bp.retry", "")] == 1.0    # retried, landed ONCE
    finally:
        agg.readers_stop()
    # the bool wrapper keeps the socket-reader contract: REJECTED is the
    # only falsy verdict (0), BACKPRESSURE is -1 (truthy), OK is 1
    assert INJECT_REJECTED == 0 and INJECT_OK == 1
    assert INJECT_BACKPRESSURE == -1
