"""Native metricpb import decoder (vi_import) vs the Python import path.

The global tier's gRPC payload decoded+staged in C++ must produce the
SAME flushed aggregates as the Python import_into path on the same
serialized MetricList — the differential idiom of tests/test_native.py,
extended to the import direction (reference importsrv/server.go:97
SendMetrics → worker.go:438 ImportMetricGRPC).
"""

import numpy as np
import pytest

from veneur_tpu.aggregation.host import BatchSpec
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.proto import forwardrpc_pb2 as fpb
from veneur_tpu.proto import metricpb_pb2 as mpb
from veneur_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native engine unavailable")

SPEC = TableSpec(counter_capacity=256, gauge_capacity=64,
                 status_capacity=16, set_capacity=32, histo_capacity=64)
BSPEC = BatchSpec(counter=512, gauge=128, status=16, set=64, histo=512,
                  histo_stat=64)


def _mk_list(rng, n_counters=40, n_gauges=10, n_timers=8, n_sets=3):
    """A MetricList shaped like a local's forward payload."""
    ml = fpb.MetricList()
    for i in range(n_counters):
        m = ml.metrics.add()
        m.name = f"imp.c.{i}"
        m.tags.extend([f"host:h{i % 3}", "env:prod"])
        m.type = mpb.Counter
        m.counter.value = int(rng.integers(-5, 1000))
    for i in range(n_gauges):
        m = ml.metrics.add()
        m.name = f"imp.g.{i}"
        m.type = mpb.Gauge
        m.gauge.value = float(rng.uniform(-10, 10))
    for i in range(n_timers):
        m = ml.metrics.add()
        m.name = f"imp.t.{i}"
        m.tags.append("svc:api")
        m.type = mpb.Timer
        m.scope = mpb.Global
        td = m.histogram.t_digest
        vals = rng.lognormal(2, 0.8, 30)
        for v in vals:
            c = td.main_centroids.add()
            c.mean = float(v)
            c.weight = float(rng.integers(1, 4))
        td.min = float(vals.min())
        td.max = float(vals.max())
        td.reciprocalSum = float(np.sum(1.0 / vals))
    for i in range(n_sets):
        m = ml.metrics.add()
        m.name = f"imp.s.{i}"
        m.type = mpb.Set
        from veneur_tpu.ops import hll
        regs = np.zeros(hll.num_registers(SPEC.hll_precision), np.uint8)
        regs[rng.integers(0, len(regs), 50)] = rng.integers(1, 20, 50)
        m.set.hyper_log_log = hll.serialize(regs)
    # proto3-default edge cases: min == 0.0 is ELIDED from the wire (a
    # digest containing a 0.0 sample), and an all-negative digest elides
    # nothing but exercises negative min/max — both must stage exactly
    # what the Python path stages (r05 review finding: +-inf sentinels
    # for absent fields silently no-op'd the scatter-min/max)
    m = ml.metrics.add()
    m.name = "imp.t.zero_min"
    m.type = mpb.Timer
    td = m.histogram.t_digest
    for mean, weight in ((0.0, 1.0), (3.5, 2.0), (8.0, 1.0)):
        c = td.main_centroids.add()
        c.mean, c.weight = mean, weight
    td.min = 0.0      # elided on the wire
    td.max = 8.0
    td.reciprocalSum = 0.0   # elided (0.0-mean makes it undefined)
    m = ml.metrics.add()
    m.name = "imp.t.negative"
    m.type = mpb.Timer
    td = m.histogram.t_digest
    for mean, weight in ((-9.5, 1.0), (-2.25, 3.0)):
        c = td.main_centroids.add()
        c.mean, c.weight = mean, weight
    td.min = -9.5
    td.max = -2.25    # negative max; 0.0 would be elided
    td.reciprocalSum = float(1.0 / -9.5 + 3.0 / -2.25)
    return ml


def _flush_of(agg):
    out, table = agg.flush([0.5, 0.99])
    by = {}
    for kind in ("counter", "gauge", "set", "histogram"):
        for i, (_slot, meta) in enumerate(table.get_meta(kind)):
            by[(meta.kind, meta.name, meta.joined_tags)] = {
                k: np.asarray(v)[i] for k, v in out.items()
                if k.startswith(
                    {"counter": "counter", "gauge": "gauge",
                     "set": "set", "histogram": "histo"}[kind])}
    return by


def test_native_import_matches_python_import():
    from veneur_tpu.forward.convert import import_into
    from veneur_tpu.server.aggregator import Aggregator
    from veneur_tpu.server.native_aggregator import NativeAggregator

    rng = np.random.default_rng(11)
    ml = _mk_list(rng)
    data = ml.SerializeToString()

    py = Aggregator(SPEC, BSPEC)
    for m in ml.metrics:
        import_into(py, m)

    nat = NativeAggregator(SPEC, BSPEC)
    total, errors = nat.import_pb_bytes(data)
    assert total == len(ml.metrics)
    assert errors == 0

    a, b = _flush_of(py), _flush_of(nat)
    assert set(a) == set(b), (set(a) ^ set(b))
    for key in a:
        for field in a[key]:
            av, bv = a[key][field], b[key][field]
            np.testing.assert_allclose(
                av, bv, rtol=1e-5, atol=1e-6,
                err_msg=f"{key} {field}")


def test_native_import_imported_only_marking():
    """A slot FIRST created by the import path is imported_only (the
    Python path's host.py alloc imported=True marks every import-created
    slot); a slot first created by the wire path is not."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    rng = np.random.default_rng(3)
    nat = NativeAggregator(SPEC, BSPEC)
    nat.feed(b"wire.c:1|c")        # wire-created slot first
    nat.import_pb_bytes(_mk_list(rng).SerializeToString())
    table = nat.table
    assert all(m.imported_only for _s, m in table.get_meta("histogram"))
    by_name = {m.name: m for _s, m in table.get_meta("counter")}
    assert not by_name["wire.c"].imported_only
    assert by_name["imp.c.0"].imported_only


def test_native_import_staging_overflow_reenters():
    """A MetricList bigger than the staging lanes emits mid-request and
    re-enters at the reported boundary — nothing lost, counts exact."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    rng = np.random.default_rng(5)
    small = BatchSpec(counter=16, gauge=8, status=8, set=16, histo=64,
                      histo_stat=8)
    nat = NativeAggregator(SPEC, small)
    ml = _mk_list(rng, n_counters=100, n_gauges=20, n_timers=6, n_sets=0)
    total, errors = nat.import_pb_bytes(ml.SerializeToString())
    assert (total, errors) == (len(ml.metrics), 0)
    out, table = nat.flush([0.5])
    names = {m.name for _s, m in table.get_meta("counter")}
    assert len(names) == 100
    # every counter value exact despite the mid-request emits
    vals = {m.name: float(np.asarray(out["counter"])[i])
            for i, (_s, m) in enumerate(table.get_meta("counter"))}
    for m in ml.metrics:
        if m.WhichOneof("value") == "counter":
            assert vals[m.name] == float(m.counter.value)


def test_native_import_lane_full_at_entry_not_dropped():
    """Staging already full when the request arrives (e.g. wire traffic
    filled the lanes): the importer must emit and re-enter, never
    misread the boundary stop as an undecodable tail (r05 review)."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    rng = np.random.default_rng(9)
    tiny = BatchSpec(counter=4, gauge=8, status=8, set=16, histo=64,
                     histo_stat=8)
    nat = NativeAggregator(SPEC, tiny)
    # fill the counter lane exactly to capacity via the wire path
    for i in range(4):
        nat.feed(b"wire.%d:1|c" % i)
    ml = _mk_list(rng, n_counters=10, n_gauges=0, n_timers=0, n_sets=0)
    total, errors = nat.import_pb_bytes(ml.SerializeToString())
    assert (total, errors) == (len(ml.metrics), 0)
    out, table = nat.flush([0.5])
    names = {m.name for _s, m in table.get_meta("counter")}
    assert {f"imp.c.{i}" for i in range(10)} <= names


def test_import_digest_consistent_hash_partition():
    """reference importsrv/server_test.go:31 TestSendMetrics_ConsistentHash:
    the exact 2-way partition of five known metrics pins the import hash
    (fnv1a over name, Type.String(), tags) bit-for-bit — a mixed fleet
    shards identically whichever implementation runs the global tier."""
    from veneur_tpu.forward.convert import metric_digest
    inputs = [("test.counter", mpb.Counter, ("tag:1",)),
              ("test.gauge", mpb.Gauge, ()),
              ("test.histogram", mpb.Histogram, ("type:histogram",)),
              ("test.set", mpb.Set, ()),
              ("test.gauge3", mpb.Gauge, ())]
    assert [metric_digest(n, t, tags) % 2
            for n, t, tags in inputs] == [0, 1, 1, 1, 0]


def test_native_import_fuzz_no_crash():
    """vi_import parses untrusted network bytes: random mutations of
    valid MetricLists (truncate/flip/splice/insert/pure-random) must
    never crash or wedge the engine. A 2x300s deep-fuzz run of the same
    generator (160k+ payloads) was clean at commit time; this pins the
    property at suite scale."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    rng = np.random.default_rng(99)
    bases = [_mk_list(rng, n_counters=8, n_gauges=4, n_timers=3,
                      n_sets=2).SerializeToString() for _ in range(4)]
    nat = NativeAggregator(SPEC, BSPEC)
    for i in range(1500):
        b = bytearray(bases[int(rng.integers(0, len(bases)))])
        op = rng.integers(0, 5)
        if op == 0 and len(b) > 1:
            data = bytes(b[:rng.integers(0, len(b))])
        elif op == 1:
            for _ in range(int(rng.integers(1, 8))):
                b[int(rng.integers(0, len(b)))] = int(
                    rng.integers(0, 256))
            data = bytes(b)
        elif op == 2 and len(b) > 8:
            i0 = int(rng.integers(0, len(b) - 4))
            j0 = int(rng.integers(i0, min(len(b), i0 + 64)))
            data = bytes(b[:i0]) + bytes(b[j0:])
        elif op == 3:
            i0 = int(rng.integers(0, len(b) + 1))
            junk = rng.integers(0, 256,
                                int(rng.integers(1, 32))).astype(np.uint8)
            data = bytes(b[:i0]) + junk.tobytes() + bytes(b[i0:])
        else:
            data = rng.integers(
                0, 256, int(rng.integers(0, 512))).astype(
                    np.uint8).tobytes()
        total, errors = nat.import_pb_bytes(data)
        assert total >= 0 and errors >= 0


def test_native_import_malformed_tail_counted():
    """Garbage after valid metrics: the valid prefix lands, the tail is
    counted as one error instead of crashing the pipeline."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    rng = np.random.default_rng(7)
    nat = NativeAggregator(SPEC, BSPEC)
    ml = _mk_list(rng, n_counters=5, n_gauges=0, n_timers=0, n_sets=0)
    data = ml.SerializeToString() + b"\x0a\xff\xff\xff\xff\x7f"
    total, errors = nat.import_pb_bytes(data)
    assert total == len(ml.metrics)   # the valid prefix all landed
    assert errors == 1


# An importing server's lanes (server.bspec_from_config): the stats lane
# as wide as the histo lane.
WIDE = BatchSpec(counter=512, gauge=128, status=16, set=64, histo=512,
                 histo_stat=512)


def _digest_requests(k, seed=21):
    """k forwarded requests of 22 digests each over the same names, ~605
    centroid rows a request, so that the 512-row histo lane stops about
    once a request."""
    rng = np.random.default_rng(seed)
    return [_mk_list(rng, n_counters=0, n_gauges=0, n_timers=20,
                     n_sets=0).SerializeToString() for _ in range(k)]


def test_import_stats_ride_the_lane_stops_steps():
    """On an importing aggregator the digests' stats dispatch no step of
    their own: the steps are the lane stops' and the swap's one emit."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    nat = NativeAggregator(SPEC, WIDE)
    for data in _digest_requests(6):
        assert nat.import_pb_bytes(data)[1] == 0
    stops = nat.import_lane_stops
    assert stops >= 6
    assert nat.import_stat_steps == 0
    assert nat.steps_total == nat.import_steps == stops
    nat.swap()
    assert nat.steps_total == stops + 1
    assert nat.ring_stats()["import_stat_steps"] == 0


def test_import_lane_stop_steps_compact():
    """A step an import's lane stop dispatches carries a full lane of
    imported centroids and compacts, whatever compact_every says: a
    digest row is compacted as often a lane of imported centroids as when
    the stats lane made steps of its own."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    nat = NativeAggregator(SPEC, WIDE, compact_every=8)
    for data in _digest_requests(6, seed=25):
        nat.import_pb_bytes(data)
    assert nat.import_lane_stops >= 6
    assert nat.compactions == nat.import_lane_stops == nat.steps_total
    # a wire step keeps compact_every's cadence
    nat.feed(b"wire.c:1|c")
    nat._emit_native()
    assert nat.compactions == (nat.import_lane_stops
                               + (nat.steps_total % 8 == 0))


def test_carried_stats_flush_as_the_python_import_does():
    """The same requests through the carrying native path and through a
    256-wide Python import that carries nothing: min and max bit for bit,
    the reciprocal sum (read as the harmonic mean) to f32 reordering."""
    from veneur_tpu.forward.convert import import_into
    from veneur_tpu.server.aggregator import Aggregator
    from veneur_tpu.server.native_aggregator import NativeAggregator
    requests = _digest_requests(6, seed=23)
    py = Aggregator(SPEC, BatchSpec(counter=512, gauge=128, status=16,
                                    set=64, histo=512))
    nat = NativeAggregator(SPEC, WIDE)
    for data in requests:
        for m in fpb.MetricList.FromString(data).metrics:
            import_into(py, m)
        nat.import_pb_bytes(data)
    assert nat.import_stat_steps == 0
    a, b = _flush_of(py), _flush_of(nat)
    timers = [key for key in a if key[0] == "timer"]
    assert len(timers) == 22 and set(a) == set(b)
    for key in timers:
        for field in ("histo_min", "histo_max"):
            np.testing.assert_array_equal(a[key][field], b[key][field],
                                          err_msg=f"{key} {field}")
        np.testing.assert_allclose(a[key]["histo_hmean"],
                                   b[key]["histo_hmean"], rtol=1e-5,
                                   err_msg=f"{key} histo_hmean")


def test_stats_lane_overflow_folds_every_stat_and_is_counted():
    """A request of more single-centroid digests than a deliberately
    small stats lane holds: the lane's own steps are counted in
    import_stat_steps, the rest ride the swap's emit, and every digest's
    min, max and reciprocal sum lands exactly."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    n, lane = 30, 8
    ml = fpb.MetricList()
    values = np.arange(1, n + 1, dtype=np.float32) * np.float32(1.25)
    for i, v in enumerate(values.tolist()):
        m = ml.metrics.add()
        m.name, m.type, m.scope = f"one.t.{i}", mpb.Timer, mpb.Global
        td = m.histogram.t_digest
        c = td.main_centroids.add()
        c.mean, c.weight = v, 1.0
        td.min = td.max = v
        td.reciprocalSum = 1.0 / v
    nat = NativeAggregator(SPEC, BatchSpec(
        counter=512, gauge=128, status=16, set=64, histo=512,
        histo_stat=lane))
    assert nat.import_pb_bytes(ml.SerializeToString()) == (n, 0)
    assert nat.import_stat_steps == n // lane
    assert nat.import_steps == n // lane and nat.import_lane_stops == 0
    got = _flush_of(nat)
    for i, v in enumerate(values.tolist()):
        row = got[("timer", f"one.t.{i}", "")]
        assert row["histo_min"] == v and row["histo_max"] == v
        np.testing.assert_allclose(row["histo_hmean"], v, rtol=1e-6)


def test_carried_stats_rows_are_restored_on_the_buffers_next_use():
    """_carry_stats' sentinel contract, as vt_emit_packed's for its lanes:
    a packed buffer that carried 10 stats rows and then carries 1 holds
    that one row and sentinels past it, exactly a fresh buffer's; the
    batcher's stats lane is left empty and at its sentinels."""
    from veneur_tpu.aggregation.step import packed_layout
    from veneur_tpu.server.native_aggregator import NativeAggregator
    nat = NativeAggregator(SPEC, WIDE)
    b = nat.batcher
    layout, _words = packed_layout(nat._pk_sizes)
    fresh = nat._new_packed()[0]
    flat, _prev, carried = nat._new_packed()
    for i in range(10):
        b.add_histo_stats(i, float(i), float(i) + 1.0, 0.5)
    nat._carry_stats(flat, carried)
    assert carried == [10] and b.nhs == 0
    b.add_histo_stats(3, -1.0, 9.0, 0.25)
    nat._carry_stats(flat, carried)
    assert carried == [1] and b.nhs == 0

    def lanes(buf):
        out = {}
        for name in ("histo_stat_slot", "histo_stat_min", "histo_stat_max",
                     "histo_stat_recip"):
            off, n, _w = layout[name]
            v = buf[off:off + n]
            out[name] = v if name.endswith("slot") else v.view(np.float32)
        return out

    got, want = lanes(flat), lanes(fresh)
    assert (got["histo_stat_slot"][0], got["histo_stat_min"][0],
            got["histo_stat_max"][0], got["histo_stat_recip"][0]) == (
                3, -1.0, 9.0, 0.25)
    for name in got:
        np.testing.assert_array_equal(got[name][1:], want[name][1:],
                                      err_msg=name)
    np.testing.assert_array_equal(flat[1:layout["histo_stat_slot"][0]],
                                  fresh[1:layout["histo_stat_slot"][0]])
    assert (b.hs_slot == SPEC.histo_capacity).all()
    assert np.isposinf(b.hs_min).all() and np.isneginf(b.hs_max).all()
    assert (b.hs_recip == 0).all()


@pytest.mark.parametrize("grpc_address", ["", "127.0.0.1:0"])
def test_stats_lane_width_follows_the_import_listener(grpc_address):
    """A server without a gRPC import listener keeps the 256-row stats
    lane, so its packed ingest program's lane sizes are what they were;
    one with a listener makes the lane as wide as tpu_batch_histo."""
    from tests.test_server import small_config
    from veneur_tpu.server.server import Server
    srv = Server(small_config(grpc_address=grpc_address))
    try:
        stat = 512 if grpc_address else 256
        assert srv.aggregator.bspec.histo_stat == stat
        assert srv.aggregator._pk_sizes == (
            512, 512, 128, 128, 16, 16, 64, 64, 64, 512, 512, 512,
            stat, stat, stat, stat)
    finally:
        srv.shutdown()
