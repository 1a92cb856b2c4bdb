"""Columnar MetricFrame parity vs the object path.

generate_frame is a performance twin of generate_intermetrics (the
reference's generateInterMetrics, flusher.go:225-298): same emission
rules, different materialization. These tests pin them to byte-identical
output as multisets across every rule that differs by scope/tier."""

import numpy as np
import pytest

from veneur_tpu.aggregation.host import (
    KeyTable, SCOPE_GLOBAL, SCOPE_LOCAL, SCOPE_MIXED)
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.server.flusher import (
    generate_frame, generate_intermetrics)


def _mk_table_and_flush():
    spec = TableSpec(counter_capacity=64, gauge_capacity=64,
                     status_capacity=64, set_capacity=64,
                     histo_capacity=64)
    t = KeyTable(spec)
    rng = np.random.default_rng(7)
    scopes = [SCOPE_MIXED, SCOPE_LOCAL, SCOPE_GLOBAL]
    for i in range(9):
        t.slot_for("counter", f"c{i}", (f"k:{i}",), scopes[i % 3], i)
        t.slot_for("gauge", f"g{i}", (), scopes[i % 3], i)
        t.slot_for("set", f"s{i}", ("veneursinkonly:debug",)
                   if i == 4 else (), scopes[i % 3], i)
    for i in range(6):
        t.slot_for("status", f"st{i}", (), SCOPE_MIXED, i)
        t.tables["status"].meta[i][1].message = f"msg{i}"
    for i in range(12):
        t.slot_for("histogram", f"h{i}", ("az:a",), scopes[i % 3], i,
                   imported=(i % 4 == 0))
    # one timer (shares the histo table, distinct namespace)
    t.slot_for("timer", "tm0", (), SCOPE_MIXED, 99)

    nh = len(t.get_meta("histogram"))
    flush = {
        "counter": rng.uniform(1, 5, 9),
        "gauge": rng.uniform(-1, 1, 9),
        "status": np.arange(6, dtype=np.float64),
        "set_estimate": rng.uniform(10, 20, 9),
        "histo_quantiles": rng.uniform(0, 9, (nh, 3)),
        "histo_count": np.asarray(
            [0.0 if i == 5 else float(i + 1) for i in range(nh)]),
        "histo_min": np.asarray(
            [np.inf if i == 2 else 0.1 for i in range(nh)]),
        "histo_max": np.asarray(
            [-np.inf if i == 2 else 9.0 for i in range(nh)]),
        "histo_median": rng.uniform(1, 5, nh),
        "histo_avg": rng.uniform(1, 5, nh),
        "histo_sum": rng.uniform(1, 50, nh),
        "histo_hmean": rng.uniform(1, 5, nh),
    }
    return t, flush


def _key(m):
    return (m.name, m.timestamp, round(m.value, 9), tuple(m.tags),
            m.type, m.message, m.hostname, m.sinks)


@pytest.mark.parametrize("is_local", [False, True])
@pytest.mark.parametrize("aggregates", [
    ["min", "max", "count", "avg"], ["min", "min", "sum"], []])
@pytest.mark.parametrize("percentiles", [[0.5, 0.99], []])
def test_frame_matches_object_path(is_local, aggregates, percentiles):
    table, flush = _mk_table_and_flush()
    kw = dict(percentiles=percentiles, aggregates=aggregates,
              is_local=is_local, timestamp=1234, hostname="host-x")
    objs = generate_intermetrics(flush, table, **kw)
    # fresh prep caches so the two paths can't share mutated state
    for kind in ("counter", "gauge", "status", "set", "histogram"):
        for _s, m in table.get_meta(kind):
            m._emit_prep = None
    frame = generate_frame(flush, table, **kw)
    mats = frame.intermetrics()
    assert len(frame) == len(mats) == len(objs)
    assert sorted(map(_key, mats)) == sorted(map(_key, objs))


def test_frame_server_integration():
    """A server whose only sink accepts frames must take the frame path
    end-to-end and flush identical metrics (exercised via DebugMetricSink,
    which materializes for introspection)."""
    from veneur_tpu.config import Config
    from veneur_tpu.samplers.parser import parse_metric
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink

    sink = DebugMetricSink()
    srv = Server(Config(interval="600s", percentiles=[0.5],
                        aggregates=["min", "max", "count"]),
                 metric_sinks=[sink])
    srv.start()
    try:
        for line in (b"fr.c:3|c", b"fr.t:5|ms", b"fr.t:7|ms",
                     b"fr.s:u1|s"):
            srv.packet_queue.put(line)
        deadline = __import__("time").time() + 30
        while __import__("time").time() < deadline \
                and srv.aggregator.processed < 4:
            __import__("time").sleep(0.05)
        assert srv.trigger_flush(timeout=30)
        got = {m.name: m.value for m in sink.flushed}
        assert got["fr.c"] == 3.0
        assert got["fr.t.count"] == 2.0
        assert got["fr.t.min"] == 5.0 and got["fr.t.max"] == 7.0
        assert got["fr.s"] == pytest.approx(1.0, abs=0.2)
    finally:
        srv.shutdown()


def test_datadog_frame_flush_matches_object_flush():
    """The datadog sink's columnar path must emit the same DDMetric series
    as its object path across routing, prefix drops, per-prefix tag
    excludes, rate conversion, and hostname fallbacks."""
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    table, flush = _mk_table_and_flush()
    kw = dict(percentiles=[0.5, 0.99], aggregates=["min", "max", "count"],
              is_local=False, timestamp=777, hostname="host-y")
    objs = generate_intermetrics(flush, table, **kw)
    for kind in ("counter", "gauge", "status", "set", "histogram"):
        for _s, m in table.get_meta(kind):
            m._emit_prep = None
    frame = generate_frame(flush, table, **kw)

    def mk_sink():
        s = DatadogMetricSink(
            api_key="k", hostname="dd-host", api_url="http://x",
            interval_s=10.0,
            metric_name_prefix_drops=["g1"],
            exclude_tags_prefix_by_prefix_metric={"h": ["az"]})
        s.set_excluded_tags(["k"])
        captured = []
        s._post_series = captured.extend
        return s, captured

    s1, got_obj = mk_sink()
    s1.flush(objs)
    s2, got_frame = mk_sink()
    s2.flush_frame(frame)

    def key(dd):
        return (dd["metric"], tuple(sorted(dd["tags"])), dd["type"],
                dd.get("interval"), tuple(map(tuple, dd["points"])),
                dd["host"])

    assert len(got_obj) == len(got_frame) > 0
    assert sorted(map(key, got_obj)) == sorted(map(key, got_frame))
    # rate conversion actually happened for counters
    assert any(dd["type"] == "rate" and dd.get("interval") == 10
               for dd in got_frame)
    # dropped prefix really dropped
    assert not any(dd["metric"].startswith("g1") for dd in got_frame)


def test_signalfx_frame_flush_matches_object_flush():
    """SignalFx columnar path parity: routing, vary-by token fan-out, tag
    prefix drops, counter-vs-gauge kind split, hostname dimension."""
    from veneur_tpu.sinks.signalfx import SignalFxMetricSink

    table, flush = _mk_table_and_flush()
    kw = dict(percentiles=[0.5, 0.99], aggregates=["min", "max", "count"],
              is_local=False, timestamp=42, hostname="host-z")
    objs = generate_intermetrics(flush, table, **kw)
    for kind in ("counter", "gauge", "status", "set", "histogram"):
        for _s, m in table.get_meta(kind):
            m._emit_prep = None
    frame = generate_frame(flush, table, **kw)

    def mk_sink():
        s = SignalFxMetricSink(
            api_key="default-key", endpoint="http://x", hostname="sfx",
            vary_key_by="k", per_tag_api_keys={"1": "key-one"},
            metric_name_prefix_drops=["g2"],
            metric_tag_prefix_drops=["az"])
        posted = []
        s._post = lambda token, body: posted.append((token, body))
        return s, posted

    s1, got_obj = mk_sink()
    s1.flush(objs)
    s2, got_frame = mk_sink()
    s2.flush_frame(frame)

    def norm(posted):
        out = []
        for token, body in posted:
            for kind in ("counter", "gauge"):
                for dp in body[kind]:
                    out.append((token, kind, dp["metric"], dp["value"],
                                dp["timestamp"],
                                tuple(sorted(dp["dimensions"].items()))))
        return sorted(out)

    a, b = norm(got_obj), norm(got_frame)
    assert a == b and len(a) > 0
    # vary-by fan-out really split tokens; counters landed in the counter lane
    assert {t for t, *_ in a} == {"default-key", "key-one"}
    assert any(kind == "counter" for _t, kind, *_ in a)
    # tag prefix drop removed az dims, name prefix drop removed g2
    assert not any(any(k == "az" for k, _v in dims)
                   for *_x, dims in a)
    assert not any(name.startswith("g2") for _t, _k, name, *_y in a)


def test_datadog_magic_tags_and_service_checks():
    """reference datadog_test.go:76 TestHostMagicTag / :97
    TestDeviceMagicTag / :374 TestDatadogFlushServiceCheck: host:/device:
    tags override fields and are removed; STATUS metrics post to the
    check_run API, on BOTH flush paths."""
    from veneur_tpu.samplers.intermetric import InterMetric
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    metrics = [
        InterMetric("m.h", 100, 10.0, ["gorch:frobble", "host:abc123",
                                       "x:e"], "counter"),
        InterMetric("m.d", 100, 3.0, ["device:dev9", "x:e"], "gauge"),
        InterMetric("svc.up", 100, 1.0, ["az:a"], "status",
                    message="degraded", hostname="h-peer"),
    ]

    def run(flush_fn, arg):
        s = DatadogMetricSink(api_key="k", hostname="badhostname",
                              api_url="http://x", interval_s=10.0)
        series_out, checks_out = [], []
        s._post_series = series_out.extend
        s._post_checks = checks_out.extend
        flush_fn(s, arg)
        return series_out, checks_out

    # object path
    series, checks = run(DatadogMetricSink.flush, metrics)

    # frame path: wrap the same rows in segments
    from veneur_tpu.aggregation.host import SlotMeta
    from veneur_tpu.server.flusher import FrameSegment, MetricFrame
    import numpy as np

    def seg(m, is_status=False):
        meta = SlotMeta(name=m.name, tags=tuple(m.tags), scope=0,
                        kind=m.type, hostname=m.hostname,
                        message=m.message)
        return FrameSegment([m.name], np.asarray([m.value]), m.type,
                            [meta], is_status)

    frame = MetricFrame(100, "", [seg(metrics[0]), seg(metrics[1]),
                                  seg(metrics[2], is_status=True)])
    fseries, fchecks = run(DatadogMetricSink.flush_frame, frame)

    for got_series, got_checks in ((series, checks), (fseries, fchecks)):
        by_name = {dd["metric"]: dd for dd in got_series}
        h = by_name["m.h"]
        assert h["host"] == "abc123"            # magic tag wins
        assert "host:abc123" not in h["tags"] and "x:e" in h["tags"]
        d = by_name["m.d"]
        assert d["device_name"] == "dev9"
        assert "device:dev9" not in d["tags"]
        assert "svc.up" not in by_name          # status is not a metric
        (chk,) = got_checks
        assert chk == {"check": "svc.up", "status": 1,
                       "host_name": "h-peer", "timestamp": 100,
                       "tags": ["az:a"], "message": "degraded"}


# -- the columns behind a frame (host.KeyColumns) ---------------------------
# generate_frame and step.live_slots read a table's columns; the Python
# KeyTable makes them from its list, a detached native interval
# (native_aggregator._IntervalKeys) hands over the arrays it holds. Both
# must emit what generate_intermetrics emits from get_meta.

TABLES = ["python", pytest.param("native", marks=pytest.mark.skipif(
    not __import__("veneur_tpu.native").native.available(),
    reason="native engine unavailable"))]
SPEC = TableSpec(counter_capacity=64, gauge_capacity=64, status_capacity=64,
                 set_capacity=64, histo_capacity=64)
SCOPES = {"mixed": [SCOPE_MIXED], "local-only": [SCOPE_LOCAL],
          "global": [SCOPE_GLOBAL],
          "every": [SCOPE_MIXED, SCOPE_LOCAL, SCOPE_GLOBAL]}


def _columns_table(kind, scopes, imported):
    """(table as the flush worker gets it, flush arrays): nine keys a
    scalar kind, twelve histograms and a timer over `scopes`, every
    fourth histogram imported_only where `imported`; histogram 2 has
    non-finite min and max, histogram 5 no samples."""
    if kind == "python":
        live = KeyTable(SPEC)
    else:
        from veneur_tpu.aggregation.host import BatchSpec
        from veneur_tpu.server.native_aggregator import NativeAggregator
        live = NativeAggregator(
            SPEC, BatchSpec(counter=64, gauge=32, status=8, set=32,
                            histo=64, histo_stat=16)).table
    ns = len(scopes)
    for i in range(9):
        live.slot_for("counter", f"c{i}", (f"k:{i}",), scopes[i % ns], i)
        live.slot_for("gauge", f"g{i}", (), scopes[(i + 1) % ns], i)
        live.slot_for("set", f"s{i}", ("veneursinkonly:debug",)
                      if i == 4 else (), scopes[(i + 2) % ns], i)
    for i in range(6):
        live.slot_for("status", f"st{i}", (), SCOPE_MIXED, i)
        live.get_meta("status")[i][1].message = f"msg{i}"
    for i in range(12):
        live.slot_for("histogram", f"h{i}", ("az:a",), scopes[i % ns], i,
                      imported=imported and i % 4 == 0)
    live.slot_for("timer", "tm0", (), scopes[0], 99)
    table = live if kind == "python" else live.detach()
    nh = 13
    rng = np.random.default_rng(11)
    flush = {
        "counter": rng.uniform(1, 5, 9),
        "gauge": rng.uniform(-1, 1, 9),
        "status": np.arange(6, dtype=np.float64),
        "set_estimate": rng.uniform(10, 20, 9),
        "histo_quantiles": rng.uniform(0, 9, (nh, 3)),
        "histo_count": np.asarray(
            [0.0 if i == 5 else float(i + 1) for i in range(nh)]),
        "histo_min": np.asarray(
            [np.inf if i == 2 else 0.1 for i in range(nh)]),
        "histo_max": np.asarray(
            [-np.inf if i == 2 else 9.0 for i in range(nh)]),
    }
    return table, flush


@pytest.mark.parametrize("imported", [False, True],
                         ids=["direct", "imported-only"])
@pytest.mark.parametrize("scopes", list(SCOPES))
@pytest.mark.parametrize("is_local", [False, True],
                         ids=["global-tier", "local-tier"])
@pytest.mark.parametrize("kind", TABLES)
def test_frame_rows_from_columns_match_object_path(kind, is_local, scopes,
                                                   imported):
    table, flush = _columns_table(kind, SCOPES[scopes], imported)
    kw = dict(percentiles=[0.5, 0.75, 0.99],
              aggregates=["min", "max", "count"], is_local=is_local,
              timestamp=99, hostname="host-c")
    frame = generate_frame(flush, table, **kw)
    got = sorted((name, round(value, 9), mtype, message, tuple(tags),
                  sinks, host)
                 for name, value, mtype, message, tags, sinks, host
                 in frame.rows())
    want = sorted((m.name, round(m.value, 9), m.type, m.message,
                   tuple(m.tags), m.sinks, m.hostname)
                  for m in generate_intermetrics(flush, table, **kw))
    assert got == want
    assert len(frame) == len(want)
    if not is_local:
        assert len(want) > 30       # the rules left something to compare
    names = [name for name, *_ in got]
    assert "h2.min" not in names and "h2.max" not in names
    assert not any(n.startswith("h5.") for n in names)
    if is_local and scopes == "global":
        assert names == [f"st{i}" for i in range(6)]


@pytest.mark.parametrize("kind", TABLES)
def test_live_slots_is_the_slot_column_of_get_meta(kind):
    import threading
    from veneur_tpu.aggregation.step import live_indices, live_slots
    from veneur_tpu.native import IMPORTED_BIT
    table, _flush = _columns_table(kind, SCOPES["every"], True)
    asked = []
    # the view makes the list when first asked, from whichever thread
    threads = [threading.Thread(
        target=lambda: asked.append(table.get_meta("histogram")))
        for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert asked[0] is asked[1] is table.get_meta("timer")
    for k in ("counter", "gauge", "status", "set", "histogram", "timer"):
        want = [s for s, _m in table.get_meta(k)]
        got = live_slots(table, k)
        assert got.dtype == np.int32 and got.tolist() == want
        assert len(want) == len(table.columns(k)) > 0
        padded = live_indices(table, k, 64)
        assert padded[:len(want)].tolist() == want and len(padded) == 64
        cols = table.columns(k)
        assert [m for m in cols.metas] == \
            [m for _s, m in table.get_meta(k)]
        assert cols.first.tolist() == [
            m.scope | (IMPORTED_BIT if m.imported_only else 0)
            for _s, m in table.get_meta(k)]
        slot, meta = table.get_meta(k)[-1]
        assert table.meta_for_slot(k, slot) is meta


@pytest.mark.parametrize("kind", TABLES)
def test_unique_timeseries_counts_from_columns(kind):
    from veneur_tpu.server.flusher import unique_timeseries
    table, _flush = _columns_table(kind, SCOPES["every"], False)
    assert unique_timeseries(table, False) == 9 * 3 + 6 + 13
    # local: counters and gauges that are not global (6 + 6), local-only
    # sets (3) and histograms (4 of 12, and not the mixed timer), status
    assert unique_timeseries(table, True) == 6 + 6 + 3 + 4 + 6


def test_flush_protect_cuts_a_frame_of_columns():
    """server._flush_protect keeps working on segments whose names and
    metas are object arrays, and on hand-built lists."""
    from veneur_tpu.server.flusher import FrameSegment
    table, flush = _columns_table("python", SCOPES["mixed"], False)
    frame = generate_frame(flush, table, percentiles=[0.5],
                           aggregates=["count"], is_local=False,
                           timestamp=1, hostname="h")
    seg = frame.segments[0]
    assert isinstance(seg.names, np.ndarray)
    cut = seg.take([1, 3])
    assert list(cut.names) == ["c1", "c3"]
    assert [m.name for m in cut.metas] == ["c1", "c3"]
    assert cut.values.tolist() == seg.values[[1, 3]].tolist()
    by_hand = FrameSegment(["a", "b", "c"], np.arange(3.0), "gauge",
                           list(seg.metas[:3])).take([0, 2])
    assert by_hand.names == ["a", "c"] and len(by_hand.metas) == 2


def test_server_counts_frame_rows_and_labels_reused():
    """server._do_flush counts what its frames emit on the aggregator that
    owned the interval; a native backend's second flush of the same keys
    takes every name from its column."""
    import time
    from veneur_tpu.config import Config
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    srv = Server(Config(interval="600s", percentiles=[0.5],
                        aggregates=["min", "max", "count"]),
                 metric_sinks=[BlackholeMetricSink()])

    def total(name):
        (sample,) = srv.metrics.get(name).samples()
        return sample[1]

    srv.start()
    try:
        seen = 0
        for k in range(3):
            for line in (b"fc.c:3|c", b"fc.t:5|ms", b"fc.g:1|g"):
                srv.packet_queue.put(line)
            seen += 3
            deadline = time.time() + 30
            while time.time() < deadline \
                    and srv.aggregator.processed < seen:
                time.sleep(0.02)
            before = (total("veneur.flush.frame_rows_total"),
                      total("veneur.flush.frame_labels_reused_total"))
            assert srv.trigger_flush(timeout=30)
            rows = total("veneur.flush.frame_rows_total") - before[0]
            reused = total("veneur.flush.frame_labels_reused_total") \
                - before[1]
            # a counter, a gauge and four rows of the timer, and the
            # server's own veneur.* rows from the second flush on
            assert rows >= 6 and 0 <= reused <= rows
            if k == 0:
                assert reused == 0
            elif hasattr(srv.aggregator, "eng"):
                assert reused >= 6
                stats = srv.aggregator.ring_stats()
                assert stats["frame_rows"] == \
                    total("veneur.flush.frame_rows_total")
                assert stats["frame_labels_reused"] == \
                    total("veneur.flush.frame_labels_reused_total")
    finally:
        srv.shutdown()
