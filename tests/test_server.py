"""End-to-end server tests over real loopback sockets — the reference's
testing stance (server_test.go setupVeneurServer + channel sinks)."""

import socket
import time

import numpy as np
import pytest

from veneur_tpu.config import Config
from veneur_tpu.samplers.intermetric import COUNTER, GAUGE, STATUS
from veneur_tpu.server.factory import new_from_config
from veneur_tpu.server.server import Server
from veneur_tpu.sinks.debug import DebugMetricSink


def small_config(**kw):
    """reference server_test.go:72 generateConfig: port 0, short interval."""
    defaults = dict(
        interval="10s", hostname="testbox", metric_max_length=4096,
        read_buffer_size_bytes=2097152, percentiles=[0.5, 0.99],
        aggregates=["min", "max", "count"],
        statsd_listen_addresses=["udp://127.0.0.1:0"],
        tpu_counter_capacity=256, tpu_gauge_capacity=64,
        tpu_status_capacity=16, tpu_set_capacity=16, tpu_histo_capacity=64,
        tpu_batch_counter=512, tpu_batch_gauge=128, tpu_batch_status=16,
        tpu_batch_set=64, tpu_batch_histo=512)
    defaults.update(kw)
    return Config(**defaults)


@pytest.fixture
def server():
    sink = DebugMetricSink()
    srv = Server(small_config(), metric_sinks=[sink])
    srv.start()
    yield srv, sink
    srv.shutdown()


def _send_udp(addr, lines):
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    s.sendto(b"\n".join(lines), addr)
    s.close()


def _total_parse_errors(srv):
    return srv.parse_errors + srv.aggregator.extra_parse_errors()


def _wait_processed(srv, n, timeout=60.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if srv.aggregator.processed + _total_parse_errors(srv) >= n:
            return
        time.sleep(0.02)
    raise TimeoutError(
        f"only {srv.aggregator.processed} processed after {timeout}s")


def _wait_until(cond, timeout=60.0, what="condition"):
    """Poll until cond() holds; raise a diagnosable TimeoutError instead
    of letting the caller proceed into an opaque assert. Timeouts are
    sized for a loaded host (a sharded flush can pay a fresh mesh
    compile); a passing run exits as soon as the condition holds."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        if cond():
            return
        time.sleep(0.05)
    raise TimeoutError(f"{what} not reached after {timeout}s")


def by_name(metrics):
    return {m.name: m for m in metrics}


def test_udp_ingest_to_flush(server):
    srv, sink = server
    addr = srv.local_addr()
    _send_udp(addr, [
        b"a.counter:3|c",
        b"a.counter:2|c",
        b"a.gauge:7.5|g|#env:prod",
        b"a.timer:100|ms",
        b"a.timer:200|ms",
        b"a.timer:300|ms",
        b"a.set:user1|s",
        b"a.set:user2|s",
        b"a.set:user1|s",
        b"bad packet!!!",
    ])
    _wait_processed(srv, 10)
    srv.trigger_flush()

    m = by_name(sink.flushed)
    assert m["a.counter"].value == 5.0
    assert m["a.counter"].type == COUNTER
    assert m["a.gauge"].value == 7.5
    assert m["a.gauge"].tags == ["env:prod"]
    assert m["a.timer.min"].value == 100.0
    assert m["a.timer.max"].value == 300.0
    assert m["a.timer.count"].value == 3.0
    assert m["a.timer.count"].type == COUNTER
    # standalone (not local): percentiles emitted
    assert "a.timer.50percentile" in m
    assert m["a.set"].value == pytest.approx(2.0, abs=0.1)
    assert _total_parse_errors(srv) == 1
    # flush resets the interval state (self-telemetry veneur.* / ssf.*
    # metrics may ride later intervals — flush-stage spans loop back through
    # the span pipeline; only app metrics must be gone)
    sink.flushed.clear()
    srv.trigger_flush()
    assert not [m for m in sink.flushed
                if not (m.name.startswith(("veneur.", "sink.", "worker."))
                        or m.name == "ssf.names_unique")]


def test_sample_rate_and_magic_tags(server):
    srv, sink = server
    addr = srv.local_addr()
    _send_udp(addr, [
        b"r.counter:1|c|@0.5",             # counts as 2
        b"scoped.gauge:4|g|#veneurlocalonly",
        b"r.timer:5|ms|@0.5",              # weight 2 (samplers_test.go:473
        b"r.timer:15|ms|@0.5",             # TestHistoSampleRate: count is
    ])                                     # the 1/rate-weighted total)
    _wait_processed(srv, 4)
    srv.trigger_flush()
    m = by_name(sink.flushed)
    assert m["r.counter"].value == 2.0
    assert m["scoped.gauge"].value == 4.0
    assert m["scoped.gauge"].tags == []  # magic tag stripped
    assert m["r.timer.count"].value == 4.0
    assert m["r.timer.max"].value == 15.0   # max is the raw sample


def test_tick_delay_aligns_to_interval():
    """reference server_test.go:994 TestCalculateTickerDelay: at
    11:45:26.371 with a 10s interval, the next aligned tick is 3.629s
    out."""
    from veneur_tpu.server.server import tick_delay
    import calendar
    now = calendar.timegm((2014, 11, 12, 11, 45, 26)) + 0.371
    assert tick_delay(10.0, now) == pytest.approx(3.629, abs=1e-6)


def test_global_accepts_histograms_over_udp():
    """reference flusher_test.go:148 TestGlobalAcceptsHistogramsOverUDP:
    a GLOBAL instance hit directly over the wire by a mixed-scope
    histogram flushes its aggregates (nowhere to forward; the direct
    hit means it is not imported_only) alongside percentiles."""
    sink = DebugMetricSink()
    srv = Server(small_config(), metric_sinks=[sink])  # no forward_address
    assert not srv.cfg.is_local
    srv.start()
    try:
        _send_udp(srv.local_addr(), [b"g.histo:20|h"])
        _wait_processed(srv, 1)
        srv.trigger_flush()
        m = by_name(sink.flushed)
        assert m["g.histo.min"].value == 20.0
        assert m["g.histo.count"].value == 1.0
        assert "g.histo.50percentile" in m
    finally:
        srv.shutdown()


def test_events_and_service_checks(server):
    srv, sink = server
    addr = srv.local_addr()
    _send_udp(addr, [
        b"_e{5,5}:hello|world|#env:prod",
        b"_sc|my.check|1|#env:prod|m:all good",
    ])
    _wait_processed(srv, 1)  # service check counts; event goes to buffer
    t0 = time.time()
    while not srv.event_samples and time.time() - t0 < 5:
        time.sleep(0.02)
    srv.trigger_flush()
    m = by_name(sink.flushed)
    assert m["my.check"].type == STATUS
    assert m["my.check"].value == 1.0


def test_local_mode_suppresses_percentiles_and_sets():
    """flusher.go:61-77: a forwarding (local) instance emits aggregates
    only for mixed histograms and nothing for sets."""
    sink = DebugMetricSink()
    srv = Server(small_config(forward_address="http://global:1"),
                 metric_sinks=[sink])
    srv.start()
    try:
        _send_udp(srv.local_addr(), [
            b"h.timer:100|ms", b"h.timer:200|ms",
            b"s.set:x|s",
            b"c.global:1|c|#veneurglobalonly",
            b"l.timer:50|ms|#veneurlocalonly",
        ])
        _wait_processed(srv, 4)
        srv.trigger_flush()
        m = by_name(sink.flushed)
        assert "h.timer.min" in m and "h.timer.count" in m
        assert "h.timer.50percentile" not in m
        assert "s.set" not in m
        assert "c.global" not in m       # forwarded, not flushed
        # local-only timers flush fully, with percentiles
        assert "l.timer.50percentile" in m
    finally:
        srv.shutdown()


def test_default_config_udp_listener_is_not_lossy():
    """Regression: a directly-constructed Config leaves
    read_buffer_size_bytes at 0 (the YAML path applies the 2MiB default);
    setsockopt(SO_RCVBUF, 0) clamps the kernel buffer to ~2KB and a burst
    of a few dozen loopback datagrams silently drops all but 2-3. The
    server must leave the kernel default alone when unconfigured."""
    srv = Server(Config(statsd_listen_addresses=["udp://127.0.0.1:0"],
                        interval="600s", hostname="t",
                        tpu_counter_capacity=64, tpu_gauge_capacity=16,
                        tpu_status_capacity=8, tpu_set_capacity=8,
                        tpu_histo_capacity=16),
                 metric_sinks=[DebugMetricSink()])
    srv.start()
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        n = 200
        for i in range(n):
            s.sendto(b"burst.count:1|c", srv.local_addr())
        s.close()
        _wait_processed(srv, n)
        assert srv.packets_received == n
    finally:
        srv.shutdown()


def test_tcp_listener():
    sink = DebugMetricSink()
    srv = Server(small_config(
        statsd_listen_addresses=["tcp://127.0.0.1:0"]), metric_sinks=[sink])
    srv.start()
    try:
        addr = srv.local_addr()
        s = socket.create_connection(addr, timeout=5)
        s.sendall(b"tcp.counter:4|c\ntcp.counter:1|c\n")
        s.close()
        _wait_processed(srv, 2)
        srv.trigger_flush()
        m = by_name(sink.flushed)
        assert m["tcp.counter"].value == 5.0
    finally:
        srv.shutdown()


def test_localfile_plugin(tmp_path):
    from veneur_tpu.sinks.localfile import LocalFilePlugin
    out = tmp_path / "flush.tsv"
    sink = DebugMetricSink()
    srv = Server(small_config(),
                 metric_sinks=[sink],
                 plugins=[LocalFilePlugin(str(out), "testbox", 1)])
    srv.start()
    try:
        _send_udp(srv.local_addr(), [b"f.counter:1|c"])
        _wait_processed(srv, 1)
        srv.trigger_flush()
        data = out.read_text()
        assert "f.counter" in data
        assert "testbox" in data
    finally:
        srv.shutdown()


def test_factory_wiring(tmp_path):
    cfg = small_config(debug_flushed_metrics=True,
                       flush_file=str(tmp_path / "x.tsv"))
    srv = new_from_config(cfg)
    assert any(s.name == "debug" for s in srv.metric_sinks)
    assert any(p.name == "localfile" for p in srv.plugins)


def test_sink_routing_and_tag_exclusion(server):
    srv, sink = server
    sink.set_excluded_tags(["secret"])
    _send_udp(srv.local_addr(), [
        b"routed:1|c|#veneursinkonly:datadog",
        b"plain:1|c|#secret:x,keep:y",
    ])
    _wait_processed(srv, 2)
    srv.trigger_flush()
    m = by_name(sink.flushed)
    # debug sink is not 'datadog', so the routed metric must be filtered
    assert "routed" not in m
    assert "plain" in m
    # exclusion applies at sink level
    assert sink.strip_excluded(m["plain"].tags) == ["keep:y"]


def test_ingest_continues_during_slow_sink_flush(server):
    """A slow sink must never stall ingest: flush runs on a dedicated
    thread, the pipeline thread only swaps state (flusher.go:105-115 runs
    sink flushes on the flush goroutine, workers keep consuming)."""
    srv, sink = server

    class SlowSink(DebugMetricSink):
        name = "slow"

        def flush(self, metrics):
            time.sleep(3.0)
            super().flush(metrics)

    addr = srv.local_addr()
    # warm-up interval: compiles ingest/flush programs so the measurement
    # below sees steady-state behavior, not first-compile latency
    _send_udp(addr, [b"warm.counter:1|c"])
    _wait_processed(srv, 1)
    srv.trigger_flush()

    slow = SlowSink()
    srv.metric_sinks.append(slow)
    _send_udp(addr, [b"pre.counter:1|c"])
    _wait_key(srv, "counter", "pre.counter")

    # kick off the flush without waiting; the slow sink holds it for 3s
    req = srv.trigger_flush(wait=False)
    time.sleep(0.3)  # let the swap happen and the sink start sleeping

    # ingest must proceed while the flush is still inside the slow sink
    t0 = time.time()
    processed0 = srv.aggregator.processed
    _send_udp(addr, [b"during.counter:%d|c" % i for i in range(50)])
    _wait_processed_delta(srv, processed0, 50, timeout=2.0)
    ingest_latency = time.time() - t0
    assert ingest_latency < 2.0, (
        f"ingest stalled {ingest_latency:.1f}s behind a slow sink flush")

    # the slow flush eventually completes with the slow sink's data —
    # waiting on THIS request, not on "any flush" (per-job semantics)
    assert req.wait(10.0), req.detail
    assert "pre.counter" in by_name(slow.flushed)

    # and the during-flush traffic lands in the NEXT interval
    srv.trigger_flush()
    assert "during.counter" in by_name(sink.flushed)


def _wait_key(srv, kind, name, timeout=10.0):
    """Wait until a metric key is registered in the live interval's table —
    unlike `processed` counts, immune to self-telemetry loop-back races.
    Read from the test's thread, a slot the pipeline thread has just
    allocated can show before its SlotMeta does (None): not yet there."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        if any(m is not None and m.name == name
               for _, m in srv.aggregator.table.get_meta(kind)):
            return
        time.sleep(0.02)
    raise TimeoutError(f"key {name} never registered")


def _wait_processed_delta(srv, base, n, timeout=10.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if srv.aggregator.processed - base >= n:
            return
        time.sleep(0.02)
    raise TimeoutError(
        f"only {srv.aggregator.processed - base}/{n} processed "
        f"after {timeout}s")


def test_backpressure_defers_interval_without_data_loss(server):
    """A backlogged flush worker must DEFER intervals (skip the swap, state
    extends on device) — never discard aggregated data. The reference never
    drops aggregated state short of a crash (flusher.go:28-131)."""
    srv, sink = server
    addr = srv.local_addr()
    # warm-up so subsequent flushes are steady-state
    _send_udp(addr, [b"warm:1|c"])
    _wait_processed(srv, 1)
    assert srv.trigger_flush() is True

    # wedge the flush worker: a sink flush that blocks until released
    import threading
    gate = threading.Event()

    class WedgedSink(DebugMetricSink):
        name = "wedged"

        def flush(self, metrics):
            gate.wait(30.0)
            super().flush(metrics)

    wedged = WedgedSink()
    srv.metric_sinks.append(wedged)

    _send_udp(addr, [b"precious:5|c"])
    _wait_key(srv, "counter", "precious")
    first = srv.trigger_flush(wait=False)   # occupies the flush worker
    time.sleep(0.2)

    # more samples land in the NEW interval; then hammer flush requests —
    # the job queue (4) fills with pending intervals and every further
    # request is deferred on the spot, WITHOUT swapping state
    _send_udp(addr, [b"precious:7|c"])
    _wait_key(srv, "counter", "precious")
    queued = []
    deferred = []
    for _ in range(10):
        req = srv.trigger_flush(wait=False)
        # the pipeline thread is unwedged, so it classifies the request
        # promptly: deferred requests complete (ok=False) right away;
        # queued ones stay pending until the worker is released
        if req.done.wait(1.0) and not req.ok:
            deferred.append(req)
        else:
            queued.append(req)
    assert len(deferred) >= 4, "queue never backlogged"
    assert all("deferred" in r.detail for r in deferred)
    assert srv.flush_intervals_deferred >= 4

    # release: every queued interval flushes; deferred intervals' data is
    # still live and flushes with the next request — zero loss
    gate.set()
    assert first.wait(10.0), first.detail
    for req in queued:
        assert req.wait(10.0), req.detail
    assert srv.trigger_flush() is True
    total = sum(m.value for m in sink.flushed if m.name == "precious")
    assert total == 12.0, f"lost samples: flushed total {total} != 12"


def test_shutdown_with_inflight_flush_is_clean(server):
    """Shutdown must complete (and leave no thread inside JAX/sinks) even
    with a flush in flight — the rc-134 teardown abort regression."""
    srv, sink = server
    addr = srv.local_addr()

    class SlowSink(DebugMetricSink):
        name = "slowshut"

        def flush(self, metrics):
            time.sleep(1.0)
            super().flush(metrics)

    slow = SlowSink()
    srv.metric_sinks.append(slow)
    _send_udp(addr, [b"final:9|c"])
    _wait_key(srv, "counter", "final")
    req = srv.trigger_flush(wait=False)    # in flight during shutdown
    srv.shutdown()
    # the in-flight flush was allowed to finish, not abandoned
    assert req.done.is_set()
    assert req.ok, req.detail
    assert "final" in by_name(slow.flushed)
    # no server thread survives shutdown
    import threading
    for t in [srv._pipeline_thread, srv._flush_thread] + srv._threads:
        assert not t.is_alive(), f"thread {t.name} survived shutdown"


def test_stats_address_mirrors_self_metrics():
    """stats_address sends self-metrics to an external statsd daemon as
    DogStatsD lines (server.go:297 statsd.New(conf.StatsAddress))."""
    ext = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    ext.bind(("127.0.0.1", 0))
    ext.settimeout(5.0)
    srv = Server(small_config(
        stats_address=f"127.0.0.1:{ext.getsockname()[1]}"),
        metric_sinks=[DebugMetricSink()])
    srv.start()
    try:
        _send_udp(srv.local_addr(), [b"sa.count:1|c"])
        _wait_processed(srv, 1)
        assert srv.trigger_flush()
        got = b""
        deadline = time.time() + 15
        while time.time() < deadline \
                and b"veneur.worker.metrics_processed_total" not in got:
            try:
                got += ext.recv(65536) + b"\n"
            except socket.timeout:
                continue   # quiet gap; the deadline bounds the wait
        assert b"veneur.worker.metrics_processed_total" in got
        assert b"|c" in got
    finally:
        srv.shutdown()
        ext.close()


def test_stats_and_profile_return_503_during_shutdown():
    """PR-11 satellite: once shutdown begins, /stats and /debug/profile
    answer 503 immediately instead of racing teardown (or stalling a
    profiler capture against a dying runtime)."""
    import urllib.error
    import urllib.request
    srv = Server(small_config(http_address="127.0.0.1:0",
                              profile_capture_enabled=True),
                 metric_sinks=[DebugMetricSink()])
    srv.start()
    try:
        port = srv.http_port
        # healthy first: /stats serves normally
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=10) as r:
            assert r.status == 200
        srv._shutdown.set()        # shutdown has begun; HTTP still up
        for path in ("/stats", "/debug/profile?seconds=1"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10)
            assert ei.value.code == 503, path
    finally:
        srv.shutdown()


def test_synchronized_ticker_aligns_first_flush():
    """synchronize_with_interval delays the first tick to a wall-clock
    multiple of the interval (server.go:866-870 CalculateTickDelay)."""
    srv = Server(small_config(interval="1s",
                              synchronize_with_interval=True),
                 metric_sinks=[DebugMetricSink()])
    srv.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline and srv.flush_count == 0:
            time.sleep(0.02)
        assert srv.flush_count > 0
        # the tick fired within ~150ms of a whole-second boundary
        frac = srv.last_flush % 1.0
        assert frac < 0.25 or frac > 0.75, frac
    finally:
        srv.shutdown()


def test_sink_flush_conventions_reported():
    """The per-sink conventions of sinks/sinks.go:11-29 — measured
    centrally by the flush fan-out and the span worker, so no sink can
    forget them: sink.metrics_flushed_total + flush duration per metric
    sink, spans_flushed/ingest-duration per span sink, all tagged
    sink:<name> and mirrored to stats_address."""
    ext = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    ext.bind(("127.0.0.1", 0))
    ext.settimeout(5.0)
    from veneur_tpu.sinks.debug import DebugSpanSink
    ssink = DebugSpanSink()
    srv = Server(small_config(
        stats_address=f"127.0.0.1:{ext.getsockname()[1]}"),
        metric_sinks=[DebugMetricSink()], span_sinks=[ssink])
    srv.start()
    try:
        _send_udp(srv.local_addr(), [b"conv.count:1|c"])
        _wait_processed(srv, 1)
        from veneur_tpu.proto import ssf_pb2
        span = ssf_pb2.SSFSpan(version=0, id=3, trace_id=3, name="s",
                               service="svc", start_timestamp=1,
                               end_timestamp=2)
        srv.span_pipeline.handle_span(span)
        deadline = time.time() + 10
        while time.time() < deadline and not ssink.spans:
            time.sleep(0.02)
        assert srv.trigger_flush()
        got = b""
        deadline = time.time() + 30
        want = (b"veneur.worker.metrics_processed_total",
                b"veneur.sink.metrics_flushed_total", b"sink:debug",
                b"veneur.sink.metric_flush_total_duration_ns",
                b"veneur.sink.spans_flushed_total",
                b"veneur.worker.span.flush_duration_ns",
                b"veneur.sink.span_ingest_total_duration_ns")
        while time.time() < deadline and not all(w in got for w in want):
            try:
                got += ext.recv(65536) + b"\n"
            except socket.timeout:
                continue   # quiet gap; the deadline bounds the wait
        for w in want:
            assert w in got, (w, got[-1500:])
    finally:
        srv.shutdown()
        ext.close()


def test_per_flush_runtime_gauges(server):
    """flusher.go:36-43: every flush reports span-chan depth/capacity,
    GC count, heap bytes, and the flush timestamp through the
    self-telemetry loop (they land via the span pipeline in a later
    interval's flush)."""
    srv, sink = server
    srv.trigger_flush()           # interval 1 emits the gauges
    want = {"veneur.worker.span_chan.total_elements",
            "veneur.worker.span_chan.total_capacity",
            "veneur.gc.number", "veneur.gc.pause_total_ns",
            "veneur.mem.heap_alloc_bytes",
            "veneur.flush.flush_timestamp_ns"}
    deadline = time.time() + 30
    got = {}
    while time.time() < deadline:
        srv.trigger_flush()       # loop-back lands in a later interval
        got = {m.name: m.value for m in sink.flushed if m.name in want}
        if want <= set(got):
            break
        time.sleep(0.1)
    assert want <= set(got), sorted(got)
    assert got["veneur.worker.span_chan.total_capacity"] == 100.0
    assert got["veneur.mem.heap_alloc_bytes"] > 1e6
    assert got["veneur.gc.pause_total_ns"] > 0
    assert got["veneur.flush.flush_timestamp_ns"] > 1e18


def test_pipeline_thread_survives_unexpected_exception():
    """The dispatch backstop: an exception class nobody anticipated must
    be counted and logged, never kill the pipeline thread (two fuzz-
    found bug classes escaped the ParseError-only catch and silently
    wedged the server before this existed). Python parse path: the
    C++ engine never raises into the dispatcher."""
    sink = DebugMetricSink()
    srv = Server(small_config(native_ingest=False), metric_sinks=[sink])
    srv.start()
    orig = srv.aggregator.process_metric

    def poisoned(m):
        if m.name == "poison":
            raise RuntimeError("injected")
        return orig(m)

    srv.aggregator.process_metric = poisoned
    try:
        _send_udp(srv.local_addr(), [b"poison:1|c"])
        _wait_until(lambda: srv.internal_errors >= 1,
                    what="backstop catch")
        _send_udp(srv.local_addr(), [b"alive.after:2|c"])
        _wait_processed(srv, 1)
        srv.trigger_flush()
        assert by_name(sink.flushed)["alive.after"].value == 2.0
    finally:
        srv.shutdown()


def test_reference_monitoring_metric_names(server):
    """README §Monitoring: veneur.worker.metrics_flushed_total must
    flush per metric type. (forward.* names: test_forward.py
    test_forward_monitoring_metrics; flush.error_total:
    test_sink_error_total_counts_failed_flushes below.)"""
    srv, sink = server
    _send_udp(srv.local_addr(), [b"mon.count:1|c", b"mon.t:3|ms"])
    _wait_processed(srv, 2)
    srv.trigger_flush()           # interval 1 emits the counts
    deadline = time.time() + 30
    got = {}
    while time.time() < deadline:
        srv.trigger_flush()
        got = {(m.name, tuple(m.tags)): m.value for m in sink.flushed
               if m.name == "veneur.worker.metrics_flushed_total"}
        if got:
            break
        time.sleep(0.1)
    by_type = {t[0].split(":", 1)[1]: v for (_n, t), v in got.items()
               if t}
    # counted by FLUSHED metric type: the timer's aggregates emit as
    # counter (.count) and gauge (.min/.max/percentiles) rows
    assert by_type.get("counter", 0) >= 1.0
    assert by_type.get("gauge", 0) >= 1.0, by_type


def test_sink_error_total_counts_failed_flushes():
    from veneur_tpu.sinks.base import MetricSink

    class FailingSink(MetricSink):
        name = "failing"

        def flush(self, metrics):
            raise RuntimeError("sink down")

    good = DebugMetricSink()
    srv = Server(small_config(), metric_sinks=[good, FailingSink()])
    srv.start()
    try:
        _send_udp(srv.local_addr(), [b"err.count:1|c"])
        _wait_processed(srv, 1)
        srv.trigger_flush()       # FailingSink raises; counted
        deadline = time.time() + 30
        val = 0
        while time.time() < deadline:
            srv.trigger_flush()
            vals = [m.value for m in good.flushed
                    if m.name == "veneur.flush.error_total"]
            if vals:
                val = sum(vals)
                break
            time.sleep(0.1)
        assert val >= 1.0
        errs = [m for m in good.flushed
                if m.name == "veneur.flush.error_total"]
        assert any("sink:failing" in m.tags for m in errs), (
            [m.tags for m in errs])
    finally:
        srv.shutdown()
