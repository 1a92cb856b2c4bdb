"""Sustained-load soak: many flush intervals under continuous ingest.

The reference's fault-tolerance story is flush-scoped state — nothing may
accumulate across intervals (worker.go:498 swap discards everything each
flush). This drives ~12 intervals of rotating keys through a live server
and asserts (a) per-interval counter totals stay exact — no sample loss
and no carry-over between intervals, (b) the key table really resets
(slot metadata from past intervals does not pile up), and (c) python-side
object growth stays bounded (a leaky meta/emit cache would show here)."""

import gc
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from veneur_tpu.server.server import Server
from veneur_tpu.sinks.debug import DebugMetricSink

from tests.test_server import small_config, _wait_processed


def test_soak_many_intervals_exact_and_leak_free():
    sink = DebugMetricSink()
    srv = Server(small_config(tpu_counter_capacity=1024,
                          interval="600s"),
                 metric_sinks=[sink])
    srv.start()
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        addr = srv.local_addr()
        intervals = 12
        per = 300
        baseline_objects = None
        for it in range(intervals):
            sink.flushed.clear()
            base = srv.aggregator.processed
            # rotating key space: each interval uses fresh names, so any
            # cross-interval carry-over shows as unexpected keys
            lines = [b"soak.%d.%d:2|c" % (it, i % 50) for i in range(per)]
            for i in range(0, per, 25):
                s.sendto(b"\n".join(lines[i:i + 25]), addr)
            deadline = time.time() + 30
            while (srv.aggregator.processed < base + per
                   and time.time() < deadline):
                time.sleep(0.02)
            assert srv.aggregator.processed >= base + per, (
                f"interval {it}: ingest stalled")
            assert srv.trigger_flush(timeout=120)
            app = [m for m in sink.flushed
                   if m.name.startswith("soak.")]
            # exactness: this interval's keys only, totals exact
            assert all(m.name.startswith(f"soak.{it}.") for m in app), (
                sorted({m.name.split(".")[1] for m in app}))
            assert sum(m.value for m in app) == 2.0 * per
            assert len(app) == 50
            # key table reset: live counters == this interval's keys (+
            # self-telemetry), never the cumulative key count
            live = len(srv.aggregator.table.get_meta("counter"))
            assert live < 50 + 40, f"interval {it}: table not resetting"
            if it == 3:
                gc.collect()
                baseline_objects = len(gc.get_objects())
        gc.collect()
        growth = len(gc.get_objects()) - baseline_objects
        # 8 more intervals after the baseline must not accrete per-interval
        # state (allow slack for logging/queue internals)
        assert growth < 20_000, f"object growth {growth} over 8 intervals"
        assert srv.packets_dropped == 0
    finally:
        srv.shutdown()


def test_flush_watchdog_aborts_on_wedged_flush_worker(tmp_path):
    """Crash-only semantics (reference server.go:900 FlushWatchdog): a
    wedged flush worker must abort the PROCESS (exit 3) rather than let
    the server silently stop reporting. Subprocess: tiny interval,
    watchdog budget, a PLUGIN whose flush blocks forever (sinks cannot
    wedge the worker — per-sink flush threads are joined with a budget
    of one flush interval, server._do_flush; plugins run inline
    post-flush and are exactly what the watchdog protects against)."""
    script = tmp_path / "wedge.py"
    script.write_text(r"""
import os, sys, threading, time
sys.path.insert(0, %r)
from veneur_tpu.config import Config
from veneur_tpu.server.server import Server

from veneur_tpu.sinks.debug import DebugMetricSink

class WedgedPlugin:
    name = "wedged"
    def flush(self, metrics):
        # marker proves the WEDGE (not first-flush compile) trips the
        # watchdog: the budget below is far above compile time, so rc 3
        # can only happen after this plugin has started blocking
        print("WEDGE-REACHED", flush=True)
        time.sleep(3600)

srv = Server(Config(interval="2s", hostname="w",
                    flush_watchdog_missed_flushes=15,
                    statsd_listen_addresses=[], percentiles=[0.5],
                    aggregates=["count"],
                    tpu_counter_capacity=256, tpu_gauge_capacity=64,
                    tpu_status_capacity=16, tpu_set_capacity=16,
                    tpu_histo_capacity=64),
             metric_sinks=[DebugMetricSink()],
             plugins=[WedgedPlugin()])
srv.start()
# the ticker flushes; self-telemetry gives the sink metrics to wedge on
time.sleep(90)
print("watchdog did not fire", flush=True)
sys.exit(0)
""" % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, env=env,
                          timeout=150)
    assert proc.returncode == 3, (proc.returncode, proc.stderr[-500:])
    assert "flush watchdog" in proc.stderr
    assert "WEDGE-REACHED" in proc.stdout


def test_wedged_sink_does_not_block_shutdown(tmp_path):
    """A sink that blows its per-flush join budget leaves a dangling
    thread; it must be daemon so process exit is clean (rc 0), not a
    hang or teardown abort."""
    script = tmp_path / "slowsink.py"
    script.write_text(r"""
import sys, time
sys.path.insert(0, %r)
from veneur_tpu.config import Config
from veneur_tpu.server.server import Server
from veneur_tpu.sinks.base import MetricSink

class SlowSink(MetricSink):
    name = "slow"
    def flush(self, metrics):
        print("SINK-WEDGED", flush=True)
        time.sleep(3600)

srv = Server(Config(interval="1s", hostname="w",
                    statsd_listen_addresses=[], percentiles=[0.5],
                    aggregates=["count"],
                    tpu_counter_capacity=256, tpu_gauge_capacity=64,
                    tpu_status_capacity=16, tpu_set_capacity=16,
                    tpu_histo_capacity=64),
             metric_sinks=[SlowSink()])
srv.start()
import threading
# wait until the wedge has provably been skipped twice: flushes keep
# completing AND later intervals skip the wedged sink
deadline = time.time() + 90
while srv.sink_flushes_skipped < 2 and time.time() < deadline:
    time.sleep(0.2)
assert srv.sink_flushes_skipped >= 2, (
    srv.sink_flushes_skipped, srv.flush_count)
assert srv.flush_count >= 3, "flushes stalled behind the wedged sink"
slow_threads = sum(1 for t in threading.enumerate()
                   if getattr(t, "_target", None) is not None
                   and "flush_sink" in getattr(t._target, "__name__", ""))
assert slow_threads <= 1, f"{slow_threads} dangling sink threads"
srv.shutdown()
print("CLEAN-EXIT", flush=True)
""" % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, env=env,
                          timeout=150)
    assert proc.returncode == 0, (proc.returncode, proc.stderr[-500:])
    assert "CLEAN-EXIT" in proc.stdout


def test_soak_sharded_mesh_all_types():
    """The soak story on the production multi-device path: a sharded
    (replica, shard) mesh server over the virtual 8-device CPU mesh,
    every metric type live, 4 intervals of rotating keys — exactness
    for counters/gauges, estimate envelopes for sets/timers, and a
    clean table reset every interval (the worker.go:498 contract on the
    shard_map backend)."""
    from tests.test_sharded_server import sharded_config

    sink = DebugMetricSink()
    srv = Server(sharded_config(interval="600s"), metric_sinks=[sink])
    srv.start()
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        addr = srv.local_addr()
        rng = np.random.default_rng(11)
        for it in range(4):
            sink.flushed.clear()
            base = srv.aggregator.processed
            vals = rng.uniform(1, 100, 48)
            lines = ([b"sk%d.c.%d:3|c" % (it, i) for i in range(24)]
                     + [f"sk{it}.t:{v:.3f}|ms".encode() for v in vals]
                     + [b"sk%d.s:u%d|s" % (it, i) for i in range(20)]
                     + [b"sk%d.g:%d|g" % (it, it + 7)])
            for i in range(0, len(lines), 20):
                s.sendto(b"\n".join(lines[i:i + 20]), addr)
            _wait_processed(srv, base + len(lines))
            assert srv.trigger_flush(timeout=180)
            m = {x.name: x for x in sink.flushed
                 if x.name.startswith("sk")}
            # this interval's keys ONLY — carry-over shows as sk<it-1> keys
            assert all(k.startswith(f"sk{it}.") for k in m), sorted(m)[:6]
            for i in range(24):
                assert m[f"sk{it}.c.{i}"].value == 3.0
            assert m[f"sk{it}.g"].value == it + 7.0
            assert m[f"sk{it}.t.count"].value == 48.0
            assert m[f"sk{it}.s"].value == pytest.approx(20, abs=3)
            p50 = m[f"sk{it}.t.50percentile"].value
            assert abs(p50 - np.percentile(vals, 50)) / 100.0 < 0.05
    finally:
        s.close()
        srv.shutdown()


def test_combined_storm_exact_totals():
    """Metrics, service checks, and events from concurrent sender
    threads with concurrent ticker-style flushes: counter totals must
    stay EXACT across interval swaps and service checks must flush, with
    zero internal errors (one flush worker, many writers — the
    concurrency shape production runs; events ride along to exercise
    the buffer path under contention)."""
    import threading

    msink = DebugMetricSink()
    srv = Server(small_config(
        tpu_counter_capacity=1024, tpu_histo_capacity=256,
        tpu_set_capacity=64, tpu_gauge_capacity=128),
        metric_sinks=[msink])
    srv.start()
    addr = srv.local_addr()
    try:
        errors = []

        def storm(tid):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                for it in range(3):
                    for i in range(200):
                        s.sendto(b"st%d.c%d:2|c" % (tid, i % 40), addr)
                        if i % 7 == 0:
                            s.sendto(b"st%d.t:%d|ms" % (tid, i), addr)
                        if i % 60 == 0:
                            s.sendto(b"_e{5,5}:hello|world", addr)
                            s.sendto(b"_sc|st%d.chk|0|m:ok" % tid, addr)
                    time.sleep(0.03)
            except Exception as e:
                errors.append(e)
            finally:
                s.close()

        flush_oks = []

        def flusher():
            for _ in range(5):
                time.sleep(0.4)
                flush_oks.append(srv.trigger_flush(timeout=120))

        ts = [threading.Thread(target=storm, args=(t,)) for t in range(3)]
        ts.append(threading.Thread(target=flusher))
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors, errors
        assert all(flush_oks), flush_oks
        # drain by the PROCESSED counter (works on the native-reader
        # path too, where UDP datagrams bypass packet_queue): per
        # thread-iteration 200 counters + 29 timers + 4 service checks
        want_processed = 3 * 3 * (200 + 29 + 4)
        deadline = time.time() + 60
        while time.time() < deadline \
                and srv.aggregator.processed < want_processed \
                and srv.packets_dropped == 0:
            time.sleep(0.05)
        assert srv.trigger_flush(timeout=120)
        if srv.packets_dropped:
            pytest.skip(f"loopback dropped {srv.packets_dropped} "
                        "datagrams; exactness unverifiable this run")
        import re
        counter_name = re.compile(r"st\d+\.c\d+$")
        total = sum(m.value for m in msink.flushed
                    if counter_name.match(m.name))
        expect = 3 * 3 * 200 * 2
        assert srv.internal_errors == 0
        assert srv.aggregator.dropped_capacity == 0
        assert total == expect, (total, expect)
        # service checks flushed through the status path under contention
        chk = {m.name for m in msink.flushed
               if m.name.endswith(".chk")}
        assert chk == {f"st{t}.chk" for t in range(3)}, chk
    finally:
        srv.shutdown()
