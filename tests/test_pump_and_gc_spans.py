"""The host's time named where the work happens: the engine's pump
counters (dogstatsd.cpp PumpCounters, read through `ring_stats()`), the
interpreter's collections as `gc.collect` records and totals
(observability/hostspans.py), and the per-layer readers built on them
(perfbench/layer_metrics/: pump_busy_share, pump_wait_share,
parse_key_share, key_probes_per_lookup, gc_window_share, gc_tick_ms) on
hand-made inputs."""

import gc
import os
import socket
import sys
import threading
import time

import pytest

from veneur_tpu import native
from veneur_tpu.aggregation.host import BatchSpec
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.observability import hostspans as H

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")

SPEC = TableSpec(counter_capacity=4096, gauge_capacity=64,
                 status_capacity=16, set_capacity=32, histo_capacity=64)
BSPEC = BatchSpec(counter=8192, gauge=128, status=16, set=64, histo=256)
PUMP_KEYS = ("pump_wait_ns", "pump_busy_ns", "parse_sampled_ns",
             "parse_key_sampled_ns", "parse_sampled_datagrams",
             "key_lookups_sampled", "key_probes_sampled")
needs_engine = pytest.mark.skipif(not native.available(),
                                  reason="native engine not buildable")


def _datagram(i, lines=20):
    return b"\n".join(b"pg.c.%d:1|c|#k:v" % (i * lines + j)
                      for j in range(lines))


# -- the engine's pump counters ---------------------------------------------

@pytest.fixture
def reader():
    """A NativeIngest with one reader on an ephemeral loopback port."""
    eng = native.NativeIngest(SPEC, BSPEC)
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    eng.readers_start([rx.fileno()])
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        yield eng, tx, rx.getsockname()
    finally:
        eng.readers_stop()
        rx.close()
        tx.close()


def _pump_all(eng, n):
    """Pump until n datagrams have been parsed (reader threads deliver
    asynchronously)."""
    got, deadline = 0, time.monotonic() + 20
    while got < n and time.monotonic() < deadline:
        full, st = eng.pump(20)
        got += st["parsed"]
        if full:
            eng.reset()
    return got


@needs_engine
def test_pump_counts_busy_and_samples_one_datagram_in_64(reader):
    eng, tx, addr = reader
    before = eng.ring_stats()
    assert all(before[k] == 0 for k in PUMP_KEYS)
    n = 130
    for i in range(n):
        tx.sendto(_datagram(i), addr)
    assert _pump_all(eng, n) == n
    st = eng.ring_stats()
    assert st["pump_busy_ns"] > 0
    # datagrams 0, 64 and 128 of the group's stream are the sampled ones
    assert st["parse_sampled_datagrams"] == 3
    assert 0 < st["parse_key_sampled_ns"] <= st["parse_sampled_ns"]
    assert st["parse_sampled_ns"] <= st["pump_busy_ns"]
    # their 60 lines' lookups, each reading at least its key's home entry
    assert st["key_lookups_sampled"] == 3 * 20
    assert st["key_probes_sampled"] >= st["key_lookups_sampled"]


@needs_engine
def test_pump_on_an_empty_ring_counts_its_wait(reader):
    eng, _tx, _addr = reader
    w0 = eng.ring_stats()["pump_wait_ns"]
    t0 = time.monotonic_ns()
    full, st = eng.pump(60)
    took = time.monotonic_ns() - t0
    assert not full and st["parsed"] == 0
    waited = eng.ring_stats()["pump_wait_ns"] - w0
    assert 50e6 <= waited <= took
    # the call's rest is busy, and small beside the wait
    assert eng.ring_stats()["pump_busy_ns"] < waited


@needs_engine
def test_multi_ring_workers_count_the_same_summed_across_rings():
    eng = native.NativeIngest(SPEC, BSPEC)
    eng.rings_start(2)
    try:
        n = 140
        for i in range(n):
            assert eng.rings_inject(i % 2, _datagram(i, lines=2)) == 1
        deadline = time.monotonic() + 20
        while (eng.ring_stats()["pump_batches"] < n
               and time.monotonic() < deadline):
            time.sleep(0.01)
        time.sleep(0.15)         # each worker waits again on its empty ring
        per = eng.ring_stats_per_ring()
        agg = eng.ring_stats()
        assert agg["pump_batches"] == n
        for k in PUMP_KEYS:
            assert agg[k] == sum(r[k] for r in per), k
        # each ring's worker began 70 datagrams: its 0th and 64th sampled
        assert [r["parse_sampled_datagrams"] for r in per] == [2, 2]
        for r in per:
            assert r["pump_wait_ns"] > 0 and r["pump_busy_ns"] > 0
            assert 0 < r["parse_key_sampled_ns"] <= r["parse_sampled_ns"]
            # two lines a sampled datagram; a key new to the ring reads
            # its replica, then the master's index
            assert r["key_lookups_sampled"] == 4
            assert r["key_probes_sampled"] >= 2 * r["key_lookups_sampled"]
    finally:
        eng.readers_stop()


@needs_engine
def test_native_aggregator_ring_stats_carry_the_pump_keys():
    from veneur_tpu.server.native_aggregator import NativeAggregator
    agg = NativeAggregator(SPEC, BSPEC)
    # what the harness reads as its ring.* counters
    assert set(PUMP_KEYS) <= set(agg.ring_stats())


# -- the interpreter's collections --------------------------------------------

def _mark():
    H.record("test.mark", 0, 0)
    return H.records()[-1].index + 1


def _mine(mark, name=None):
    return [r for r in H.records() if r.index >= mark
            and (name is None or r.name == name)]


def test_full_collection_is_one_record_under_the_open_span():
    mark = _mark()
    H.set_thread_seq(17)
    try:
        with H.span("gc.outer", seq=4) as outer:
            gc.collect()
    finally:
        H.set_thread_seq(None)
    full = [r for r in _mine(mark, H.GC_COLLECT) if r.tag[0] == 2]
    assert len(full) == 1
    (r,) = full
    assert r.seq is None                 # no interval's, not the span's
    assert r.parent == outer.index
    assert r.thread == threading.current_thread().name
    assert outer.start_ns <= r.start_ns < r.end_ns <= (
        outer.start_ns + outer.ns)
    assert isinstance(r.tag[1], int)     # what it collected
    # a record is not a child span: the phase timers do not see it
    assert H.GC_COLLECT not in outer.children


def test_open_pump_run_stays_one_record_across_a_collection():
    mark = _mark()
    H.run_call("t.gcpump")
    H.run_returned()
    gc.collect()
    H.run_call("t.gcpump")
    H.run_returned()
    H.close_run()
    runs = _mine(mark, "t.gcpump")
    assert len(runs) == 1 and runs[0].tag[0] == 2
    (col,) = [r for r in _mine(mark, H.GC_COLLECT) if r.tag[0] == 2]
    assert runs[0].start_ns <= col.start_ns <= col.end_ns <= runs[0].end_ns
    assert col.parent is None


def test_gc_totals_only_grow_and_count_every_generation():
    before, stats0 = H.gc_totals(), gc.get_stats()
    assert len(before) == 3
    junk = [[i] for i in range(200_000)]     # collections on the way
    gc.collect(0)
    gc.collect(2)
    del junk
    after, stats1 = H.gc_totals(), gc.get_stats()
    for (c0, ns0), (c1, ns1) in zip(before, after):
        assert c1 >= c0 and ns1 >= ns0
    assert after[0][0] > before[0][0] and after[2][1] > before[2][1]
    # every collection CPython counts is one the callback timed
    assert [c1 - c0 for (c0, _), (c1, _) in zip(before, after)] == [
        b["collections"] - a["collections"] for a, b in zip(stats0, stats1)]


def test_young_collection_leaves_a_record_only_when_long(monkeypatch):
    mark = _mark()
    monkeypatch.setattr(H, "GC_RECORD_NS", 0)
    gc.collect(0)
    monkeypatch.setattr(H, "GC_RECORD_NS", 10**15)
    gc.collect(1)
    gens = [r.tag[0] for r in _mine(mark, H.GC_COLLECT)]
    assert 0 in gens and 1 not in gens


def test_collection_on_another_thread_is_recorded_there():
    mark = _mark()
    t = threading.Thread(target=gc.collect, name="gc-elsewhere")
    t.start()
    t.join(10)
    (r,) = [r for r in _mine(mark, H.GC_COLLECT) if r.tag[0] == 2]
    assert r.thread == "gc-elsewhere" and r.parent is None


def test_runtime_gauges_report_the_pause():
    from veneur_tpu.utils.statsd_emit import runtime_gauges
    gc.collect()
    rss, ngc, pause = runtime_gauges()
    assert rss > 0 and ngc > 0
    assert pause >= sum(ns for _c, ns in H.gc_totals()) - 1 and pause > 0


# -- the per-layer readers on hand-made inputs --------------------------------

@pytest.fixture(scope="module")
def bench():
    """perfbench/readers.py and span_reduce.py by their plain names, as
    run.py imports them."""
    sys.path.insert(0, BENCH)
    try:
        import readers
        import span_reduce
        yield readers, span_reduce
    finally:
        sys.path.remove(BENCH)


MS = 1_000_000


def _ctx(start=None, end=None, window_ms=2000):
    pseudo = {"window_ns": window_ms * MS}
    return {"counters_start": {**(start or {}), "window_ns": 0},
            "counters_end": {**(end or {}), **pseudo}}


@pytest.mark.parametrize("metric,start,end,want", [
    ("pump_busy_share", {"ring.pump_busy_ns": 100 * MS},
     {"ring.pump_busy_ns": 1700 * MS}, 80.0),
    ("pump_wait_share", {"ring.pump_wait_ns": 0},
     {"ring.pump_wait_ns": 50 * MS}, 2.5),
    ("parse_key_share",
     {"ring.parse_sampled_ns": 1000, "ring.parse_key_sampled_ns": 300},
     {"ring.parse_sampled_ns": 5000, "ring.parse_key_sampled_ns": 1500},
     30.0),
    ("key_probes_per_lookup",
     {"ring.key_lookups_sampled": 10, "ring.key_probes_sampled": 12},
     {"ring.key_lookups_sampled": 410, "ring.key_probes_sampled": 452},
     1.1),
])
def test_counter_ratio_metrics(bench, metric, start, end, want):
    readers, _ = bench
    assert readers.read(metric, _ctx(start, end)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["pump_busy_share", "pump_wait_share",
                                    "parse_key_share",
                                    "key_probes_per_lookup"])
def test_counter_ratio_metrics_absent_without_the_counters(bench, metric):
    readers, _ = bench
    assert readers.read(metric, _ctx()) is None


def _rec(span_reduce, name, a, b, index, seq=None, thread="pipeline",
         tag=None):
    return span_reduce.Rec(name, seq, thread, a * MS, b * MS, None, index,
                           tag)


def _ticks(span_reduce, gcs=()):
    """Swaps ending at 0, 1000, 2000 and 3000 ms: with a window of
    2000 ms it runs 1000-3000, and intervals 2 and 3 are swapped inside
    it. An interval's way to the sink runs from its swap's start (10 ms
    before its end) to its sink_fanout's end, 100 ms after."""
    R = lambda *a, **k: _rec(span_reduce, *a, **k)  # noqa: E731
    out, idx = [], 0
    for seq in range(4):
        at = 1000 * seq - 10
        out += [R("swap", at, at + 10, idx, seq),
                R("frame_build", at + 40, at + 70, idx + 1, seq, "worker"),
                R("sink_fanout", at + 80, at + 110, idx + 2, seq, "worker")]
        idx += 3
    for a, b, thread in gcs:
        out.append(R("gc.collect", a, b, idx, None, thread, (2, 0)))
        idx += 1
    return out


@pytest.mark.parametrize("gcs,share,tick_ms", [
    # none in the window: both read 0, not nothing
    ((), 0.0, 0.0),
    # one straddles the window's start (10 of its 20 ms inside), one its
    # end (5 of 10 inside) and lies on interval 3's way, 2990-3100
    (((990, 1010, "pipeline"), (2995, 3005, "pipeline")), 0.75, 5.0),
    # one between two stages of interval 2's tick (frame_build ends at
    # 2060, sink_fanout starts at 2070), on the flush worker; one in the
    # stream, under no tick, with one overlapping it on another thread
    (((2062, 2068, "worker"), (1500, 1530, "pipeline"),
      (1520, 1540, "worker")), 2.3, 3.0),
])
def test_gc_readers_on_hand_made_records(bench, monkeypatch, gcs, share,
                                          tick_ms):
    readers, span_reduce = bench
    recs = _ticks(span_reduce, gcs)
    monkeypatch.setattr(span_reduce, "program_records", lambda: recs)
    ctx = _ctx(window_ms=2000)
    assert readers.read("gc_window_share", ctx) == pytest.approx(share)
    assert readers.read("gc_tick_ms", ctx) == pytest.approx(tick_ms)


@pytest.mark.parametrize("metric", ["gc_window_share", "gc_tick_ms"])
def test_gc_readers_absent_where_nothing_records_collections(
        bench, monkeypatch, metric):
    readers, span_reduce = bench
    recs = _ticks(span_reduce)
    monkeypatch.setattr(span_reduce, "program_records", lambda: recs)
    monkeypatch.delattr(H, "gc_totals")
    assert readers.read(metric, _ctx()) is None
    monkeypatch.setattr(span_reduce, "program_records", lambda: None)
    assert readers.read(metric, _ctx()) is None
