"""Multi-ring pre-sharded ingest parity (round 14, README §Host feed
architecture): the C++ route digest is byte-identical to the Python
recipe, the pre-sharded emit produces exactly the state _split_shards
did, and the multi-ring engine's concurrent drain preserves per-key
flush values plus the datagrams == toolong + admitted + shed invariant
folded across every ring."""

import threading
import time

import numpy as np
import pytest

from veneur_tpu import native
from veneur_tpu.aggregation.host import BatchSpec
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.collective import keytable as ckt

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native engine not buildable")

SPEC = TableSpec(counter_capacity=256, gauge_capacity=64,
                 status_capacity=16, set_capacity=32, histo_capacity=64)
BSPEC = BatchSpec(counter=512, gauge=128, status=16, set=64, histo=512)


# -- routing digest parity ----------------------------------------------------

def test_route_digest_parity_fuzz():
    """vt_route_digest == collective.keytable.route_digest over a fuzz
    corpus including raw-byte names that only surrogateescape can round
    trip — the pre-sharded emit groups by this digest, so one divergent
    key would land rows on the wrong shard."""
    rng = np.random.default_rng(14)
    kinds = ["counter", "gauge", "set", "histogram", "timer"]
    cases = [("counter", "plain.name", ""),
             ("gauge", "tagged", "env:prod,team:infra"),
             ("set", b"\xff\xfe raw".decode("utf-8", "surrogateescape"),
              b"k:\xc3\x28".decode("utf-8", "surrogateescape")),
             ("timer", "unicode.\u00e9\u4e2d", "t:\u2603")]
    for i in range(300):
        raw = bytes(rng.integers(1, 256, rng.integers(1, 40)).tolist())
        name = raw.decode("utf-8", "surrogateescape")
        tags = raw[::-1].decode("utf-8", "surrogateescape") \
            if i % 3 else ""
        cases.append((kinds[i % len(kinds)], name, tags))
    for kind, name, joined in cases:
        assert native.route_digest(kind, name, joined) == \
            ckt.route_digest(kind, name, joined), (kind, name, joined)


# -- pre-sharded emit vs _split_shards ---------------------------------------

def _corpus(n=240):
    """Mixed-kind lines over few enough keys that gauges repeat (the
    last-write-wins ordering _split_shards' stable argsort preserves and
    the pre-sharded counting sort must too)."""
    rng = np.random.default_rng(7)
    lines = []
    for i in range(n):
        r = i % 6
        if r < 2:
            lines.append(b"ps.c%d:2|c|#env:prod" % (i % 37))
        elif r == 2:
            lines.append(b"ps.g%d:%d|g" % (i % 9, rng.integers(0, 100)))
        elif r == 3:
            lines.append(b"ps.s%d:user-%d|s" % (i % 5, i % 40))
        elif r == 4:
            lines.append(b"ps.h%d:%d|ms" % (i % 11, 1 + i % 50))
        else:
            lines.append(b"ps.c%d:1|c" % (i % 37))
    return lines


def _state_leaves(state):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(state)]


def test_preshard_state_byte_identical_to_split_shards():
    """Same single-threaded feed through preshard=True and =False
    NativeShardedAggregators: detached interval state is byte-identical
    leaf for leaf — the C++ counting sort is a drop-in for the numpy
    argsort/searchsorted split, including gauge arrival order."""
    from veneur_tpu.server.native_aggregator import NativeShardedAggregator
    aggs = [NativeShardedAggregator(SPEC, BSPEC, n_shards=4, preshard=p)
            for p in (False, True)]
    buf = b"\n".join(_corpus())
    for agg in aggs:
        agg.feed(buf)
    states = []
    for agg in aggs:
        state, table = agg.swap()
        states.append(state)
        assert table.get_meta("counter")   # corpus actually landed
    for a, b in zip(_state_leaves(states[0]), _state_leaves(states[1])):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b)


def test_preshard_server_flush_parity(tmp_path):
    """Server-level flush parity across backends on identical UDP
    traffic: single-device native, sharded with the numpy split, sharded
    with the C++ pre-sharded emit — same (name, value) sets out of the
    sink."""
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink
    from tests.test_server import _send_udp, _wait_processed, small_config
    lines = _corpus(120)
    flushed = []
    for kw in ({}, {"tpu_n_shards": 2},
               {"tpu_n_shards": 2, "native_preshard_enabled": True}):
        sink = DebugMetricSink()
        srv = Server(small_config(**kw), metric_sinks=[sink])
        srv.start()
        try:
            if kw.get("tpu_n_shards"):
                assert srv.aggregator.preshard == bool(
                    kw.get("native_preshard_enabled"))
            _send_udp(srv.local_addr(), lines)
            _wait_processed(srv, len(lines))
            srv.trigger_flush(wait=True)
            flushed.append({(m.name, tuple(m.tags)): round(m.value, 4)
                            for m in sink.flushed
                            if not m.name.startswith("veneur.")})
        finally:
            srv.shutdown()
    assert flushed[1] == flushed[2]         # preshard == numpy split
    assert flushed[0] == flushed[1]         # sharded == single device


def test_preshard_collective_attached_flush_parity():
    """A preshard local server attached to a co-located collective tier:
    the pre-sharded emit rides the local flush path into the tier's
    routed absorb, and the global flush sees the exact totals."""
    from veneur_tpu.collective.tier import CollectiveGlobalTier
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink
    from tests.test_server import (_send_udp, _wait_processed, by_name,
                                   small_config)
    gsink = DebugMetricSink()
    gsrv = Server(small_config(collective_enabled=True,
                               collective_group="ps1",
                               tpu_n_shards=4, tpu_n_replicas=2),
                  metric_sinks=[gsink])
    assert isinstance(gsrv.aggregator, CollectiveGlobalTier)
    gsrv.start()
    lsink = DebugMetricSink()
    lsrv = Server(small_config(collective_attach="ps1", tpu_n_shards=2,
                               native_preshard_enabled=True),
                  metric_sinks=[lsink])
    try:
        assert lsrv.aggregator.preshard
        lsrv.start()
        lines = ([b"psc.count:3|c|#veneurglobalonly"] * 5
                 + [b"psc.timer:%d|ms" % v for v in (10, 20, 30, 40)])
        _send_udp(lsrv.local_addr(), lines)
        _wait_processed(lsrv, len(lines))
        lsrv.trigger_flush()
        assert gsrv.aggregator.absorbed_rows > 0
        gsink.flushed.clear()
        gsrv.trigger_flush()
        m = by_name(gsink.flushed)
        assert m["psc.count"].value == 15.0
        assert m["psc.timer.50percentile"].value == 25.0
    finally:
        lsrv.shutdown()
        gsrv.shutdown()


# -- multi-ring engine --------------------------------------------------------

def _per_key(state, table):
    """(kind, name, joined_tags) -> flush-relevant value, computed from
    the detached interval state. Counters/histo aggregates fold the
    two-float accumulators; sets compare packed HLL registers (max-merge
    is order-free); histo digests compare scalar aggregates only (the
    cell layout depends on compaction cadence, the quantile answer does
    not)."""
    out = {}
    acc, hi, lo = (np.asarray(state.counter_acc),
                   np.asarray(state.counter_hi),
                   np.asarray(state.counter_lo))
    for slot, m in table.get_meta("counter"):
        out[("counter", m.name, m.joined_tags)] = float(
            acc[slot] + hi[slot] + lo[slot])
    g = np.asarray(state.gauge)
    for slot, m in table.get_meta("gauge"):
        out[("gauge", m.name, m.joined_tags)] = float(g[slot])
    hll = np.asarray(state.hll)
    for slot, m in table.get_meta("set"):
        out[("set", m.name, m.joined_tags)] = hll[slot].tobytes()
    cnt = (np.asarray(state.h_count_acc) + np.asarray(state.h_count_hi)
           + np.asarray(state.h_count_lo))
    sm = (np.asarray(state.h_sum_acc) + np.asarray(state.h_sum_hi)
          + np.asarray(state.h_sum_lo))
    mn, mx = np.asarray(state.h_min), np.asarray(state.h_max)
    for slot, m in table.get_meta("histogram"):
        out[("histo", m.name, m.joined_tags)] = (
            float(cnt[slot]), float(sm[slot]),
            float(mn[slot]), float(mx[slot]))
    return out


def _drain_rings(agg, expected, timeout=60.0):
    deadline = time.time() + timeout
    while agg.eng.stats()["processed"] < expected:
        agg.pump(10)
        if time.time() > deadline:
            raise TimeoutError(
                f"only {agg.eng.stats()['processed']}/{expected} parsed")
    agg.pump(0)


def test_multiring_per_key_flush_parity_and_accounting():
    """4-ring concurrent drain vs a serial single-engine feed of the
    SAME lines: per-key flush values identical (keys route to rings by
    key so per-key arrival order — gauge LWW — rides one FIFO ring), and
    every datagram pushed is exactly one of toolong/admitted/shed with
    each term folded across all rings."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    lines = _corpus(360)
    ref = NativeAggregator(SPEC, BSPEC)
    ref.feed(b"\n".join(lines))
    ref_state, ref_table = ref.swap()

    agg = NativeAggregator(SPEC, BSPEC)
    agg.rings_start(4)
    agg.admission_set(True, 0, 1e9, 1e9, [])
    try:
        for ln in lines:
            ring = hash(ln.split(b":", 1)[0]) % 4
            assert agg.eng.rings_inject(ring, ln)
        _drain_rings(agg, len(lines))
        datagrams = toolong = admitted = shed = 0
        for r in range(agg.eng.n_rings):
            c = agg.eng.ring_counters_one(r)
            datagrams += c["datagrams"]
            toolong += c["toolong"]
            adm = agg.eng.ring_admission_drain_one(r)
            admitted += sum(adm["admitted"].values())
            shed += sum(adm["shed"].values())
        assert datagrams == len(lines)
        assert datagrams == toolong + admitted + shed
        state, table = agg.swap()
    finally:
        agg.readers_stop()
    assert _per_key(state, table) == _per_key(ref_state, ref_table)


def test_multiring_swap_quiesce_under_concurrent_inject():
    """Swaps racing live injector threads lose and double-count nothing:
    the summed counter mass over every detached interval equals the
    number of injected lines exactly (each line is +1), proving the
    pause barrier quiesces parse mid-stream and leftovers land in the
    NEXT interval rather than vanishing."""
    from veneur_tpu.server.native_aggregator import NativeAggregator
    agg = NativeAggregator(SPEC, BSPEC)
    agg.rings_start(4)
    n_per_thread = 600
    sent = [0, 0]
    stop = threading.Event()

    def injector(t):
        from veneur_tpu.native import INJECT_BACKPRESSURE
        for i in range(n_per_thread):
            ln = b"mr.t%d.k%d:1|c" % (t, i % 19)
            while agg.eng.rings_inject((t * 2 + i) % 4,
                                       ln) == INJECT_BACKPRESSURE:
                time.sleep(0.001)   # ring full: uncounted, retry exact
            sent[t] += 1
        stop.set() if sent[0] + sent[1] == 2 * n_per_thread else None

    threads = [threading.Thread(target=injector, args=(t,))
               for t in (0, 1)]
    mass = 0.0

    def interval_mass(state):
        return float(np.sum(np.asarray(state.counter_acc))
                     + np.sum(np.asarray(state.counter_hi))
                     + np.sum(np.asarray(state.counter_lo)))

    try:
        for t in threads:
            t.start()
        # swap repeatedly while the injectors are live
        for _ in range(6):
            agg.pump(5)
            state, _table = agg.swap()
            mass += interval_mass(state)
        for t in threads:
            t.join()
        _drain_rings(agg, 2 * n_per_thread)
        state, _table = agg.swap()
        mass += interval_mass(state)
    finally:
        agg.readers_stop()
    assert mass == float(2 * n_per_thread)


def test_multiring_server_reader_rings():
    """Server wiring: reader_rings=4 starts the vrm engine under the
    real UDP listener, per-ring stats rows exist, and flush totals are
    exact."""
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink
    from tests.test_server import (_send_udp, _wait_processed, by_name,
                                   small_config)
    sink = DebugMetricSink()
    srv = Server(small_config(reader_rings=4), metric_sinks=[sink])
    srv.start()
    try:
        assert srv.aggregator.eng.n_rings == 4
        lines = [b"mrs.c:1|c" for _ in range(100)]
        _send_udp(srv.local_addr(), lines)
        _wait_processed(srv, len(lines))
        rows = srv.aggregator.ring_stats_per_ring()
        assert len(rows) == 4
        assert sum(r["datagrams"] for r in rows) \
            == srv.aggregator.reader_counters()["datagrams"]
        srv.trigger_flush(wait=True)
        m = by_name(sink.flushed)
        assert m["mrs.c"].value == 100.0
    finally:
        srv.shutdown()


# -- non-native reader fold batching (satellite 5) ---------------------------

class _CountingLock:
    def __init__(self):
        self._lock = threading.Lock()
        self.acquisitions = 0

    def __enter__(self):
        self._lock.acquire()
        self.acquisitions += 1
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False

    # threading.Lock API used elsewhere in the server
    def acquire(self, *a, **kw):
        self.acquisitions += 1
        return self._lock.acquire(*a, **kw)

    def release(self):
        self._lock.release()


def test_udp_reader_folds_counters_batched():
    """The Python reader path folds its shared counters ONCE per recv
    batch, not once per datagram: with the fold lock held while a burst
    lands in the kernel queue, the readers catch up in a handful of
    acquisitions, and the counters still come out exact."""
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink
    from tests.test_server import _send_udp, _wait_processed, small_config
    srv = Server(small_config(native_udp_readers=False, num_readers=2),
                 metric_sinks=[DebugMetricSink()])
    srv.start()
    try:
        assert not srv._native_readers_active
        lock = _CountingLock()
        srv._reader_fold_lock = lock
        n = 120
        with lock._lock:   # block the fold, not the kernel queue
            for i in range(n):
                _send_udp(srv.local_addr(), [b"fold.c%d:1|c" % (i % 8)])
            time.sleep(0.3)  # let readers block on the held fold lock
            base = lock.acquisitions
        _wait_processed(srv, n)
        deadline = time.time() + 10.0
        while srv._packets_received < n and time.time() < deadline:
            time.sleep(0.02)
        # exactness first: every datagram counted despite the batching
        assert srv._packets_received == n
        # batching: the burst drained in far fewer folds than datagrams
        # (each recv-loop iteration folds once for up to 64 datagrams)
        assert lock.acquisitions - base < n
    finally:
        srv.shutdown()
