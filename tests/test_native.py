"""Native C++ ingest engine: parity with the Python parser + key table.

Rung 1.5 of the test strategy (SURVEY §4): kernel-vs-reference parity on
the same inputs."""

import numpy as np
import pytest

from veneur_tpu.aggregation.host import Batcher, BatchSpec, KeyTable
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.samplers import parser
from veneur_tpu import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native engine not buildable")

SPEC = TableSpec(counter_capacity=128, gauge_capacity=64,
                 status_capacity=16, set_capacity=32, histo_capacity=64)
BSPEC = BatchSpec(counter=256, gauge=128, status=16, set=64, histo=256)


def mk():
    return native.NativeIngest(SPEC, BSPEC)


def emit_arrays():
    return (np.full(BSPEC.counter, SPEC.counter_capacity, np.int32),
            np.zeros(BSPEC.counter, np.float32),
            np.full(BSPEC.gauge, SPEC.gauge_capacity, np.int32),
            np.zeros(BSPEC.gauge, np.float32),
            np.full(BSPEC.set, SPEC.set_capacity, np.int32),
            np.zeros(BSPEC.set, np.int32),
            np.zeros(BSPEC.set, np.uint8),
            np.full(BSPEC.histo, SPEC.histo_capacity, np.int32),
            np.zeros(BSPEC.histo, np.float32),
            np.zeros(BSPEC.histo, np.float32))


GOOD_PACKETS = [
    b"a.b.c:1|c",
    b"a.b.c:2.5|c|@0.5",
    b"gauge.x:-3.25|g",
    b"timer.t:101.5|ms",
    b"histo.h:7|h",
    b"dist.d:8|d",
    b"set.s:user-42|s",
    b"tagged:1|c|#env:prod,team:infra",
    b"tagged:1|c|#team:infra,env:prod",      # same key, different order
    b"scoped:4|g|#veneurlocalonly",
    b"scoped2:4|g|#a:b,veneurglobalonly,z:y",
    b"rate.tags:9|ms|@0.25|#k:v",
    b"tags.rate:9|ms|#k:v|@0.25",
]

BAD_PACKETS = [
    b"nocolon|c",
    b":1|c",
    b"novalue:|c",
    b"noname:1",
    b"x:1|",
    b"x:1|q",
    b"x:abc|c",
    b"x:1_0|c",
    b"x: 1|c",
    b"x:1 |c",
    b"x:inf|c",
    b"x:nan|g",
    b"x:0x1p3|c",
    b"x:1|c|@2",
    b"x:1|c|@0",
    b"x:1|c|@0.5|@0.5",
    b"x:1|c|#a:b|#c:d",
    b"x:1|c|",
    b"x:1|c||#a:b",
    b"x:1|c|zzz",
]


def test_parse_parity_good():
    """Every accepted packet lands in the same (kind, slot) as the Python
    KeyTable fed by the Python parser, with identical staged values."""
    eng = mk()
    table = KeyTable(SPEC)
    batcher = Batcher(SPEC, BSPEC)
    for pkt in GOOD_PACKETS:
        eng.feed(pkt)
        m = parser.parse_metric(pkt)
        slot = table.slot_for(m.type, m.name, m.tags, m.scope, m.digest)
        if m.type == "counter":
            batcher.add_counter(slot, m.value, m.sample_rate)
        elif m.type == "gauge":
            batcher.add_gauge(slot, m.value)
        elif m.type == "set":
            batcher.add_set(slot, str(m.value).encode())
        else:
            batcher.add_histo(slot, m.value, m.sample_rate)

    arrays = emit_arrays()
    nc, ng, ns, nh = eng.emit_into(arrays)
    (c_slot, c_inc, g_slot, g_val, s_slot, s_reg, s_rho,
     h_slot, h_val, h_wt) = arrays
    assert (nc, ng, ns, nh) == (batcher.nc, batcher.ng, batcher.ns,
                                batcher.nh)
    np.testing.assert_array_equal(c_slot[:nc], batcher.c_slot[:nc])
    np.testing.assert_allclose(c_inc[:nc], batcher.c_inc[:nc], rtol=1e-6)
    np.testing.assert_array_equal(g_slot[:ng], batcher.g_slot[:ng])
    np.testing.assert_allclose(g_val[:ng], batcher.g_val[:ng])
    np.testing.assert_array_equal(s_slot[:ns], batcher.s_slot[:ns])
    np.testing.assert_array_equal(s_reg[:ns], batcher.s_reg[:ns])
    np.testing.assert_array_equal(s_rho[:ns], batcher.s_rho[:ns])
    np.testing.assert_array_equal(h_slot[:nh], batcher.h_slot[:nh])
    np.testing.assert_allclose(h_val[:nh], batcher.h_val[:nh])
    np.testing.assert_allclose(h_wt[:nh], batcher.h_wt[:nh])

    # key metadata parity: same names/scopes/tags in same slots
    native_keys = {(k, s): (sc, n, t)
                   for k, s, sc, n, t, _imp in eng.drain_new_keys()}
    for kind_name in ("counter", "gauge", "set", "histogram"):
        for slot, meta in table.get_meta(kind_name):
            nk = native_keys[(meta.kind, slot)]
            assert nk[0] == meta.scope
            assert nk[1] == meta.name
            assert nk[2] == ",".join(meta.tags)


def test_parse_parity_bad():
    eng = mk()
    for pkt in BAD_PACKETS:
        with pytest.raises(parser.ParseError):
            parser.parse_metric(pkt)
        eng.feed(pkt)
    assert eng.stats()["parse_errors"] == len(BAD_PACKETS)
    assert eng.stats()["processed"] == 0


def test_randomized_digest_parity():
    """Randomized packets: the C++ fnv1a digest and sharding must place
    keys exactly where the Python path does (2-shard table)."""
    rng = np.random.default_rng(9)
    eng = native.NativeIngest(SPEC, BSPEC, n_shards=2)
    table = KeyTable(SPEC, n_shards=2)
    for i in range(200):
        name = f"m{rng.integers(0, 50)}.{rng.integers(0, 4)}"
        ntags = rng.integers(0, 4)
        tags = [f"t{rng.integers(0, 5)}:v{rng.integers(0, 3)}"
                for _ in range(ntags)]
        typ = ["c", "g", "ms", "h", "s"][rng.integers(0, 5)]
        val = "x" if typ == "s" else f"{rng.uniform(0, 100):.3f}"
        pkt = f"{name}:{val}|{typ}"
        if tags:
            pkt += "|#" + ",".join(tags)
        pkt_b = pkt.encode()
        eng.feed(pkt_b)
        m = parser.parse_metric(pkt_b)
        table.slot_for(m.type, m.name, m.tags, m.scope, m.digest)
    native_keys = {(k, s) for k, s, _, _, _, _ in eng.drain_new_keys()}
    python_keys = set()
    for kind_name in ("counter", "gauge", "set", "histogram"):
        for slot, meta in table.get_meta(kind_name):
            python_keys.add((meta.kind, slot))
    assert native_keys == python_keys


def test_specials_escalated():
    eng = mk()
    eng.feed(b"_e{5,5}:hello|world\n_sc|chk|1\nplain:1|c")
    assert eng.drain_specials() == [b"_e{5,5}:hello|world", b"_sc|chk|1"]
    assert eng.stats()["processed"] == 1


def test_batch_full_backpressure():
    eng = mk()
    lines = b"\n".join(b"k%d:1|c" % (i % 100)
                       for i in range(BSPEC.counter + 10))
    full, off = eng.feed(lines)
    assert full
    assert 0 < off < len(lines)
    assert eng.pending() == BSPEC.counter
    arrays = emit_arrays()
    nc, _, _, _ = eng.emit_into(arrays)
    assert nc == BSPEC.counter
    # the unconsumed tail resumes from the returned absolute offset —
    # same buffer, no re-slice copy
    full2, off2 = eng.feed(lines, off)
    assert not full2
    assert off2 == len(lines)
    nc2, _, _, _ = eng.emit_into(emit_arrays())
    assert nc2 == 10


def test_reset_keeps_keys_and_empties_the_live_list():
    """A flush boundary keeps a key and its slot: the second interval
    leaves no new-key record, and the key is live in it only once it has
    arrived again."""
    eng = mk()
    eng.feed(b"a:1|c\nb:1|c")
    first = eng.drain_new_keys()
    assert [(k, n) for k, _s, _sc, n, _t, _i in first] == [
        ("counter", "a"), ("counter", "b")]
    slot_of = {n: s for _k, s, _sc, n, _t, _i in first}
    assert eng.live_keys("counter")[0].tolist() == [slot_of["a"],
                                                    slot_of["b"]]
    eng.reset()
    assert eng.live_keys("counter")[0].tolist() == []
    assert eng.table_stats()["counter"][0] == 0
    eng.feed(b"b:1|c")
    assert eng.drain_new_keys() == []
    assert eng.live_keys("counter")[0].tolist() == [slot_of["b"]]
    assert eng.table_stats()["counter"][0] == 1
    eng.reset()
    assert eng.key_counters() == {"keys_live": 3, "keys_new": 2,
                                  "keys_evicted": 0}


def test_native_udp_reader_group_lossless_and_counted():
    """C++ recvmmsg readers: a multi-socket burst is fully received,
    parsed, and counted (packets_received from the reader group's
    counters), and shutdown joins the reader threads cleanly."""
    import socket
    import numpy as np

    from veneur_tpu import native
    if not native.available():
        pytest.skip("native engine not built")

    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink
    from tests.test_server import by_name, small_config, _wait_processed

    sink = DebugMetricSink()
    srv = Server(small_config(num_readers=2), metric_sinks=[sink])
    srv.start()
    try:
        assert srv._native_readers_active
        n_clients, per = 4, 100
        socks = [socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                 for _ in range(n_clients)]
        for ci, s in enumerate(socks):
            for i in range(per):
                s.sendto(b"nr.count.%d:1|c" % (i % 8), srv.local_addr())
            s.close()
        total = n_clients * per
        _wait_processed(srv, total)
        assert srv.aggregator.processed >= total
        assert srv.packets_received >= total
        assert srv.packets_dropped == 0
        srv.trigger_flush()
        m = by_name(sink.flushed)
        got = sum(m[f"nr.count.{i}"].value for i in range(8))
        assert got == float(total)
    finally:
        srv.shutdown()
    # reader group freed; counters must be safely zero afterwards
    assert not srv._native_readers_active


def test_fuzz_differential_parse_parity():
    """Randomized differential fuzz: structured mutations of valid lines
    plus raw random bytes must be ACCEPTED/REJECTED identically by the
    C++ engine and the Python parser, and accepted lines must stage the
    same (kind, slot, value). The fixed parity lists above pin known
    shapes; this hunts the unknown ones."""
    rng = np.random.default_rng(0x5EED)

    names = [b"a", b"metric.name", b"x" * 64, b"dot.", b".lead",
             b"uni\xc3\xa9", b"sp ace", b"tab\t"]
    values = [b"1", b"-3.5", b"1e3", b"0", b"nan", b"inf", b"-inf",
              b"0x1p3", b"1.", b".5", b"", b"abc", b"1_000", b" 1", b"1 "]
    types = [b"c", b"g", b"ms", b"h", b"d", b"s", b"cc", b"", b"m"]
    rates = [b"", b"|@0.5", b"|@1", b"|@0", b"|@-1", b"|@2", b"|@abc",
             b"|@0.001"]
    tagss = [b"", b"|#", b"|#a:b", b"|#b:2,a:1", b"|#veneurlocalonly",
             b"|#veneurglobalonly,x:y", b"|#dup:1,dup:2", b"|#:v", b"|#k:",
             b"|#comma\\,esc"]
    extras = [b"", b"|", b"|x:y", b"||", b"|c"]

    lines = []
    for _ in range(1500):
        ln = (names[rng.integers(len(names))] + b":"
              + values[rng.integers(len(values))] + b"|"
              + types[rng.integers(len(types))]
              + rates[rng.integers(len(rates))]
              + tagss[rng.integers(len(tagss))]
              + extras[rng.integers(len(extras))])
        lines.append(ln)
    for _ in range(500):   # raw noise (printable-heavy so memchr paths vary)
        n = int(rng.integers(1, 60))
        lines.append(bytes(rng.integers(32, 127, n).astype(np.uint8)))

    eng = mk()
    table = KeyTable(SPEC)
    batcher = Batcher(SPEC, BSPEC)
    py_accept = 0
    for ln in lines:
        st0 = eng.stats()
        eng.feed(ln)
        st1 = eng.stats()
        # processed advances on accept; dropped advances when the parse
        # succeeded but the key table was full — both count as "parsed"
        native_parsed = (st1["processed"] + st1["dropped"]
                         == st0["processed"] + st0["dropped"] + 1)
        try:
            m = parser.parse_metric(ln)
        except parser.ParseError:
            assert not native_parsed, ln
            continue
        assert native_parsed, ln
        py_accept += 1
        slot = table.slot_for(m.type, m.name, m.tags, m.scope, m.digest)
        if slot is None:
            continue
        if m.type == "counter":
            batcher.add_counter(slot, m.value, m.sample_rate)
        elif m.type == "gauge":
            batcher.add_gauge(slot, m.value)
        elif m.type == "set":
            v = m.value if isinstance(m.value, bytes) else str(
                m.value).encode()
            batcher.add_set(slot, v)
        elif m.type == "status":
            batcher.add_status(slot, m.value)
        else:
            batcher.add_histo(slot, m.value, m.sample_rate)
    # aggregate accept/reject parity
    st = eng.stats()
    assert st["processed"] + st["dropped"] == py_accept, (
        st, py_accept)

    # staged-sample parity on everything accepted
    arrays = emit_arrays()
    nc, ng, ns, nh = eng.emit_into(arrays)
    (c_slot, c_inc, g_slot, g_val, s_slot, s_reg, s_rho,
     h_slot, h_val, h_wt) = arrays
    assert (nc, ng, ns, nh) == (batcher.nc, batcher.ng, batcher.ns,
                                batcher.nh)
    np.testing.assert_array_equal(c_slot[:nc], batcher.c_slot[:nc])
    np.testing.assert_allclose(c_inc[:nc], batcher.c_inc[:nc], rtol=1e-6)
    np.testing.assert_array_equal(g_slot[:ng], batcher.g_slot[:ng])
    np.testing.assert_allclose(g_val[:ng], batcher.g_val[:ng], rtol=1e-6)
    np.testing.assert_array_equal(s_slot[:ns], batcher.s_slot[:ns])
    np.testing.assert_array_equal(s_reg[:ns], batcher.s_reg[:ns])
    np.testing.assert_array_equal(s_rho[:ns], batcher.s_rho[:ns])
    np.testing.assert_array_equal(h_slot[:nh], batcher.h_slot[:nh])
    np.testing.assert_allclose(h_val[:nh], batcher.h_val[:nh], rtol=1e-6)
    np.testing.assert_allclose(h_wt[:nh], batcher.h_wt[:nh], rtol=1e-6)


def test_fuzz_multiline_packet_splitting_parity():
    """Datagram splitting parity: feeding N lines as one newline-joined
    packet must parse exactly like feeding them line by line (counts and
    staged samples), including lines that are rejects, specials, and
    empty strings."""
    lines = (GOOD_PACKETS + BAD_PACKETS
             + [b"", b"_sc|db.up|1", b"_e{5,2}:hello|hi"]) * 3

    one = mk()
    for ln in lines:
        one.feed(ln)
    spl_one = one.drain_specials()

    packed = mk()
    packed.feed(b"\n".join(lines))
    spl_packed = packed.drain_specials()

    assert one.stats() == packed.stats()
    assert spl_one == spl_packed
    a1, a2 = emit_arrays(), emit_arrays()
    n1 = one.emit_into(a1)
    n2 = packed.emit_into(a2)
    assert n1 == n2
    for x, y in zip(a1, a2):
        np.testing.assert_array_equal(x, y)


# -- documented native-path deviations, pinned -------------------------------
# native_aggregator.py:14-27 documents two deliberate cross-stream
# imprecisions. These tests FAIL if the documented behavior drifts, so a
# regression (or an undocumented "fix") is visible.

def _flush_names(agg, percentiles=(0.5,), is_local=False):
    from veneur_tpu.server.flusher import generate_intermetrics
    state, table = agg.swap()
    flush, table = agg.compute_flush(state, table, list(percentiles))
    return {m.name: m.value for m in generate_intermetrics(
        flush, table, percentiles=list(percentiles),
        aggregates=["min", "max", "count"], is_local=is_local,
        timestamp=0)}


def _small_native_agg():
    from veneur_tpu.server.native_aggregator import NativeAggregator
    spec = TableSpec(counter_capacity=64, gauge_capacity=64,
                     status_capacity=16, set_capacity=16,
                     histo_capacity=64)
    return spec, NativeAggregator(spec, BatchSpec(
        counter=128, gauge=64, status=16, set=64, histo=128))


def test_deviation_imported_only_sticky_across_wire_hits():
    """Import-then-wire histo keeps imported_only for the interval on the
    NATIVE path (aggregates suppressed on a global tier, percentiles
    flush) — while the pure-Python path clears it. Both halves pinned."""
    import jax

    m = parser.parse_metric(b"hdev:5|h")
    payload = {"means": np.asarray([2.0, 4.0], np.float32),
               "weights": np.asarray([1.0, 1.0], np.float32)}

    spec, nat = _small_native_agg()
    nat.import_metric("histogram", "hdev", (), m.scope, m.digest, payload)
    nat.feed(b"hdev:5|h\n")          # direct wire hit, same key
    got = _flush_names(nat)
    assert "hdev.50percentile" in got          # percentiles always flush
    assert "hdev.count" not in got, \
        "native path now clears imported_only on wire hits — update " \
        "native_aggregator.py:14-27 and this pin together"

    from veneur_tpu.server.aggregator import Aggregator
    py = Aggregator(spec, BatchSpec(counter=128, gauge=64, status=16,
                                    set=64, histo=128))
    py.import_metric("histogram", "hdev", (), m.scope, m.digest, payload)
    py.process_metric(m)             # python path clears the flag
    got = _flush_names(py)
    assert "hdev.count" in got and got["hdev.count"] == 3.0
    jax.block_until_ready(py.state)


def test_deviation_gauge_lww_per_stream_not_arrival_ordered():
    """Cross-stream gauge LWW: the Python-side batch emits after the
    native staging at swap, so the Python write wins even when the wire
    sample arrived LATER. Single-stream ordering stays exact."""
    _spec, nat = _small_native_agg()
    nat.process_metric(parser.parse_metric(b"gdev:1.0|g"))  # python stream
    nat.feed(b"gdev:2.0|g\n")        # wire arrives after — but loses
    got = _flush_names(nat)
    assert got["gdev"] == 1.0, \
        "cross-stream gauge LWW became arrival-ordered — update " \
        "native_aggregator.py:14-27 and this pin together"

    # single-stream (wire-only) stays arrival-ordered
    _spec, nat2 = _small_native_agg()
    nat2.feed(b"gdev:1.5|g\ngdev:3.5|g\n")
    got = _flush_names(nat2)
    assert got["gdev"] == 3.5


def test_full_server_native_vs_python_differential():
    """Two live servers — one on the C++ engine, one on the Python parse
    path — fed IDENTICAL mixed traffic must flush IDENTICAL results:
    same keys, same values, same tags (the staged-array fuzzers prove
    stage-level parity; this pins it through the whole server, device
    math and flush labeling included)."""
    import numpy as np

    from tests.test_server import small_config, _wait_processed
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink

    rng = np.random.default_rng(21)
    lines = []
    for i in range(40):
        lines.append(b"d.c%d:%d|c|#k:v" % (i % 7, rng.integers(1, 9)))
        lines.append(b"d.t:%d|ms" % rng.integers(1, 500))
    lines += [b"d.g:%d|g" % v for v in (3, 9, 4)]      # LWW -> 4
    lines += [b"d.s:u%d|s" % i for i in range(16)]
    lines += [b"d.rate:1|c|@0.25",                     # counts as 4
              b"d.scoped:5|c|#veneurlocalonly,env:x",
              b"_sc|d.check|2|m:warn",
              b"not a metric!!!"]
    payloads = [b"\n".join(lines[i:i + 10])
                for i in range(0, len(lines), 10)]

    results = {}
    for native in (True, False):
        sink = DebugMetricSink()
        srv = Server(small_config(native_ingest=native),
                     metric_sinks=[sink])
        srv.start()
        try:
            assert srv._native == native
            for p in payloads:
                srv.packet_queue.put(p)
            _wait_processed(srv, len(lines) - 1)   # 1 parse error
            srv.trigger_flush()
            results[native] = {
                (m.name, tuple(m.tags)): (m.value, m.type)
                for m in sink.flushed
                if not m.name.startswith(("veneur.", "ssf."))}
        finally:
            srv.shutdown()

    nat, py = results[True], results[False]
    assert set(nat) == set(py), (
        set(nat) ^ set(py))
    for key in nat:
        nv, nt = nat[key]
        pv, pt = py[key]
        assert nt == pt, (key, nt, pt)
        # identical staged inputs -> identical device math; exact equality
        assert nv == pv, (key, nv, pv)
    # spot-check semantics on both
    assert nat[("d.g", ())][0] == 4.0
    assert nat[("d.rate", ())][0] == 4.0
    assert nat[("d.scoped", ("env:x",))][0] == 5.0


# -- zero-copy packed emit: golden parity + invariants (r06) -----------------
# The packed-emit tentpole replaced the Batch path (sentinel-filled
# arrays -> emit_into -> ten .copy()s -> Batch -> pack_batch repack)
# with vt_emit_packed writing staged lanes straight into the flat
# double-buffered host buffer. These tests pin the new path against an
# in-test reconstruction of the removed one: same wire bytes, byte-
# identical device state.

def _attach_old_batch_emit(ref):
    """Reattach the pre-packed-emit (r05) native emit as an instance
    attribute: fresh sentinel-initialized lanes, emit_into, a Batch with
    constant status/histo-stat lanes, then the _on_batch repack. This is
    the reference the zero-copy path must match bit-for-bit."""
    from veneur_tpu.aggregation.step import Batch

    def old_emit():
        b, sp = ref.bspec, ref.spec
        c_slot = np.full(b.counter, sp.counter_capacity, np.int32)
        c_inc = np.zeros(b.counter, np.float32)
        g_slot = np.full(b.gauge, sp.gauge_capacity, np.int32)
        g_val = np.zeros(b.gauge, np.float32)
        s_slot = np.full(b.set, sp.set_capacity, np.int32)
        s_reg = np.zeros(b.set, np.int32)
        s_rho = np.zeros(b.set, np.uint8)
        h_slot = np.full(b.histo, sp.histo_capacity, np.int32)
        h_val = np.zeros(b.histo, np.float32)
        h_wt = np.zeros(b.histo, np.float32)
        nc, ng, ns, nh = ref.eng.emit_into(
            (c_slot, c_inc, g_slot, g_val, s_slot, s_reg, s_rho,
             h_slot, h_val, h_wt))
        if nc + ng + ns + nh == 0:
            return
        batch = Batch(
            counter_slot=c_slot, counter_inc=c_inc,
            gauge_slot=g_slot, gauge_val=g_val,
            status_slot=np.full(b.status, sp.status_capacity, np.int32),
            status_val=np.zeros(b.status, np.float32),
            set_slot=s_slot, set_reg=s_reg, set_rho=s_rho,
            histo_slot=h_slot, histo_val=h_val, histo_wt=h_wt,
            histo_stat_slot=np.full(b.histo_stat, sp.histo_capacity,
                                    np.int32),
            histo_stat_min=np.full(b.histo_stat, np.inf, np.float32),
            histo_stat_max=np.full(b.histo_stat, -np.inf, np.float32),
            histo_stat_recip=np.zeros(b.histo_stat, np.float32),
        )
        ref._on_batch(batch)

    ref._emit_native = old_emit


def _parity_waves():
    """Mixed-kind traffic in waves; emit between waves so successive
    emits alternate packed buffers AND leave stale tails (wave sizes
    shrink, so later emits must re-sentinel rows the earlier ones
    dirtied)."""
    waves = []
    for scale in (40, 25, 7, 1):
        lines = []
        for i in range(scale):
            lines.append(b"pz.c%d:%d|c" % (i, i + 1))
            lines.append(b"pz.c%d:2|c|@0.5" % (i % 11))
            if i < 30:
                lines.append(b"pz.g%d:%d.25|g" % (i % 30, i))
                lines.append(b"pz.h%d:%d|ms" % (i % 20, i * 3))
            if i < 10:
                lines.append(b"pz.s%d:u%d|s" % (i % 4, i))
        waves.append(b"\n".join(lines))
    return waves


def test_packed_emit_state_parity_with_batch_path():
    """GOLDEN: zero-copy packed emit vs the removed Batch path on
    identical wire bytes -> byte-identical device state and identical
    flushed values. Any divergence (sentinel restore bound, lane
    offsets, compact-flag cadence, stale-tail handling) fails here."""
    import jax

    _spec, nat = _small_native_agg()
    _spec2, ref = _small_native_agg()
    _attach_old_batch_emit(ref)

    for wave in _parity_waves():
        for agg in (nat, ref):
            agg.feed(wave)
            agg._emit_native()

    assert nat.steps_total == ref.steps_total > 1

    state_n, table_n = nat.swap()
    state_r, table_r = ref.swap()
    leaves_n = jax.tree.leaves(state_n)
    leaves_r = jax.tree.leaves(state_r)
    assert len(leaves_n) == len(leaves_r)
    for a, b in zip(leaves_n, leaves_r):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # second interval straight through flush: values identical too
    for agg in (nat, ref):
        agg.feed(b"\n".join([b"pz2.c:3|c", b"pz2.g:1.5|g",
                             b"pz2.h:7|ms", b"pz2.h:9|ms",
                             b"pz2.s:ua|s", b"pz2.s:ub|s"]))
    got_n = _flush_names(nat)
    got_r = _flush_names(ref)
    assert got_n == got_r
    assert got_n["pz2.c"] == 3.0 and got_n["pz2.g"] == 1.5


def test_packed_emit_sharded_flush_parity():
    """Sharded fan-out (argsort/searchsorted shard split) vs the single
    backend on the same wire bytes: identical flushed names and values.
    Percentile names are compared by value too — identical arrival order
    per key means identical digest folds on one host."""
    from veneur_tpu.server.native_aggregator import (
        NativeAggregator, NativeShardedAggregator)

    spec = TableSpec(counter_capacity=64, gauge_capacity=64,
                     status_capacity=16, set_capacity=16,
                     histo_capacity=64)
    bspec = BatchSpec(counter=128, gauge=64, status=16, set=64, histo=128)
    single = NativeAggregator(spec, bspec)
    shard = NativeShardedAggregator(spec, bspec, n_shards=2)

    for wave in _parity_waves():
        for agg in (single, shard):
            agg.feed(wave)
            agg._emit_native()

    got_s = _flush_names(single)
    got_h = _flush_names(shard)
    assert set(got_s) == set(got_h), set(got_s) ^ set(got_h)
    for name in got_s:
        if "percentile" in name:
            assert got_h[name] == pytest.approx(got_s[name]), name
        else:
            assert got_h[name] == got_s[name], name


def test_packed_sentinel_tail_invariant_after_partial_emit():
    """vt_emit_packed's incremental sentinel contract: after a big emit
    then a small emit into the SAME buffer, every row past the new count
    in the six C++-maintained lanes (slot lanes, counter_inc, histo_wt)
    is back at its sentinel — only rows the previous emit dirtied are
    rewritten, value-lane tails stay stale by design (the in-kernel
    sentinel scatter drops them)."""
    from veneur_tpu.aggregation.step import packed_layout

    spec, agg = _small_native_agg()
    eng = agg.eng
    layout, _words = packed_layout(agg._pk_sizes)
    flat, prev, _carried = agg._new_packed()

    for i in range(40):
        eng.feed(b"t.c%d:1|c" % i)
    for i in range(10):
        eng.feed(b"t.g%d:2|g" % i)
        eng.feed(b"t.h%d:3|ms" % i)
        eng.feed(b"t.s%d:u%d|s" % (i, i))
    counts = eng.emit_packed(flat, agg._pk_offs, prev)
    assert counts == (40, 10, 10, 10)
    assert tuple(prev) == counts      # updated in place for next emit

    eng.feed(b"t.zz:5|c")
    counts = eng.emit_packed(flat, agg._pk_offs, prev)
    assert counts == (1, 0, 0, 0)
    assert tuple(prev) == counts

    def lane(name, f32=False):
        off, n, _w = layout[name]
        v = flat[off:off + n]
        return v.view(np.float32) if f32 else v

    # staged row 0 is live, rows [1:40) were dirtied last emit and must
    # be sentinel again; rows [40:] were never touched
    assert lane("counter_slot")[0] != spec.counter_capacity
    assert lane("counter_inc", f32=True)[0] == 5.0
    assert (lane("counter_slot")[1:] == spec.counter_capacity).all()
    assert (lane("counter_inc", f32=True)[1:] == 0.0).all()
    for name, cap in (("gauge_slot", spec.gauge_capacity),
                      ("set_slot", spec.set_capacity),
                      ("histo_slot", spec.histo_capacity)):
        assert (lane(name) == cap).all(), name
    assert (lane("histo_wt", f32=True) == 0.0).all()
    # Python-owned constant regions never touched by C++
    assert (lane("status_slot") == spec.status_capacity).all()
    assert (lane("histo_stat_slot") == spec.histo_capacity).all()
    assert (lane("histo_stat_min", f32=True) == np.inf).all()
    assert (lane("histo_stat_max", f32=True) == -np.inf).all()


def test_native_admission_shed_accounting_exact():
    """In-engine admission (tentpole (c)): with the ring forced to
    SHEDDING, per-class admitted/shed counts drained from C++ are exact
    against what was sent, drain-and-reset is exact-once, and
    fold_native_counts lands them in the controller's own counters —
    sent == admitted + shed with no Python in the datagram path."""
    import socket
    import time as _time

    from veneur_tpu.reliability.overload import OverloadController

    _spec, agg = _small_native_agg()
    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    tx.connect(rx.getsockname())
    try:
        agg.readers_start([rx.fileno()], max_len=4097)
        agg.admission_set(True, 2, 0.0, 0.0, ("veneur.priority:high",))
        for _ in range(5):
            tx.send(b"veneur.self.x:1|c")                    # self class
        for _ in range(7):
            tx.send(b"app.h:1|c|#veneur.priority:high")      # high class
        for _ in range(9):
            tx.send(b"app.l:1|c")                            # low class
        deadline = _time.monotonic() + 10
        while (agg.reader_counters()["datagrams"] < 21
               and _time.monotonic() < deadline):
            _time.sleep(0.005)
        rc = agg.reader_counters()
        assert rc["datagrams"] == 21 and rc["toolong"] == 0

        d = agg.admission_drain()
        assert d["admitted"] == {"self": 5, "high": 7}
        assert d["shed"] == {"low": 9}
        d2 = agg.admission_drain()                 # exact-once drain
        assert d2 == {"admitted": {}, "shed": {}}

        # shed datagrams never reached the ring; admitted ones did
        agg.pump(50)
        assert agg.processed == 12

        ov = OverloadController(signals=lambda: {})
        ov.fold_native_counts(d)
        assert ov.admitted == {"self": 5, "high": 7}
        assert ov.shed == {"low": 9}
        assert sum(ov.admitted.values()) + sum(ov.shed.values()) == 21
    finally:
        agg.readers_stop()
        rx.close()
        tx.close()
