"""The native key table across flush intervals.

The C++ engine's key table outlives the interval (dogstatsd.cpp
KindTable): a key keeps its slot, a swap hands the interval's live keys
over as arrays, and capacity is counted in the interval's own keys. What
an interval emits must stay what the flush-scoped Python KeyTable
(aggregation/host.py, behind the plain Aggregator) emits from the same
datagrams: interval by interval, row for row and in order. Every case
runs over the single-ring `vr_*` engine behind a real loopback socket and
over the `vrm_*` engine at two rings, at one and at four table shards.
"""

import socket
import time

import numpy as np
import pytest

from veneur_tpu import native
from veneur_tpu.aggregation.host import BatchSpec
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.samplers import parser

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native engine unavailable")

SPEC = TableSpec(counter_capacity=16, gauge_capacity=8, status_capacity=8,
                 set_capacity=8, histo_capacity=8)
BSPEC = BatchSpec(counter=64, gauge=32, status=8, set=32, histo=64,
                  histo_stat=16)
KINDS = ("counter", "gauge", "set", "histogram")
VALUE_OF = {"counter": "counter", "gauge": "gauge", "set": "set_estimate",
            "histogram": "histo_count"}

ENGINES = [pytest.param(("vr", 1), id="vr-1shard"),
           pytest.param(("vr", 4), id="vr-4shards"),
           pytest.param(("vrm", 1), id="vrm2-1shard"),
           pytest.param(("vrm", 4), id="vrm2-4shards")]


class Pair:
    """A NativeAggregator behind one engine and the plain Aggregator, fed
    the same datagrams one at a time (each parsed before the next is
    sent, so first-arrival order is the order sent on every engine)."""

    def __init__(self, engine: str, n_shards: int, spec=SPEC):
        from veneur_tpu.server.aggregator import Aggregator
        from veneur_tpu.server.native_aggregator import NativeAggregator
        self.engine, self.n_shards = engine, n_shards
        self.py = Aggregator(spec, BSPEC, n_shards=n_shards)
        self.nat = NativeAggregator(spec, BSPEC, n_shards=n_shards)
        self.lines = 0
        self.sent = 0
        self.rx = self.tx = None
        if engine == "vr":
            self.rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            self.rx.bind(("127.0.0.1", 0))
            self.tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            self.tx.connect(self.rx.getsockname())
            self.nat.readers_start([self.rx.fileno()])
        else:
            self.nat.rings_start(2)

    def close(self):
        self.nat.readers_stop()
        for s in (self.rx, self.tx):
            if s is not None:
                s.close()

    def rebuild(self, spec, n_shards):
        """What reshard/coordinator.py and tables/growth.py do after the
        swap that applied a staged change: new backends around the SAME
        engine."""
        from veneur_tpu.server.aggregator import Aggregator
        from veneur_tpu.server.native_aggregator import NativeAggregator
        self.n_shards = n_shards
        self.py = Aggregator(spec, BSPEC, n_shards=n_shards)
        self.nat = NativeAggregator(spec, BSPEC, n_shards=n_shards,
                                    engine=self.nat.eng)

    def send(self, *lines: bytes):
        dgram = b"\n".join(lines)
        if self.engine == "vr":
            self.tx.send(dgram)
        else:
            assert self.nat.eng.rings_inject(self.sent % 2, dgram) == \
                native.INJECT_OK
        self.sent += 1
        self.lines += len(lines)
        deadline = time.time() + 60
        while True:
            self.nat.pump(5)
            st = self.nat.eng.stats()
            if st["processed"] + st["dropped"] >= self.lines:
                break
            assert time.time() < deadline, (st, self.lines)
        for ln in lines:
            self.py.process_metric(parser.parse_metric(ln))

    def import_list(self, ml):
        from veneur_tpu.forward.convert import import_into
        total, errors = self.nat.import_pb_bytes(ml.SerializeToString())
        assert (total, errors) == (len(ml.metrics), 0)
        self.lines += total     # the engine counts an import as processed
        for m in ml.metrics:
            import_into(self.py, m)

    def flush(self):
        """Both backends' interval as rows, and the native interval's
        detached keys."""
        out = []
        for agg in (self.py, self.nat):
            res, table = agg.flush([0.5])
            out.append((rows_of(res, table), table))
        (py_rows, _), (nat_rows, nat_table) = out
        return py_rows, nat_rows, nat_table


def rows_of(res, table):
    rows = []
    for kind in KINDS:
        vals = np.asarray(res[VALUE_OF[kind]], np.float64)
        for i, (_slot, m) in enumerate(table.get_meta(kind)):
            rows.append((m.kind, m.name, m.tags, m.scope, m.imported_only,
                         round(float(vals[i]), 3)))
    return rows


@pytest.fixture(params=ENGINES)
def pair(request):
    engine, n_shards = request.param
    p = Pair(engine, n_shards)
    yield p
    p.close()


def fresh_counters(pair, n, avoid=(), prefix="k"):
    """n counter names per table shard (n * n_shards in all) that are not
    in `avoid`, dealt round-robin over the shards so a prefix fills every
    shard evenly."""
    by_shard = [[] for _ in range(pair.n_shards)]
    i = 0
    while min(len(b) for b in by_shard) < n:
        name = f"{prefix}{i}"
        i += 1
        if name in avoid:
            continue
        m = parser.parse_metric(f"{name}:1|c".encode())
        b = by_shard[m.digest % pair.n_shards]
        if len(b) < n:
            b.append(name)
    return [b[j] for j in range(n) for b in by_shard]


def key_counters(pair):
    st = pair.nat.ring_stats()
    return st["keys_live"], st["keys_new"], st["keys_evicted"]


MIXED = (b"a.count:2|c|#env:prod,az:b", b"a.gauge:7.5|g",
         b"a.set:member|s|#env:prod", b"a.timer:12|ms|#svc:api",
         b"a.histo:3|h", b"a.count:3|c|#az:b,env:prod")


def test_a_key_in_every_interval(pair):
    for k in range(3):
        pair.send(*MIXED[:3])
        pair.send(*MIXED[3:])
        py, nat, _ = pair.flush()
        assert nat == py
        assert len(nat) == 5
        # paid for once: the later swaps find no new-key record
        assert key_counters(pair) == (5 * (k + 1), 5, 0)
    assert pair.nat.ring_stats()["keys_reused"] == 10
    assert pair.nat.dropped_capacity == pair.py.dropped_capacity == 0


def test_a_key_that_stays_away_is_not_emitted_and_returns(pair):
    pair.send(b"stay:1|c", b"away:1|c|#veneurlocalonly,t:1", b"away.g:4|g")
    py1, nat1, table1 = pair.flush()
    slot_of = {m.name: s for s, m in table1.get_meta("counter")}
    pair.send(b"stay:5|c")
    py2, nat2, _ = pair.flush()
    assert nat2 == py2
    assert [r[1] for r in nat2] == ["stay"]
    # back on its old terms: the same rows as in its first interval, from
    # the slot it kept, with nothing allocated
    pair.send(b"away.g:4|g", b"away:1|c|#t:1,veneurlocalonly", b"stay:1|c")
    py3, nat3, table3 = pair.flush()
    assert nat3 == py3
    assert sorted(nat3) == sorted(nat1) and nat1 == py1
    assert [m.name for _s, m in table3.get_meta("counter")] == \
        ["away", "stay"]
    assert {m.name: s for s, m in table3.get_meta("counter")} == slot_of
    assert key_counters(pair) == (3 + 1 + 3, 3, 0)


def test_capacity_is_counted_in_the_intervals_own_keys(pair):
    per_shard = SPEC.counter_capacity // pair.n_shards
    first = fresh_counters(pair, per_shard)
    pair.send(*[f"{n}:1|c".encode() for n in first])
    py, nat, _ = pair.flush()
    assert nat == py and len(nat) == SPEC.counter_capacity
    # the table is full of last interval's keys: as many new ones, and
    # none is dropped
    second = fresh_counters(pair, per_shard, avoid=set(first), prefix="n")
    half = len(second) // 2
    pair.send(*[f"{n}:2|c".encode() for n in second[:half]])
    pair.send(*[f"{n}:2|c".encode() for n in second[half:]])
    assert pair.nat.dropped_capacity == pair.py.dropped_capacity == 0
    assert pair.nat.eng.table_stats()["counter"][0] == SPEC.counter_capacity
    # one key more drops exactly one, on both
    pair.send(b"one.more:1|c")
    assert pair.nat.dropped_capacity == pair.py.dropped_capacity == 1
    # and a key of the interval is still found, not dropped
    pair.send(f"{second[0]}:2|c".encode())
    assert pair.nat.dropped_capacity == 1
    py, nat, _ = pair.flush()
    assert nat == py
    assert [r[1] for r in nat] == second
    live, new, evicted = key_counters(pair)
    assert (live, new) == (2 * SPEC.counter_capacity,
                           2 * SPEC.counter_capacity)
    assert evicted == SPEC.counter_capacity


def test_a_seeded_stream_that_churns_past_capacity(pair):
    """Keys of every kind come, go and return over five intervals, more
    of them than the tables hold, on short and long names: the rows are
    the flush-scoped table's, interval by interval, drops included."""
    rng = np.random.default_rng(42 + pair.n_shards)
    kinds = ("c", "g", "s", "ms", "h")
    for k in range(5):
        ids = rng.integers(8 * k, 8 * k + 40, size=120)
        lines = []
        for i, key in enumerate(ids.tolist()):
            name = f"ch{key}" if key % 2 else f"churn.service.latency.{key}"
            tags = ("", "|#az:b,env:p", "|#veneurlocalonly,t:1")[key % 3]
            value = f"m{i % 5}" if kinds[key % 5] == "s" else str(1 + i % 7)
            lines.append(f"{name}:{value}|{kinds[key % 5]}{tags}".encode())
        for j in range(0, len(lines), 12):
            pair.send(*lines[j:j + 12])
        assert pair.nat.dropped_capacity == pair.py.dropped_capacity
        py, nat, _ = pair.flush()
        assert nat == py
    assert pair.py.dropped_capacity > 0
    assert key_counters(pair)[2] > 0    # evicted


def test_a_key_that_returns_with_another_scope(pair):
    pair.send(b"sc:1|c|#veneurlocalonly,a:1", b"sc.t:1|ms|#veneurglobalonly")
    py1, nat1, table1 = pair.flush()
    assert nat1 == py1
    pair.send(b"sc:1|c|#a:1,veneurglobalonly", b"sc.t:1|ms")
    py2, nat2, table2 = pair.flush()
    assert nat2 == py2
    assert [(r[1], r[3]) for r in nat1] == [("sc", 1), ("sc.t", 2)]
    assert [(r[1], r[3]) for r in nat2] == [("sc", 2), ("sc.t", 0)]
    # the first interval's view still says what it said
    assert [m.scope for _s, m in table1.get_meta("counter")] == [1]
    assert [m.scope for _s, m in table1.get_meta("timer")] == [2]
    assert table1.get_meta("counter")[0][1] is not \
        table2.get_meta("counter")[0][1]
    assert key_counters(pair) == (4, 2, 0)


def _timer_list(name="imp.t"):
    from veneur_tpu.proto import forwardrpc_pb2 as fpb
    from veneur_tpu.proto import metricpb_pb2 as mpb
    ml = fpb.MetricList()
    m = ml.metrics.add()
    m.name = name
    m.tags.append("svc:api")
    m.type = mpb.Timer
    td = m.histogram.t_digest
    for mean, weight in ((1.0, 1.0), (3.5, 2.0), (8.0, 1.0)):
        c = td.main_centroids.add()
        c.mean, c.weight = mean, weight
    td.min, td.max = 1.0, 8.0
    td.reciprocalSum = 1.0 + 2.0 / 3.5 + 1.0 / 8.0
    return ml


def test_a_histogram_first_imported_then_sampled_directly(pair):
    pair.import_list(_timer_list())
    py1, nat1, table1 = pair.flush()
    assert nat1 == py1
    assert [(r[1], r[4]) for r in nat1] == [("imp.t", True)]
    pair.send(b"imp.t:5|ms|#svc:api")
    py2, nat2, _ = pair.flush()
    assert nat2 == py2
    assert [(r[1], r[4], r[5]) for r in nat2] == [("imp.t", False, 1.0)]
    # imported alone again, and the first view is as it was
    pair.import_list(_timer_list())
    py3, nat3, _ = pair.flush()
    assert nat3 == py3 == nat1
    assert table1.get_meta("timer")[0][1].imported_only is True
    assert key_counters(pair) == (3, 1, 0)


def test_a_direct_sample_from_python_ends_imported_only(pair):
    """process_metric on an import-created slot (a span-extracted timer)
    clears imported_only for that interval only, on both tables."""
    ln = b"imp.t:5|ms|#svc:api"
    for agg in (pair.py, pair.nat):
        from veneur_tpu.forward.convert import import_into
        for m in _timer_list().metrics:
            import_into(agg, m)
        agg.process_metric(parser.parse_metric(ln))
    py1, nat1, _ = pair.flush()
    assert nat1 == py1
    assert [(r[1], r[4]) for r in nat1] == [("imp.t", False)]
    pair.import_list(_timer_list())
    py2, nat2, _ = pair.flush()
    assert nat2 == py2
    assert [(r[1], r[4]) for r in nat2] == [("imp.t", True)]


def test_a_detached_view_outlives_the_reuse_of_its_slots(pair):
    per_shard = SPEC.counter_capacity // pair.n_shards
    first = fresh_counters(pair, per_shard)
    pair.send(*[f"{n}:1|c|#i:1".encode() for n in first])
    state1, view1 = pair.nat.swap()
    second = fresh_counters(pair, per_shard, avoid=set(first), prefix="n")
    pair.send(*[f"{n}:2|c|#i:2".encode() for n in second])
    live_view = pair.nat.table.get_meta("counter")   # the interval so far
    state2, view2 = pair.nat.swap()
    assert pair.nat.ring_stats()["keys_evicted"] == SPEC.counter_capacity
    # every slot of the first view now belongs to a key of the second
    slots1 = sorted(s for s, _m in view1.get_meta("counter"))
    assert slots1 == sorted(s for s, _m in view2.get_meta("counter"))
    assert [(m.name, m.tags) for _s, m in view1.get_meta("counter")] == \
        [(n, ("i:1",)) for n in first]
    assert [(m.name, m.tags) for _s, m in view2.get_meta("counter")] == \
        [(n, ("i:2",)) for n in second]
    assert [(s, m.name) for s, m in live_view] == \
        [(s, m.name) for s, m in view2.get_meta("counter")]
    for view, names in ((view1, first), (view2, second)):
        slot, m = view.get_meta("counter")[3]
        assert view.meta_for_slot("counter", slot) is m
        assert m.name == names[3]
    # and the values sit at the slots the views name
    for state, view, v in ((state1, view1, 1.0), (state2, view2, 2.0)):
        acc = (np.asarray(state.counter_acc) + np.asarray(state.counter_hi)
               + np.asarray(state.counter_lo)).reshape(-1)
        assert [float(acc[s]) for s, _m in view.get_meta("counter")] == \
            [v] * SPEC.counter_capacity


def test_a_staged_shard_map_and_capacity_change(pair):
    """The one place that needs the tables empty: the reset that applies
    a staged map or capacity clears them, every key is allocated again
    under the new layout, and persistence goes on from there."""
    from veneur_tpu.reshard.quiesce import shard_map_swap
    import dataclasses
    names = fresh_counters(pair, 3)
    lines = [f"{n}:1|c|#t:x".encode() for n in names] + [b"g.one:2|g"]
    pair.send(*lines)
    new_shards = 4 if pair.n_shards == 1 else 2
    new_spec = dataclasses.replace(SPEC, counter_capacity=32)
    pair.nat.eng.capacity_set(32, SPEC.gauge_capacity, SPEC.set_capacity,
                              SPEC.histo_capacity)
    state, table = shard_map_swap(pair.nat, new_shards)
    res = pair.nat.compute_flush(state, table, [0.5])[0]
    py_res, py_table = pair.py.flush([0.5])
    assert rows_of(res, table) == rows_of(py_res, py_table)
    pair.rebuild(new_spec, new_shards)
    assert pair.nat.eng.table_stats()["counter"] == (0, 0, 32)
    for k in range(2):
        pair.send(*reversed(lines))
        py, nat, nat_table = pair.flush()
        assert nat == py
        assert [r[1] for r in nat] == list(reversed(names)) + ["g.one"]
        per = 32 // new_shards
        for slot, m in nat_table.get_meta("counter"):
            d = parser.parse_metric(f"{m.name}:1|c|#t:x".encode()).digest
            assert slot // per == d % new_shards
    n = len(lines)
    # allocated twice (before the change and after), reused once
    assert key_counters(pair) == (3 * n, 2 * n, 0)


# -- the frame of a detached interval ---------------------------------------
# The view a swap hands to the flush worker is columns
# (native_aggregator._SlotColumns); a frame's names are read by slot from
# the feed's columns on the worker while the next interval may write the
# same slots.

FRAME_KW = dict(percentiles=[0.5, 0.99], aggregates=["min", "max", "count"],
                timestamp=7, hostname="h")


def frame_of(agg, state, table, is_local=False):
    from veneur_tpu.server.flusher import generate_frame
    res = agg.compute_flush(state, table, FRAME_KW["percentiles"])[0]
    frame = generate_frame(res, table, is_local=is_local, **FRAME_KW)
    agg.count_frame(len(frame), frame.labels_reused)
    return frame


def labelled(frame):
    """name -> (tags, value) of a frame's rows, each name once."""
    out = {}
    for name, value, _t, _msg, tags, _sinks, _host in frame.rows():
        assert name not in out, name
        out[name] = (tuple(tags), round(value, 3))
    return out


def fresh_lines(pair, n, line):
    """n keys per table shard, dealt as fresh_counters deals them, of the
    lines `line(i)` for i = 0, 1, ...: the i's (a key's shard is its
    digest's, which its tags are part of)."""
    by_shard = [[] for _ in range(pair.n_shards)]
    i = 0
    while min(len(b) for b in by_shard) < n:
        b = by_shard[parser.parse_metric(line(i)).digest % pair.n_shards]
        if len(b) < n:
            b.append(i)
        i += 1
        assert i < 4096, "the lines' digests leave a shard out"
    return [b[j] for j in range(n) for b in by_shard]


def fresh_timers(pair, n, prefix):
    return [f"{prefix}{i}" for i in fresh_lines(
        pair, n, lambda i: f"{prefix}{i}:1|ms".encode())]


def test_keys_that_arrive_in_another_order_keep_their_labels(pair):
    def counter(i):
        return f"ord.c{i}:{i + 1}|c|#i:{7 * i + 3}".encode()

    def timer(i):
        return f"ord.t{i}:{10 + i}|ms|#t:{5 * i + 1}".encode()

    counters = fresh_lines(pair, 2, counter)
    timers = fresh_lines(pair, 1, timer)
    lines = [counter(i) for i in counters] + [timer(i) for i in timers]
    want = {f"ord.c{i}": ((f"i:{7 * i + 3}",), i + 1.0) for i in counters}
    for i in timers:
        for suf, v in ((".min", 10.0 + i), (".max", 10.0 + i),
                       (".count", 1.0), (".50percentile", 10.0 + i),
                       (".99percentile", 10.0 + i)):
            want[f"ord.t{i}{suf}"] = ((f"t:{5 * i + 1}",), v)
    orders = (lines, lines[::-1], lines[1:] + lines[:1])
    for k, order in enumerate(orders):
        pair.send(*order)
        frame = frame_of(pair.nat, *pair.nat.swap())
        assert labelled(frame) == want
        # the second and third flush build no name
        assert frame.labels_reused == (len(frame) if k else 0)
    pair.py.flush([0.5])


def test_a_frame_is_labelled_by_its_own_interval(pair):
    """Interval k is detached and not yet flushed while interval k+1
    evicts its slots for other keys and gives one of its keys another
    scope: k's frame carries k's names, tags and scopes, and k+1's its
    own afterwards."""
    per_c = SPEC.counter_capacity // pair.n_shards
    per_h = SPEC.histo_capacity // pair.n_shards

    old_c = fresh_counters(pair, per_c)
    old_t = fresh_timers(pair, per_h, "ft.old")
    first = [f"{n}:1|c|#i:1".encode() for n in old_c[:-1]] + \
        [f"{old_c[-1]}:1|c|#veneurlocalonly,i:1".encode()] + \
        [f"{n}:5|ms|#i:1".encode() for n in old_t] + \
        [b"ft.g:3|g|#veneurlocalonly"]

    def of_first(frame):
        got = labelled(frame)
        assert {n for n in got if "." not in n or n == "ft.g"} == \
            set(old_c) | {"ft.g"}
        assert {n.rsplit(".", 1)[0] for n in got
                if n.startswith("ft.old")} == set(old_t)
        for name, (tags, _v) in got.items():
            if name != "ft.g":
                assert tags == ("i:1",), name
        return got

    # interval 0 is flushed at once: its compound names are kept
    pair.send(*first)
    of_first(frame_of(pair.nat, *pair.nat.swap()))
    # interval k: the same keys, detached and left waiting
    pair.send(*first)
    state_k, view_k = pair.nat.swap()
    # interval k+1: every counter and timer slot goes to another key; the
    # gauge, whose slot stays, returns global-only
    new_c = fresh_counters(pair, per_c, avoid=set(old_c), prefix="n")
    new_t = fresh_timers(pair, per_h, "ft.new")
    pair.send(*[f"{n}:2|c|#i:2".encode() for n in new_c])
    pair.send(*[f"{n}:6|ms|#i:2".encode() for n in new_t])
    pair.send(b"ft.g:4|g|#veneurglobalonly")
    assert pair.nat.ring_stats()["keys_evicted"] == 0   # counted at reset
    state_n, view_n = pair.nat.swap()
    assert pair.nat.ring_stats()["keys_evicted"] == \
        SPEC.counter_capacity + SPEC.histo_capacity
    assert sorted(view_k.columns("counter").slots.tolist()) == \
        sorted(view_n.columns("counter").slots.tolist())
    # k's frame, built after all that: a local tier still emits the gauge
    # and the local-only counter as k scoped them, and every mixed timer's
    # aggregates under its own name
    frame_k = frame_of(pair.nat, state_k, view_k, is_local=True)
    got = of_first(frame_k)
    assert got["ft.g"] == ((), 3.0)
    assert got[old_c[-1]][1] == 1.0
    assert {n for n in got if n.endswith("percentile")} == set()
    assert got[old_t[0] + ".count"] == (("i:1",), 1.0)
    assert [m.scope for m in view_k.columns("gauge").metas] == [1]
    assert [m.scope for m in view_n.columns("gauge").metas] == [2]
    # no label of k's was kept over a key of k+1
    frame_n = frame_of(pair.nat, state_n, view_n)
    got_n = labelled(frame_n)
    assert {n for n in got_n if not n.startswith("ft.")} == set(new_c)
    assert {n.rsplit(".", 1)[0] for n in got_n
            if n.startswith("ft.new")} == set(new_t)
    assert not any(n.startswith("ft.old") for n in got_n)
    assert all(tags == ("i:2",) for n, (tags, _v) in got_n.items()
               if n != "ft.g")
    assert got_n["ft.g"][1] == 4.0
    assert frame_n.labels_reused == 0
    # and the same keys again take every name from its column
    pair.send(*[f"{n}:2|c|#i:2".encode() for n in new_c])
    pair.send(*[f"{n}:6|ms|#i:2".encode() for n in new_t])
    frame = frame_of(pair.nat, *pair.nat.swap())
    assert {k: v for k, v in got_n.items() if k != "ft.g"} == labelled(frame)
    assert frame.labels_reused == len(frame)
    for _ in range(4):
        pair.py.swap()


def test_frame_rows_and_labels_reused_are_counted(pair):
    steady = (b"fl.c0:1|c", b"fl.c1:1|c", b"fl.g:2|g", b"fl.s:a|s",
              b"fl.t0:3|ms", b"fl.t1:4|ms")
    rows = 4 + 2 * 5        # min, max, count and two percentiles a timer
    pair.send(*steady)
    frame_of(pair.nat, *pair.nat.swap())
    st = pair.nat.ring_stats()
    assert (st["frame_rows"], st["frame_labels_reused"]) == (rows, 0)
    pair.send(*steady)
    frame_of(pair.nat, *pair.nat.swap())
    st = pair.nat.ring_stats()
    assert (st["frame_rows"], st["frame_labels_reused"]) == (2 * rows, rows)
    # two new counters and a new timer: their eight rows are built
    pair.send(*steady, b"fl.c2:1|c", b"fl.c3:1|c", b"fl.t2:5|ms")
    frame = frame_of(pair.nat, *pair.nat.swap())
    st = pair.nat.ring_stats()
    assert len(frame) == rows + 7
    assert (st["frame_rows"], st["frame_labels_reused"]) == \
        (3 * rows + 7, 2 * rows)
    for _ in range(3):
        pair.py.swap()


def test_names_are_read_by_slot_while_the_next_interval_writes():
    """The flush worker takes names from the feed's columns by slot, with
    no lock, while the pipeline thread gives slots of the interval being
    flushed to other keys (_SlotMetas: the stamp is written before a
    label and read after it). Whatever the interleaving, a view's rows
    carry its own keys' names, and no compound name of an old key is kept
    over a new one."""
    import sys
    import threading
    from veneur_tpu.aggregation.host import SlotMeta
    from veneur_tpu.server.native_aggregator import _SlotMetas
    n = 192
    metas = _SlotMetas(TableSpec(counter_capacity=8, gauge_capacity=8,
                                 status_capacity=8, set_capacity=8,
                                 histo_capacity=n))
    rng = np.random.default_rng(3)
    first = np.zeros(n, np.uint8)
    sufs = ("", ".min", ".99percentile")

    def relabel(gen, slots):
        for s in slots:
            metas.put("histo", s, SlotMeta(
                name=f"gen{gen}.k{s}", tags=(), scope=0, kind="timer"))

    relabel(0, range(n))
    held = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for gen in range(1, 250):
            # the swap: this interval's view, then the next interval
            view = metas.columns(
                "histo", rng.permutation(n).astype(np.int32), first)
            metas.epoch += 1
            own = [m.name for m in view.metas]
            writer = threading.Thread(
                target=relabel,
                args=(gen, rng.permutation(n)[:n // 2].tolist()))
            writer.start()
            sel = np.sort(rng.permutation(n)[:n // 3])
            got = [(suf, rows, view.names(rows, suf)[0].tolist())
                   for suf in sufs for rows in (None, sel)]
            writer.join(timeout=60)
            assert not writer.is_alive()
            for suf, rows, names in got:
                want = own if rows is None else [own[i] for i in rows]
                assert names == [w + suf for w in want], (gen, suf)
    finally:
        sys.setswitchinterval(held)
    # what the columns keep now is the last writer's
    view = metas.columns("histo", np.arange(n, dtype=np.int32), first)
    for suf in sufs:
        names, _reused = view.names(None, suf)
        assert names.tolist() == [m.name + suf for m in view.metas]
