"""Slot parity of the engine's key table (dogstatsd.cpp KindTable over its
flat KeyIndex) against a Python model of KindTable's rules.

The rules: a shard hands out its slots in first-arrival order; when it
has handed out its whole range, one sweep from the shard's top slot down
evicts every key the interval has not touched, and the freed slots are
reused from the back of that list; a key is dropped only when every slot
of its shard was touched in the interval; a staged capacity or shard map
empties the tables at the reset that applies it. For the same arrival
sequence the engine must give the model's slots, in the staged lanes'
batch order, the model's live lists, new-key records, evictions and drops,
interval by interval, on one table shard and on four, with datagrams
parked on a full lane and resumed. The flush-scoped Python KeyTable's
rows are held to the engine by tests/test_native_key_table_intervals.py.
"""

import copy
import time

import numpy as np
import pytest

from veneur_tpu import native
from veneur_tpu.aggregation.host import BatchSpec
from veneur_tpu.aggregation.state import TableSpec
from veneur_tpu.samplers import parser

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native engine unavailable")

TABLE_OF = {"counter": "counter", "gauge": "gauge", "set": "set",
            "histogram": "histo", "timer": "histo"}
LANES = ("counter", "gauge", "set", "histo")
# small lanes, so that a 30-line datagram parks on a full one now and then
BSPEC = BatchSpec(counter=256, gauge=64, status=8, set=64, histo=128,
                  histo_stat=8)


class ModelTable:
    """KindTable's rules over Python dicts."""

    def __init__(self, capacity, n_shards):
        self.per = capacity // n_shards
        self.n_shards = n_shards
        self.slot_of, self.key_at = {}, {}
        self.next_free = [0] * n_shards
        self.free = [[] for _ in range(n_shards)]
        self.dropped = self.evicted = 0
        self.next_interval()

    def next_interval(self):
        self.touched = set()
        self.live, self.first = [], []
        self.live_in = [0] * self.n_shards

    def slot_for(self, key, digest, scope, new):
        slot = self.slot_of.get(key)
        if slot is None:
            shard = digest % self.n_shards
            if self.live_in[shard] >= self.per:
                self.dropped += 1
                return None
            if self.next_free[shard] < self.per:
                local = self.next_free[shard]
                self.next_free[shard] += 1
            else:
                free = self.free[shard]
                if not free:
                    for loc in range(self.per - 1, -1, -1):
                        s = shard * self.per + loc
                        if s in self.touched or s not in self.key_at:
                            continue
                        del self.slot_of[self.key_at.pop(s)]
                        free.append(loc)
                        self.evicted += 1
                local = free.pop()
            slot = shard * self.per + local
            self.slot_of[key], self.key_at[slot] = slot, key
            new.append((slot, scope))
        if slot not in self.touched:
            self.touched.add(slot)
            self.live.append(slot)
            self.first.append(scope)
            self.live_in[slot // self.per] += 1
        return slot


class Model:
    def __init__(self, caps, n_shards):
        self.tables = {t: ModelTable(c, n_shards) for t, c in caps.items()}
        self.new = []

    def feed(self, line, lanes):
        m = parser.parse_metric(line)
        t = TABLE_OF[m.type]
        key = (m.type, m.name, m.joined_tags)
        new = []
        slot = self.tables[t].slot_for(key, m.digest, m.scope, new)
        self.new += [(m.type, s, sc, m.name, m.joined_tags, False)
                     for s, sc in new]
        if slot is not None:
            lanes[t].append(slot)

    def next_interval(self):
        for t in self.tables.values():
            t.next_interval()


def _lines(rng, universe, n):
    """n lines over `universe` key ids, half Zipf-skewed and half uniform:
    kinds, scopes, keys
    shorter and longer than 15 bytes, and now and then a line the parser
    refuses or hands back (an event)."""
    hot = np.minimum(rng.zipf(1.1, n) - 1, len(universe) - 1)
    ids = universe[np.where(rng.random(n) < 0.5, hot,
                            rng.integers(0, len(universe), n))]
    out = []
    for i, k in enumerate(ids.tolist()):
        kind = ("c", "c", "c", "g", "s", "ms", "h")[k % 7]
        name = f"k{k}" if k % 3 else f"svc.endpoint.latency.{k}"
        tags = ("", f"|#env:p,az:{k % 5}", "|#veneurlocalonly,t:1",
                f"|#veneurglobalonly,host:h{k % 11}")[k % 4]
        value = f"m{i % 13}" if kind == "s" else f"{1 + i % 9}"
        out.append(f"{name}:{value}|{kind}{tags}".encode())
        if i % 997 == 5:
            out.append(b"not a metric")
        if i % 1499 == 7:
            out.append(b"_e{5,4}:title|text")
    return out


class Engine:
    """A NativeIngest fed through vt_feed, its lanes emitted whenever one
    fills, and the Python model beside it."""

    def __init__(self, caps, n_shards):
        spec = TableSpec(counter_capacity=caps["counter"],
                         gauge_capacity=caps["gauge"], status_capacity=8,
                         set_capacity=caps["set"],
                         histo_capacity=caps["histo"])
        self.eng = native.NativeIngest(spec, BSPEC, n_shards=n_shards)
        self.model = Model(caps, n_shards)
        self.lanes = {t: [] for t in LANES}
        self.want = {t: [] for t in LANES}
        self.parks = 0
        self.keys = set()

    def emit(self):
        arrays = (np.zeros(BSPEC.counter, np.int32),
                  np.zeros(BSPEC.counter, np.float32),
                  np.zeros(BSPEC.gauge, np.int32),
                  np.zeros(BSPEC.gauge, np.float32),
                  np.zeros(BSPEC.set, np.int32), np.zeros(BSPEC.set, np.int32),
                  np.zeros(BSPEC.set, np.uint8),
                  np.zeros(BSPEC.histo, np.int32),
                  np.zeros(BSPEC.histo, np.float32),
                  np.zeros(BSPEC.histo, np.float32))
        counts = self.eng.emit_into(arrays)
        for t, arr, n in zip(LANES, arrays[0:1] + arrays[2:3] + arrays[4:5]
                             + arrays[7:8], counts):
            self.lanes[t] += arr[:n].tolist()

    def send(self, lines):
        data = b"\n".join(lines)
        full, off = self.eng.feed(data)
        while full:
            # parked: what the engine holds is exactly the lines before
            # `off`, nothing of the rest looked up or touched
            self.parks += 1
            done = data[:off].count(b"\n")
            self.check_live(lines[:done])
            self.emit()
            full, off = self.eng.feed(data, off)
        assert off == len(data)
        for ln in lines:
            if not ln.startswith((b"not", b"_e{")):
                self.model.feed(ln, self.want)
                self.keys.add(ln.split(b":")[0] + ln.partition(b"|")[2])

    def check_live(self, prefix=()):
        model = copy.deepcopy(self.model)
        for ln in prefix:
            if not ln.startswith((b"not", b"_e{")):
                model.feed(ln, {t: [] for t in LANES})
        for t, m in model.tables.items():
            slots, first = self.eng.live_keys(t)
            assert slots.tolist() == m.live, t
            assert (first & 0x7F).tolist() == m.first, t

    def close_interval(self):
        self.emit()
        assert self.lanes == self.want
        self.check_live()
        assert self.eng.drain_new_keys() == self.model.new
        stats = self.eng.table_stats()
        for t, m in self.model.tables.items():
            assert stats[t][:2] == (len(m.live), m.dropped), t
        before = self.eng.key_counters()["keys_evicted"]
        self.eng.reset()
        evicted = sum(m.evicted for m in self.model.tables.values())
        assert self.eng.key_counters()["keys_evicted"] - before == evicted
        for m in self.model.tables.values():
            m.evicted = 0
        self.model.new = []
        self.model.next_interval()
        self.lanes = {t: [] for t in LANES}
        self.want = {t: [] for t in LANES}


def _datagrams(lines, per=30):
    return [lines[i:i + per] for i in range(0, len(lines), per)]


@pytest.mark.parametrize("n_shards", [1, 4])
def test_slots_follow_the_model_across_intervals(n_shards):
    """Tens of thousands of keys over six intervals whose key sets drift,
    into tables that fill: eviction sweeps, backward-shift deletions,
    drops; then a staged capacity and a staged shard map."""
    rng = np.random.default_rng(4200 + n_shards)
    caps = {"counter": 1024, "gauge": 256, "set": 256, "histo": 512}
    e = Engine(caps, n_shards)
    keys = rng.permutation(60_000)
    evicted = dropped = 0
    for k in range(6):
        universe = keys[k * 6_000:k * 6_000 + 20_000]
        for dg in _datagrams(_lines(rng, universe, 15_000)):
            e.send(dg)
        evicted += sum(m.evicted for m in e.model.tables.values())
        dropped = sum(m.dropped for m in e.model.tables.values())
        e.close_interval()
        if k == 3:
            # applied by the next reset: the tables start empty
            caps = {"counter": 2048, "gauge": 128, "set": 256, "histo": 512}
            e.eng.capacity_set(*caps.values())
        if k == 4:
            n_shards = 2 if n_shards == 1 else 1
            e.eng.shard_map_set(n_shards)
        if k in (3, 4):
            e.eng.reset()
            old, e.model = e.model, Model(caps, n_shards)
            for t, m in e.model.tables.items():
                m.dropped = old.tables[t].dropped   # counted since start
            assert e.eng.table_stats()["counter"][::2] == (0, caps["counter"])
    assert len(e.keys) > 20_000
    assert evicted > 1000 and dropped > 1000 and e.parks > 100


def test_keys_keep_their_slots_across_arena_compaction():
    """Every interval evicts the last one's 1,000 long keys for 1,000 new
    ones while 24 keys stay: the key arena's dead bytes pass 1 MiB, it is
    packed, and the staying keys are still found at their slots."""
    e = Engine({"counter": 1024, "gauge": 64, "set": 64, "histo": 64}, 1)
    stay = [f"stay.{i}:1|c".encode() for i in range(24)]
    pad = "p" * 40
    for k in range(30):
        fresh = [f"churn.{pad}.{k}.{i}:1|c".encode() for i in range(1000)]
        for dg in _datagrams(stay + fresh):
            e.send(dg)
        e.close_interval()
    assert e.eng.key_counters()["keys_evicted"] == 29 * 1000


def test_a_parked_datagram_resumes_in_line_order():
    """A datagram longer than the counter lane parks, is emitted and
    resumes: its keys arrive in line order, each allocated once, and the
    lines after the stop are neither touched nor recorded before it."""
    e = Engine({"counter": 4096, "gauge": 64, "set": 64, "histo": 64}, 1)
    lines = [f"park.{'x' * (i % 23)}{i}:1|c|#i:{i}".encode()
             for i in range(3 * BSPEC.counter + 17)]
    e.send(lines)
    assert e.parks == 3
    e.send(lines[::-1])
    e.close_interval()


def test_ring_replicas_give_the_masters_slots():
    """Two ring parsers with their replicas: a key allocated through ring
    0 is found at the same slot by ring 1 (a replica miss, then the
    master) and by both again (replica hits)."""
    caps = {"counter": 2048, "gauge": 64, "set": 64, "histo": 64}
    spec = TableSpec(counter_capacity=2048, gauge_capacity=64,
                     status_capacity=8, set_capacity=64, histo_capacity=64)
    eng = native.NativeIngest(spec, BSPEC)
    model = Model(caps, 1)
    names = [f"ring.{'y' * (i % 19)}{i}" for i in range(1500)]
    eng.rings_start(2)
    got = {}

    def drain(ring):
        arrays = (np.zeros(BSPEC.counter, np.int32),
                  np.zeros(BSPEC.counter, np.float32),
                  *(np.zeros(n, d) for n, d in (
                      (BSPEC.gauge, np.int32), (BSPEC.gauge, np.float32),
                      (BSPEC.set, np.int32), (BSPEC.set, np.int32),
                      (BSPEC.set, np.uint8), (BSPEC.histo, np.int32),
                      (BSPEC.histo, np.float32), (BSPEC.histo, np.float32))))
        bounds = np.zeros(4 * 2, np.int32)
        n = eng.rings_emit_sharded(ring, arrays, bounds)[0]
        for slot, inc in zip(arrays[0][:n].tolist(), arrays[1][:n].tolist()):
            got.setdefault(int(inc) - 1, set()).add(slot)

    def send(rings, sent):
        for d, ring in enumerate(rings):
            dg = b"\n".join(f"{names[i]}:{i + 1}|c".encode()
                            for i in range(d * 30, min(len(names),
                                                       d * 30 + 30)))
            assert eng.rings_inject(ring, dg) == native.INJECT_OK
            sent += dg.count(b"\n") + 1
            deadline = time.monotonic() + 20
            while eng.stats()["processed"] < sent:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            drain(ring)
        return sent

    try:
        n_dg = (len(names) + 29) // 30
        sent = send([0] * n_dg, 0)
        sent = send([1] * n_dg, sent)
        sent = send([d % 2 for d in range(n_dg)], sent)
        for i, name in enumerate(names):
            model.feed(f"{name}:1|c".encode(), {t: [] for t in LANES})
        want = model.tables["counter"].slot_of
        assert got == {i: {want[("counter", n, "")]}
                       for i, n in enumerate(names)}
        per = eng.ring_stats_per_ring()
        assert sum(r["key_lookups_sampled"] for r in per) > 0
    finally:
        eng.readers_stop()
