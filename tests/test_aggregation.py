"""Aggregation engine tests: key table + scatter ingest step + flush.

Modeled on the reference's samplers_test.go (per-type sample/flush fidelity,
sample-rate weighting, cross-instance merge) and worker_test.go (ProcessMetric
routing), but against exact numpy oracles.
"""

import collections
import time

import numpy as np
import pytest

from veneur_tpu.aggregation import (
    Batch, Batcher, DeviceState, KeyTable, TableSpec, compact, empty_state,
    flush_compute, fold_scalars, ingest_step)
from veneur_tpu.aggregation.host import BatchSpec


SPEC = TableSpec(counter_capacity=256, gauge_capacity=64, status_capacity=16,
                 set_capacity=16, histo_capacity=64, hll_precision=12)
BSPEC = BatchSpec(counter=1024, gauge=256, status=64, set=2048, histo=4096)

def _flush_full(state, qs, *, spec):
    from veneur_tpu.aggregation.step import finish_flush
    return finish_flush(flush_compute(state, qs, spec=spec))



def _empty_batch(spec, bspec):
    return Batch(
        counter_slot=np.full(bspec.counter, spec.counter_capacity, np.int32),
        counter_inc=np.zeros(bspec.counter, np.float32),
        gauge_slot=np.full(bspec.gauge, spec.gauge_capacity, np.int32),
        gauge_val=np.zeros(bspec.gauge, np.float32),
        status_slot=np.full(bspec.status, spec.status_capacity, np.int32),
        status_val=np.zeros(bspec.status, np.float32),
        set_slot=np.full(bspec.set, spec.set_capacity, np.int32),
        set_reg=np.zeros(bspec.set, np.int32),
        set_rho=np.zeros(bspec.set, np.uint8),
        histo_slot=np.full(bspec.histo, spec.histo_capacity, np.int32),
        histo_val=np.zeros(bspec.histo, np.float32),
        histo_wt=np.zeros(bspec.histo, np.float32),
    )


def test_counter_exact_vs_numpy():
    rng = np.random.RandomState(0)
    state = empty_state(SPEC)
    oracle = np.zeros(SPEC.counter_capacity, np.float64)
    for step in range(20):
        b = _empty_batch(SPEC, BSPEC)
        n = 700
        slots = rng.randint(0, 32, n).astype(np.int32)
        incs = rng.randint(1, 1000, n).astype(np.float32)
        b.counter_slot[:n] = slots
        b.counter_inc[:n] = incs
        np.add.at(oracle, slots, incs.astype(np.float64))
        state = ingest_step(state, b, spec=SPEC)
        if step % 7 == 6:
            state = fold_scalars(state)
    state = fold_scalars(state)
    state = compact(state, spec=SPEC)
    out = _flush_full(state, np.array([0.5], np.float32), spec=SPEC)
    got = np.asarray(out["counter"], np.float64)
    np.testing.assert_allclose(got[:32], oracle[:32], rtol=1e-6)
    assert got[32:].sum() == 0


def test_counter_sample_rate_weighting():
    # reference samplers.go:142-144: value scaled by 1/rate
    state = empty_state(SPEC)
    b = _empty_batch(SPEC, BSPEC)
    b.counter_slot[:2] = [0, 0]
    b.counter_inc[:2] = [5 * (1 / 0.5), 3 * (1 / 0.1)]
    state = fold_scalars(ingest_step(state, b, spec=SPEC))
    out = _flush_full(compact(state, spec=SPEC),
                        np.array([0.5], np.float32), spec=SPEC)
    assert float(out["counter"][0]) == pytest.approx(10 + 30)


def test_gauge_last_write_wins():
    state = empty_state(SPEC)
    b = _empty_batch(SPEC, BSPEC)
    # slot 3 written three times in one batch: last (42) must win
    b.gauge_slot[:4] = [3, 3, 5, 3]
    b.gauge_val[:4] = [1.0, 7.0, 9.0, 42.0]
    state = ingest_step(state, b, spec=SPEC)
    # a later batch overwrites slot 5
    b2 = _empty_batch(SPEC, BSPEC)
    b2.gauge_slot[:1] = [5]
    b2.gauge_val[:1] = [-2.0]
    state = ingest_step(state, b2, spec=SPEC)
    out = _flush_full(compact(fold_scalars(state), spec=SPEC),
                        np.array([0.5], np.float32), spec=SPEC)
    assert float(out["gauge"][3]) == 42.0
    assert float(out["gauge"][5]) == -2.0


def test_status_last_write_wins():
    state = empty_state(SPEC)
    b = _empty_batch(SPEC, BSPEC)
    b.status_slot[:2] = [1, 1]
    b.status_val[:2] = [0.0, 2.0]  # OK then CRITICAL; CRITICAL wins
    state = ingest_step(state, b, spec=SPEC)
    out = _flush_full(compact(fold_scalars(state), spec=SPEC),
                        np.array([0.5], np.float32), spec=SPEC)
    assert float(out["status"][1]) == 2.0


def test_set_cardinality_table():
    from veneur_tpu.utils.hashing import hll_reg_rho
    state = empty_state(SPEC)
    rng = np.random.RandomState(5)
    true_card = 5000
    members = [b"user-%d" % i for i in range(true_card)]
    # feed each member 1-3 times across batches into slot 2
    feed = members * 2 + [members[i] for i in rng.randint(0, true_card, 3000)]
    rng.shuffle(feed)
    i = 0
    while i < len(feed):
        b = _empty_batch(SPEC, BSPEC)
        chunk = feed[i:i + BSPEC.set]
        for j, m in enumerate(chunk):
            reg, rho = hll_reg_rho(m, SPEC.hll_precision)
            b.set_slot[j] = 2
            b.set_reg[j] = reg
            b.set_rho[j] = rho
        i += len(chunk)
        state = ingest_step(state, b, spec=SPEC)
    out = _flush_full(compact(fold_scalars(state), spec=SPEC),
                        np.array([0.5], np.float32), spec=SPEC)
    est = float(out["set_estimate"][2])
    assert est == pytest.approx(true_card, rel=0.05)
    assert float(out["set_estimate"][3]) == 0.0


def _run_histo(data_by_slot, compact_every=4, spec=SPEC, bspec=BSPEC,
               qs=(0.5, 0.9, 0.99)):
    state = empty_state(spec)
    streams = {s: list(v) for s, v in data_by_slot.items()}
    flat = [(s, v) for s, vs in streams.items() for v in vs]
    rng = np.random.RandomState(9)
    rng.shuffle(flat)
    step = 0
    i = 0
    while i < len(flat):
        b = _empty_batch(spec, bspec)
        chunk = flat[i:i + bspec.histo]
        b.histo_slot[:len(chunk)] = [s for s, _ in chunk]
        b.histo_val[:len(chunk)] = [v for _, v in chunk]
        b.histo_wt[:len(chunk)] = 1.0
        i += len(chunk)
        state = ingest_step(state, b, spec=spec)
        step += 1
        if step % compact_every == 0:
            state = compact(state, spec=spec)
    state = compact(fold_scalars(state), spec=spec)
    return _flush_full(state, np.array(qs, np.float32), spec=spec)


def test_histo_quantiles_uniform_two_keys():
    rng = np.random.RandomState(1)
    data = {0: rng.uniform(0, 1, 30_000).astype(np.float32),
            7: rng.uniform(0, 1, 30_000).astype(np.float32)}
    out = _run_histo(data)
    for slot in (0, 7):
        got = np.asarray(out["histo_quantiles"][slot])
        exact = np.quantile(data[slot], [0.5, 0.9, 0.99])
        err = np.abs(got - exact)
        assert err[0] < 0.02, f"slot {slot} p50 err {err}"
        assert err[2] < 0.01, f"slot {slot} p99 err {err}"


def test_histo_quantiles_lognormal():
    rng = np.random.RandomState(2)
    data = {3: rng.lognormal(3.0, 1.0, 40_000).astype(np.float32)}
    out = _run_histo(data)
    got = np.asarray(out["histo_quantiles"][3])
    exact = np.quantile(data[3], [0.5, 0.9, 0.99])
    rel = np.abs(got - exact) / exact
    assert rel[0] < 0.02, f"p50 rel err {rel}"
    assert rel[1] < 0.02, f"p90 rel err {rel}"
    assert rel[2] < 0.015, f"p99 rel err {rel}"


def test_histo_p99_max_error_per_key_zipf():
    """The ≤1% p99 budget is PER KEY, not a mean (VERDICT r04 weak #3 /
    BASELINE): Zipf-popularity names with heavy-tail latencies through
    the production ingest path — exact-extreme protection
    (ops/tdigest.py) plus extremeness-priority temp allocation
    (step._histo_update) must hold every key's p99 inside 1%, from
    few-sample tail names through multi-thousand-sample hot names."""
    rng = np.random.RandomState(7)
    names = 256
    total = 120_000
    ranks = np.arange(1, names + 1, dtype=np.float64)
    p = (1.0 / ranks) / np.sum(1.0 / ranks)
    name_of = rng.choice(names, size=total, p=p)
    vals = rng.lognormal(3.0, 0.9, total).astype(np.float32)
    data = {}
    for n in range(names):
        v = vals[name_of == n]
        if len(v) >= 20:
            data[int(n)] = v
    spec = TableSpec(counter_capacity=16, gauge_capacity=16,
                     status_capacity=16, set_capacity=16,
                     histo_capacity=256)
    out = _run_histo(data, compact_every=2, spec=spec)
    # midpoint-rank oracle, the digest's (and reference Quantile's)
    # convention — np.quantile's linear-rank convention diverges at
    # heavy-tail extremes (an 80→391 sample gap moves the conventions
    # ~2.5x apart on a 94-sample key) and would measure the convention,
    # not the digest
    from benchmarks.tdigest_analysis import midpoint_quantile
    worst = (0.0, -1, 0)
    for slot, v in data.items():
        exact = midpoint_quantile(np.sort(np.asarray(v, np.float64)),
                                  0.99)
        got = float(out["histo_quantiles"][slot][2])
        rel = abs(got - exact) / exact
        if rel > worst[0]:
            worst = (rel, slot, len(v))
    assert worst[0] < 0.01, (
        f"worst per-key p99 err {worst[0]:.4f} at slot {worst[1]} "
        f"(n={worst[2]})")


def test_tiled_flush_matches_single_shot(monkeypatch):
    """A flush whose live buckets exceed FLUSH_BLOCK_ROWS loops one
    block-shaped executable over row blocks, so that compile time and
    the program's working set are bounded by the block and not by live
    cardinality — and must produce EXACTLY the single-shot flush's
    values, in the same get_meta positional order. The benchmark's cell
    agent-1m-names measures the tiling on the chip (five blocks a flush);
    tests/test_1m_names_deployment.py holds it on the served path."""
    from veneur_tpu.samplers import parser
    from veneur_tpu.aggregation import step as step_mod
    from veneur_tpu.server.aggregator import Aggregator

    def build_and_flush():
        agg = Aggregator(TableSpec(counter_capacity=512,
                                   gauge_capacity=256,
                                   status_capacity=8, set_capacity=32,
                                   histo_capacity=256),
                         BatchSpec(counter=1024, histo=1024))
        for i in range(300):
            agg.process_metric(parser.parse_metric(b"c.%d:%d|c" % (i, i)))
        for i in range(150):
            agg.process_metric(
                parser.parse_metric(b"t.%d:%d.5|ms" % (i, i)))
        for i in range(20):
            agg.process_metric(parser.parse_metric(b"s.%d:m%d|s" % (i, i)))
        out, table = agg.flush([0.5, 0.99])
        return out, table

    big, table_a = build_and_flush()           # single shot (block 2^17)
    monkeypatch.setattr(step_mod, "FLUSH_BLOCK_ROWS", 64)
    tiled, table_b = build_and_flush()         # 300 counters -> 5 blocks

    assert [m.name for _s, m in table_a.get_meta("counter")] == \
           [m.name for _s, m in table_b.get_meta("counter")]
    for key in big:
        a, b = np.asarray(big[key]), np.asarray(tiled[key])
        assert a.shape == b.shape, (key, a.shape, b.shape)
        np.testing.assert_array_equal(a, b, err_msg=key)  # NaN == NaN ok


def test_histo_aggregates_exact():
    rng = np.random.RandomState(3)
    vals = rng.exponential(10.0, 20_000).astype(np.float32)
    out = _run_histo({4: vals})
    v64 = vals.astype(np.float64)
    assert float(out["histo_count"][4]) == pytest.approx(len(vals), rel=1e-6)
    assert float(out["histo_min"][4]) == pytest.approx(v64.min(), rel=1e-6)
    assert float(out["histo_max"][4]) == pytest.approx(v64.max(), rel=1e-6)
    assert float(out["histo_sum"][4]) == pytest.approx(v64.sum(), rel=1e-4)
    assert float(out["histo_avg"][4]) == pytest.approx(v64.mean(), rel=1e-4)
    hmean = len(vals) / (1.0 / v64).sum()
    assert float(out["histo_hmean"][4]) == pytest.approx(hmean, rel=1e-3)


def test_histo_compact_cadence_consistency():
    # same data, different compaction cadence -> quantiles agree closely
    rng = np.random.RandomState(4)
    data = {0: rng.normal(100.0, 15.0, 20_000).astype(np.float32)}
    a = _run_histo(data, compact_every=2)
    b = _run_histo(data, compact_every=16)
    qa = np.asarray(a["histo_quantiles"][0])
    qb = np.asarray(b["histo_quantiles"][0])
    exact = np.quantile(data[0], [0.5, 0.9, 0.99])
    assert np.all(np.abs(qa - exact) / exact < 0.01)
    assert np.all(np.abs(qb - exact) / exact < 0.01)


def test_keytable_and_batcher_end_to_end():
    table = KeyTable(SPEC, n_shards=4)
    batches = []
    batcher = Batcher(SPEC, BSPEC, on_batch=batches.append)
    from veneur_tpu.utils.hashing import fnv1a_32

    def digest(name, t, tags):
        return fnv1a_32((name + t + ",".join(tags)).encode())

    s1 = table.slot_for("counter", "a.b", ("x:1",), 0, digest("a.b", "c", ("x:1",)))
    s2 = table.slot_for("counter", "a.b", ("x:1",), 0, digest("a.b", "c", ("x:1",)))
    s3 = table.slot_for("counter", "a.b", ("x:2",), 0, digest("a.b", "c", ("x:2",)))
    assert s1 == s2 and s1 != s3
    sh = table.slot_for("timer", "lat", (), 0, digest("lat", "ms", ()))
    sh2 = table.slot_for("histogram", "lat", (), 0, digest("lat", "h", ()))
    assert sh != sh2  # distinct namespaces share the histo table

    batcher.add_counter(s1, 5.0, 1.0)
    batcher.add_counter(s3, 2.0, 0.5)
    batcher.add_histo(sh, 100.0, 1.0)
    batcher.add_set(table.slot_for("set", "uids", (), 0, 123), b"u1")
    batcher.emit()
    assert len(batches) == 1
    state = empty_state(SPEC)
    state = ingest_step(state, batches[0], spec=SPEC)
    out = _flush_full(compact(fold_scalars(state), spec=SPEC),
                        np.array([0.5], np.float32), spec=SPEC)
    assert float(out["counter"][s1]) == 5.0
    assert float(out["counter"][s3]) == 4.0
    assert float(out["histo_count"][sh]) == 1.0
    # slot metadata for flush labeling
    metas = dict(table.get_meta("counter"))
    assert metas[s1].name == "a.b"


def test_keytable_overflow_drops():
    spec = TableSpec(counter_capacity=4, gauge_capacity=4, status_capacity=4,
                     set_capacity=4, histo_capacity=4, hll_precision=10)
    t = KeyTable(spec, n_shards=1)
    slots = [t.slot_for("counter", f"m{i}", (), 0, i) for i in range(6)]
    assert slots[:4] == [0, 1, 2, 3]
    assert slots[4] is None and slots[5] is None
    assert t.dropped() == 2


def test_counter_exactness_envelope_beyond_f32():
    """The documented counter precision contract vs the reference's int64
    (samplers/samplers.go:129-144): per-slot totals stay EXACT as long as
    (a) each fold window's accumulated increments stay within f32's 24-bit
    integer range and (b) the interval total stays within the two-float
    pair's ~48-bit range. 2^32 + 1 is unrepresentable in f32 (a plain
    hi+lo flush collapses it to 2^32) but must flush exactly."""
    state = empty_state(SPEC)
    b = BSPEC.counter
    inc = np.zeros(b, np.float32)
    slot = np.zeros(b, np.int32)
    # 64 batches x 1024 lanes x 65536.0 = 2^32 into slot 0, all within
    # the per-window exact range (fold every 16 batches: 2^30 < 2^24?
    # no — 16*1024*65536 = 2^30 > 2^24 as a SINGLE value is fine: f32
    # represents every multiple of 64 up to 2^30 exactly since each
    # addend is a power of two and partial sums are multiples of 2^16)
    inc[:] = 65536.0
    empty = dict(
        gauge_slot=np.full(BSPEC.gauge, SPEC.gauge_capacity, np.int32),
        gauge_val=np.zeros(BSPEC.gauge, np.float32),
        status_slot=np.full(BSPEC.status, SPEC.status_capacity, np.int32),
        status_val=np.zeros(BSPEC.status, np.float32),
        set_slot=np.full(BSPEC.set, SPEC.set_capacity, np.int32),
        set_reg=np.zeros(BSPEC.set, np.int32),
        set_rho=np.zeros(BSPEC.set, np.uint8),
        histo_slot=np.full(BSPEC.histo, SPEC.histo_capacity, np.int32),
        histo_val=np.zeros(BSPEC.histo, np.float32),
        histo_wt=np.zeros(BSPEC.histo, np.float32))
    batch = Batch(counter_slot=slot, counter_inc=inc, **empty)
    for step in range(64):
        state = ingest_step(state, batch, spec=SPEC)
        if (step + 1) % 16 == 0:
            state = fold_scalars(state)
    # one more odd unit lands the total on 2^32 + 1
    one = inc.copy()
    one[:] = 0.0
    one[0] = 1.0
    state = ingest_step(state, Batch(counter_slot=slot, counter_inc=one,
                                     **empty), spec=SPEC)
    state = fold_scalars(state)
    out = _flush_full(state, np.array([0.5], np.float32), spec=SPEC)
    assert out["counter"].dtype == np.float64
    assert float(out["counter"][0]) == 2.0 ** 32 + 1.0


def test_counter_error_bound_documented_envelope():
    """Beyond the exact envelope the error is bounded by f32 rounding of
    the per-window accumulator: relative error < 2^-22 per interval for
    any mix of magnitudes (vs int64's zero error — the documented
    deviation)."""
    rng = np.random.RandomState(7)
    state = empty_state(SPEC)
    exact = 0.0
    for _ in range(32):
        inc = rng.uniform(0, 1e6, BSPEC.counter).astype(np.float32)
        exact += float(np.sum(inc.astype(np.float64)))
        batch = Batch(
            counter_slot=np.zeros(BSPEC.counter, np.int32),
            counter_inc=inc,
            gauge_slot=np.full(BSPEC.gauge, SPEC.gauge_capacity, np.int32),
            gauge_val=np.zeros(BSPEC.gauge, np.float32),
            status_slot=np.full(BSPEC.status, SPEC.status_capacity,
                                np.int32),
            status_val=np.zeros(BSPEC.status, np.float32),
            set_slot=np.full(BSPEC.set, SPEC.set_capacity, np.int32),
            set_reg=np.zeros(BSPEC.set, np.int32),
            set_rho=np.zeros(BSPEC.set, np.uint8),
            histo_slot=np.full(BSPEC.histo, SPEC.histo_capacity, np.int32),
            histo_val=np.zeros(BSPEC.histo, np.float32),
            histo_wt=np.zeros(BSPEC.histo, np.float32))
        state = ingest_step(state, batch, spec=SPEC)
        state = fold_scalars(state)
    out = _flush_full(state, np.array([0.5], np.float32), spec=SPEC)
    got = float(out["counter"][0])
    assert abs(got - exact) / exact < 2.0 ** -22


def test_packed_batch_roundtrip_and_ingest_parity():
    """pack_batch -> ingest_step_packed must equal ingest_step on the
    same batch — the packed i32 carrier is bit-exact for every lane
    (f32 values incl. inf sentinels, i32 slots, u8 rhos)."""
    import jax
    from veneur_tpu.aggregation.step import (
        batch_sizes, ingest_step_packed, pack_batch, unpack_batch)

    rng = np.random.RandomState(3)
    b = _empty_batch(SPEC, BSPEC)
    b.counter_slot[:50] = rng.randint(0, 256, 50)
    b.counter_inc[:50] = rng.uniform(0, 10, 50).astype(np.float32)
    b.gauge_slot[:20] = rng.randint(0, 64, 20)
    b.gauge_val[:20] = rng.uniform(-5, 5, 20).astype(np.float32)
    b.status_slot[:4] = rng.randint(0, 16, 4)
    b.status_val[:4] = [0, 1, 2, 1]
    b.set_slot[:30] = rng.randint(0, 16, 30)
    b.set_reg[:30] = rng.randint(0, 1 << 12, 30)
    b.set_rho[:30] = rng.randint(1, 50, 30)
    b.histo_slot[:100] = rng.randint(0, 64, 100)
    b.histo_val[:100] = rng.lognormal(1, 1, 100).astype(np.float32)
    b.histo_wt[:100] = 1.0
    b = b._replace(
        histo_stat_slot=np.full(BSPEC.histo_stat, SPEC.histo_capacity,
                                np.int32),
        histo_stat_min=np.full(BSPEC.histo_stat, np.inf, np.float32),
        histo_stat_max=np.full(BSPEC.histo_stat, -np.inf, np.float32),
        histo_stat_recip=np.zeros(BSPEC.histo_stat, np.float32))

    # lane-level roundtrip (host pack -> device unpack, jitted identity;
    # flat[0] is the in-band compact control word)
    sizes = batch_sizes(b)
    flat = pack_batch(b)
    assert flat[0] == 0 and pack_batch(b, do_compact=True)[0] == 1
    back = jax.jit(lambda f: unpack_batch(f[1:], sizes))(flat)
    for name, orig, got in zip(Batch._fields, b, back):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(orig), err_msg=name)

    # full ingest parity, with and without the fused compact branch
    ref = fold_scalars(ingest_step(empty_state(SPEC), b, spec=SPEC))
    packed, rows = ingest_step_packed(empty_state(SPEC), pack_batch(b),
                                      spec=SPEC, sizes=sizes)
    assert int(rows) == 0
    for name, a, c in zip(ref._fields, ref, packed):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(c), err_msg=name)
    ref_c = compact(fold_scalars(ingest_step(empty_state(SPEC), b,
                                             spec=SPEC)), spec=SPEC)
    packed_c, rows = ingest_step_packed(empty_state(SPEC),
                                        pack_batch(b, do_compact=True),
                                        spec=SPEC, sizes=sizes)
    assert int(rows) == len(np.unique(
        b.histo_slot[(b.histo_slot < SPEC.histo_capacity)
                     & (b.histo_wt > 0)]))
    for name, a, c in zip(ref_c._fields, ref_c, packed_c):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(c), err_msg=name)


def test_packed_batch_none_stat_lanes():
    """A default-constructed Batch (histo_stat_* = None, the pure-ingest
    common case) must pack, unpack back to None, and ingest identically
    to the unpacked path."""
    from veneur_tpu.aggregation.step import (
        batch_sizes, ingest_step_packed, pack_batch)

    b = _empty_batch(SPEC, BSPEC)           # stat lanes default to None
    b.histo_slot[:10] = np.arange(10)
    b.histo_val[:10] = np.linspace(1, 10, 10).astype(np.float32)
    b.histo_wt[:10] = 1.0
    sizes = batch_sizes(b)
    assert sizes[-4:] == (0, 0, 0, 0)
    ref = fold_scalars(ingest_step(empty_state(SPEC), b, spec=SPEC))
    packed, _rows = ingest_step_packed(empty_state(SPEC), pack_batch(b),
                                       spec=SPEC, sizes=sizes)
    for name, a, c in zip(ref._fields, ref, packed):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(c), err_msg=name)


def test_set_member_invalid_utf8_survives_python_path():
    """A set member that is not valid UTF-8 (parser decodes it with
    surrogateescape) must stage without raising — a plain encode() threw
    UnicodeEncodeError out of process_metric, killing the pipeline
    thread: one corrupt datagram was a denial of service (found by the
    extended differential fuzz). The restored bytes must hash like the
    raw wire bytes (C++ engine parity)."""
    from veneur_tpu.utils.hashing import hll_reg_rho
    from veneur_tpu.samplers import parser
    from veneur_tpu.server.aggregator import Aggregator

    raw = b"\xf3\x28"                      # invalid UTF-8 member bytes
    agg = Aggregator(TableSpec(counter_capacity=64, gauge_capacity=16,
                               status_capacity=8, set_capacity=16,
                               histo_capacity=16))
    m = parser.parse_metric(b"s.bin:" + raw + b"|s")
    agg.process_metric(m)                  # must not raise
    assert agg.processed == 1
    b = agg.batcher
    assert b.ns == 1
    reg, rho = hll_reg_rho(raw, agg.spec.hll_precision)
    assert (b.s_slot[0] < agg.spec.set_capacity
            and b.s_reg[0] == reg
            and b.s_rho[0] == rho), "member bytes must round-trip"


def _feed_python(agg, i):
    from veneur_tpu.samplers import parser
    before, k = agg.steps_total, 0
    while agg.steps_total == before:    # until a lane fills
        agg.process_metric(
            parser.parse_metric(b"c.%d:%d|c" % (k % 7, i + 1)))
        k += 1


def _feed_native(agg, i):
    agg.feed(b"c.%d:%d|c\nt.%d:%d|ms" % (i % 3, i + 1, i % 5, i))
    agg._emit_native()


def _feed_rings(agg, i):
    done = agg.eng.stats()["processed"] + 1
    assert agg.eng.rings_inject(i % 2, b"c.%d:%d|c" % (i % 3, i + 1))
    deadline = time.monotonic() + 30
    while agg.eng.stats()["processed"] < done:
        assert time.monotonic() < deadline, "the ring worker stalled"
        time.sleep(0.0005)
    assert agg._emit_rings()


def _step_site(name):
    """(aggregator, feed) for one of the step sites that run on the CPU;
    feed(agg, i) makes it dispatch one step whose words depend on i."""
    spec = TableSpec(counter_capacity=64, gauge_capacity=16,
                     status_capacity=8, set_capacity=16, histo_capacity=32)
    lanes = BatchSpec(counter=4, gauge=4, status=4, set=4, histo=4)
    if name == "aggregator":
        from veneur_tpu.server.aggregator import Aggregator
        return Aggregator(spec, lanes, compact_every=2), _feed_python
    if name == "sharded-row":
        from veneur_tpu.server.sharded_aggregator import ShardedAggregator
        return (ShardedAggregator(spec, lanes, n_shards=2, compact_every=2),
                _feed_python)
    if name == "tier-row":
        from veneur_tpu.collective.tier import CollectiveGlobalTier
        return (CollectiveGlobalTier(spec, lanes, n_shards=2, n_replicas=2,
                                     compact_every=2), _feed_python)
    from veneur_tpu.server.native_aggregator import NativeAggregator
    agg = NativeAggregator(spec, BatchSpec(counter=8, gauge=8, status=4,
                                           set=8, histo=8), compact_every=2)
    if name == "native-packed":
        return agg, _feed_native
    agg.rings_start(2)
    return agg, _feed_rings


@pytest.mark.parametrize("site", ["aggregator", "native-packed",
                                  "native-rings", "sharded-row", "tier-row"])
def test_no_packed_buffer_is_written_before_its_step_is_settled(site):
    """The invariant of Aggregator._init_step_site, at every step site:
    a runtime may read a host buffer in place for as long as its step is
    queued (the CPU client does), so the buffer a step was given holds
    the words it was dispatched with until _settle_step has popped that
    step. The step is replaced by one that keeps each buffer and a copy
    of its words."""
    from veneur_tpu.server.aggregator import _MAX_STEPS_IN_FLIGHT

    agg, feed = _step_site(site)
    kept = collections.deque()      # (buffer, its words) of unsettled steps
    dispatch, settle = agg._dispatch_step, agg._settle_step

    def unwritten():
        for n, (flat, words) in enumerate(kept):
            assert np.array_equal(flat, words), (
                f"the buffer of the step {len(kept) - n} back was written "
                f"while that step was in flight")

    def keeping_dispatch(step, flat, *args, **static):
        def keeping_step(state, flat, **static):
            unwritten()
            kept.append((flat, flat.copy()))
            return step(state, flat, **static)
        dispatch(keeping_step, flat, *args, **static)

    def checking_settle():
        unwritten()
        settle()
        kept.popleft()

    agg._dispatch_step, agg._settle_step = keeping_dispatch, checking_settle
    try:
        steps = 3 * (_MAX_STEPS_IN_FLIGHT + 1) + 2
        for i in range(steps):
            feed(agg, i)
            assert len(kept) == len(agg._steps_in_flight)
        assert agg.steps_total == steps
        assert len(kept) == _MAX_STEPS_IN_FLIGHT
        agg.swap()
        assert not kept
    finally:
        if site == "native-rings":
            agg.readers_stop()


@pytest.mark.parametrize("steps", [3, 21])
def test_dispatch_counts_compactions_and_bounds_steps_in_flight(steps):
    """_count_step: every compact_every-th step of the interval carries
    the compaction, which compresses the three rows that took samples
    (the count is settled at the swap at the latest). _dispatch_step:
    the host never has more than _MAX_STEPS_IN_FLIGHT steps queued that
    it has not seen finish, and the answer is what it was without the
    bound."""
    from veneur_tpu.samplers import parser
    from veneur_tpu.server import aggregator as aggregator_mod

    spec = TableSpec(counter_capacity=64, gauge_capacity=16,
                     status_capacity=8, set_capacity=16, histo_capacity=32)
    agg = aggregator_mod.Aggregator(
        spec, BatchSpec(counter=4, gauge=4, status=4, set=4, histo=4),
        compact_every=2)
    seen = []
    for i in range(steps * 4):
        agg.process_metric(parser.parse_metric(b"t.%d:%d|ms" % (i % 3, i)))
        agg.process_metric(parser.parse_metric(b"c.hot:2|c"))
        seen.append(len(agg._steps_in_flight))
    assert agg.steps_total >= steps
    assert max(seen) == min(agg.steps_total,
                            aggregator_mod._MAX_STEPS_IN_FLIGHT)
    assert agg.compactions == agg.steps_total // 2
    out, table = agg.flush([0.5])
    assert not agg._steps_in_flight
    assert agg.compact_rows == agg.compactions * 3
    slot = {m.name: s for s, m in table.get_meta("counter")}["c.hot"]
    assert float(np.asarray(out["counter"])[slot]) == steps * 4 * 2
    by_name = {m.name: s for s, m in table.get_meta("histo")}
    assert sum(float(np.asarray(out["histo_count"])[s])
               for s in by_name.values()) == steps * 4


# -- compaction over the dirty rows (PERF.md PR 31) ---------------------------

SMALL = TableSpec(counter_capacity=64, gauge_capacity=32, status_capacity=8,
                  set_capacity=16, histo_capacity=64, hll_precision=8,
                  temp_cells=16)
SMALL_B = BatchSpec(counter=64, gauge=64, status=64, set=64, histo=64)


def _whole_table_compact(h_w, h_wm, spec):
    """The form compact_core had until PR 31, kept as the oracle:
    compress_rows on every row, whatever it holds."""
    import jax.numpy as jnp
    from veneur_tpu.ops import tdigest as td
    m2, w2 = td.compress_rows(
        h_wm / jnp.maximum(h_w, 1e-30), h_w, compression=spec.compression,
        cells_per_k=spec.cells_per_k, out_c=spec.centroids,
        exact_extremes=spec.exact_extremes)
    pad = jnp.zeros(w2.shape[:-1] + (spec.temp_cells,), w2.dtype)
    return (np.asarray(jnp.concatenate([w2, pad], axis=-1)),
            np.asarray(jnp.concatenate([m2 * w2, pad], axis=-1)))


@pytest.mark.parametrize("fused", [False, True],
                         ids=["xla-chain", "fused-kernel"])
def test_a_row_changes_only_where_temp_n_marks_it(fused):
    """What compact_core leans on: between two compactions a row's h_w /
    h_wm differ from their post-compaction bytes only if its h_temp_n is
    above 0, in both ingest forms; and h_temp_n is above 0 exactly on the
    rows that took a sample. Hot rows overflow their temp cells here (16
    of them), so samples land in estimate cells too."""
    import jax
    from functools import partial
    from veneur_tpu.aggregation.step import compact_core, ingest_core
    from veneur_tpu.ops import pallas_ingest

    spec, kh = SMALL, SMALL.histo_capacity
    rng = np.random.default_rng(5)
    pallas_ingest.set_enabled(fused)
    try:
        ingest = jax.jit(partial(ingest_core, spec=spec))
        compact_rows = jax.jit(partial(compact_core, spec=spec))
        state = empty_state(spec)
        for cycle in range(3):
            w0, wm0 = np.asarray(state.h_w), np.asarray(state.h_wm)
            assert not np.asarray(state.h_temp_n).any()
            touched = np.zeros(kh, bool)
            for _ in range(3):
                b = _empty_batch(spec, SMALL_B)
                n = 48
                # a few hot rows, a sparse rest, and rows no batch names
                slot = np.where(rng.random(n) < 0.5,
                                rng.integers(0, 3, n),
                                rng.integers(0, kh // 2, n)).astype(np.int32)
                slot[rng.integers(0, n, 4)] = kh + 3        # dropped
                wt = rng.uniform(0.5, 2, n).astype(np.float32)
                wt[rng.integers(0, n, 6)] = 0.0             # dropped
                b.histo_slot[:n] = slot
                b.histo_val[:n] = rng.gamma(2.0, 15.0, n)
                b.histo_wt[:n] = wt
                touched[slot[(slot < kh) & (wt > 0)]] = True
                state = ingest(state, b)
                marked = np.asarray(state.h_temp_n) > 0
                changed = ((np.asarray(state.h_w) != w0).any(axis=1)
                           | (np.asarray(state.h_wm) != wm0).any(axis=1))
                assert not (changed & ~marked).any()
                np.testing.assert_array_equal(marked, touched)
            assert np.asarray(state.h_temp_n).max() == spec.temp_cells
            state = compact_rows(state)
    finally:
        pallas_ingest.set_enabled(None)


def _random_tables(rng, lead, spec, dirty_counts):
    """Digest tables of arbitrary bytes with h_temp_n marking
    dirty_counts[i] random rows of the i-th table."""
    kh, cells = spec.histo_capacity, spec.total_cells
    w = rng.integers(0, 3, lead + (kh, cells)).astype(np.float32)
    wm = w * rng.gamma(2.0, 15.0, w.shape).astype(np.float32)
    tn = np.zeros((len(dirty_counts), kh), np.int32)
    for row, d in zip(tn, dirty_counts):
        row[rng.permutation(kh)[:d]] = rng.integers(1, 17, d)
    return w, wm, tn.reshape(lead + (kh,))


@pytest.mark.parametrize("rows,block,dirty", [
    (64, 16, (0,)), (64, 16, (16,)), (64, 16, (21,)), (64, 16, (64,)),
    (72, 16, (70,)), (64, 1024, (21,)), (64, 16, (0, 21, 64))],
    ids=["none-dirty", "one-whole-block", "not-a-multiple-of-the-block",
         "all-dirty", "table-not-whole-blocks", "block-above-the-table",
         "vmap-vmap-unequal-shards"])
def test_compact_core_compresses_the_dirty_rows_and_no_other(
        rows, block, dirty, monkeypatch):
    """compact_core against the whole-table form: a dirty row comes out
    bit-identical to compress_rows' row, a clean row bit-identical to
    what went in, h_temp_n all zero. The last case is the sharded step's
    shape, vmap(vmap(compact_core)) over [1, 3] tiles whose dirty counts
    differ, so the loop runs to the largest."""
    import dataclasses
    import jax
    from functools import partial
    from veneur_tpu.aggregation import step

    monkeypatch.setattr(step, "COMPACT_ROW_BLOCK", block)
    spec = dataclasses.replace(SMALL, histo_capacity=rows)
    lead = (1, len(dirty)) if len(dirty) > 1 else ()
    w, wm, tn = _random_tables(np.random.default_rng(31), lead, spec, dirty)
    fn = partial(step.compact_core, spec=spec)
    state = empty_state(spec)
    if lead:
        fn = jax.vmap(jax.vmap(fn))
        state = jax.tree.map(
            lambda a: np.broadcast_to(np.asarray(a), lead + a.shape), state)
    out = jax.jit(fn)(state._replace(h_w=w, h_wm=wm, h_temp_n=tn))
    want_w, want_wm = _whole_table_compact(w, wm, spec)
    took = (tn > 0)[..., None]
    assert [int(d) for d in took.sum(axis=(-2, -1)).ravel()] == list(dirty)
    np.testing.assert_array_equal(np.asarray(out.h_w),
                                  np.where(took, want_w, w))
    np.testing.assert_array_equal(np.asarray(out.h_wm),
                                  np.where(took, want_wm, wm))
    assert not np.asarray(out.h_temp_n).any()
    for name in set(out._fields) - {"h_w", "h_wm", "h_temp_n"}:
        np.testing.assert_array_equal(np.asarray(getattr(out, name)),
                                      np.asarray(getattr(state, name)))


def test_compressing_a_canonical_row_again_only_coarsens():
    """The ground for leaving clean rows alone: a row compress_rows made,
    fed to it again, keeps its total weight and occupies no more cells
    than it did. The second pass adds no sample; where it differs at all
    it has folded two neighbours at a cell's edge."""
    from veneur_tpu.ops import tdigest as td
    rng = np.random.default_rng(7)
    n, m_len = 48, SMALL.total_cells
    w = np.zeros((n, m_len), np.float32)
    for row, k in zip(w, rng.integers(1, m_len + 1, n)):
        row[rng.permutation(m_len)[:k]] = rng.integers(1, 9, k)
    mean = rng.gamma(2.0, 15.0, w.shape).astype(np.float32)
    kw = dict(compression=SMALL.compression, cells_per_k=SMALL.cells_per_k,
              out_c=SMALL.centroids, exact_extremes=SMALL.exact_extremes)
    m1, w1 = td.compress_rows(mean, w, **kw)
    m2, w2 = td.compress_rows(m1, w1, **kw)
    w1, w2 = np.asarray(w1), np.asarray(w2)
    np.testing.assert_array_equal(w2.sum(axis=1), w.sum(axis=1))
    np.testing.assert_array_equal(w1.sum(axis=1), w.sum(axis=1))
    assert ((w2 > 0).sum(axis=1) <= (w1 > 0).sum(axis=1)).all()


@pytest.mark.parametrize("kind", ["timers", "counters-only"])
def test_compact_rows_is_the_devices_count_of_rows_that_took_samples(kind):
    """Over an interval compact_rows equals the distinct timer slots of
    each compaction group, added up (counted here in NumPy from the
    stream); a stream with no timer compacts just as often and
    compresses no row."""
    from veneur_tpu.samplers import parser
    from veneur_tpu.server.aggregator import Aggregator

    lane, every, n = 4, 3, 90
    agg = Aggregator(
        SMALL, BatchSpec(counter=lane, gauge=lane, status=lane, set=lane,
                         histo=lane), compact_every=every)
    rng = np.random.default_rng(3)
    names = rng.integers(0, 40, n)
    for i, name in enumerate(names):
        line = (b"t.%d:%d|ms" % (name, i) if kind == "timers"
                else b"c.%d:1|c" % name)
        agg.process_metric(parser.parse_metric(line))
    agg.flush([0.5])
    # a step takes `lane` samples (the swap emits the remainder as one
    # more); every `every`-th step compacts what its group touched
    steps = -(-n // lane)
    assert agg.steps_total == steps
    assert agg.compactions == steps // every > 2
    group = lane * every
    want = sum(len(set(names[g * group:(g + 1) * group]))
               for g in range(steps // every))
    assert agg.compact_rows == (want if kind == "timers" else 0)
    assert want > agg.compactions * 5
