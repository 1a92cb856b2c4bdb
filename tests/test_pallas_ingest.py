"""Golden parity + selection for the fused Pallas ingest kernel
(veneur_tpu/ops/pallas_ingest.py).

The kernel's whole correctness contract is BYTE parity with the XLA
scatter chain in ingest_core — same duplicate-resolution order, same
drop semantics for sentinel/overflow slots, same packed 6-bit register
arithmetic. These tests pin that contract in interpret mode on CPU (the
exact configuration tier-1 runs everywhere), plus the packed-register
equivalences (estimate / wire serialize vs dense u8) and the v1
dense-u8 checkpoint migration into the packed table.
"""

import json
import pathlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from veneur_tpu.aggregation.state import TableSpec, empty_state
from veneur_tpu.aggregation.step import Batch, ingest_core
from veneur_tpu.ops import hll
from veneur_tpu.ops import pallas_ingest

SPEC = TableSpec(counter_capacity=64, gauge_capacity=32, status_capacity=8,
                 set_capacity=16, histo_capacity=32, hll_precision=8)


@pytest.fixture
def fused_on():
    """Force the fused path (interpret mode on CPU); always restore
    the backend rule so later test modules see the default behavior."""
    pallas_ingest.set_enabled(True)
    try:
        yield
    finally:
        pallas_ingest.set_enabled(None)


def _rand_batch(rng, spec, b=64):
    """A randomized padded batch deliberately hostile to the kernel:
    duplicate slots (scatter ordering), sentinel tails (slot == cap),
    overflow slots (slot > cap, dropped by both paths), zero-weight
    histo rows, and set registers covering word-straddling 6-bit
    fields."""
    def slots(cap, n):
        # small range -> lots of duplicates; a few overflow rows mixed in
        s = rng.integers(0, max(cap // 2, 1), size=n).astype(np.int32)
        s[rng.integers(0, n, size=max(n // 8, 1))] = cap + 3
        return np.concatenate([s, np.full(b - n, cap, np.int32)])
    n = (3 * b) // 4
    wt = rng.uniform(0, 2, b).astype(np.float32)
    wt[rng.integers(0, b, size=b // 4)] = 0.0
    return Batch(
        counter_slot=slots(spec.counter_capacity, n),
        counter_inc=rng.uniform(-3, 5, b).astype(np.float32),
        gauge_slot=slots(spec.gauge_capacity, n),
        gauge_val=rng.uniform(-10, 10, b).astype(np.float32),
        status_slot=slots(spec.status_capacity, n),
        status_val=rng.integers(0, 4, b).astype(np.float32),
        set_slot=slots(spec.set_capacity, n),
        set_reg=rng.integers(0, hll.num_registers(spec.hll_precision),
                             b).astype(np.int32),
        set_rho=rng.integers(0, 54, b).astype(np.uint8),
        histo_slot=slots(spec.histo_capacity, n),
        histo_val=rng.uniform(0.01, 100, b).astype(np.float32),
        histo_wt=wt,
    )


def _assert_states_equal(got, want):
    for name, a, b in zip(got._fields, got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, name
        assert np.array_equal(a, b, equal_nan=True), \
            f"leaf {name} diverges between fused kernel and XLA chain"


def test_fused_matches_scatter_chain_byte_exact(fused_on):
    """Interpret-mode fused kernel == XLA chain on every state leaf,
    accumulated over several randomized batches (state carries between
    iterations, so revisit/aliasing bugs compound and surface)."""
    assert pallas_ingest.active() and pallas_ingest.interpret_mode()
    rng = np.random.default_rng(0)
    s_fused = empty_state(SPEC)
    s_chain = empty_state(SPEC)
    for _ in range(6):
        batch = _rand_batch(rng, SPEC)
        s_fused = ingest_core(s_fused, batch, spec=SPEC)
        s_chain = ingest_core(s_chain, batch, spec=SPEC,
                              allow_pallas=False)
        _assert_states_equal(s_fused, s_chain)


def test_fused_parity_multi_block_grid(fused_on):
    """Capacities above the VMEM tile sizes force a multi-block grid:
    the copy-on-first-visit prologue and the clamped revisit index maps
    are only exercised when g_total > nb for some kind."""
    spec = TableSpec(counter_capacity=1 << 16, gauge_capacity=32,
                     status_capacity=8, set_capacity=1 << 13,
                     histo_capacity=32, hll_precision=8)
    rng = np.random.default_rng(3)
    b = 256
    batch = _rand_batch(rng, spec, b=b)
    # spread counter/set rows across the whole (multi-block) range
    cs = rng.integers(0, spec.counter_capacity, b).astype(np.int32)
    cs[-8:] = spec.counter_capacity
    ss = rng.integers(0, spec.set_capacity, b).astype(np.int32)
    ss[-8:] = spec.set_capacity
    batch = batch._replace(counter_slot=cs, set_slot=ss)
    got = ingest_core(empty_state(spec), batch, spec=spec)
    want = ingest_core(empty_state(spec), batch, spec=spec,
                       allow_pallas=False)
    _assert_states_equal(got, want)


def test_fused_duplicate_slot_ordering(fused_on):
    """Every row targets the SAME slot: gauge/status must keep the last
    write, counters the full sum, sets the register max — the exact
    duplicate-resolution semantics of the XLA scatter chain."""
    b = 32
    batch = Batch(
        counter_slot=np.zeros(b, np.int32),
        counter_inc=np.arange(b, dtype=np.float32),
        gauge_slot=np.zeros(b, np.int32),
        gauge_val=np.arange(b, dtype=np.float32),
        status_slot=np.zeros(b, np.int32),
        status_val=np.arange(b, dtype=np.float32) % 4,
        set_slot=np.zeros(b, np.int32),
        set_reg=np.full(b, 17, np.int32),
        set_rho=(np.arange(b) % 7 + 1).astype(np.uint8),
        histo_slot=np.zeros(b, np.int32),
        histo_val=np.full(b, 2.5, np.float32),
        histo_wt=np.ones(b, np.float32),
    )
    got = ingest_core(empty_state(SPEC), batch, spec=SPEC)
    want = ingest_core(empty_state(SPEC), batch, spec=SPEC,
                       allow_pallas=False)
    _assert_states_equal(got, want)
    assert float(np.asarray(got.gauge)[0]) == b - 1  # last write wins
    # ingest_core's epilogue folds the accumulator into the hi/lo pair
    total = (np.asarray(got.counter_hi, np.float64)
             + np.asarray(got.counter_lo))[0]
    assert total == b * (b - 1) / 2


# -- packed-register equivalences -------------------------------------------

def test_packed_estimate_and_serialize_match_dense_u8():
    """estimate() and serialize() on a 6-bit packed row must be exactly
    the dense-u8 answer at production precision — wire bytes unchanged,
    so forwarded sets keep merging across a mixed fleet."""
    p = 14
    rng = np.random.default_rng(5)
    dense = rng.integers(0, 42, size=(4, 1 << p)).astype(np.uint8)
    dense[0, :] = 0                       # linear-counting branch
    dense[1, 1 << 13:] = 0                # mixed zeros
    packed = hll.pack_registers_np(dense, p)
    est_d = np.asarray(hll.estimate(jnp.asarray(dense), precision=p))
    est_p = np.asarray(hll.estimate(jnp.asarray(packed), precision=p))
    np.testing.assert_array_equal(est_d, est_p)
    for i in range(dense.shape[0]):
        assert hll.serialize(dense[i], p) == hll.serialize(packed[i], p)


def test_pack_unpack_roundtrip_full_register_range():
    p = 8
    rng = np.random.default_rng(9)
    regs = rng.integers(0, 62, size=(7, 1 << p)).astype(np.uint8)
    np.testing.assert_array_equal(
        hll.unpack_registers_np(hll.pack_registers_np(regs, p), p), regs)
    # jnp twins agree with the numpy twins bit-for-bit
    np.testing.assert_array_equal(
        np.asarray(hll.pack_registers(jnp.asarray(regs), precision=p)),
        hll.pack_registers_np(regs, p))


def test_packed_hbm_ratio_at_p14():
    """The optimization's memory claim: packed rows beat the i32 scatter
    operand the XLA chain materializes by >= 4x at p=14."""
    p = 14
    dense_u8 = 1 << p
    packed = hll.packed_words(p) * 4
    i32_operand = (1 << p) * 4
    assert packed < dense_u8
    assert i32_operand / packed >= 4.0


# -- v1 dense-u8 checkpoint migration ---------------------------------------

def test_v1_dense_u8_checkpoint_restores_byte_exact(tmp_path):
    """A v1 checkpoint (dense uint8 register rows, frozen v1 schema pin)
    folds through the normal restore merge path into the packed table
    byte-exact; the same bytes under the wrong pin are rejected."""
    from tests.test_persistence import BSPEC, _feed, _snapshot_of
    from veneur_tpu.persistence import CorruptSnapshot, fold_snapshot
    from veneur_tpu.persistence import codec
    from veneur_tpu.persistence.codec import (MANIFEST_NAME, encode_to_dir,
                                              load_dir, read_manifest)
    from veneur_tpu.server.aggregator import Aggregator

    spec = TableSpec(counter_capacity=64, gauge_capacity=32,
                     status_capacity=8, set_capacity=8, histo_capacity=32)
    a1 = Aggregator(spec, BSPEC)
    _feed(a1, 0)
    snap = _snapshot_of(a1, spec, agg_kind="single", n_shards=1)
    packed_orig = np.array(snap["arrays"]["hll"])
    assert packed_orig.dtype == np.int32
    set_rows_orig = list(snap["tables"]["set"])

    # rewrite the snapshot the way a v1 build stored it: dense u8 rows
    snap["arrays"]["hll"] = hll.unpack_registers_np(
        packed_orig, spec.hll_precision)
    ckpt = tmp_path / "ckpt-00000000"
    ckpt.mkdir()
    encode_to_dir(str(ckpt), snap)
    mpath = pathlib.Path(ckpt) / MANIFEST_NAME
    man = json.loads(mpath.read_text())
    man["format_version"] = 1

    # version 1 with a non-v1 hash must NOT slip through the migration
    mpath.write_text(json.dumps(man))
    with pytest.raises(CorruptSnapshot):
        read_manifest(str(ckpt))

    man["schema_hash"] = codec._SCHEMA_PINS[1]
    mpath.write_text(json.dumps(man))
    loaded = load_dir(str(ckpt))
    assert loaded["arrays"]["hll"].dtype == np.uint8

    a2 = Aggregator(spec, BSPEC)
    fold_snapshot(a2, loaded)
    snap2 = _snapshot_of(a2, spec, agg_kind="single", n_shards=1)
    assert list(snap2["tables"]["set"]) == set_rows_orig
    assert snap2["arrays"]["hll"].dtype == np.int32
    np.testing.assert_array_equal(np.asarray(snap2["arrays"]["hll"]),
                                  packed_orig)


# -- block layout -------------------------------------------------------------

def test_blocks_sit_on_the_tpu_tiling():
    """Mosaic refuses a block whose last two dims are neither (8, 128)-
    aligned nor the whole array — interpret mode does not care, so the
    layout rule is pinned here (tests/test_tpu_compile.py compiles it)."""
    from veneur_tpu.config import Config
    from veneur_tpu.server.server import spec_from_config
    spec = spec_from_config(Config())
    tiles, caps, nblocks, g_total = pallas_ingest._layout(spec)
    tc, tg, tst, ts, th, ths = tiles
    for tile, cap in ((tc, caps[0]), (tg, caps[1]), (tst, caps[2]),
                      (ths, caps[5])):
        lanes = pallas_ingest._lanes(cap)
        assert tile % lanes == 0
        assert tile == cap or (tile // lanes) % 8 == 0
    for tile, cap in ((ts, caps[3]), (th, caps[4])):
        assert tile == cap or (tile % 8 == 0 and cap % tile == 0)
    assert g_total == max(nblocks)
    # small tables ride whole, whatever their size
    assert pallas_ingest._tile_1d(40) == 40 and pallas_ingest._lanes(40) == 40
    assert pallas_ingest._row_tile(5, 64) == 5
    # a large 1-D table must be lane-dense
    with pytest.raises(ValueError):
        pallas_ingest._tile_1d(100_000)


# -- selection ---------------------------------------------------------------

def test_selection_rule_and_override(monkeypatch):
    """No probe, no environment variable: the backend and the module
    constant decide, and the config override beats both."""
    assert jax.default_backend() == "cpu"
    pallas_ingest.set_enabled(None)
    try:
        # CPU: the XLA chain, whatever the constant says
        monkeypatch.setattr(pallas_ingest, "ENABLED", True)
        assert not pallas_ingest.active()
        assert pallas_ingest.interpret_mode()
        # a TPU backend selects the kernel iff the module constant is on
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert pallas_ingest.active()
        monkeypatch.setattr(pallas_ingest, "ENABLED", False)
        assert not pallas_ingest.active()
        # the force variables of the probe era are gone
        monkeypatch.setenv("VENEUR_TPU_PALLAS_INGEST", "1")
        assert not pallas_ingest.active()
        # config-level override beats the rule entirely
        pallas_ingest.set_enabled(True)
        assert pallas_ingest.active()
        monkeypatch.setattr(pallas_ingest, "ENABLED", True)
        pallas_ingest.set_enabled(False)
        assert not pallas_ingest.active()
    finally:
        pallas_ingest.set_enabled(None)


def test_config_wires_override():
    """`pallas_ingest_enabled: false` must pin the XLA chain before any
    aggregator compiles; the default leaves the backend rule in place."""
    from tests.test_server import small_config
    from veneur_tpu.server.server import Server
    from veneur_tpu.sinks.debug import DebugMetricSink

    try:
        srv = Server(small_config(pallas_ingest_enabled=False),
                     metric_sinks=[DebugMetricSink()])
        assert pallas_ingest._OVERRIDE is False
        assert not pallas_ingest.active()
        del srv
        srv = Server(small_config(), metric_sinks=[DebugMetricSink()])
        assert pallas_ingest._OVERRIDE is None
        del srv
    finally:
        pallas_ingest.set_enabled(None)
