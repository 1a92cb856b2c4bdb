"""A digest row is stored in whole 128-lane tiles (TableSpec.stored_cells).

The width is what makes the TPU keep the digest tables in rows between
programs (state.py, stored_cells); what the chip's compiler makes of it
is tests/test_tpu_compile.py's. Here, on the CPU: the pad columns are
never written by any program the state goes through, no answer depends
on them, and nothing that leaves the device (raw rows for forwarding,
checkpoints and the history ring) carries them.
"""

import dataclasses

import jax
import numpy as np
import pytest

from veneur_tpu.aggregation import step
from veneur_tpu.aggregation.host import Batcher, BatchSpec
from veneur_tpu.aggregation.state import (
    DeviceState, TableSpec, empty_state, empty_state_compiled)

SPEC = TableSpec(counter_capacity=256, gauge_capacity=64, status_capacity=16,
                 set_capacity=16, histo_capacity=64, hll_precision=12)
BSPEC = BatchSpec(counter=128, gauge=64, status=16, set=64, histo=512)
TABLES = ("h_w", "h_wm")


def _flat(seed: int, do_compact: bool):
    """One packed batch of every kind; hot digest rows, so that the
    step's temp cells fill and a compaction has rows to compress."""
    rng = np.random.default_rng(seed)
    b = Batcher(SPEC, BSPEC).force_emit()
    for slot, cap in (("counter_slot", 40), ("gauge_slot", 20),
                      ("status_slot", 8), ("set_slot", 8),
                      ("histo_slot", 24)):
        getattr(b, slot)[:] = rng.integers(0, cap, getattr(b, slot).size)
    b.counter_inc[:] = rng.integers(1, 9, b.counter_inc.size)
    b.gauge_val[:] = rng.normal(size=b.gauge_val.size)
    b.status_val[:] = rng.integers(0, 3, b.status_val.size)
    b.set_reg[:] = rng.integers(0, SPEC.registers, b.set_reg.size)
    b.set_rho[:] = rng.integers(1, 40, b.set_rho.size)
    b.histo_val[:] = rng.gamma(2.0, 15.0, b.histo_val.size)
    b.histo_wt[:] = 1.0
    return step.pack_batch(b, do_compact=do_compact), step.batch_sizes(b)


def _flush_inputs():
    buckets = (64, 64, 16, 16, 64)
    idx = [np.arange(n, dtype=np.int32) for n in buckets]
    return step.pack_flush_inputs([0.5, 0.99], idx), buckets


def _narrow(state: DeviceState) -> DeviceState:
    """The same state with its digest tables exactly total_cells wide:
    the cores take a table's width from the table."""
    return state._replace(h_w=state.h_w[:, :SPEC.total_cells],
                          h_wm=state.h_wm[:, :SPEC.total_cells])


def _assert_pad_untouched(state):
    for name in TABLES:
        table = np.asarray(getattr(state, name))
        assert table.shape == (SPEC.histo_capacity, SPEC.stored_cells)
        assert not table[:, SPEC.total_cells:].any(), name


def _assert_same_bytes(got, want, narrow=False):
    for name, a, b in zip(DeviceState._fields, got, want):
        a = np.asarray(a)
        if narrow and name in TABLES:
            a = a[:, :SPEC.total_cells]
        np.testing.assert_array_equal(a, np.asarray(b), err_msg=name)


@pytest.mark.parametrize("temp_cells, total, stored", [
    (192, 472, 512),     # the shipped row
    (232, 512, 512),     # already whole tiles: nothing added
    (233, 513, 640),
    (16, 296, 384),
])
def test_stored_cells_rounds_a_row_up_to_whole_lane_tiles(temp_cells, total,
                                                          stored):
    spec = dataclasses.replace(TableSpec(histo_capacity=8),
                               temp_cells=temp_cells)
    assert (spec.total_cells, spec.stored_cells) == (total, stored)
    state = jax.eval_shape(lambda: empty_state(spec))
    assert state.h_w.shape == state.h_wm.shape == (8, stored)


def test_the_state_programs_keep_the_names_the_trace_readers_look_for():
    from veneur_tpu.aggregation import state as state_mod
    assert "jit_empty_state" in state_mod._empty_state_jit.lower(
        spec=SPEC).as_text()
    flat, sizes = _flat(0, False)
    st = empty_state_compiled(SPEC)
    for prog, args, kw, name in (
            (step.ingest_step_packed, (flat,), dict(spec=SPEC, sizes=sizes),
             "jit_packed_step_core"),
            (step.ingest_step_packed_rings, (flat[None],),
             dict(spec=SPEC, sizes=sizes), "jit_packed_rings_core"),
            (step.compact, (), dict(spec=SPEC), "jit_compact_core"),
            (step.fold_scalars, (), {}, "jit__fold_core")):
        assert name in prog.lower(st, *args, **kw).as_text(), name


@pytest.mark.parametrize("do_compact", [False, True],
                         ids=["control_word_clear", "control_word_set"])
def test_state_goes_through_every_program_and_the_pad_stays_empty(do_compact):
    """empty_state_compiled -> ingest_step_packed (x3, the last with or
    without the control word) -> compact -> fold_scalars ->
    flush_live_in_packed, each program fed the state of the one before:
    the un-jitted cores' bytes, the pad columns all zero, the tables
    updated in place, and in the row's own columns the bytes a table
    exactly total_cells wide comes to."""
    flush_in, buckets = _flush_inputs()
    got = empty_state_compiled(SPEC)
    want = empty_state(SPEC)
    narrow = _narrow(want)
    _assert_same_bytes(got, want)
    for i, dc in enumerate((False, False, do_compact)):
        flat, sizes = _flat(i, dc)
        given = got
        got, rows = step.ingest_step_packed(got, flat, spec=SPEC,
                                            sizes=sizes)
        want, want_rows = step.packed_step_core(want, flat, spec=SPEC,
                                                sizes=sizes)
        narrow, _ = step.packed_step_core(narrow, flat, spec=SPEC,
                                          sizes=sizes)
        assert all(getattr(given, name).is_deleted() for name in TABLES)
        assert int(rows) == int(want_rows) and (int(rows) > 0) == dc
        _assert_same_bytes(got, want)
        _assert_same_bytes(got, narrow, narrow=True)
        _assert_pad_untouched(got)
    given = got
    got = step.compact(got, spec=SPEC)
    want = step.compact_core(want, spec=SPEC)
    narrow = step.compact_core(narrow, spec=SPEC)
    assert given.h_w.is_deleted()
    _assert_same_bytes(got, want)
    _assert_same_bytes(got, narrow, narrow=True)
    _assert_pad_untouched(got)
    got = step.fold_scalars(got)
    _assert_same_bytes(got, step._fold_core(want))
    _assert_pad_untouched(got)
    out = step.flush_live_in_packed(got, flush_in, spec=SPEC, n_q=2,
                                    buckets=buckets)
    # the quantiles of a row do not see its pad: the same bytes from the
    # table that has none
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(step.flush_live_in_packed(
            _narrow(got), flush_in, spec=SPEC, n_q=2, buckets=buckets)))
    assert not got.h_w.is_deleted()        # a flush reads; it takes nothing


def test_raw_digest_rows_leave_the_device_without_the_pad():
    """What forwarding, checkpoints and the history ring are handed is a
    row's total_cells columns, as before the row was stored wider."""
    flush_in, buckets = _flush_inputs()
    state = empty_state_compiled(SPEC)
    for i in range(2):
        flat, sizes = _flat(i, False)
        state, _ = step.ingest_step_packed(state, flat, spec=SPEC,
                                           sizes=sizes)
    packed = step.flush_live_in_packed(state, flush_in, spec=SPEC, n_q=2,
                                       buckets=buckets, want_raw=True)
    out = step.unpack_flush(
        np.asarray(packed),
        step.flush_live_shapes(SPEC, *buckets, 2, want_raw=True))
    w = np.asarray(state.h_w)[:, :SPEC.total_cells]
    wm = np.asarray(state.h_wm)[:, :SPEC.total_cells]
    assert out["raw_h_weight"].shape == (64, SPEC.total_cells)
    np.testing.assert_array_equal(out["raw_h_weight"], w)
    np.testing.assert_array_equal(
        out["raw_h_mean"], wm / np.maximum(w, np.float32(1e-30)))
    assert w.sum() > 0
