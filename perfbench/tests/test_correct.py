"""`correct` at test size: the program as configured passes; the control
and each planted fault come out not correct."""

import pytest

import conftest


def numbers(out):
    return {name: value for name, value, _limit, _ok in out["compared"]}


def test_program_is_correct(small_cell):
    out = conftest.run(small_cell, 5)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_control_is_not_correct(small_cell):
    """The program's own lower-precision digest path (the configuration's
    control.overrides) and one-float counters in the reference's place."""
    out = conftest.run(small_cell, 6, control=True)
    assert not out["correct"], out["compared"]
    over = {name for name, value, limit, ok in out["compared"] if not ok}
    # the digest at compression 20 fails the percentiles by itself
    assert over & {"p99_rank_wmean", "p99_rank_max", "p50_rank_wmean"}, over


@pytest.mark.parametrize("row, factor, number", [
    ("", 2.0, "exact_mismatch"),
    ("50percentile", 1.5, "p50_rank_max"),
])
def test_altered_answer_is_not_correct(small_cell, monkeypatch, row, factor,
                                       number):
    """An answer altered where it is produced: one value of one frame, a
    count or a percentile."""
    make = conftest.harness.make_sink
    prefix = small_cell["traffic_file"]["prefix"]

    def altered():
        sink = make()
        flush = sink.flush_frame

        def flush_frame(frame):
            if len(sink.handed) == 2:          # the window's first flush
                for seg in frame.segments:
                    hit = [i for i, n in enumerate(seg.names)
                           if n.startswith(prefix) and n.endswith(row)]
                    if hit:
                        seg.values[hit[0]] *= factor
                        break
            flush(frame)
        sink.flush_frame = flush_frame
        return sink

    monkeypatch.setattr(conftest.harness, "make_sink", altered)
    out = conftest.run(small_cell, 7)
    assert not out["correct"]
    over = {name for name, value, limit, ok in out["compared"] if not ok}
    assert over == {number}, out["compared"]


def test_step_left_out_is_not_correct(small_cell, monkeypatch):
    """A step that returns its state unchanged: every fifth ingest step of
    the timed path drops its batch. The step runs (it returns the pair
    the aggregator takes: state and the rows compacted) and the state it
    was given is put back, from a copy, since the step donates it."""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.server import native_aggregator
    real, calls = native_aggregator.ingest_step_packed, [0]

    def lossy(state, flat, *a, **kw):
        calls[0] += 1
        if calls[0] % 5:
            return real(state, flat, *a, **kw)
        kept = jax.tree_util.tree_map(jnp.copy, state)
        _state, rows = real(state, flat, *a, **kw)
        return kept, rows

    monkeypatch.setattr(native_aggregator, "ingest_step_packed", lossy)
    out = conftest.run(small_cell, 8)
    assert calls[0] >= 5
    assert not out["correct"]
    assert numbers(out)["exact_mismatch"] + numbers(out)["rows_missing"] >= 1


def test_one_float32_unit_over_the_largest_sample_is_that_sample(
        small_cell, monkeypatch):
    """A digest's mean of tied samples rounds, so a percentile that is the
    timer's largest sample can leave the program a float32 unit or two above it
    (on the chip: an interval of over 64 pool cycles). That is no rank
    error of 1 - q: the run stays correct. Eight units above, it is one."""
    import numpy as np
    make = conftest.harness.make_sink
    prefix = small_cell["traffic_file"]["prefix"]
    units, moved = [1], [0]

    def nudged():
        sink = make()
        flush = sink.flush_frame

        def flush_frame(frame):
            at = {n: (seg, i) for seg in frame.segments
                  for i, n in enumerate(seg.names)
                  if n.startswith(prefix + ".t.")}
            for name, (seg, i) in at.items():
                if not name.endswith("99percentile"):
                    continue
                top, j = at[name[:-len("99percentile")] + "max"]
                if seg.values[i] == top.values[j]:
                    v = np.float32(seg.values[i])
                    for _ in range(units[0]):
                        v = np.nextafter(v, np.float32(np.inf))
                    seg.values[i] = v
                    moved[0] += 1
            flush(frame)
        sink.flush_frame = flush_frame
        return sink

    monkeypatch.setattr(conftest.harness, "make_sink", nudged)
    out = conftest.run(small_cell, 9)
    assert moved[0] > 100
    assert out["correct"], out["compared"]
    units[0], moved[0] = 8, 0
    out = conftest.run(small_cell, 9)
    assert moved[0] > 100
    over = {name for name, value, limit, ok in out["compared"] if not ok}
    assert over == {"p99_rank_wmean", "p99_rank_max"}, out["compared"]
