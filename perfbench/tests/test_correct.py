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
    the timed path drops its batch."""
    from veneur_tpu.aggregation import step
    real, calls = step.ingest_step_packed, [0]

    def lossy(state, flat, **kw):
        calls[0] += 1
        if calls[0] % 5 == 0:
            return state
        return real(state, flat, **kw)

    monkeypatch.setattr(step, "ingest_step_packed", lossy)
    out = conftest.run(small_cell, 8)
    assert calls[0] >= 5
    assert not out["correct"]
    assert numbers(out)["exact_mismatch"] + numbers(out)["rows_missing"] >= 1
