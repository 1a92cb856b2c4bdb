"""span_reduce.py on hand-made records, and the readers built on it when
the program kept none."""

import pytest

import conftest  # noqa: F401  (puts perfbench/ on the path)
import readers
import span_reduce
from span_reduce import Rec

MS = 1_000_000
PIPE, WORKER = "pipeline", "flush-worker"


def rec(name, start_ms, end_ms, index, seq=None, parent=None, thread=PIPE,
        tag=None):
    return Rec(name, seq, thread, start_ms * MS, end_ms * MS, parent, index,
               tag)


def interval(seq, at, idx):
    """One tick at `at` ms: a swap of 100 ms with its three children, a
    queue wait of 2 ms, then a flush whose stages leave 3 ms uncovered
    between post_device and frame_build."""
    s = at
    return [
        rec("swap.emit_staged", s, s + 5, idx + 1, seq, idx),
        rec("swap.device_wait", s + 5, s + 90, idx + 2, seq, idx),
        rec("swap.reset", s + 90, s + 99, idx + 3, seq, idx),
        rec("swap", s, s + 100, idx, seq),
        rec("queue_wait", s + 100, s + 102, idx + 4, seq, thread=WORKER),
        rec("flush_dispatch", s + 103, s + 104, idx + 7, seq, idx + 6,
            WORKER),
        rec("flush_d2h", s + 104, s + 120, idx + 8, seq, idx + 6, WORKER),
        rec("device_update", s + 102, s + 122, idx + 6, seq, idx + 5, WORKER),
        rec("post_device", s + 122, s + 130, idx + 9, seq, idx + 5, WORKER),
        rec("frame_build", s + 133, s + 150, idx + 10, seq, idx + 5, WORKER),
        rec("sink_fanout", s + 150, s + 160, idx + 11, seq, idx + 5, WORKER),
        rec("self_metrics", s + 160, s + 170, idx + 12, seq, idx + 5, WORKER),
        rec("flush", s + 102, s + 171, idx + 5, seq, thread=WORKER),
    ]


def run_records():
    """A warm-up tick, tick 0 at 1 s and two ticks 1 s apart; between the
    ticks the pipeline thread pumps, emits, dispatches and syncs."""
    out = interval(0, 0, 100) + interval(1, 1000, 200)
    idx = 300
    for k, seq in ((0, 2), (1, 3)):
        t = 1100 + 1000 * k             # the swap before ended here
        out += [
            # a pump run of 400 ms, 350 of them inside its 7 calls
            rec("pipeline.pump", t, t + 400, idx, seq, tag=(7, 350 * MS)),
            rec("pipeline.emit", t + 400, t + 410, idx + 1, seq),
            rec("pipeline.dispatch", t + 410, t + 600, idx + 2, seq),
            rec("pipeline.sampled_sync", t + 600, t + 700, idx + 3, seq),
            rec("pipeline.item", t + 700, t + 705, idx + 4, seq,
                tag="PipelineRequest"),
            rec("pipeline.pump", t + 705, t + 895, idx + 5, seq,
                tag=(3, 190 * MS)),
        ]
        idx += 10
        out += interval(seq, t + 900, 400 + 100 * k)
    return out


WINDOW_NS = 2000 * MS       # tick 0's swap end (1100) to the last's (3100)


def test_window_is_found_from_the_swaps():
    first, last = span_reduce.window(run_records(), WINDOW_NS)
    assert (first.seq, last.seq) == (1, 3)
    assert last.end_ns - first.end_ns == WINDOW_NS
    # 40 ms off is the same window, 60 ms off is none
    assert span_reduce.window(run_records(), WINDOW_NS + 40 * MS)[0] == first
    assert span_reduce.window(run_records(), WINDOW_NS + 60 * MS) is None
    assert span_reduce.window(run_records()[:4], WINDOW_NS) is None
    assert span_reduce.window([], WINDOW_NS) is None


def test_covered_cuts_to_the_window_and_unions():
    recs = [rec("a", 0, 10, 1), rec("b", 5, 20, 2), rec("c", 30, 40, 3)]
    assert span_reduce.covered(recs, 0, 100 * MS) == 30 * MS
    assert span_reduce.covered(recs, 8 * MS, 35 * MS) == 17 * MS
    assert span_reduce.covered([], 0, MS) == 0


def test_self_time_and_a_missing_child():
    recs = interval(0, 0, 100)
    self_ns = span_reduce.self_times(recs)
    assert self_ns[100] == 1 * MS               # swap: 100 less 5+85+9
    assert self_ns[106] == 3 * MS               # device_update: 20 less 1+16
    assert self_ns[105] == (69 - 20 - 8 - 17 - 10 - 10) * MS    # flush root
    assert self_ns[102] == 85 * MS              # a leaf keeps all of it
    # the child that never closed, or fell off the store: the parent keeps
    # the time, and an orphan pointing at no record harms nothing
    short = [r for r in recs if r.name != "swap.device_wait"]
    assert span_reduce.self_times(short)[100] == 86 * MS
    orphan = [rec("x", 0, 4, 1, parent=999)]
    assert span_reduce.self_times(orphan) == {1: 4 * MS}


def test_shares_of_the_window():
    recs = run_records()
    sync = span_reduce.share_of_window(recs, WINDOW_NS,
                                       {"pipeline.sampled_sync"})
    assert sync == pytest.approx(100.0 * 200 / 2000)
    # per second: 5 ms between item and... nothing; the pump runs' glue
    # (50 ms + 0) and the 5 ms hole before the swap are unspanned
    un = span_reduce.unspanned_share(recs, WINDOW_NS)
    assert un == pytest.approx(100.0 * 2 * (50 + 5) / 2000)
    # spans plus the unspanned share make the whole window
    names = {"pipeline.emit", "pipeline.dispatch", "pipeline.sampled_sync",
             "pipeline.item", "swap"}
    rest = span_reduce.share_of_window(recs, WINDOW_NS, names)
    pump_inside = 100.0 * 2 * (350 + 190) / 2000
    assert rest + pump_inside + un == pytest.approx(100.0)
    # a pump run cut by the window's edge counts by the part inside it
    cut = recs + [rec("pipeline.pump", 1050, 1150, 900, 1,
                      tag=(2, 80 * MS))]
    assert span_reduce.unspanned_share(cut, WINDOW_NS) == pytest.approx(
        100.0 * (110 - 40) / 2000)
    assert span_reduce.share_of_window([], WINDOW_NS, names) is None
    assert span_reduce.unspanned_share([], WINDOW_NS) is None


def test_swap_to_sink_leaves_the_root_out():
    rows = span_reduce.swap_to_sink(run_records(), WINDOW_NS)
    assert [seq for seq, _w, _g in rows] == [2, 3]      # not tick 0's
    for _seq, whole, gap in rows:
        assert whole == 60 * MS and gap == 3 * MS
    # an interval whose flush emitted nothing has no fan-out: left out
    short = [r for r in run_records()
             if not (r.name == "sink_fanout" and r.seq == 3)]
    assert [s for s, _w, _g in span_reduce.swap_to_sink(short, WINDOW_NS)] \
        == [2]
    assert span_reduce.swap_to_sink([], WINDOW_NS) is None


@pytest.mark.parametrize("metric", [
    "swap_to_sink_unattributed_ms", "pipeline_sync_share",
    "pipeline_unspanned_share"])
def test_reader_finds_nothing_in_empty_records(metric, monkeypatch):
    ctx = {"counters_end": {"window_ns": WINDOW_NS}}
    for none in ([], None):
        monkeypatch.setattr(span_reduce, "program_records", lambda: none)
        assert readers.read(metric, ctx) is None
    # records, but of another run's length: no window, no number
    monkeypatch.setattr(span_reduce, "program_records", run_records)
    assert readers.read(metric, {"counters_end": {"window_ns": 7e9}}) is None
    assert readers.read(metric, ctx) is not None


@pytest.mark.parametrize("metric", [
    "swap_device_wait_ms", "swap_host_ms", "flush_queue_wait_ms",
    "flush_d2h_ms"])
def test_phase_reader_finds_nothing_without_the_phase(metric):
    ctx = {"phases_start": {"ingest_drain": (1, 5.0)},
           "phases_end": {"ingest_drain": (4, 50.0)}}
    assert readers.read(metric, ctx) is None
    phase = readers.spec_of(metric)["phase"]
    ctx["phases_end"][phase] = (3, 6e6)
    assert readers.read(metric, ctx) == pytest.approx(2.0)


def test_program_records_without_the_module(monkeypatch):
    import sys

    import veneur_tpu.observability as package
    monkeypatch.setitem(sys.modules,
                        "veneur_tpu.observability.hostspans", None)
    # where the module was imported before, the package holds it too
    monkeypatch.delattr(package, "hostspans", raising=False)
    assert span_reduce.program_records() is None
