"""From the program's own host spans to numbers.

The program (veneur_tpu/observability/hostspans.py, where it has one)
keeps one record per host span: name, seq (the interval's number), thread,
start_ns and end_ns on time.monotonic_ns(), parent (the index of the
enclosing span on the same thread), index, tag. The readers beside
layer_metrics/ get them from `records()`: the server ran in this process.
A program without that module has no such spans: `program_records`
returns None and every reader built on it leaves its metric out.

Everything here is plain interval arithmetic on those tuples, checked on
hand-made records by tests/test_span_reduce.py. A record is anything with
those attributes; `Rec` makes one by hand.
"""

from __future__ import annotations

import collections

from trace_reduce import clip, union

Rec = collections.namedtuple(
    "Rec", "name seq thread start_ns end_ns parent index tag")

SWAP, FLUSH, PUMP = "swap", "flush", "pipeline.pump"
WINDOW_TOLERANCE_NS = 50e6


def program_records():
    """The program's records, or None where it keeps none."""
    try:
        from veneur_tpu.observability import hostspans
    except ImportError:
        return None
    return hostspans.records()


def covered(records, lo, hi) -> float:
    """Nanoseconds of [lo, hi] that the records' union covers."""
    return sum(b - a for a, b in union(clip(
        [(r.start_ns, r.end_ns) for r in records], lo, hi)))


def window(records, window_ns):
    """The harness's window, from the records alone: it ends at the end
    of the last `swap` record (the last tick's) and starts at the end of
    the `swap` record that lies nearest to `window_ns` before that (tick
    0's). (start record, end record), or None when no swap ends within
    50 ms of where the window began."""
    swaps = sorted((r for r in records if r.name == SWAP),
                   key=lambda r: r.end_ns)
    if len(swaps) < 2:
        return None
    last = swaps[-1]
    want = last.end_ns - window_ns
    first = min(swaps[:-1], key=lambda r: abs(r.end_ns - want))
    if abs(first.end_ns - want) > WINDOW_TOLERANCE_NS:
        return None
    return first, last


def self_times(records) -> dict:
    """index -> the span's duration less what its children cover. A child
    whose record is missing (it fell off the bounded store, or never
    closed) takes nothing off its parent."""
    kids = collections.defaultdict(list)
    for r in records:
        if r.parent is not None:
            kids[r.parent].append(r)
    return {r.index: (r.end_ns - r.start_ns)
            - covered(kids.get(r.index, ()), r.start_ns, r.end_ns)
            for r in records}


def share_of_window(records, window_ns, names):
    """Per cent of the window the pipeline thread (the thread of the
    `swap` records) spent inside spans of the given names."""
    w = window(records, window_ns)
    if w is None:
        return None
    lo, hi = w[0].end_ns, w[1].end_ns
    mine = [r for r in records
            if r.thread == w[1].thread and r.name in names]
    return 100.0 * covered(mine, lo, hi) / (hi - lo)


def unspanned_share(records, window_ns):
    """Per cent of the window in which the pipeline thread was in no span:
    Python glue, waits for the GIL, the scheduler. A pump run (one record
    for many calls, tag = (calls, ns inside them)) covers only the time
    inside its calls: the glue between them is unspanned too."""
    w = window(records, window_ns)
    if w is None:
        return None
    lo, hi = w[0].end_ns, w[1].end_ns
    mine = [r for r in records if r.thread == w[1].thread]
    spanned = covered([r for r in mine if r.name != PUMP], lo, hi)
    for r in mine:
        if r.name != PUMP or r.end_ns <= r.start_ns:
            continue
        inside = r.tag[1] if isinstance(r.tag, tuple) else (
            r.end_ns - r.start_ns)
        # a run cut by the window's edge counts by the part inside it
        part = covered([r], lo, hi) / (r.end_ns - r.start_ns)
        spanned += inside * part
    return 100.0 * (hi - lo - spanned) / (hi - lo)


def swap_to_sink(records, window_ns, last_stage="sink_fanout"):
    """For each interval detached inside the window: (seq, nanoseconds
    from its swap's end to the end of its `last_stage`, nanoseconds of
    that stretch no span of that seq covers). The `flush` root is left
    out of the cover: it spans the whole flush worker's job and would hide
    every gap between its stages."""
    w = window(records, window_ns)
    if w is None:
        return None
    by_seq = collections.defaultdict(list)
    for r in records:
        if r.seq is not None and w[0].seq < r.seq <= w[1].seq:
            by_seq[r.seq].append(r)
    out = []
    for seq, recs in sorted(by_seq.items()):
        swaps = [r for r in recs if r.name == SWAP]
        ends = [r for r in recs if r.name == last_stage]
        if not swaps or not ends:
            continue
        lo, hi = swaps[0].end_ns, ends[-1].end_ns
        if hi <= lo:
            continue
        stages = [r for r in recs if r.name != FLUSH]
        out.append((seq, hi - lo, hi - lo - covered(stages, lo, hi)))
    return out
