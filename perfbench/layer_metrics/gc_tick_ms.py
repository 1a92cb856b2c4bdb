"""gc_tick_ms: for each interval swapped inside the window, the part of
its way to the sink, from its `swap` record's start to its
`sink_fanout` record's end, that the program's `gc.collect` records (any
thread) cover; mean over those intervals, in ms. 0 where none of them
lay under a collection; left out by a program that does not record
collections (no `hostspans.gc_totals`)."""

import span_reduce

ENDS = (span_reduce.SWAP, "sink_fanout")


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    from veneur_tpu.observability import hostspans
    if not hasattr(hostspans, "gc_totals"):
        return None
    w = span_reduce.window(records, ctx["counters_end"]["window_ns"])
    if w is None:
        return None
    ends = {}
    for r in records:
        if r.name in ENDS and r.seq is not None \
                and w[0].seq < r.seq <= w[1].seq:
            ends.setdefault(r.seq, {})[r.name] = r
    paths = [(got[ENDS[0]].start_ns, got[ENDS[1]].end_ns)
             for got in ends.values() if len(got) == 2]
    if not paths:
        return None
    gcs = [r for r in records if r.name == "gc.collect"]
    return sum(span_reduce.covered(gcs, lo, hi)
               for lo, hi in paths) / len(paths) / 1e6
