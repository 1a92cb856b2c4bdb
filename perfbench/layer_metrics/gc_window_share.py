"""gc_window_share: per cent of the window in which the interpreter was
collecting: the union of the program's `gc.collect` records, on any
thread (a collection holds the interpreter for every thread), cut to the
window. A program that does not record collections (no
`hostspans.gc_totals`) leaves the metric out; one that records them and
has none in the window reads 0."""

import span_reduce


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
    lo, hi = w[0].end_ns, w[1].end_ns
    gcs = [r for r in records if r.name == "gc.collect"]
    return 100.0 * span_reduce.covered(gcs, lo, hi) / (hi - lo)
