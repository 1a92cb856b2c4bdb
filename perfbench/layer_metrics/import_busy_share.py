"""import_busy_share: per cent of the window the pipeline thread spent
folding forwarded MetricLists: its `pipeline.item` records tagged
`_ImportBytes` (one a request, NativeAggregator.import_pb_bytes with the
emits and steps it dispatches), cut to the window."""

import span_reduce

ITEM, TAG = "pipeline.item", "_ImportBytes"


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    w = span_reduce.window(records, ctx["counters_end"]["window_ns"])
    if w is None:
        return None
    lo, hi = w[0].end_ns, w[1].end_ns
    items = [r for r in records if r.name == ITEM and r.tag == TAG
             and r.thread == w[1].thread]
    if not items:
        return None
    return 100.0 * span_reduce.covered(items, lo, hi) / (hi - lo)
