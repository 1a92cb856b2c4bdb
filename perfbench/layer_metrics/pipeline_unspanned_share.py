"""pipeline_unspanned_share: per cent of the window in which the pipeline
thread was inside none of the program's spans (pump, emit, dispatch,
sampled sync, items, the swap): Python glue, waits for the GIL, the
scheduler. A stall of that thread reads here."""

import span_reduce


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    return span_reduce.unspanned_share(
        records, ctx["counters_end"]["window_ns"])
