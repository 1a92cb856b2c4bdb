"""dispatch_wait_max_ms: the longest `pipeline.dispatch` span the pipeline
thread recorded inside the window. The mean (dispatch_wait_ms_per_step)
hides the one step in `compact_every` that finds a compaction queued ahead
of it and waits inside the jitted call for the whole of it."""

import span_reduce


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    w = span_reduce.window(records, ctx["counters_end"]["window_ns"])
    if w is None:
        return None
    lo, hi = w[0].end_ns, w[1].end_ns
    waits = [r.end_ns - r.start_ns for r in records
             if r.name == "pipeline.dispatch" and r.thread == w[1].thread
             and lo <= r.start_ns and r.end_ns <= hi]
    return max(waits) / 1e6 if waits else None
