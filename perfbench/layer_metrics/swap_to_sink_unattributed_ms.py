"""swap_to_sink_unattributed_ms: per interval of the window, the time from
the end of its swap to the end of its sink fan-out that no span of that
interval covers (queue wait, device update, post-device work, frame build
and fan-out all being spans); mean, in ms."""

import span_reduce


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    rows = span_reduce.swap_to_sink(
        records, ctx["counters_end"]["window_ns"])
    if not rows:
        return None
    return sum(gap for _seq, _whole, gap in rows) / len(rows) / 1e6
