"""pipeline_sync_share: per cent of the window the pipeline thread spent
in `pipeline.sampled_sync`, the block_until_ready it takes every 64th
ingest step to feed step_ns."""

import span_reduce


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    return span_reduce.share_of_window(
        records, ctx["counters_end"]["window_ns"], {"pipeline.sampled_sync"})
