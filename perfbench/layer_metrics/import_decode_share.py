"""import_decode_share: of the time the pipeline thread spent folding
forwarded MetricLists in the window (its `_ImportBytes` `pipeline.item`
records), the per cent its `import.decode` records cover: the engine's
decode, key lookup and staging, against the emits, dispatches and the
digests' stats lane around them. A program without the span leaves the
metric out."""

import span_reduce

ITEM, TAG, DECODE = "pipeline.item", "_ImportBytes", "import.decode"


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    w = span_reduce.window(records, ctx["counters_end"]["window_ns"])
    if w is None:
        return None
    lo, hi = w[0].end_ns, w[1].end_ns
    mine = [r for r in records if r.thread == w[1].thread]
    decodes = [r for r in mine if r.name == DECODE]
    busy = span_reduce.covered(
        [r for r in mine if r.name == ITEM and r.tag == TAG], lo, hi)
    if not decodes or not busy:
        return None
    return 100.0 * span_reduce.covered(decodes, lo, hi) / busy
