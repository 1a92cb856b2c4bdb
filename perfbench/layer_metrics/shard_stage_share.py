"""shard_stage_share: per cent of the window the pipeline thread spent in
the sharded backend's own staging: its `pipeline.shard_split` records
(the engine lanes' split by owner shard and their copy into the shard
batchers) and `pipeline.shard_pack` records (the packing of a step's
tiles), each less its children, so that a shard_pack inside a
shard_split and the step's `pipeline.dispatch` inside a shard_pack are
not counted twice nor the device's queue counted at all. Records that
end in the window."""

import span_reduce

NAMES = ("pipeline.shard_split", "pipeline.shard_pack")


def read(ctx):
    records = span_reduce.program_records()
    if not records:
        return None
    w = span_reduce.window(records, ctx["counters_end"]["window_ns"])
    if w is None:
        return None
    lo, hi = w[0].end_ns, w[1].end_ns
    mine = [r for r in records if r.thread == w[1].thread]
    staged = [r for r in mine if r.name in NAMES and lo < r.end_ns <= hi]
    if not staged:
        return None
    own = span_reduce.self_times(mine)
    return 100.0 * sum(own[r.index] for r in staged) / (hi - lo)
