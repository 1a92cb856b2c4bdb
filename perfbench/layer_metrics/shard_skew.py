"""shard_skew: how unevenly the stream falls on the key shards. The
busiest shard's staged samples over the mean shard's, window deltas of
`ring_stats()`'s `shard_rows_max` and `shard_rows_staged`, the shards as
the run reports them; 1.0 is an even split."""


def read(ctx):
    start, end = ctx["counters_start"], ctx["counters_end"]
    keys = ("ring.shard_rows_max", "ring.shard_rows_staged")
    if any(start.get(k) is None or end.get(k) is None for k in keys):
        return None
    staged = end[keys[1]] - start[keys[1]]
    shards = ctx["info"].get("shards")
    if not staged or not shards:
        return None
    return (end[keys[0]] - start[keys[0]]) / (staged / shards)
