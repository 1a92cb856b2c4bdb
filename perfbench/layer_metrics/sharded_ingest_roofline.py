"""sharded_ingest_roofline: the least time the chips could take for the
traced steps of the sharded backend, as a share of the device time its
ingest program took a call on the busiest chip.

A step of the sharded backend holds one tile a key shard, each on its own
chip, and each tile the samples of its shard out of the slice of the
stream the step absorbed. A name lives on one tile: its slot is
shard x per-shard capacity + local slot (server/sharded_aggregator.py
per_shard_spec), so the cells and digest rows the tiles touch add up to
the slice's distinct names, whatever shard owns each name, and the least
bytes of a step are those of its slice (roofline.ingest_min_bytes), moved
over every chip's bandwidth at once."""

import json
import os

import peaks
import readers
import roofline


def step_min_bytes(pool, samples_per_step: float, compact_every: int,
                   digest_columns: int) -> float:
    """Mean least bytes of one step of `samples_per_step` samples over all
    its tiles: each sample's record once, each touched state cell read
    and written once, each touched digest row read and written once in a
    group of `compact_every` steps."""
    return roofline.ingest_min_bytes(pool, samples_per_step, compact_every,
                                     digest_columns)


def read(ctx):
    with open(os.path.join(os.path.dirname(__file__),
                           "sharded_ingest_device_ms_per_step.json")) as f:
        programs = json.load(f)["programs"]
    calls, seconds = readers.program_time(ctx, programs)
    steps = (ctx["counters_end"]["steps_total"]
             - ctx["counters_start"]["steps_total"])
    if not calls or not steps or not seconds:
        return None
    state = ctx["config"]["state"]
    per_step = step_min_bytes(
        ctx["pool"], ctx["counters_end"]["window_samples"] / steps,
        state["compact_every"], state["digest_columns"])
    chips = ctx["trace"]["n_devices"]
    least_s = per_step / (chips * peaks.peak(ctx["device_kind"],
                                             "hbm_bytes_per_s"))
    return 100.0 * least_s / (seconds / calls)
