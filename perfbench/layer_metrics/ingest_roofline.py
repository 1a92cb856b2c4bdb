"""ingest_roofline: the least time the chip could take for the traced
ingest steps (bytes the stream forces, over the HBM peak) as a share of
the device time the ingest program took."""

import json
import os

import peaks
import readers
import roofline


def read(ctx):
    with open(os.path.join(os.path.dirname(__file__),
                           "ingest_device_ms_per_step.json")) as f:
        programs = json.load(f)["programs"]
    calls, seconds = readers.program_time(ctx, programs)
    steps = (ctx["counters_end"]["steps_total"]
             - ctx["counters_start"]["steps_total"])
    if not calls or not steps or not seconds:
        return None
    state = ctx["config"]["state"]
    per_step = roofline.ingest_min_bytes(
        ctx["pool"], ctx["counters_end"]["window_samples"] / steps,
        state["compact_every"], state["digest_columns"])
    # every shard's program runs once a step, each on its own chip with a
    # quarter of the stream: bytes over all chips' bandwidth
    chips = ctx["trace"]["n_devices"]
    least_s = per_step / (chips * peaks.peak(ctx["device_kind"],
                                             "hbm_bytes_per_s"))
    return 100.0 * least_s / (seconds / calls)
