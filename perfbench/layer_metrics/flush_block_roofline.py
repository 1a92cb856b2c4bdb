"""flush_block_roofline: the least time the chip could take for one block
of the tiled flush (the bytes the live rows force, over the HBM peak) as
a share of the device time one call of the flush program took.

The flush gathers each kind's live rows out of the state, computes the
percentiles and the set estimates, and packs one f32 answer. There is
next to no arithmetic a byte, so the roofline is bytes over bandwidth.
The bytes are counted from the stream's live names and the state's stored
row widths, never from the padded bucket the program runs at."""

import json
import os

import peaks
import readers

LANES = 128
# scalar state a live row is gathered from, with its i32 slot index
# (aggregation/state.py DeviceState, step.flush_live_core): a counter's
# two-float pair; a gauge; a timer's min, max and the count, sum and
# reciprocal-sum pairs
ROW_IN_BYTES = {"counter": 4 + 2 * 4, "gauge": 4 + 4, "set": 4,
                "timer": 4 + 8 * 4}
# what leaves for it in the packed output (step.flush_live_shapes): the
# counter's pair, the gauge, the set's estimate, a timer's min, max, three
# pairs and median; its percentiles are added below
ROW_OUT_BYTES = {"counter": 2 * 4, "gauge": 4, "set": 4, "timer": 9 * 4}


def block_min_bytes(names: dict, blocks: float, state: dict,
                    n_percentiles: int) -> float:
    """Least bytes of one of a flush's `blocks` equal blocks. `names` is
    kind -> live names of the interval; `state` the configuration's."""
    # a set row is its 2^p six-bit registers; a digest row is its mean x
    # weight and weight columns, f32, stored a multiple of 128 lanes wide
    hll_row = (1 << state["hll_precision"]) * 6 // 8
    digest_row = 2 * 4 * -(-state["digest_columns"] // LANES) * LANES
    total = 0
    for kind, n in names.items():
        total += n * (ROW_IN_BYTES[kind] + ROW_OUT_BYTES[kind])
    total += names.get("set", 0) * hll_row
    total += names.get("timer", 0) * (digest_row + 4 * n_percentiles)
    return total / blocks


def read(ctx):
    with open(os.path.join(os.path.dirname(__file__),
                           "flush_block_device_ms.json")) as f:
        programs = json.load(f)["programs"]
    calls, seconds = readers.program_time(ctx, programs)
    start, end = ctx["counters_start"], ctx["counters_end"]
    if not calls or not seconds or "ring.flush_blocks" not in end:
        return None
    flushes = end["ring.flushes"] - start["ring.flushes"]
    if not flushes:
        return None
    blocks = (end["ring.flush_blocks"] - start["ring.flush_blocks"]) / flushes
    config = ctx["config"]
    least_bytes = block_min_bytes(ctx["pool"].names_per_kind, blocks,
                                  config["state"],
                                  len(config["expect"]["percentiles"]))
    least_s = least_bytes / peaks.peak(ctx["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / (seconds / calls)
