"""The least bytes an ingest step has to move, counted from the stream and
the state's dtypes and never from the implementation.

A step that absorbs a slice of the stream has to: read each sample's
record once; read and write once each state cell the slice touches; and,
for timers, read and write each touched digest row once per
`compact_every` steps (a digest is re-compressed, which no layout of the
data avoids). Ingest is memory-bound: there is no arithmetic to speak of,
so the roofline is bytes over the chip's HBM bandwidth.
"""

from __future__ import annotations

import numpy as np

from traffic import KINDS

# one sample as it has to reach the device: slot i32 + value f32 (+ weight
# f32 for a timer; register i32 + rho u8 for a set member)
RECORD_BYTES = {"counter": 8, "gauge": 8, "timer": 12, "set": 9}
# state touched per distinct name, read + write (aggregation/state.py
# DeviceState): counter_acc f32; gauge f32 + stamp u8; one i32 register
# word of a set; a timer's temp count i32 and min, max, count, sum and
# reciprocal-sum accumulators f32
CELL_BYTES = {"counter": 2 * 4, "gauge": 2 * 5, "set": 2 * 4,
              "timer": 2 * 6 * 4}
# a timer sample lands in one temp cell of h_wm and of h_w (f32 each)
TIMER_SAMPLE_WRITE = 2 * 4


def ingest_min_bytes(pool, samples_per_step: float, compact_every: int,
                     digest_columns: int) -> float:
    """Mean least bytes per step, over one pool cycle cut into steps of
    `samples_per_step` samples in stream order."""
    step = max(1, int(round(samples_per_step)))
    n = pool.n_samples
    n_steps = max(1, n // step)
    total = 0.0
    timer_k = KINDS.index("timer")
    for s in range(n_steps):
        lo, hi = s * step, min(n, (s + 1) * step)
        kind, name = pool.kind[lo:hi], pool.name[lo:hi]
        for ki, k in enumerate(KINDS):
            sel = kind == ki
            count = int(sel.sum())
            if not count:
                continue
            total += count * RECORD_BYTES[k]
            total += len(np.unique(name[sel])) * CELL_BYTES[k]
            if ki == timer_k:
                total += count * TIMER_SAMPLE_WRITE
    # each digest row touched in a group of `compact_every` steps is read
    # and written once: wm and w, f32
    group = step * compact_every
    for lo in range(0, n_steps * step, group):
        hi = min(n, lo + group)
        sel = pool.kind[lo:hi] == timer_k
        rows = len(np.unique(pool.name[lo:hi][sel]))
        total += rows * digest_columns * 2 * 4 * 2
    return total / n_steps
