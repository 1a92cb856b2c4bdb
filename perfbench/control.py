#!/usr/bin/env python3
"""The control of a cell's comparison: it has to come out as not correct.

    python perfbench/control.py --workload <name> --seed <n> --seconds <s>

The same run as run.py's, at the cell's own size and load, with the
configuration's `control` in the program's place: the program's own
lower-precision digest path switched on from the configuration
(`control.overrides`), and the plain reference put where the program's
counters and sets were, its counters computed in one float
(`control.counter_dtype`) and its sets from a plain HyperLogLog of
2^`control.hll_precision` registers. A cell whose traffic has no timers and
no sets is failed by the counters alone. The
benchmark's own runs never run this; PERF.md holds its readings.
"""

import sys

import run

if __name__ == "__main__":
    sys.exit(run.main(control=True))
