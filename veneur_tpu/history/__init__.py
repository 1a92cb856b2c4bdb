"""On-device history tier: a packed per-key ring of the last K flush
intervals in HBM, with tiered 2x decimation and windowed-merge range
queries (ROADMAP item 4; ISSUE 18).

    spec.py     HistorySpec — frozen shape contract (ring geometry)
    device.py   HistoryState + jitted write / decimate / read programs
    writer.py   HistoryWriter — host admission index, window metadata,
                fused-flush protocol, persistence
    merge.py    range-merge programs (XLA chain + combined launch) and
                the packed wire helpers

The Pallas variant of the masked HLL window merge lives in
ops/pallas_history.py, selected like the digest kernel: by the
backend and a module constant.
"""

from veneur_tpu.history.spec import HistorySpec
from veneur_tpu.history.writer import HistoryPlan, HistoryWriter, RangePlan

__all__ = ["HistorySpec", "HistoryWriter", "HistoryPlan", "RangePlan"]
