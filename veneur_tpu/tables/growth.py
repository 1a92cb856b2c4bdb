"""The ONE sanctioned key-table grow site (ISSUE 20 tentpole a).

Growth reuses the reshard drain's staged-then-applied-at-reset
discipline (reshard/quiesce.py): new per-kind capacities are STAGED on
the C++ engine under its key mutex (`capacity_set` → pending_caps),
then APPLIED by the `vt_reset` that runs inside the very next swap's
quiesce — which empties the engine's tables for it, while (for the
multi-ring group) the ring workers are paused. An interval's device
state starts empty at every swap and the backend is rebuilt around the
engine (a fresh Python KeyTable from spec; the C++ tables, which
otherwise keep their keys across intervals, allocate every key anew
under the new capacity), so a grow needs NO mid-interval rehash at
all: the grow pause IS the swap pause, bounded at one flush interval by
construction.

Shard assignment (`route_digest % n_shards`, host.py slot rule) is
capacity-independent, so growth only changes a shard's slot budget —
the C++ preshard emit path's shard split stays byte-identical across a
grow (pinned by the fuzz test in tests/test_tables.py).

The vtlint `table-grow-quiesce` pass makes this module (plus the ctypes
binding layer) the only place allowed to call the capacity mutators;
any other grow site is a finding.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Optional, Tuple

log = logging.getLogger("veneur.tables")

# table kind -> TableSpec field, in the native capacity_set argument
# order for the first four (status is Python-side on every backend)
KIND_FIELDS = (("counter", "counter_capacity"),
               ("gauge", "gauge_capacity"),
               ("set", "set_capacity"),
               ("histo", "histo_capacity"),
               ("status", "status_capacity"))


def spec_capacities(spec) -> Dict[str, int]:
    """Per-kind capacities of a TableSpec, by table kind."""
    return {k: int(getattr(spec, f)) for k, f in KIND_FIELDS}


def grown_spec(spec, targets: Dict[str, int]):
    """A new TableSpec with the given per-kind capacities applied.
    Only capacity fields change — sketch geometry (compression, HLL
    precision, ...) is identity-relevant and never grows live."""
    fields = dict(KIND_FIELDS)
    changes = {fields[k]: int(v) for k, v in targets.items()
               if k in fields and int(v) != getattr(spec, fields[k])}
    return dataclasses.replace(spec, **changes) if changes else spec


class GrowConflict(RuntimeError):
    """Grow refused because a conflicting live operation (reshard) owns
    the swap boundary; carries .status = 409 for admin surfaces."""

    status = 409


def grow_swap(server, new_spec) -> Tuple[object, object, object]:
    """Execute a per-kind capacity change at the swap boundary.

    MUST run on the pipeline thread (it IS the interval flush swap).
    Returns (state, table, old_aggregator) — the detached interval,
    which the caller enqueues as this interval's flush job exactly like
    a plain swap; the flush math runs against the OLD aggregator's spec.

    Sequence (mirrors reshard/coordinator.py `_begin_on_pipeline`):
    stage capacities on the engine → swap (the quiesce's reset applies
    them while tables are empty) → rebuild the backend around the SAME
    engine with the new spec → carry the lifetime counters over →
    install. Ingest never restarts; readers keep feeding the same C++
    handle throughout.
    """
    old = server.aggregator
    eng = getattr(old, "eng", None)
    if eng is not None:
        caps = spec_capacities(new_spec)
        eng.capacity_set(caps["counter"], caps["gauge"], caps["set"],
                         caps["histo"])
    state, table = old.swap()
    new_agg, native = server._make_aggregator(
        getattr(old, "n_shards", 1), engine=eng, spec=new_spec)
    # lifetime-counter continuity (same set the reshard drain carries)
    new_agg.processed = old.processed
    new_agg.dropped_capacity = old.dropped_capacity
    new_agg.h2d_bytes = old.h2d_bytes
    new_agg.last_set_shift = old.last_set_shift
    if old._pressure is not None:
        new_agg.set_pressure(old._pressure)
    server.aggregator = new_agg
    server._native = native
    log.info("key tables grown: %s -> %s",
             spec_capacities(old.spec), spec_capacities(new_spec))
    return state, table, old


def adopt_capacities(server, caps: Dict[str, int]) -> bool:
    """Restore-time re-grow: adopt a checkpoint sidecar's per-kind
    capacities BEFORE folding rows. Startup only — the pipeline is not
    running yet, so the swap boundary is trivially quiescent and the
    discarded empty interval costs nothing. Returns True if the spec
    changed. fold_snapshot is capacity-independent (restore.py digest
    routing), so folding works either way; adopting first means the
    restored process starts with the table headroom it had when the
    checkpoint was taken instead of re-walking the grow ladder."""
    spec = server.aggregator.spec
    new_spec = grown_spec(spec, caps)
    if new_spec is spec:
        return False
    n_shards = getattr(server.aggregator, "n_shards", 1)
    bad = [k for k, v in spec_capacities(new_spec).items()
           if v <= 0 or v % n_shards]
    if bad:
        log.warning("checkpoint capacities %s not adoptable at "
                    "n_shards=%d; restoring at config capacities",
                    caps, n_shards)
        return False
    grow_swap(server, new_spec)
    return True
