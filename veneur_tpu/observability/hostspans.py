"""Host spans of the program itself, on two clocks at once.

`span(name)` is the one primitive. It enters a
`jax.profiler.TraceAnnotation`, so any profiler capture (an operator's
`GET /debug/profile`, a benchmark's traced run) shows the pipeline
thread's and the flush worker's stages in the same `.xplane.pb` as the
device ops, on the profiler's clock; and it appends one `Record` with
`time.monotonic_ns()` stamps to a process-global bounded deque, which
`records()` copies out for anyone who reduces them after the fact.

There is no switch. "Tracing off" is "no profiler session": the
annotation is then a flag check and the record two clock reads and an
append (~2 us a span on one slow CPU core). That is only affordable at
step or stage granularity, so spans never go per datagram, per sample or
per row: about three per ingest step and eighteen per flush. Work that
repeats faster than that (the pump loop) is recorded as a run (`run_call`
/ `run_returned`): one record per run of consecutive calls, with their
number.

The interpreter's collections are the one stop of the whole interpreter
that no span marks, so a `gc.callbacks` entry, registered at import,
marks them: every collection adds its pause to `gc_totals()`; a full
collection (generation 2) enters a `gc.collect` annotation on the
profiler's clock, and it and any collection of 1 ms or more leave a
`gc.collect` record on the collecting thread, under that thread's open
span, with no interval number.

Like jaxruntime's compile counters the store is process-global: several
Server instances in one process (the test suite) share it, and the
records of one interval are told apart by `seq`, the interval's number
(the server counts swaps; the pipeline thread carries the live
interval's number as its thread default, the flush job carries the
detached one's).
"""

from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from typing import List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# Records kept. Saturated ingest on a v5e writes 35-36 a second (PERF.md
# §6, PR 28: ~11 steps/s x pump run + emit + dispatch, a sampled sync
# every 64th step, ~18 a flush); 65536 holds half an hour of that, so a
# 30 s run with its warm-up never wraps, at ~20 MB when full.
MAX_RECORDS = 1 << 16


class Record(NamedTuple):
    name: str
    seq: Optional[int]      # the interval's number, None outside any
    thread: str
    start_ns: int           # time.monotonic_ns()
    end_ns: int
    parent: Optional[int]   # `index` of the enclosing span, same thread
    index: int              # process-wide, in order of span start
    tag: object             # free: an item's class, a run's (calls, ns)


_records: "collections.deque[Record]" = collections.deque(maxlen=MAX_RECORDS)
_index = itertools.count()
_local = threading.local()


class _ThreadState:
    __slots__ = ("stack", "seq", "run", "name")

    def __init__(self):
        self.stack = []         # open spans, outermost first
        self.seq = None         # set_thread_seq's default
        self.run = None         # the open _Run, if any
        self.name = threading.current_thread().name


def _state() -> _ThreadState:
    try:
        return _local.state
    except AttributeError:
        st = _local.state = _ThreadState()
        return st


def set_thread_seq(seq: Optional[int]) -> None:
    """The interval number spans of this thread carry when neither they
    nor an enclosing span name one (the pipeline thread's live
    interval)."""
    _state().seq = seq


def _enclosing(st: _ThreadState, seq: Optional[int]):
    """(the thread's innermost open span or None, the seq a new record
    carries: its own, else the enclosing span's, else the thread's)."""
    parent = st.stack[-1] if st.stack else None
    if seq is None:
        seq = parent.seq if parent is not None else st.seq
    return parent, seq


def _append(name, seq, st, start_ns, end_ns, parent, index, tag) -> None:
    _records.append(Record(name, seq, st.name, start_ns, end_ns,
                           None if parent is None else parent.index,
                           index, tag))


class span:
    """Context manager: one host span. After exit `ns` is its duration
    and `children` maps each direct child's name to its summed
    duration, so a caller can observe a timer from what a lower layer
    spanned without a second registry."""

    __slots__ = ("name", "seq", "tag", "ns", "children", "start_ns",
                 "index", "_parent", "_st", "_ann")

    def __init__(self, name: str, seq: Optional[int] = None, tag=None):
        self.name = name
        self.seq = seq
        self.tag = tag
        self.ns = 0
        self.children = {}

    def __enter__(self) -> "span":
        st = self._st = _state()
        _end_run(st)
        self._parent, self.seq = _enclosing(st, self.seq)
        self.index = next(_index)
        st.stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.monotonic_ns()
        self._ann.__exit__(*exc)
        st = self._st
        _end_run(st)
        stack = st.stack
        # a child leaked by an exception between its enter and its exit
        # goes with its parent
        while stack and stack.pop() is not self:
            pass
        self.ns = end - self.start_ns
        parent = self._parent
        if parent is not None:
            parent.children[self.name] = (
                parent.children.get(self.name, 0) + self.ns)
        _append(self.name, self.seq, st, self.start_ns, end, parent,
                self.index, self.tag)


class _Run:
    """The thread's open run of consecutive calls (see `run_call`)."""

    __slots__ = ("name", "seq", "parent", "index", "start_ns", "end_ns",
                 "calls", "inside_ns", "t0", "ann")

    def __init__(self, name: str, st: _ThreadState, now: int):
        self.name = name
        self.parent, self.seq = _enclosing(st, None)
        self.index = next(_index)
        self.calls = self.inside_ns = 0
        self.start_ns = self.end_ns = self.t0 = now
        self.ann = TraceAnnotation(name)
        self.ann.__enter__()


def _end_run(st: _ThreadState) -> None:
    run = st.run
    if run is not None:
        st.run = None
        run.ann.__exit__(None, None, None)
        _append(run.name, run.seq, st, run.start_ns, run.end_ns,
                run.parent, run.index, (run.calls, run.inside_ns))


def run_call(name: str) -> None:
    """Before one call of a run: a run is ONE record for consecutive
    calls of one kind on one thread, from the first call after other
    work to the last return before other work. `vr_pump` returns as soon
    as it has drained what is queued, so the pipeline loop may spin
    thousands of times a second while datagrams trickle in; a record a
    call would be a record a datagram. The run closes when any `span`
    opens or closes on the thread (that is the other work) or a run of
    another name begins, and its tag is (calls, nanoseconds inside the
    calls): the rest of its duration is glue between them."""
    st = _state()
    now = time.monotonic_ns()
    run = st.run
    if run is None or run.name != name:
        _end_run(st)
        st.run = _Run(name, st, now)
    else:
        run.t0 = now


def run_returned() -> None:
    """After that call returned."""
    run = _state().run
    if run is None:     # a span inside the call ended the run: calls
        return          # that open spans are not for runs
    run.end_ns = time.monotonic_ns()
    run.calls += 1
    run.inside_ns += run.end_ns - run.t0


def close_run() -> None:
    """Ends the thread's open run, if any (a thread about to exit)."""
    _end_run(_state())


def record(name: str, start_ns: int, end_ns: int,
           seq: Optional[int] = None, tag=None) -> None:
    """A span stamped by hand on the same clock and recorded once it is
    over: a stretch that begins on one thread and ends on another (a
    job's wait in a queue), or work only worth a record when it turns
    out to have done something (a ring emit that was no empty poll).
    Parent and default `seq` are the calling thread's, as for `span`. It
    has no annotation: the profiler cannot be backdated."""
    st = _state()
    _end_run(st)
    parent, seq = _enclosing(st, seq)
    _append(name, seq, st, start_ns, end_ns, parent, next(_index), tag)


# A young collection leaves a record only if it stopped the interpreter
# this long: on the chip's host most take 0.1-0.7 ms (PERF.md, PR 41).
GC_RECORD_NS = 1_000_000
GC_COLLECT = "gc.collect"

_gc_count = [0, 0, 0]        # collections by generation
_gc_ns = [0, 0, 0]           # their pauses, ns
_gc_t0 = 0                   # the collection under way: its start,
_gc_ann = None               # and a full one's annotation


def _on_gc(phase: str, info: dict) -> None:
    """gc.callbacks: called on the collecting thread, interpreter held,
    around each collection (never two at once)."""
    global _gc_t0, _gc_ann
    gen = info["generation"]
    if phase == "start":
        if gen == 2:
            _gc_ann = TraceAnnotation(GC_COLLECT)
            _gc_ann.__enter__()
        _gc_t0 = time.monotonic_ns()
        return
    end = time.monotonic_ns()
    if _gc_ann is not None:
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None
    _gc_count[gen] += 1
    _gc_ns[gen] += end - _gc_t0
    if gen == 2 or end - _gc_t0 >= GC_RECORD_NS:
        # appended, not `record`ed: a collection in the glue between two
        # pump calls must not end the thread's open run
        st = _state()
        _append(GC_COLLECT, None, st, _gc_t0, end,
                st.stack[-1] if st.stack else None, next(_index),
                (gen, info["collected"]))


gc.callbacks.append(_on_gc)


def gc_totals() -> List[tuple]:
    """(collections, pause ns) of each generation, 0 to 2, since import."""
    return list(zip(_gc_count, _gc_ns))


def records() -> List[Record]:
    """A copy of the records kept, oldest first by span END (a child
    precedes its parent). Safe while other threads append."""
    while True:
        try:
            return list(_records)
        except RuntimeError:    # the deque changed under the copy
            continue
