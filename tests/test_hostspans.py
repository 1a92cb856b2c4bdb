"""Host spans inside the program (observability/hostspans.py): the
primitive itself, the spans a served interval leaves on the pipeline
thread and the flush worker, and the stable names on the device
programs."""

import threading
import time

import jax
import numpy as np
import pytest

from veneur_tpu.observability import hostspans as H


def _mine(mark):
    """The records appended since `mark` (the store is process-global
    and other tests' servers write to it too)."""
    return [r for r in H.records() if r.index >= mark]


def _mark():
    H.record("test.mark", 0, 0)
    return H.records()[-1].index + 1


def _by_name(recs):
    out = {}
    for r in recs:
        out.setdefault(r.name, []).append(r)
    return out


# -- the primitive ------------------------------------------------------------

def test_nesting_parent_index_and_children():
    mark = _mark()
    with H.span("outer", seq=7) as outer:
        with H.span("inner") as a:
            time.sleep(0.002)
        with H.span("inner") as b:
            pass
        with H.span("other"):
            pass
    recs = _by_name(_mine(mark))
    (o,) = recs["outer"]
    assert o.parent is None and o.seq == 7
    assert o.thread == threading.current_thread().name
    for r in recs["inner"] + recs["other"]:
        assert r.parent == o.index
        assert r.seq == 7                      # inherited
        assert o.start_ns <= r.start_ns <= r.end_ns <= o.end_ns
    # children end (and are recorded) before their parent
    order = [r.name for r in _mine(mark)]
    assert order == ["inner", "inner", "other", "outer"]
    assert outer.ns == o.end_ns - o.start_ns
    assert outer.children["inner"] == a.ns + b.ns
    assert a.ns >= 2_000_000
    assert set(outer.children) == {"inner", "other"}


def test_parent_is_per_thread_and_thread_default_seq():
    mark = _mark()
    ready, go = threading.Event(), threading.Event()

    def worker():
        H.set_thread_seq(41)
        with H.span("w.outer"):
            ready.set()
            go.wait(10)
            with H.span("w.inner"):
                pass

    t = threading.Thread(target=worker, name="hostspans-worker")
    with H.span("m.outer", seq=3):
        t.start()
        assert ready.wait(10)
        with H.span("m.inner"):      # opened while w.outer is open
            go.set()
            t.join(10)
    assert not t.is_alive()
    recs = _by_name(_mine(mark))
    (mo,), (mi,) = recs["m.outer"], recs["m.inner"]
    (wo,), (wi,) = recs["w.outer"], recs["w.inner"]
    assert mi.parent == mo.index and wi.parent == wo.index
    assert wo.parent is None         # not the main thread's open span
    assert wo.thread == wi.thread == "hostspans-worker"
    assert (wo.seq, wi.seq) == (41, 41)
    assert (mo.seq, mi.seq) == (3, 3)


def test_exception_pops_the_stack():
    mark = _mark()
    with pytest.raises(ValueError):
        with H.span("boom.outer"):
            with H.span("boom.inner"):
                raise ValueError("x")
    with H.span("after"):
        pass
    recs = _by_name(_mine(mark))
    assert recs["after"][0].parent is None
    assert recs["boom.inner"][0].parent == recs["boom.outer"][0].index


def test_run_is_one_record_closed_by_other_work():
    mark = _mark()
    for _ in range(5):
        H.run_call("t.pump")
        time.sleep(0.001)
        H.run_returned()
    with H.span("t.work"):
        pass
    for _ in range(2):
        H.run_call("t.pump")
        H.run_returned()
    H.close_run()
    H.close_run()                    # idempotent
    recs = _mine(mark)
    assert [r.name for r in recs] == ["t.pump", "t.work", "t.pump"]
    first, work, second = recs
    calls, inside = first.tag
    assert calls == 5 and second.tag[0] == 2
    assert 5_000_000 <= inside <= first.end_ns - first.start_ns
    assert first.end_ns <= work.start_ns and work.end_ns <= second.start_ns


def test_record_by_hand_inherits_and_keeps_stamps():
    mark = _mark()
    with H.span("hand.outer", seq=9) as outer:
        H.record("hand.made", 100, 250)
        H.record("hand.other", 5, 6, seq=2, tag="x")
    recs = _by_name(_mine(mark))
    (made,), (other,) = recs["hand.made"], recs["hand.other"]
    assert (made.start_ns, made.end_ns, made.seq) == (100, 250, 9)
    assert made.parent == outer.index
    assert (other.seq, other.tag) == (2, "x")


def test_bound_and_records_while_another_thread_appends():
    assert H._records.maxlen == H.MAX_RECORDS
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            with H.span("spin"):
                pass

    t = threading.Thread(target=spin)
    t.start()
    try:
        deadline = time.monotonic() + 0.5
        n = 0
        while time.monotonic() < deadline:
            recs = H.records()
            assert len(recs) <= H.MAX_RECORDS
            n += 1
        assert n > 0
    finally:
        stop.set()
        t.join(10)
    assert not t.is_alive()
    # the store never grows past its bound
    for _ in range(H.MAX_RECORDS + 10):
        H.record("fill", 0, 0)
    recs = H.records()
    assert len(recs) == H.MAX_RECORDS
    assert recs[-1].name == "fill"


# -- one served interval ------------------------------------------------------

PIPELINE_SPANS = {"pipeline.pump", "pipeline.emit", "pipeline.dispatch",
                  "pipeline.sampled_sync", "pipeline.item"}
SWAP_CHILDREN = {"swap.emit_staged", "swap.finalize", "swap.device_wait",
                 "swap.reset"}
FLUSH_STAGES = {"device_update", "post_device", "frame_build",
                "sink_fanout", "self_metrics"}
DEVICE_UPDATE_CHILDREN = {"flush_plan", "flush_dispatch", "flush_d2h"}


def _phase_counts(srv):
    return {key[0]: count
            for key, (count, _sum) in srv._t_flush_phase.totals().items()}


def test_served_interval_leaves_every_span():
    from tests.test_server import small_config, _send_udp, _wait_processed
    from veneur_tpu.server.server import Server, _SpanMetricBatch
    from veneur_tpu.sinks.debug import DebugMetricSink

    srv = Server(small_config(), metric_sinks=[DebugMetricSink()])
    if not srv._native:
        pytest.skip("the native engine did not build here")
    mark = _mark()
    srv.start()
    try:
        # the next ingest step is a 64th: it takes the sampled sync
        srv.aggregator.steps_total = 63
        seq0 = srv._interval_seq
        _send_udp(srv.local_addr(), [b"hs.count:1|c", b"hs.t:3|ms",
                                     b"hs.g:2|g", b"hs.s:a|s"])
        _wait_processed(srv, 4)
        srv.packet_queue.put(_SpanMetricBatch([]))
        assert srv.trigger_flush()
        assert srv.trigger_flush()      # a second, empty interval
    finally:
        srv.shutdown()
    recs = [r for r in _mine(mark)]
    names = _by_name(recs)
    wanted = (PIPELINE_SPANS | SWAP_CHILDREN | FLUSH_STAGES
              | DEVICE_UPDATE_CHILDREN | {"swap", "flush", "queue_wait"})
    assert wanted <= set(names), sorted(wanted - set(names))

    swaps = sorted(names["swap"], key=lambda r: r.seq)
    flushes = sorted(names["flush"], key=lambda r: r.seq)
    assert [r.seq for r in swaps] == [seq0, seq0 + 1]
    assert [r.seq for r in flushes] == [seq0, seq0 + 1]
    assert swaps[0].thread != flushes[0].thread
    index = {r.index: r for r in recs}

    def inside(child, parent):
        return (child.parent == parent.index and child.seq == parent.seq
                and child.thread == parent.thread
                and parent.start_ns <= child.start_ns
                and child.end_ns <= parent.end_ns)

    # swap contains its three children; the first interval ingested, so
    # it waited for the device
    first = [r for r in recs if r.parent == swaps[0].index]
    assert SWAP_CHILDREN <= {r.name for r in first}
    assert all(inside(r, swaps[0]) for r in first)
    # flush contains its stages, device_update its three
    stages = [r for r in recs if r.parent == flushes[0].index]
    assert FLUSH_STAGES <= {r.name for r in stages}
    assert all(inside(r, flushes[0]) for r in stages)
    (dev,) = [r for r in stages if r.name == "device_update"]
    kids = [r for r in recs if r.parent == dev.index]
    assert DEVICE_UPDATE_CHILDREN <= {r.name for r in kids}
    assert all(inside(r, dev) for r in kids)
    # the job waited between the swap's end and the flush's start
    (wait,) = [r for r in names["queue_wait"] if r.seq == seq0]
    assert swaps[0].start_ns <= wait.start_ns <= wait.end_ns
    assert wait.end_ns <= flushes[0].start_ns
    # ingest before the first swap carries that interval's number, on
    # the swap's thread; the item says what it was
    ingest = [r for r in recs if r.name in PIPELINE_SPANS
              and r.end_ns <= swaps[0].start_ns]
    assert ingest and all(r.seq == seq0 for r in ingest)
    assert {r.thread for r in ingest} == {swaps[0].thread}
    assert "_SpanMetricBatch" in {r.tag for r in names["pipeline.item"]}
    sync = names["pipeline.sampled_sync"][0]
    assert index.get(sync.parent) is None or \
        index[sync.parent].name != "pipeline.dispatch"
    # the new phases are observed once a flush, beside the old ones
    counts = _phase_counts(srv)
    for phase in ("ingest_drain", "swap_device_wait", "swap_host",
                  "queue_wait", "device_update", "flush_plan",
                  "flush_dispatch", "flush_d2h", "post_device", "frame_build",
                  "self_metrics", "total"):
        assert counts.get(phase) == 2, (phase, counts)
    totals = srv._t_flush_phase.totals()
    drain = totals[("ingest_drain",)][1]
    assert totals[("swap_device_wait",)][1] + totals[("swap_host",)][1] \
        == pytest.approx(drain)
    assert totals[("flush_d2h",)][1] <= totals[("device_update",)][1]


def test_timer_totals_does_not_fold():
    from veneur_tpu.observability import TelemetryRegistry
    reg = TelemetryRegistry()
    t = reg.timer("veneur.test.totals_ns", "x", labelnames=("phase",))
    t.observe(5, phase="a")
    t.observe(7, phase="a")
    t.observe(1, phase="b")
    assert t.totals() == {("a",): (2, 12.0), ("b",): (1, 1.0)}
    # nothing was folded into the sketch: the samples are still buffered
    assert sum(len(st.buf) for st in t._states.values()) == 3


# -- names on the device programs ---------------------------------------------

def _small_spec():
    from veneur_tpu.aggregation.state import TableSpec
    return TableSpec(counter_capacity=64, gauge_capacity=64,
                     status_capacity=16, set_capacity=16, histo_capacity=32)


def test_ingest_program_carries_scope_names():
    from veneur_tpu.aggregation.host import Batcher, BatchSpec
    from veneur_tpu.aggregation.state import empty_state_compiled
    from veneur_tpu.aggregation.step import (
        batch_sizes, ingest_step_packed, pack_batch)
    spec = _small_spec()
    batch = Batcher(spec, BatchSpec(counter=32, gauge=32, status=16, set=16,
                                    histo=32)).force_emit()
    sizes = batch_sizes(batch)
    text = ingest_step_packed.lower(
        empty_state_compiled(spec), pack_batch(batch), spec=spec,
        sizes=sizes).as_text(debug_info=True)
    for scope in ("unpack", "ingest.counter", "ingest.gauge", "ingest.set",
                  "ingest.histo", "fold", "maybe_compact", "/compact/"):
        assert scope in text, scope
    assert "jit_packed_step_core" in text


def test_flush_program_carries_scope_names():
    from veneur_tpu.aggregation.state import empty_state_compiled
    from veneur_tpu.aggregation.step import (
        flush_live_in_packed, pack_flush_inputs)
    spec = _small_spec()
    buckets = (64, 64, 16, 16, 32)
    flat = pack_flush_inputs(
        [0.5, 0.99], [np.zeros(b, np.int32) for b in buckets])
    text = flush_live_in_packed.lower(
        empty_state_compiled(spec), flat, spec=spec, n_q=2,
        buckets=buckets, want_raw=False).as_text(debug_info=True)
    for scope in ("flush.gather", "flush.quantiles", "flush.hll_estimate",
                  "flush.pack"):
        assert scope in text, scope
    assert "jit__flush_live_in_packed_core" in text


def test_sharded_programs_lower_under_different_names():
    from veneur_tpu.aggregation.host import Batcher, BatchSpec
    from veneur_tpu.aggregation.step import batch_sizes, packed_layout
    from veneur_tpu.parallel.sharded import (
        make_merged_flush, make_mesh, make_sharded_ingest_packed,
        sharded_empty_state)
    import jax.numpy as jnp
    spec = _small_spec()
    mesh = make_mesh(1, 2)
    sizes = batch_sizes(Batcher(spec, BatchSpec(
        counter=32, gauge=32, status=16, set=16, histo=32)).force_emit())
    state = sharded_empty_state(spec, 1, 2, mesh)
    flat = np.zeros((1, 2, packed_layout(sizes)[1]), np.int32)
    ingest = make_sharded_ingest_packed(mesh, spec, sizes).lower(
        state, flat).as_text()
    flush = make_merged_flush(mesh, spec).lower(
        state, jnp.asarray([0.5], jnp.float32)).as_text()

    def module(text):
        return text.split("module @", 1)[1].split()[0]

    assert module(ingest) == "jit_sharded_packed_step"
    assert module(flush) == "jit_sharded_merged_flush"
