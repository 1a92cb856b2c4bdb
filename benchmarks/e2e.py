"""The five BASELINE benchmark configs, end-to-end.

Where round-1's bench.py timed only the jitted device step, these run the
WHOLE pipeline — wire bytes → parse → key/dictionary → staging → H2D →
device scatter (with compact/fold at production cadence) → flush math →
sink — the path the reference's own benchmarks cover
(server_test.go:1139 BenchmarkServerFlush, worker_test.go:506
BenchmarkWork, parser_test.go:818 BenchmarkParseMetric).

Configs (BASELINE.md §North-star):
  1. counter replay over REAL UDP loopback → blackhole sink
  2. 100k-name Zipf-latency timers → t-digest p50/p90/p99 vs exact
  3. 1M unique uids → HLL cardinality vs exact
  4. 64 local → 1 global gRPC forward, mixed counter+digest merge
  5. SSF span firehose → count-min heavy hitters (+ extraction timers)

Configs 2/3 feed pre-built wire packets through the server's packet queue
(everything UDP gives except the kernel socket read) so the accuracy
oracle is lossless; config 1 uses real sockets and reports drops honestly.

Run:  python -m benchmarks.e2e [--config N] [--scale S]
Each config prints one JSON object; `main()` returns the list of results
(bench.py embeds them in its single output line).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

import numpy as np

DEFAULT_PORT = 0
FLUSH_WAIT = 60.0
# First compile of the ingest+swap+flush programs on a real TPU takes tens
# of seconds; warm-up flushes get a budget that covers it.
WARM_TIMEOUT = 600.0


def midpoint_quantile(vals, q):
    """Quantile of raw samples under the t-digest midpoint-mass convention —
    what a PERFECT digest (one centroid per sample) returns, and the
    convention of the Go reference digest (merging_digest.go:302 Quantile).
    Using numpy's order-statistic interpolation as the oracle instead would
    charge the sketch for a definitional difference that grows as 1/n.
    ONE implementation, shared with the analysis harness."""
    from benchmarks.tdigest_analysis import midpoint_quantile as _mq
    return _mq(vals, q)


def _mk_server(metric_sinks, span_sinks=(), udp=False, **cfg_kw):
    from veneur_tpu.config import Config
    from veneur_tpu.server.server import Server
    defaults = dict(
        # long interval: the benchmark drives flushes manually; a ticker
        # flush mid-measurement would contend for the flush worker
        interval="600s", hostname="bench", metric_max_length=4096,
        read_buffer_size_bytes=4 * 1024 * 1024,
        percentiles=[0.5, 0.9, 0.99], aggregates=["min", "max", "count"],
        statsd_listen_addresses=(["udp://127.0.0.1:0"] if udp else []),
        num_readers=1,
        span_channel_capacity=8192)
    defaults.update(cfg_kw)
    srv = Server(Config(**defaults), metric_sinks=list(metric_sinks),
                 span_sinks=list(span_sinks))
    srv.start()
    return srv


DRAIN_TIMEOUT = 600.0


def _drain(srv, want_processed, timeout=DRAIN_TIMEOUT):
    """Wait until the pipeline has consumed `want_processed` samples (or
    the packet queue is empty and counts stopped moving)."""
    t0 = time.time()
    last = -1
    while time.time() - t0 < timeout:
        done = srv.aggregator.processed + srv.aggregator.dropped_capacity
        if done >= want_processed:
            return done
        if srv.packet_queue.qsize() == 0 and done == last:
            return done  # drops upstream of the queue; nothing left to do
        last = done
        time.sleep(0.05)
    return srv.aggregator.processed + srv.aggregator.dropped_capacity


def _feed_queue(srv, payloads):
    """Lossless feed: pre-built wire payloads straight into the pipeline
    queue (the post-socket path: split, parse, key, stage, H2D, ingest)."""
    put = srv.packet_queue.put
    for p in payloads:
        put(p)


def _warm(srv, lines, sinks=()):
    """Prove the pipeline is live before t0. Deliberately does NOT flush:
    a warm-up flush at near-empty live counts would compile a flush
    program for a smaller size bucket than the real load's. Each
    config's cycle 0 is untimed-in-spirit and absorbs every compile at
    the TRUE buckets; cycle 1 is the steady state."""
    phase("warm_ingest")   # first sample compiles the ingest program
    base = srv.aggregator.processed
    for ln in lines:
        srv.packet_queue.put(ln)
    _drain(srv, base + len(lines), timeout=WARM_TIMEOUT)
    for s in sinks:
        s.flushed.clear()
    phase("warm_done")


def _flush_checked(srv, timeout=FLUSH_WAIT):
    """Manual flush that fails loudly instead of silently timing out."""
    ok = srv.trigger_flush(timeout=timeout)
    if not ok:
        raise RuntimeError("timed flush did not complete within %.0fs"
                           % timeout)


def _acc(errs, what, **diag):
    """Accuracy reduction guard: an empty error list means the pipeline
    produced no checkable output — fail with a diagnostic, not a numpy
    ValueError from np.max([])."""
    if not len(errs):
        raise RuntimeError(
            "no %s values to check — pipeline produced no matching sink "
            "output (%s)" % (what, ", ".join(
                f"{k}={v}" for k, v in diag.items())))
    return errs


# -- config 1: UDP counter replay → blackhole --------------------------------

def config1_counter_replay(scale=1.0):
    """10k-name DogStatsD counter replay via UDP loopback (BASELINE #1;
    the reference's veneur-emit replay mode is the traffic model)."""
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    names = 10_000
    datagrams = max(200, int(50_000 * scale))
    lines_per = 40
    rng = np.random.default_rng(1)

    payloads = []
    for _ in range(datagrams):
        ns = rng.integers(0, names, lines_per)
        payloads.append(b"\n".join(
            b"replay.counter.%d:1|c" % n for n in ns))
    total = datagrams * lines_per

    n_senders = 4
    # big staging lanes: fewer dispatches, and large batches are the
    # grain the device wants anyway
    srv = _mk_server([BlackholeMetricSink()], udp=True,
                     tpu_counter_capacity=1 << 14, num_readers=n_senders,
                     tpu_batch_counter=1 << 16)
    try:
        addr = srv.local_addr()
        # warm the compiled path so the timed region is steady-state;
        # the untimed first cycle compiles the live-slot flush at the
        # run's true cardinality bucket (reference benchmarks loop b.N
        # times for the same reason)
        _warm(srv, [b"replay.counter.0:1|c"])

        # many-clients traffic model (the reference's veneur-emit replay
        # fleet): each sender thread has its own socket, so distinct
        # 4-tuples hash across the SO_REUSEPORT reader group
        send_errors = []

        def send_slice(chunk):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                for p in chunk:
                    s.sendto(p, addr)
            except OSError as e:
                send_errors.append(e)
            finally:
                s.close()

        for cycle in range(2):
            phase(f"cycle{cycle}")
            base = srv.aggregator.processed
            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=send_slice, args=(payloads[i::n_senders],))
                for i in range(n_senders)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if send_errors:
                raise RuntimeError(f"sender failed: {send_errors[0]}")
            done = _drain(srv, base + total) - base
            # cycle 0 pays the size-bucket flush compile
            _flush_checked(srv, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            dt = time.perf_counter() - t0

        processed = srv.aggregator.processed - base
        return {
            "config": 1, "name": "udp_counter_replay",
            "samples_per_sec": round(processed / dt, 1),
            "samples_sent": total,
            "samples_processed": int(processed),
            # self-telemetry loop-back can push `done` a hair past `total`
            "drop_fraction": round(max(0.0, 1.0 - done / total), 4),
            "wall_seconds": round(dt, 3),
        }
    finally:
        srv.shutdown()


# -- config 2: Zipf-latency timers → quantile accuracy -----------------------

def config2_zipf_timers(scale=1.0):
    """100k names × heavy-tail latencies → t-digest p50/p90/p99 error vs
    exact (BASELINE #2; budget ≤1% p99 PER KEY — p99_err_max is the
    gate, VERDICT r04 #3). Exact-extreme protection + extremeness-
    priority temp (ops/tdigest.py, step._histo_update) hold the worst
    key inside 1%; a sequential reference-style merging digest (δ=100)
    on the same data measures max 9.6% — this pipeline beats the
    reference algorithm at the tails, not just matches it."""
    from veneur_tpu.sinks.debug import DebugMetricSink

    names = max(1000, int(100_000 * scale))
    samples = max(5000, int(1_000_000 * scale))
    rng = np.random.default_rng(2)

    # Zipf-rank name popularity; latencies lognormal (heavy tail)
    ranks = np.arange(1, names + 1, dtype=np.float64)
    pname = (1.0 / ranks) / np.sum(1.0 / ranks)
    name_of = rng.choice(names, size=samples, p=pname)
    vals = rng.lognormal(3.0, 0.9, samples).astype(np.float32)

    by_name_vals = {}
    lines = []
    for n, v in zip(name_of, vals):
        lines.append(b"lat.%d:%.4f|ms" % (n, v))
        by_name_vals.setdefault(int(n), []).append(float(v))
    per = 40
    payloads = [b"\n".join(lines[i:i + per])
                for i in range(0, len(lines), per)]

    sink = DebugMetricSink()
    srv = _mk_server([sink], tpu_histo_capacity=1 << 17,
                     tpu_batch_histo=1 << 16, tpu_compact_every=2)
    try:
        _warm(srv, [b"warm.t:1.0|ms"], sinks=[sink])
        for cycle in range(2):   # first cycle compiles the size bucket
            phase(f"cycle{cycle}")
            sink.flushed.clear()
            base = srv.aggregator.processed
            t0 = time.perf_counter()
            _feed_queue(srv, payloads)
            _drain(srv, base + samples)
            _flush_checked(srv, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            dt = time.perf_counter() - t0

        flushed = {m.name: m.value for m in sink.flushed}
        errs = {0.5: [], 0.9: [], 0.99: []}
        checked = 0
        # check the most-sampled names (stable exact quantiles)
        top = sorted(by_name_vals, key=lambda n: -len(by_name_vals[n]))[:200]
        for n in top:
            v = np.asarray(by_name_vals[n])
            if len(v) < 10:
                continue
            for q in errs:
                key = f"lat.{n}.{int(q * 100)}percentile"
                if key not in flushed:
                    continue
                exact = midpoint_quantile(v, q)
                if exact > 0:
                    errs[q].append(abs(flushed[key] - exact) / exact)
            checked += 1
        return {
            "config": 2, "name": "zipf_timers",
            "samples_per_sec": round(samples / dt, 1),
            "names": names, "samples": samples,
            "names_checked": checked,
            "p50_err_mean": round(float(np.mean(_acc(
                errs[0.5], "p50", names_checked=checked,
                flushed_keys=len(flushed)))), 5),
            "p99_err_mean": round(float(np.mean(errs[0.99])), 5),
            "p99_err_max": round(float(np.max(_acc(
                errs[0.99], "p99", names_checked=checked,
                flushed_keys=len(flushed)))), 5),
            "wall_seconds": round(dt, 3),
        }
    finally:
        srv.shutdown()


# -- config 3: 1M-uid sets → HLL accuracy ------------------------------------

def config3_set_cardinality(scale=1.0):
    """1M unique user ids into set metrics → HLL estimate vs exact
    (BASELINE #3)."""
    from veneur_tpu.sinks.debug import DebugMetricSink

    uids = max(20_000, int(1_000_000 * scale))
    keys = 4
    lines = [b"users.active.%d:uid-%d|s" % (i % keys, i)
             for i in range(uids)]
    per = 40
    payloads = [b"\n".join(lines[i:i + per])
                for i in range(0, len(lines), per)]

    sink = DebugMetricSink()
    srv = _mk_server([sink], tpu_set_capacity=16, tpu_batch_set=1 << 15)
    try:
        _warm(srv, [b"warm.s:uid-w|s"], sinks=[sink])
        for cycle in range(2):   # first cycle compiles the size bucket
            phase(f"cycle{cycle}")
            sink.flushed.clear()
            base = srv.aggregator.processed
            t0 = time.perf_counter()
            _feed_queue(srv, payloads)
            _drain(srv, base + uids)
            _flush_checked(srv, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            dt = time.perf_counter() - t0

        flushed = {m.name: m.value for m in sink.flushed}
        per_key = {k: sum(1 for i in range(uids) if i % keys == k)
                   for k in range(keys)}
        errs = []
        for k in range(keys):
            got = flushed.get(f"users.active.{k}")
            if got is not None:
                errs.append(abs(got - per_key[k]) / per_key[k])
        return {
            "config": 3, "name": "set_cardinality",
            "samples_per_sec": round(uids / dt, 1),
            "unique_ids": uids,
            "estimate_err_mean": round(float(np.mean(_acc(
                errs, "HLL estimate", flushed_keys=len(flushed)))), 5),
            "estimate_err_max": round(float(np.max(errs)), 5),
            "wall_seconds": round(dt, 3),
        }
    finally:
        srv.shutdown()


# -- config 4: 64 local → 1 global gRPC merge --------------------------------

def config4_global_merge(scale=1.0):
    """64 local tiers forward mixed counters + digests to one global over
    real loopback gRPC; global must merge exactly (counters) and within
    the digest error budget (BASELINE #4)."""
    from veneur_tpu.aggregation.host import BatchSpec
    from veneur_tpu.aggregation.state import TableSpec
    from veneur_tpu.forward.convert import export_metrics
    from veneur_tpu.forward.rpc import ForwardClient
    from veneur_tpu.samplers.parser import parse_metric
    from veneur_tpu.server.aggregator import Aggregator
    from veneur_tpu.sinks.debug import DebugMetricSink

    n_locals = 64
    counters = max(8, int(200 * scale))
    histos = max(4, int(50 * scale))
    histo_samples = 20
    rng = np.random.default_rng(4)

    spec = TableSpec(counter_capacity=1 << 10, gauge_capacity=64,
                     status_capacity=16, set_capacity=16,
                     histo_capacity=1 << 8)
    bspec = BatchSpec(counter=2048, gauge=64, status=16, set=64, histo=2048)

    all_histo_vals = {h: [] for h in range(histos)}
    exports = []
    for li in range(n_locals):
        agg = Aggregator(spec, bspec)
        for c in range(counters):
            m = parse_metric(
                b"merged.counter.%d:%d|c|#veneurglobalonly" % (c, li + c))
            agg.process_metric(m)
        for h in range(histos):
            vals = rng.lognormal(2.0, 0.8, histo_samples)
            all_histo_vals[h].extend(vals.tolist())
            for v in vals:
                agg.process_metric(
                    parse_metric(b"merged.timer.%d:%.4f|ms" % (h, v)))
        _, table, raw = agg.flush([0.5], want_raw=True)
        exports.append(export_metrics(raw, table, compression=spec.compression,
                                      hll_precision=spec.hll_precision))

    sink = DebugMetricSink()
    glob = _mk_server([sink], grpc_address="127.0.0.1:0",
                      tpu_counter_capacity=1 << 12,
                      tpu_histo_capacity=1 << 9)
    try:
        # prove the global's pipeline is live; cycle 0 absorbs the
        # ingest+flush compiles at the true size buckets (_warm no longer
        # flushes -- see its docstring)
        _warm(glob, [b"warm.c:1|c", b"warm.t:1.0|ms"], sinks=[sink])
        client = ForwardClient(f"127.0.0.1:{glob.grpc_port}")
        n_metrics = sum(len(e) for e in exports)
        flush_seconds = []    # steady-state flush walls (cycle 0's
        # flush pays the size-bucket compile and is excluded); config13
        # replays this exact load with 100k watches registered and its
        # bench.py gate compares against these
        for cycle in range(2):   # first cycle compiles the size bucket
            phase(f"cycle{cycle}")
            sink.flushed.clear()
            t0 = time.perf_counter()
            for e in exports:
                client.send_metrics(e, timeout=30.0)
            # imports ride the pipeline queue; drain then flush
            t1 = time.time()
            while glob.packet_queue.qsize() and \
                    time.time() - t1 < FLUSH_WAIT:
                time.sleep(0.02)
            tf = time.perf_counter()
            _flush_checked(glob, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            if cycle > 0:
                flush_seconds.append(time.perf_counter() - tf)
            dt = time.perf_counter() - t0

        # Sustained absorption (VERDICT r04 #5): pump pre-serialized
        # MetricLists over the live gRPC channel for a fixed window and
        # measure what the global ABSORBS (decode→slot→stage→device),
        # not just the two accuracy cycles' request-response wall time.
        # A 64-local fleet at 100k keys each needs ~640k/s inside one
        # interval (reference bar: importsrv/server_test.go:115).
        from veneur_tpu.proto import forwardrpc_pb2 as fpb
        phase("sustained_absorb")
        ml = fpb.MetricList()
        for e in exports[:8]:
            ml.metrics.extend(e)
        payload = ml.SerializeToString()
        per_req = len(ml.metrics)
        base = glob.imported_total
        t0 = time.perf_counter()
        reqs = 0
        window = 1.5
        inflight = []
        # request cap bounds the post-window drain on slow backends (the
        # CPU smoke's device step is ~1000x a real chip's)
        while time.perf_counter() - t0 < window and reqs < 400:
            inflight.append(client.send_serialized(payload, timeout=30.0,
                                                   wait=False))
            reqs += 1
            if len(inflight) >= 32:   # a fleet's worth of overlap
                inflight.pop(0).result()
        for f in inflight:
            f.result()
        # drain: absorption isn't done until the pipeline consumed it
        t1 = time.time()
        while glob.imported_total - base < reqs * per_req and \
                time.time() - t1 < FLUSH_WAIT:
            time.sleep(0.01)
        absorb_dt = time.perf_counter() - t0
        absorbed = glob.imported_total - base
        client.close()

        flushed = {m.name: m.value for m in sink.flushed}
        counter_exact = all(
            flushed.get(f"merged.counter.{c}") ==
            sum(li + c for li in range(n_locals))
            for c in range(counters))
        p99_errs = []
        for h in range(histos):
            got = flushed.get(f"merged.timer.{h}.99percentile")
            exact = midpoint_quantile(all_histo_vals[h], 0.99)
            if got is not None and exact > 0:
                p99_errs.append(abs(got - exact) / exact)
        return {
            "config": 4, "name": "global_merge_64to1",
            "forwarded_metrics_per_sec": round(n_metrics / dt, 1),
            "absorbed_metrics_per_sec": round(absorbed / absorb_dt, 1),
            "absorbed_metrics": int(absorbed),
            "n_locals": n_locals, "metrics_forwarded": n_metrics,
            "counters_exact": bool(counter_exact),
            "merged_p99_err_mean": round(float(np.mean(_acc(
                p99_errs, "merged p99", flushed_keys=len(flushed)))), 5),
            "merged_p99_err_max": round(float(np.max(p99_errs)), 5),
            "flush_seconds": [round(s, 3) for s in flush_seconds],
            "flush_p99_seconds": round(float(
                np.percentile(flush_seconds, 99)), 3),
            "wall_seconds": round(dt, 3),
        }
    finally:
        glob.shutdown()


# -- config 5: SSF span firehose → count-min ---------------------------------

def config5_span_firehose(scale=1.0):
    """High-cardinality tagged span stream: protobuf parse → span workers →
    count-min heavy hitters + metric extraction (BASELINE #5)."""
    from veneur_tpu.proto import ssf_pb2
    from veneur_tpu.protocol.wire import parse_ssf
    from veneur_tpu.sinks.debug import DebugMetricSink

    spans = max(2000, int(100_000 * scale))
    hot_tags = 20
    tail_tags = max(1000, int(1_000_000 * scale))
    rng = np.random.default_rng(5)

    # 50% of spans carry one of `hot_tags`, the rest near-unique tags
    payloads = []
    true_counts = np.zeros(hot_tags, np.int64)
    for i in range(spans):
        span = ssf_pb2.SSFSpan(version=0, trace_id=i + 1, id=i + 2,
                               service="svc", name="op",
                               start_timestamp=1000 + i,
                               end_timestamp=2000 + i)
        if i % 2 == 0:
            t = int(rng.integers(0, hot_tags))
            true_counts[t] += 1
            span.tags["customer"] = f"hot{t}"
        else:
            span.tags["customer"] = f"tail{int(rng.integers(0, tail_tags))}"
        payloads.append(span.SerializeToString())

    sink = DebugMetricSink()
    srv = _mk_server([sink], tag_frequency_enabled=True,
                     tag_frequency_top_k=hot_tags,
                     tag_frequency_batch_size=8192)
    try:
        import functools
        # production wire path includes the per-service intake counters
        handle = functools.partial(srv.span_pipeline.handle_span,
                                   ssf_format="packet")
        # warm: one span through the pipeline compiles the count-min
        # update; flush resets the sketch so warm tags don't leak in
        warm_span = ssf_pb2.SSFSpan(version=0, trace_id=1, id=2,
                                    service="svc", name="warm",
                                    start_timestamp=1, end_timestamp=2)
        warm_span.tags["customer"] = "warm"
        phase("warm_ingest")   # first span compiles the count-min update
        handle(parse_ssf(warm_span.SerializeToString()))
        t1 = time.time()
        while srv.tag_frequency.spans_seen < 1 and \
                time.time() - t1 < WARM_TIMEOUT:
            time.sleep(0.02)
        srv.tag_frequency.flush()
        base = srv.tag_frequency.spans_seen
        phase("warm_done")

        t0 = time.perf_counter()
        dropped0 = srv.span_pipeline.spans_dropped
        phase("span_feed")
        for p in payloads:
            while not handle(parse_ssf(p)):   # retry on full channel
                time.sleep(0.001)
        phase("span_drain")
        t1 = time.time()
        while srv.tag_frequency.spans_seen - base < spans and \
                time.time() - t1 < FLUSH_WAIT:
            time.sleep(0.05)
        phase("sketch_flush")
        samples = srv.tag_frequency.flush()
        dt = time.perf_counter() - t0

        got = {s.tags["tag"]: s.value for s in samples
               if s.name == "veneur.span.tag_frequency"}
        true_top = {f"customer:hot{t}" for t in
                    np.argsort(-true_counts)[:10]}
        recall = len(true_top & set(got)) / len(true_top)
        errs = []
        for t in range(hot_tags):
            est = got.get(f"customer:hot{t}")
            if est is not None and true_counts[t] > 0:
                errs.append((est - true_counts[t]) / true_counts[t])
        return {
            "config": 5, "name": "span_firehose_heavy_hitters",
            "spans_per_sec": round(spans / dt, 1),
            "spans": spans,
            "top10_recall": round(recall, 3),
            "overestimate_mean": round(float(np.mean(_acc(
                errs, "heavy-hitter count", reported=len(got)))), 5),
            "wall_seconds": round(dt, 3),
        }
    finally:
        srv.shutdown()


def config6_cardinality_stress(scale=1.0):
    """10M LIVE names across every metric type — SURVEY §7's declared
    hardest part, absorbed by the self-adjusting key tables (README
    §Key tables) instead of the old fixed-90% saturation drill. The
    counter table starts at ~1/8 of the counter name space and a
    "cardinality march" feeds ever-larger prefixes with a flush between
    steps, so the manager's high-water doubling grows it live to the
    full population; the first march step deliberately overshoots the
    initial capacity so the report can assert the dropped count is
    EXACTLY the over-capacity attempts. Beyond the growth story the
    config still measures host key-dictionary throughput (first-touch
    alloc vs steady-state hit), packed H2D feed bandwidth, and flush
    wall time at full live cardinality through the columnar frame path
    (per-metric object labeling would be ~20s host time at 10M; see
    flusher.MetricFrame). Gates: drop_fraction < 1% always; the
    grow-pause-fits-one-flush-interval gate arms on TPU only (a CPU
    grow pause is dominated by the XLA recompile for the new shape)."""
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    names_total = max(50_000, int(10_000_000 * scale))
    n_c = int(names_total * 0.60)
    n_g = int(names_total * 0.25)
    n_t = int(names_total * 0.10)
    n_s = names_total - n_c - n_g - n_t
    # HBM guard: each set row is a 16KB HLL register block (2^14
    # registers — the reference's precision, samplers.go:372), so the
    # natural 5% set share would alone claim 8GB of a 16GB chip at the
    # full 10M-name scale. Cap set rows and shift the excess names to
    # counters (the cheapest rows): total unique-name cardinality — the
    # thing this config stresses — is preserved, and the report carries
    # the actual mix.
    set_row_cap = 150_000
    if n_s > set_row_cap:
        n_c += n_s - set_row_cap
        n_s = set_row_cap
    # counters carry the growth story: start at ~n_c/8 (power of two)
    # and let the flush-boundary grow ladder reach the full population
    cap_c0 = 1 << max(12, (n_c // 8).bit_length())

    def build_payloads():
        per = 200
        payloads = []
        lines = []
        for i in range(n_c):
            lines.append(b"c%d:1|c" % i)
            if len(lines) >= per:
                payloads.append(b"\n".join(lines))
                lines = []
        for prefix, fmt, n in ((b"g", b"g%d:0.5|g", n_g),
                               (b"t", b"t%d:3.25|ms", n_t),
                               (b"s", b"s%d:u%d|s", n_s)):
            for i in range(n):
                lines.append(fmt % ((i, i) if prefix == b"s" else i))
                if len(lines) >= per:
                    payloads.append(b"\n".join(lines))
                    lines = []
        if lines:
            payloads.append(b"\n".join(lines))
        return payloads

    payloads = build_payloads()
    n_pay_c = n_c // 200            # pure-counter payload prefix
    sink = BlackholeMetricSink()
    srv = _mk_server(
        [sink],
        table_grow_enabled=True,
        table_max_capacity=max(1 << 24, 4 * n_c),
        tpu_counter_capacity=cap_c0,
        # static kinds carry >15% headroom so the 85% high-water mark
        # never triggers growth the bench didn't script
        tpu_gauge_capacity=int(n_g * 1.25) + 64,
        tpu_set_capacity=int(n_s * 1.25) + 64,
        tpu_histo_capacity=int(n_t * 1.25) + 64,
        tpu_status_capacity=64,
        tpu_batch_counter=1 << 16, tpu_batch_gauge=1 << 15,
        tpu_batch_set=1 << 14, tpu_batch_histo=1 << 14,
        tpu_compact_every=8)
    try:
        _warm(srv, [b"warm.c6:1|c"])
        stats = {}
        import jax
        on_tpu = jax.default_backend() == "tpu"

        def _device_sync():
            # jax dispatch is async: _drain returns when parsing/staging
            # is done, but ingest steps may still be queued on the
            # device. Without this barrier pass A's compute bleeds into
            # pass B's timer (observed 7x skew at 1M names on CPU).
            jax.block_until_ready(jax.tree.leaves(srv.aggregator.state))

        def _feed_counters(k):      # first k pure-counter payloads
            done0 = (srv.aggregator.processed
                     + srv.aggregator.dropped_capacity)
            _feed_queue(srv, payloads[:k])
            _drain(srv, done0 + k * 200)
            _device_sync()

        # -- cardinality march: grow live to the full population ------
        # each step feeds a prefix sized against the CURRENT capacity
        # (over the high-water mark, under the slot count → no drops),
        # then flushes; the manager doubles the counter table at that
        # swap. Only the first step overshoots the slot count, so total
        # drops are exactly that step's over-capacity attempts.
        phase("march")
        march_attempts = 0
        overshoot_expected = None
        pause_ns = []
        cap = srv.aggregator.spec.counter_capacity
        assert cap == cap_c0
        # march until the FULL population sits under the high-water
        # mark — stopping at bare residency would leave steady-state
        # demand over 85% and the first cycle flush would re-grow
        # (an unscripted compile inside the measured window)
        while cap * 0.85 < n_c + 64:
            if overshoot_expected is None:
                k = min(int(cap * 1.10), n_c) // 200
                overshoot_expected = max(0, k * 200 - cap)
            else:
                k = min(int(cap * 0.97), n_c) // 200
            k = min(k, n_pay_c)
            _feed_counters(k)
            march_attempts += k * 200
            # every march flush pays the compile for the grown spec —
            # the grow pause the report records is exactly this swap
            _flush_checked(srv, timeout=3 * WARM_TIMEOUT)
            newcap = srv.aggregator.spec.counter_capacity
            if newcap == cap:
                break               # demand already fits: march done
            pause_ns.append(srv.tables.last_grow_swap_ns)
            cap = newcap
        assert cap * 0.85 >= n_c + 64, f"march stalled at capacity {cap}"
        grow_flushes = len(pause_ns)

        for cycle in range(2):      # cycle 0 absorbs every compile
            phase(f"cycle{cycle}")
            done0 = srv.aggregator.processed + srv.aggregator.dropped_capacity
            h2d0 = srv.aggregator.h2d_bytes
            t0 = time.perf_counter()
            _feed_queue(srv, payloads)          # pass A: first touch
            _drain(srv, done0 + names_total)
            _device_sync()
            t_alloc = time.perf_counter() - t0
            t0 = time.perf_counter()
            _feed_queue(srv, payloads)          # pass B: dictionary hits
            _drain(srv, done0 + 2 * names_total)
            _device_sync()
            t_hit = time.perf_counter() - t0
            h2d = srv.aggregator.h2d_bytes - h2d0
            rows0 = sink.frames_rows
            t0 = time.perf_counter()
            # cycle 0's flush pays the flush-program compile at multi-
            # million-key buckets — the single largest compile in the
            # whole bench
            _flush_checked(srv, timeout=3 * WARM_TIMEOUT if cycle == 0
                           else 300.0)
            t_flush = time.perf_counter() - t0
            stats = dict(t_alloc=t_alloc, t_hit=t_hit, t_flush=t_flush,
                         h2d=h2d, rows=sink.frames_rows - rows0)

        # defaults from _mk_server: 3 aggregates + 3 percentiles per
        # timer. Every name is resident now — growth absorbed the full
        # population, so no capacity truncation term remains.
        expected_rows = n_c + n_g + n_s + 6 * n_t
        dropped = srv.aggregator.dropped_capacity
        total_attempts = march_attempts + 2 * 2 * names_total
        # self-telemetry shares the pipeline by design (the reference
        # always tallies flush totals back into itself, flusher.go:300-336)
        # and its counter-typed names contend for slots in the one
        # over-full march interval — so accounting is checked to a band
        # of a few dozen self-metrics around the exact over-capacity
        # prediction, with the raw error reported.
        drop_err = dropped - overshoot_expected
        rows_err = stats["rows"] - expected_rows
        drop_fraction = dropped / total_attempts
        pause_ms = max(pause_ns) / 1e6 if pause_ns else 0.0
        return {
            "config": 6, "name": "cardinality_10M_stress",
            "names": names_total, "live_keys": names_total,
            "mix": {"counter": n_c, "gauge": n_g, "timer": n_t,
                    "set": n_s},
            "counter_capacity_initial": cap_c0,
            "counter_capacity_final": cap,
            "grow_flushes": grow_flushes,
            "grow_events": srv.tables.grow_events,
            "grows": dict(srv.tables.grows),
            # the grow pause IS the swap pause (README §Key tables); the
            # one-flush-interval bound is gated on TPU where the ingest
            # program for the grown spec is pre-built off the swap path —
            # a CPU pause is dominated by the XLA recompile instead
            "grow_pause_ms_max": round(pause_ms, 2),
            "grow_pause_gate_armed": on_tpu,
            "grow_pause_le_interval": ((pause_ms / 1e3 <= 10.0)
                                       if on_tpu else None),
            "samples_per_sec": round(
                2 * names_total / (stats["t_alloc"] + stats["t_hit"]), 1),
            "alloc_keys_per_sec": round(
                names_total / stats["t_alloc"], 1),
            "hit_samples_per_sec": round(
                names_total / stats["t_hit"], 1),
            "drop_fraction": round(drop_fraction, 5),
            "drop_fraction_lt_1pct": drop_fraction < 0.01,
            "drop_accounting_err_keys": drop_err,
            "drop_accounting_exact": 0 <= drop_err <= 64,
            "flush_rows": stats["rows"],
            "flush_rows_err": rows_err,
            "flush_rows_exact": 0 <= rows_err <= 64,
            "flush_wall_seconds": round(stats["t_flush"], 3),
            "h2d_mb": round(stats["h2d"] / 1e6, 1),
            "h2d_mb_per_sec": round(
                stats["h2d"] / 1e6
                / (stats["t_alloc"] + stats["t_hit"]), 1),
            "parse_engine": "native" if srv._native else "python",
        }
    finally:
        srv.shutdown()


# -- config 7: checkpoint write + restore ------------------------------------

def config7_checkpoint_restore(scale=1.0):
    """Durability cost at a 200k-name mixed shape (README §Durability):
    snapshot write bandwidth, restore wall time, and — the acceptance
    gate — the flush-path overhead of checkpointing every interval,
    which must stay under 5% (the snapshot rides the flush's existing
    device→host outputs and is encoded on a background thread, so the
    flush only pays the handoff)."""
    import shutil
    import tempfile

    from veneur_tpu.persistence.codec import read_manifest
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    names_total = max(4_000, int(200_000 * scale))
    n_c = int(names_total * 0.60)
    n_t = int(names_total * 0.25)
    n_g = int(names_total * 0.10)
    n_s = names_total - n_c - n_t - n_g

    def _cap(n):
        # next power-of-two with ~25% headroom (self-telemetry rides the
        # same tables after the first flush)
        return 1 << max(8, int(n * 5 / 4).bit_length())

    caps = dict(tpu_counter_capacity=_cap(n_c), tpu_histo_capacity=_cap(n_t),
                tpu_gauge_capacity=_cap(n_g), tpu_set_capacity=_cap(n_s),
                tpu_batch_counter=1 << 15, tpu_batch_histo=1 << 14,
                tpu_batch_gauge=1 << 13, tpu_batch_set=1 << 12)

    def build_payloads():
        per = 200
        payloads, lines = [], []
        for fmt, n in ((b"kc%d:3|c", n_c), (b"kt%d:7.5|ms", n_t),
                       (b"kg%d:1|g", n_g), (b"ks%d:x|s", n_s)):
            for i in range(n):
                lines.append(fmt % i)
                if len(lines) >= per:
                    payloads.append(b"\n".join(lines))
                    lines = []
        if lines:
            payloads.append(b"\n".join(lines))
        return payloads

    payloads = build_payloads()

    def timed_flushes(srv, cycles=3):
        """Feed the full shape, then time ONLY the flush, per cycle.
        Cycle 0 pays the size-bucket compiles and is discarded."""
        walls = []
        for cycle in range(cycles):
            phase(f"cycle{cycle}")
            base = srv.aggregator.processed
            _feed_queue(srv, payloads)
            _drain(srv, base + names_total)
            t0 = time.perf_counter()
            _flush_checked(srv, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            walls.append(time.perf_counter() - t0)
        return walls[1:]   # steady state only

    ckpt_root = tempfile.mkdtemp(prefix="veneur-bench-ckpt-")
    try:
        # pass 1: checkpointing OFF — the flush-wall baseline
        phase("plain_server")
        srv = _mk_server([BlackholeMetricSink()], **caps)
        try:
            _warm(srv, [b"kc0:1|c"])
            plain_walls = timed_flushes(srv)
        finally:
            srv.shutdown()

        # pass 2: checkpoint every flush — same shape, same cycles
        phase("ckpt_server")
        srv = _mk_server([BlackholeMetricSink()], checkpoint_dir=ckpt_root,
                         checkpoint_interval_flushes=1,
                         checkpoint_on_shutdown=False, **caps)
        try:
            _warm(srv, [b"kc0:1|c"])
            ckpt_walls = timed_flushes(srv)
            if not srv._ckpt_writer.wait_idle(WARM_TIMEOUT):
                raise RuntimeError("checkpoint writer never went idle")
            writes = srv._ckpt_writer.writes
            if not writes:
                raise RuntimeError("no checkpoint was written")
            manifest = read_manifest(srv._ckpt_writer.last_path)
            snap_bytes = int(srv._c_ckpt_bytes.value())
            ((_, wstat),) = srv._t_ckpt_write.snapshot(qs=())
            write_s = wstat.sum / 1e9
        finally:
            srv.shutdown()

        # pass 3: restore wall time through the real startup path
        phase("restore_server")
        srv = _mk_server([BlackholeMetricSink()], checkpoint_dir=ckpt_root,
                         checkpoint_on_shutdown=False, **caps)
        try:
            t0 = time.perf_counter()
            srv._restore_from_checkpoint()
            restore_s = time.perf_counter() - t0
            restored = srv.aggregator.processed
            if int(srv._c_ckpt_restores.value()) != 1:
                raise RuntimeError("restore did not complete")
        finally:
            srv.shutdown()

        plain = float(np.mean(plain_walls))
        ckpt = float(np.mean(ckpt_walls))
        overhead = (ckpt - plain) / plain
        return {
            "config": 7, "name": "checkpoint_restore",
            "names": names_total,
            "mix": {"counter": n_c, "timer": n_t, "gauge": n_g, "set": n_s},
            "snapshot_rows": sum(manifest["rows"].values()),
            "snapshot_bytes": snap_bytes,
            "snapshot_writes": int(writes),
            "snapshot_write_mb_per_sec": round(
                snap_bytes / 1e6 / write_s, 1) if write_s > 0 else None,
            "restore_seconds": round(restore_s, 3),
            "restored_keys": int(restored),
            "flush_wall_plain_seconds": round(plain, 3),
            "flush_wall_ckpt_seconds": round(ckpt, 3),
            "flush_overhead_fraction": round(overhead, 4),
            "flush_overhead_under_5pct": overhead < 0.05,
        }
    finally:
        shutil.rmtree(ckpt_root, ignore_errors=True)


def config8_overload_storm(scale=1.0):
    """Sustained ingest storm at ~5x measured capacity (README §Overload
    & health). The acceptance gates, all reported as booleans:
    /healthz answers 200 throughout (a shedding server is LIVE),
    /readyz flips non-ready within one flush interval of entering
    SHEDDING and recovers within two intervals of load removal, every
    packet is accounted (admitted + shed == sent, exact — blocking
    queue puts make the feed lossless), high-priority traffic absorbs
    <1% of the shedding, and every storm flush meets the interval
    deadline."""
    import urllib.error
    import urllib.request

    from veneur_tpu.reliability.overload import PRESSURED, SHEDDING
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    interval_s = 2.0          # the flush deadline the gates measure against
    storm_intervals = 3
    n_producers = 4

    srv = _mk_server(
        [BlackholeMetricSink()], http_address="127.0.0.1:0",
        native_ingest=False,  # admission gates the Python parse path
        overload_enabled=True, overload_poll_interval_s=0.05,
        overload_hold_s=0.5,
        shed_priority_tags=["veneur.priority:high"],
        tpu_counter_capacity=1024, tpu_batch_counter=4096)
    try:
        ov = srv._overload
        port = srv.http_port

        def probe(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                    return r.status
            except urllib.error.HTTPError as e:
                return e.code

        # calibrate capacity with the controller's signals silenced —
        # admission during the baseline would measure the shed path,
        # not the parse path
        real_signals = ov._signals
        ov._signals = lambda: {}
        _warm(srv, [b"storm.l0:1|c"])
        phase("calibrate")
        # the calibration feed covers the storm's full name set (incl.
        # the high-priority rows) so the pre-storm flush compiles the
        # flush program at the storm's true size bucket — a mid-storm
        # recompile would be charged to the first flush deadline
        calib = [(b"storm.h%d:1|c|#veneur.priority:high" % (i % 64))
                 if i % 10 == 0 else (b"storm.l%d:1|c" % (i % 512))
                 for i in range(max(2_000, int(30_000 * scale)))]
        base = srv.aggregator.processed
        t0 = time.perf_counter()
        _feed_queue(srv, calib)
        _drain(srv, base + len(calib))
        capacity = len(calib) / (time.perf_counter() - t0)
        _flush_checked(srv, timeout=WARM_TIMEOUT)  # pay the size compile
        ov._signals = real_signals

        # storm traffic: 10% high-priority, 90% low; single-line packets
        high_pkts = [b"storm.h%d:1|c|#veneur.priority:high" % (i % 64)
                     for i in range(64)]
        low_pkts = [b"storm.l%d:1|c" % (i % 512) for i in range(512)]
        adm0 = dict(ov.admitted)
        shed0 = dict(ov.shed)
        sent = {"high": 0, "low": 0}
        sent_lock = threading.Lock()
        stop_evt = threading.Event()
        target_rate = 5.0 * capacity / n_producers  # per producer

        def produce(idx):
            put = srv.packet_queue.put
            h, lo, n = 0, 0, 0
            t_start = time.monotonic()
            while not stop_evt.is_set():
                burst = 100
                for i in range(burst):
                    if (n + i) % 10 == idx % 10:
                        put(high_pkts[(n + i) % len(high_pkts)])
                        h += 1
                    else:
                        put(low_pkts[(n + i) % len(low_pkts)])
                        lo += 1
                n += burst
                ahead = n / target_rate - (time.monotonic() - t_start)
                if ahead > 0:
                    stop_evt.wait(min(ahead, 0.05))
            with sent_lock:
                sent["high"] += h
                sent["low"] += lo

        health_codes, ready_log = [], []

        def poll_http():
            while not poll_stop.is_set():
                t = time.monotonic()
                health_codes.append(probe("/healthz"))
                ready_log.append((t, probe("/readyz")))
                poll_stop.wait(0.05)

        phase("storm")
        poll_stop = threading.Event()
        poller = threading.Thread(target=poll_http, daemon=True)
        poller.start()
        producers = [threading.Thread(target=produce, args=(i,),
                                      daemon=True)
                     for i in range(n_producers)]
        t_storm = time.monotonic()
        for p in producers:
            p.start()
        flush_walls = []
        for k in range(storm_intervals):
            wake = t_storm + (k + 1) * interval_s
            while time.monotonic() < wake - 0.05:
                time.sleep(0.02)
            f0 = time.perf_counter()
            _flush_checked(srv)
            flush_walls.append(time.perf_counter() - f0)
        stop_evt.set()
        for p in producers:
            p.join()
        t_load_off = time.monotonic()

        phase("recover")
        deadline = time.time() + DRAIN_TIMEOUT
        while srv.packet_queue.qsize() > 0 and time.time() < deadline:
            time.sleep(0.02)
        while (ov.state > PRESSURED
               and time.monotonic() - t_load_off < 4 * interval_s):
            time.sleep(0.02)
        time.sleep(0.2)   # let the pollers observe the recovered state
        poll_stop.set()
        poller.join()

        # accounting: every packet the producers put is either admitted
        # or shed — exactly, no third bucket
        adm_d = {k: v - adm0.get(k, 0) for k, v in ov.admitted.items()}
        shed_d = {k: v - shed0.get(k, 0) for k, v in ov.shed.items()}
        shed_d.pop("flush", None)  # flush-protection rows, not packets
        total_sent = sent["high"] + sent["low"]
        accounted = (sum(adm_d.values()) + sum(shed_d.values())
                     == total_sent)
        high_dropped = shed_d.get("high", 0)
        low_shed = shed_d.get("low", 0)

        # readiness latency vs the state machine's own transition stamps
        t_shed = next((ts for ts, _f, to in ov.transitions
                       if to >= SHEDDING and ts >= t_storm), None)
        t_flip = next((t for t, c in ready_log if c != 200), None)
        t_back = next((t for t, c in ready_log
                       if t > t_load_off and c == 200), None)
        flip_s = (t_flip - t_shed) if t_shed and t_flip else None
        recover_s = (t_back - t_load_off) if t_back else None
        return {
            "config": 8, "name": "overload_storm",
            "capacity_samples_per_sec": round(capacity, 1),
            "overload_ratio": round(
                total_sent / (t_load_off - t_storm) / capacity, 2),
            "sent": sent, "admitted": adm_d, "shed": shed_d,
            "accounting_exact": accounted,
            "healthz_all_200": all(c == 200 for c in health_codes),
            "healthz_probes": len(health_codes),
            "readyz_flip_seconds": round(flip_s, 3) if flip_s is not None
            else None,
            "readyz_flip_within_interval": flip_s is not None
            and flip_s <= interval_s,
            "readyz_recover_seconds": round(recover_s, 3)
            if recover_s is not None else None,
            "readyz_recover_within_2_intervals": recover_s is not None
            and recover_s <= 2 * interval_s,
            "high_drop_fraction": round(
                high_dropped / max(1, sent["high"]), 4),
            "high_drop_under_1pct":
                high_dropped / max(1, sent["high"]) < 0.01,
            "low_absorbed_shedding": low_shed > 0,
            "flush_wall_seconds": [round(w, 3) for w in flush_walls],
            "flush_deadline_met": max(flush_walls) <= interval_s,
            "transitions": len(ov.transitions),
        }
    finally:
        srv.shutdown()


# -- config 9: duplicate storm — exactly-once under 30% ack loss -------------

def config9_duplicate_storm(scale=1.0):
    """Config4's 64→1 merge under a hostile network: ~30% of sends lose
    their ack (FORWARD_ACK fault fires AFTER the global folded) and are
    re-sent with the SAME (source_id, epoch, seq) envelope, per the
    exactly-once retry contract. Same rng seed and load shape as config4
    so the merged-digest numbers are directly comparable: if duplicates
    double-folded, counters drift and p99 error moves. Gates: counter
    totals byte-exact, every forced duplicate suppressed AND accounted
    (dup_suppressed == forced, rejected == 0), p99 error at config4's
    level (bench.py cross-checks the two rows)."""
    from veneur_tpu.aggregation.host import BatchSpec
    from veneur_tpu.aggregation.state import TableSpec
    from veneur_tpu.forward.convert import export_metrics
    from veneur_tpu.forward.envelope import Envelope, mint_source_id
    from veneur_tpu.forward.rpc import ForwardClient
    from veneur_tpu.reliability.faults import (FAULTS, FORWARD_ACK,
                                               InjectedFault)
    from veneur_tpu.samplers.parser import parse_metric
    from veneur_tpu.server.aggregator import Aggregator
    from veneur_tpu.sinks.debug import DebugMetricSink

    n_locals = 64
    counters = max(8, int(200 * scale))
    histos = max(4, int(50 * scale))
    histo_samples = 20
    rng = np.random.default_rng(4)      # config4's seed: same oracle
    loss_rng = np.random.default_rng(90)

    spec = TableSpec(counter_capacity=1 << 10, gauge_capacity=64,
                     status_capacity=16, set_capacity=16,
                     histo_capacity=1 << 8)
    bspec = BatchSpec(counter=2048, gauge=64, status=16, set=64, histo=2048)

    all_histo_vals = {h: [] for h in range(histos)}
    exports = []
    for li in range(n_locals):
        agg = Aggregator(spec, bspec)
        for c in range(counters):
            m = parse_metric(
                b"merged.counter.%d:%d|c|#veneurglobalonly" % (c, li + c))
            agg.process_metric(m)
        for h in range(histos):
            vals = rng.lognormal(2.0, 0.8, histo_samples)
            all_histo_vals[h].extend(vals.tolist())
            for v in vals:
                agg.process_metric(
                    parse_metric(b"merged.timer.%d:%.4f|ms" % (h, v)))
        _, table, raw = agg.flush([0.5], want_raw=True)
        exports.append(export_metrics(raw, table, compression=spec.compression,
                                      hll_precision=spec.hll_precision))
    sids = [mint_source_id() for _ in range(n_locals)]

    sink = DebugMetricSink()
    glob = _mk_server([sink], grpc_address="127.0.0.1:0",
                      forward_dedup_window=64,
                      tpu_counter_capacity=1 << 12,
                      tpu_histo_capacity=1 << 9)
    try:
        _warm(glob, [b"warm.c:1|c", b"warm.t:1.0|ms"], sinks=[sink])
        client = ForwardClient(f"127.0.0.1:{glob.grpc_port}")
        n_metrics = sum(len(e) for e in exports)
        dup_forced = 0
        for cycle in range(2):   # cycle 0 compiles the size bucket
            phase(f"cycle{cycle}")
            sink.flushed.clear()
            t0 = time.perf_counter()
            for li, e in enumerate(exports):
                env = Envelope(sids[li], 0, cycle)
                if loss_rng.random() < 0.30:
                    FAULTS.arm(FORWARD_ACK, error=True, times=1)
                try:
                    client.send_metrics(e, timeout=30.0, envelope=env)
                except InjectedFault:
                    # ack lost after the fold; retry the SAME seq — the
                    # global's window must suppress it (and still ack)
                    dup_forced += 1
                    client.send_metrics(e, timeout=30.0, envelope=env)
            t1 = time.time()
            while glob.packet_queue.qsize() and \
                    time.time() - t1 < FLUSH_WAIT:
                time.sleep(0.02)
            _flush_checked(glob, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            dt = time.perf_counter() - t0
        client.close()

        suppressed = glob._c_dup_suppressed.value()
        rejected = glob._c_envelope_rejected.value()
        flushed = {m.name: m.value for m in sink.flushed}
        counter_exact = all(
            flushed.get(f"merged.counter.{c}") ==
            sum(li + c for li in range(n_locals))
            for c in range(counters))
        p99_errs = []
        for h in range(histos):
            got = flushed.get(f"merged.timer.{h}.99percentile")
            exact = midpoint_quantile(all_histo_vals[h], 0.99)
            if got is not None and exact > 0:
                p99_errs.append(abs(got - exact) / exact)
        return {
            "config": 9, "name": "duplicate_storm_30pct_ack_loss",
            "forwarded_metrics_per_sec": round(n_metrics / dt, 1),
            "n_locals": n_locals, "metrics_forwarded": n_metrics,
            "dup_forced": int(dup_forced),
            "dup_suppressed": int(suppressed),
            "dup_accounting_exact": suppressed == float(dup_forced)
            and dup_forced > 0,
            "envelope_rejected": int(rejected),
            "counters_exact": bool(counter_exact),
            "merged_p99_err_mean": round(float(np.mean(_acc(
                p99_errs, "merged p99", flushed_keys=len(flushed)))), 5),
            "merged_p99_err_max": round(float(np.max(p99_errs)), 5),
            "wall_seconds": round(dt, 3),
        }
    finally:
        FAULTS.reset()
        glob.shutdown()


# -- config 10: native wire→flush firehose — in-engine admission --------------

def config10_wire_to_flush_firehose(scale=1.0):
    """Loopback UDP firehose through the NATIVE ingest path end-to-end:
    C++ recvmmsg readers → in-engine admission (config 8's guarantees
    pushed into the reader ring) → datagram ring → pump parse/stage →
    zero-copy packed emit → donated-state device step → flush. The
    senders deliberately outrun the pump so the ring saturates and the
    overload controller drives the C++ admission into shedding; the
    acceptance identity is EXACT: every under-limit datagram the senders
    put on the wire is counted exactly once as admitted or shed by the
    reader (ring-full drops are post-admission and accounted
    separately). Senders bound their in-flight window against the
    reader's received-datagram counter so the kernel socket buffer — the
    one lossy hop the identity cannot see — never overflows. The on-chip
    throughput gate (≥5M samples/sec/host through the pump) arms on TPU
    only; CPU smoke checks the accounting + shedding behavior.

    Round 14: the firehose rides the MULTI-RING engine (reader_rings=4,
    README §Host feed architecture) — four SO_REUSEPORT sockets, one
    ring + parse worker each, per-ring admission with the rate split in
    C++. The admitted/shed identity is asserted with every term drained
    from EVERY ring (srv._sync_native_admission folds all rings), plus a
    cross-ring fold check that the aggregate reader counters equal the
    per-ring sums. The ≥20M samples/sec/host gate arms on a TPU host
    with the cores to feed four rings; the 1-core CPU CI box records the
    rate and the exactness booleans only (cpu_smoke stays green)."""
    import jax

    from veneur_tpu import native as native_mod
    from veneur_tpu.reliability.overload import SHEDDING
    from veneur_tpu.sinks.blackhole import BlackholeMetricSink

    if not native_mod.available():
        return {"config": 10, "name": "wire_to_flush_firehose",
                "skipped": "native ingest engine unavailable"}

    low_names = 512
    high_names = 64
    lines_per = 100            # ~2KB datagrams, under metric_max_length
    # must out-fill the 64k-datagram ring to force shedding; scale only
    # grows the storm, the floor is the ring + margin
    datagrams = max(100_000, int(400_000 * scale))
    n_senders = 4
    window = 512               # in-flight datagrams vs the reader counter

    # counter-firehose sizing: big counter lanes, everything else small —
    # at the server defaults the periodic compact step spends seconds
    # compacting 16k EMPTY t-digests on a CPU host, which would measure
    # the idle histogram table instead of the feed path under test
    srv = _mk_server(
        [BlackholeMetricSink()], udp=True, num_readers=2,
        reader_rings=4,
        overload_enabled=True, overload_poll_interval_s=0.05,
        overload_hold_s=0.5,
        shed_priority_tags=["veneur.priority:high"],
        tpu_counter_capacity=1 << 14, tpu_batch_counter=1 << 16,
        tpu_gauge_capacity=1 << 10, tpu_status_capacity=64,
        tpu_set_capacity=256, tpu_histo_capacity=256,
        tpu_batch_gauge=256, tpu_batch_status=64, tpu_batch_set=256,
        tpu_batch_histo=256)
    try:
        if not srv._native_readers_active:
            return {"config": 10, "name": "wire_to_flush_firehose",
                    "skipped": "native readers did not start"}
        ov = srv._overload
        addr = srv.local_addr()
        rng = np.random.default_rng(7)

        def rc():
            return srv.aggregator.reader_counters()

        # pre-built traffic: 10% high-priority datagrams (every line
        # tagged — classification is per datagram), 90% low
        high_pool = []
        for i in range(8):
            ns = rng.integers(0, high_names, lines_per)
            high_pool.append(b"\n".join(
                b"storm.h%d:1|c|#veneur.priority:high" % n for n in ns))
        low_pool = []
        for i in range(64):
            ns = rng.integers(0, low_names, lines_per)
            low_pool.append(b"\n".join(
                b"storm.l%d:1|c" % n for n in ns))
        payloads = []
        sent = {"high": 0, "low": 0}
        for i in range(datagrams):
            if i % 10 == 0:
                payloads.append(high_pool[(i // 10) % len(high_pool)])
                sent["high"] += 1
            else:
                payloads.append(low_pool[i % len(low_pool)])
                sent["low"] += 1

        # warm: every storm name through the real wire path once, then a
        # flush so the ingest + flush compiles land at the storm's true
        # size buckets, all before t0
        phase("warm")
        warm_tx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            base = srv.aggregator.processed
            warm_lines = 0
            for lo in range(0, low_names, lines_per):
                ns = range(lo, min(lo + lines_per, low_names))
                warm_tx.sendto(b"\n".join(
                    b"storm.l%d:1|c" % n for n in ns), addr)
                warm_lines += min(lines_per, low_names - lo)
            warm_tx.sendto(b"\n".join(
                b"storm.h%d:1|c|#veneur.priority:high" % n
                for n in range(high_names)), addr)
            warm_lines += high_names
        finally:
            warm_tx.close()
        deadline = time.time() + WARM_TIMEOUT
        while srv.aggregator.processed < base + warm_lines \
                and time.time() < deadline:
            time.sleep(0.02)
        if srv.aggregator.processed < base + warm_lines:
            raise RuntimeError("warm feed did not drain through the "
                               "native path")
        _flush_checked(srv, timeout=WARM_TIMEOUT)

        # quiesce, fold any outstanding C++ admission counts into the
        # controller, then snapshot — the storm deltas below must start
        # from a drained engine
        srv._sync_native_admission(ov)
        rc0 = rc()
        adm0 = dict(ov.admitted)
        shed0 = dict(ov.shed)
        proc0 = srv.aggregator.processed
        send_errors = []
        sent_lock = threading.Lock()
        sent_n = [0]

        def send_slice(idx):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            try:
                k = 0
                for p in payloads[idx::n_senders]:
                    s.sendto(p, addr)
                    with sent_lock:
                        sent_n[0] += 1
                        mine = sent_n[0]
                    k += 1
                    if k % 64 == 0:
                        # bounded in-flight: the reader consumes (shed or
                        # ring) far faster than Python sends, so this
                        # almost never spins — it exists so the kernel
                        # rcvbuf can NEVER overflow and break exactness
                        while mine - rc()["datagrams"] + rc0["datagrams"] \
                                > window:
                            time.sleep(0.0005)
            except OSError as e:
                send_errors.append(e)
            finally:
                s.close()

        phase("firehose")
        t0 = time.perf_counter()
        t_storm = time.monotonic()
        threads = [threading.Thread(target=send_slice, args=(i,))
                   for i in range(n_senders)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if send_errors:
            raise RuntimeError(f"sender failed: {send_errors[0]}")

        phase("drain")
        deadline = time.time() + DRAIN_TIMEOUT
        while rc()["datagrams"] - rc0["datagrams"] < len(payloads) \
                and time.time() < deadline:
            time.sleep(0.01)
        last = -1
        while time.time() < deadline:
            cur = srv.aggregator.processed
            if rc()["ring_depth"] == 0 and cur == last:
                break
            last = cur
            time.sleep(0.05)
        dt = time.perf_counter() - t0
        # final fold so the accounting below sees every C++ decision
        srv._sync_native_admission(ov)
        rc1 = rc()

        phase("flush")
        _flush_checked(srv, timeout=WARM_TIMEOUT)

        received = rc1["datagrams"] - rc0["datagrams"]
        toolong_d = rc1["toolong"] - rc0["toolong"]
        adm_d = {k: v - adm0.get(k, 0) for k, v in ov.admitted.items()}
        shed_d = {k: v - shed0.get(k, 0) for k, v in ov.shed.items()}
        shed_d.pop("flush", None)
        # the identity covers the firehose's classes; "self" carries the
        # server's own telemetry loop-back and is admission-exempt anyway
        adm_hl = adm_d.get("high", 0) + adm_d.get("low", 0)
        shed_hl = shed_d.get("high", 0) + shed_d.get("low", 0)
        processed = srv.aggregator.processed - proc0
        peak = max((to for ts, _f, to in ov.transitions if ts >= t_storm),
                   default=ov.state)
        sps = processed / dt
        on_tpu = jax.default_backend() == "tpu"
        # cross-ring fold exactness: the aggregate reader counters the
        # identity above used must equal the per-ring sums — a ring the
        # aggregate silently skipped would pass the identity by luck on
        # an idle ring and lose counts on a busy one
        eng = getattr(srv.aggregator, "eng", None)
        n_rings = eng.n_rings if eng is not None else 0
        per_ring_datagrams = []
        fold_exact = None
        if n_rings:
            dsum = tsum = 0
            for r in range(n_rings):
                c = eng.ring_counters_one(r)
                per_ring_datagrams.append(int(c["datagrams"]))
                dsum += c["datagrams"]
                tsum += c["toolong"]
            fold_exact = (dsum == rc1["datagrams"]
                          and tsum == rc1["toolong"])
        host_cores = len(os.sched_getaffinity(0))
        gate20_armed = on_tpu and host_cores >= 5
        return {
            "config": 10, "name": "wire_to_flush_firehose",
            "datagrams_sent": len(payloads),
            "lines_per_datagram": lines_per,
            "sent": sent,
            "datagrams_received": int(received),
            "no_kernel_drops": received == len(payloads),
            "toolong": int(toolong_d),
            "admitted": adm_d, "shed": shed_d,
            "accounting_exact": (adm_hl + shed_hl == len(payloads)
                                 and toolong_d == 0),
            "shed_active": shed_d.get("low", 0) > 0,
            "peak_state": int(peak),
            "reached_shedding": peak >= SHEDDING,
            "ring_dropped": int(rc1["ring_dropped"]
                                - rc0["ring_dropped"]),
            "samples_processed": int(processed),
            "samples_per_sec": round(sps, 1),
            "on_chip_gate_5m_armed": on_tpu,
            "samples_per_sec_ge_5m": (sps >= 5e6) if on_tpu else None,
            "n_rings": int(n_rings),
            "host_cores": host_cores,
            "per_ring_datagrams": per_ring_datagrams,
            "cross_ring_fold_exact": fold_exact,
            "host_gate_20m_armed": gate20_armed,
            "samples_per_sec_ge_20m": (sps >= 20e6) if gate20_armed
            else None,
            "wall_seconds": round(dt, 3),
        }
    finally:
        srv.shutdown()


# -- config 11: collective 64→8-device merge — zero-serialization -------------

def config11_collective_merge(scale=1.0):
    """Config4's 64→1 merge rerun over the collective mesh tier: the 64
    locals hand their raw device batches straight to a co-located
    CollectiveGlobalTier (collective/tier.py) — hash-routed all_to_all
    placement, replica merge on device — instead of serializing
    MetricLists over loopback gRPC. Same rng seed and load shape as
    config4 so the rows are directly comparable: counters must stay
    exact, merged p99 must sit at config4's digest error (bench.py
    cross-checks the two rows), and the wire path must carry ZERO bytes
    (the global has no gRPC listener; imported_total must not move).
    The linear-scaling gate — absorb+merge rate holds a per-device floor
    as the mesh grows — arms on TPU only: forced host 'devices' on the
    CPU smoke share one socket, so CPU checks routing + accuracy."""
    import jax

    from veneur_tpu.aggregation.host import BatchSpec
    from veneur_tpu.aggregation.state import TableSpec
    from veneur_tpu.collective import tier as collective_tier
    from veneur_tpu.samplers.parser import parse_metric
    from veneur_tpu.server.aggregator import Aggregator
    from veneur_tpu.sinks.debug import DebugMetricSink

    n_locals = 64
    counters = max(8, int(200 * scale))
    histos = max(4, int(50 * scale))
    histo_samples = 20
    rng = np.random.default_rng(4)      # config4's seed: same oracle

    n_dev = len(jax.devices())
    n_replicas = 2 if n_dev >= 2 else 1
    n_shards = max(1, n_dev // n_replicas)

    spec = TableSpec(counter_capacity=1 << 10, gauge_capacity=64,
                     status_capacity=16, set_capacity=16,
                     histo_capacity=1 << 8)
    bspec = BatchSpec(counter=2048, gauge=64, status=16, set=64, histo=2048)

    all_histo_vals = {h: [] for h in range(histos)}
    raws = []
    for li in range(n_locals):
        agg = Aggregator(spec, bspec)
        for c in range(counters):
            m = parse_metric(
                b"merged.counter.%d:%d|c|#veneurglobalonly" % (c, li + c))
            agg.process_metric(m)
        for h in range(histos):
            vals = rng.lognormal(2.0, 0.8, histo_samples)
            all_histo_vals[h].extend(vals.tolist())
            for v in vals:
                agg.process_metric(
                    parse_metric(b"merged.timer.%d:%.4f|ms" % (h, v)))
        # keep the RAW flush (device batches + key table), never
        # export_metrics: the absorb below is the zero-serialization path
        _, table, raw = agg.flush([0.5], want_raw=True)
        raws.append((raw, table))

    sink = DebugMetricSink()
    glob = _mk_server([sink], collective_enabled=True,
                      collective_group="bench11",
                      tpu_n_replicas=n_replicas, tpu_n_shards=n_shards,
                      tpu_counter_capacity=1 << 12,
                      tpu_histo_capacity=1 << 9)
    try:
        _warm(glob, [b"warm.c:1|c", b"warm.t:1.0|ms"], sinks=[sink])
        tier = collective_tier.lookup("bench11")
        if tier is None:
            raise RuntimeError("collective group 'bench11' not registered")
        # one participant id per local, held across cycles — exactly what
        # a co-located Server._absorb_colocated does on its first absorb
        parts = [tier.assign_participant() for _ in range(n_locals)]
        for cycle in range(2):   # first cycle compiles the size bucket
            phase(f"cycle{cycle}")
            sink.flushed.clear()
            t0 = time.perf_counter()
            absorbed = 0
            for p, (raw, table) in zip(parts, raws):
                absorbed += tier.absorb_raw(raw, table, participant=p)
            absorb_dt = time.perf_counter() - t0
            _flush_checked(glob, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            dt = time.perf_counter() - t0

        flushed = {m.name: m.value for m in sink.flushed}
        counter_exact = all(
            flushed.get(f"merged.counter.{c}") ==
            sum(li + c for li in range(n_locals))
            for c in range(counters))
        p99_errs = []
        for h in range(histos):
            got = flushed.get(f"merged.timer.{h}.99percentile")
            exact = midpoint_quantile(all_histo_vals[h], 0.99)
            if got is not None and exact > 0:
                p99_errs.append(abs(got - exact) / exact)
        rate = absorbed / absorb_dt if absorb_dt > 0 else 0.0
        on_tpu = jax.default_backend() == "tpu"
        # linear scaling ⇔ aggregate absorb+route rate holds a per-device
        # floor as devices grow; 100k merged rows/s/device is config4's
        # single-global sustained-absorb bar with decode removed, split
        # across the mesh with headroom for the all_to_all hop
        per_dev_floor = 100_000.0
        return {
            "config": 11, "name": "collective_merge_64to8dev",
            "devices": n_dev,
            "mesh_replicas": n_replicas, "mesh_shards": n_shards,
            "n_locals": n_locals,
            "metrics_forwarded": int(absorbed),   # rows, config4's unit
            "absorbed_rows": int(absorbed),
            "absorbed_rows_per_sec": round(rate, 1),
            "serialized_forward_bytes": 0,
            "wire_imports": int(glob.imported_total),
            "zero_serialization": glob.imported_total == 0,
            "counters_exact": bool(counter_exact),
            "merged_p99_err_mean": round(float(np.mean(_acc(
                p99_errs, "merged p99", flushed_keys=len(flushed)))), 5),
            "merged_p99_err_max": round(float(np.max(p99_errs)), 5),
            "on_chip_gate_linear_scaling_armed": on_tpu,
            "rows_per_sec_per_device_ge_floor":
                (rate / n_dev >= per_dev_floor) if on_tpu else None,
            "wall_seconds": round(dt, 3),
        }
    finally:
        glob.shutdown()


def config12_elastic_resize(scale=1.0):
    """Elastic live resharding under fire (README §Elasticity): resize
    the mesh 4→8→2 while producers keep feeding and the query tier keeps
    answering. Three passes over the SAME seeded storm: a static 4-shard
    reference, an elastic pass with a forced receiver crash mid-transfer
    (cycle 0 — absorbs the resize-path compiles AND proves epoch-replay
    recovery), and a steady-state elastic pass whose swap-to-done
    transition times gate the one-flush-interval bound. Acceptance, all
    booleans: final counters byte-exact vs static (timers 1e-6), every
    packet accounted (sent == admitted + shed, exact), the crash pass
    recovers with replays counted and duplicates suppressed (no
    double-count — exactness is the proof), queries stay 200 throughout,
    and the steady transitions fit one production flush interval. The
    two wall-clock gates — transition bound and query-200 — arm on TPU
    only: on the CPU smoke the resize's compute_flush pays fresh XLA
    size-bucket compiles (tens of seconds) inside the measured window,
    which stalls the pipeline past the query snapshot deadline too; both
    raw measurements are reported either way."""
    import json as _json
    import urllib.error
    import urllib.request

    import jax

    from veneur_tpu.reliability.faults import FAULTS, RESHARD_FOLD
    from veneur_tpu.sinks.debug import DebugMetricSink

    n_counter = max(64, int(2048 * scale))
    n_timer = max(32, int(512 * scale))
    n_set_names = max(8, int(64 * scale))
    set_members = 40
    interval_s = 10.0     # the production flush cadence the bound gates

    caps = dict(tpu_counter_capacity=1 << 13, tpu_gauge_capacity=256,
                tpu_set_capacity=1 << 10, tpu_histo_capacity=1 << 10,
                tpu_batch_counter=1 << 13, tpu_batch_histo=1 << 13,
                tpu_batch_set=1 << 12)

    def build_segment(seg):
        rng = np.random.default_rng(1200 + seg)
        per, payloads, lines = 100, [], []

        def put(ln):
            lines.append(ln)
            if len(lines) >= per:
                payloads.append(b"\n".join(lines))
                del lines[:]

        for i in range(n_counter):
            put(b"el.c%d:%d|c" % (i, 10007 + 3 * i + seg))
        put(b"el.g:%d|g" % (10 + seg))
        for v in rng.integers(1, 100000, n_timer):
            put(b"el.t:%d|ms" % v)
        for s in range(n_set_names):
            for j in range(set_members):
                put(b"el.s%d:m%d-%d|s" % (s, seg, j))
        if lines:
            payloads.append(b"\n".join(lines))
        samples = n_counter + 1 + n_timer + n_set_names * set_members
        return payloads, samples

    segments = [build_segment(s) for s in range(3)]

    def run_pass(elastic, crash=False, tag=""):
        sink = DebugMetricSink()
        srv = _mk_server([sink], native_ingest=False, tpu_n_shards=4,
                         overload_enabled=True,
                         http_address="127.0.0.1:0", query_enabled=True,
                         reshard_enabled=elastic,
                         reshard_transfer_timeout_s=WARM_TIMEOUT, **caps)
        summaries, q_codes, q_stale = [], [], 0
        try:
            _warm(srv, [b"el.c0:0|c", b"el.t:1|ms", b"el.s0:w|s"],
                  sinks=[sink])
            ov = srv._overload
            adm0, shed0 = dict(ov.admitted), dict(ov.shed)
            sent_pkts = 0
            port = srv.http_port
            q_stop = threading.Event()

            def poll_queries():
                nonlocal q_stale
                body = _json.dumps({"name": "el.c0"}).encode()
                while not q_stop.is_set():
                    req = urllib.request.Request(
                        f"http://127.0.0.1:{port}/query", data=body,
                        headers={"Content-Type": "application/json"})
                    try:
                        with urllib.request.urlopen(req, timeout=30) as r:
                            q_codes.append(r.status)
                            if _json.loads(r.read()).get("stale_bounded"):
                                q_stale += 1
                    except urllib.error.HTTPError as e:
                        q_codes.append(e.code)
                    except OSError:
                        q_codes.append(-1)   # transport-level failure
                    q_stop.wait(0.1)

            poller = threading.Thread(target=poll_queries, daemon=True)
            poller.start()
            processed0 = srv.aggregator.processed
            want = processed0
            for seg, (payloads, samples) in enumerate(segments):
                # the resize runs while this segment's packets are still
                # landing: the feeder thread races the swap + transfer
                feeder = threading.Thread(
                    target=_feed_queue, args=(srv, payloads), daemon=True)
                feeder.start()
                sent_pkts += len(payloads)
                if elastic and seg < 2:
                    if crash and seg == 1:
                        FAULTS.arm(RESHARD_FOLD, error=True, times=1)
                    phase(f"resize{tag}_{seg}")
                    summaries.append(srv.trigger_reshard(
                        (8, 2)[seg], timeout=WARM_TIMEOUT))
                feeder.join()
                want += samples
                _drain(srv, want)
            phase(f"final_flush{tag}")
            _flush_checked(srv, timeout=WARM_TIMEOUT)
            q_stop.set()
            poller.join()
            adm = sum(ov.admitted.values()) - sum(adm0.values())
            shed_d = {k: v - shed0.get(k, 0) for k, v in ov.shed.items()}
            shed_d.pop("flush", None)
            shed = sum(shed_d.values())
            rows = {m.name: m.value for m in sink.flushed
                    if not m.name.startswith(("veneur.", "ssf.", "warm."))}
            return {
                "rows": rows, "summaries": summaries,
                "accounting_exact": adm + shed == sent_pkts,
                "shed": shed,
                "query_codes": q_codes, "query_stale": q_stale,
            }
        finally:
            FAULTS.reset()
            srv.shutdown()

    def rows_equal(ref, got):
        if set(ref) != set(got):
            return False
        for name, want in ref.items():
            if ".t." in name and "percentile" in name:
                if abs(got[name] - want) > 1e-6 * max(1.0, abs(want)):
                    return False
            elif got[name] != want:
                return False
        return True

    phase("static_reference")
    static = run_pass(elastic=False, tag="_static")

    phase("elastic_crash")       # cycle 0: compiles + crash recovery
    crashed = run_pass(elastic=True, crash=True, tag="_crash")

    phase("elastic_steady")      # cycle 1: timed transitions
    steady = run_pass(elastic=True, tag="_steady")

    crash_sums = crashed["summaries"]
    steady_sums = steady["summaries"]
    transitions = [s["duration_ns"] / 1e9 for s in steady_sums]
    all_q = static["query_codes"] + crashed["query_codes"] \
        + steady["query_codes"]
    non200 = sum(1 for c in all_q if c != 200)
    moved = sum(s["rows_moved"] for s in steady_sums)
    on_tpu = jax.default_backend() == "tpu"
    return {
        "config": 12, "name": "elastic_resize",
        "resize_plan": [s["plan"] for s in steady_sums],
        "storm_samples": 3 * segments[0][1],
        "rows_flushed": len(static["rows"]),
        "rows_moved": int(moved),
        "moved_any": moved > 0,
        "steady_byte_exact": rows_equal(static["rows"], steady["rows"]),
        "crash_byte_exact": rows_equal(static["rows"], crashed["rows"]),
        "accounting_exact": bool(static["accounting_exact"]
                                 and crashed["accounting_exact"]
                                 and steady["accounting_exact"]),
        "shed_packets": static["shed"] + crashed["shed"] + steady["shed"],
        "crash_replayed": crash_sums[1]["replays"] >= 1,
        "crash_dup_suppressed": crash_sums[1]["dup_suppressed"] >= 1,
        "crash_recovered": not any(s["failed"] for s in crash_sums),
        "query_probes": len(all_q),
        "query_non200_probes": non200,
        "query_stale_bounded_observed": crashed["query_stale"]
        + steady["query_stale"],
        "transition_seconds": [round(t, 3) for t in transitions],
        "on_chip_gate_transition_armed": on_tpu,
        "query_all_200": (bool(all_q) and non200 == 0) if on_tpu
        else None,
        "transition_within_interval": (bool(transitions)
                                       and max(transitions) <= interval_s)
        if on_tpu else None,
    }


# -- config 13: standing-watch storm -----------------------------------------

def config13_watch_storm(scale=1.0):
    """100k standing monitors as one fused device evaluation (README
    §Watches): replay config4's EXACT global-merge load (same seed,
    same caps, same loopback-gRPC forward path) into a watch-enabled
    global, register >=100k watches over the merged population — the
    fleet size does NOT scale down; the tentpole claim IS the fleet —
    and prove the alerting tier rides the flush for free. Always-on
    gates: every watch evaluated every interval by ONE appended device
    launch (launches == intervals, no per-watch dispatches); fired /
    suppressed / notify-dropped reconcile EXACTLY against closed-form
    expected counts (the breach pattern is deterministic by
    construction); at-least-once delivery accounting over a
    deliberately stalled SSE subscriber (received + dropped ==
    transitions, exact); registrations + firing state byte-exact
    across a snapshot/restore round trip into a second server; and
    flush p99 with the fleet armed inside the watches-off band
    measured on the SAME server minutes earlier (bench.py adds the
    cross-config gate vs config4's flush_p99_seconds). The
    notification-latency gate — p99 of flush-return to
    transitions-published < one production interval — arms on TPU
    only: the CPU smoke's first packed evaluation pays an XLA compile
    that would gate compiler wall time, not the tier (the absorb
    cycle's wall is still reported)."""
    import json as _json
    import urllib.request

    import jax

    from veneur_tpu.aggregation.host import BatchSpec
    from veneur_tpu.aggregation.state import TableSpec
    from veneur_tpu.forward.convert import export_metrics
    from veneur_tpu.forward.rpc import ForwardClient
    from veneur_tpu.samplers.parser import parse_metric
    from veneur_tpu.server.aggregator import Aggregator
    from veneur_tpu.sinks.debug import DebugMetricSink
    from veneur_tpu.watch.model import WATCH_KINDS

    n_locals = 64
    counters = max(8, int(200 * scale))
    histos = max(4, int(50 * scale))
    histo_samples = 20
    rng = np.random.default_rng(4)      # config4's seed: same oracle
    interval_s = 10.0    # production cadence the TPU notify gate bounds
    K_BASE = 3           # timed watches-off flushes (the in-run baseline)
    K_WATCH = 4          # watch intervals: absorb + 3 timed

    spec = TableSpec(counter_capacity=1 << 10, gauge_capacity=64,
                     status_capacity=16, set_capacity=16,
                     histo_capacity=1 << 8)
    bspec = BatchSpec(counter=2048, gauge=64, status=16, set=64, histo=2048)

    exports = []
    for li in range(n_locals):
        agg = Aggregator(spec, bspec)
        for c in range(counters):
            agg.process_metric(parse_metric(
                b"merged.counter.%d:%d|c|#veneurglobalonly" % (c, li + c)))
        for h in range(histos):
            for v in rng.lognormal(2.0, 0.8, histo_samples):
                agg.process_metric(
                    parse_metric(b"merged.timer.%d:%.4f|ms" % (h, v)))
        _, table, raw = agg.flush([0.5], want_raw=True)
        exports.append(export_metrics(raw, table, compression=spec.compression,
                                      hll_precision=spec.hll_precision))
    n_metrics = sum(len(e) for e in exports)

    # The monitor estate, shaped like a real one: many thresholds per
    # hot metric, deltas, tail-quantile watches, plus a band of
    # cardinality watches on a namespace that never reports (the
    # NO_DATA estate). Even indices breach — counter values are
    # sums of li+c (>= 2016 > 0.5), identical every interval so a
    # breaching watch fires EXACTLY once and then holds in ALERT
    # (suppressed, counted); odd indices sit at an unreachable 1e18.
    # Delta watches see exactly 0.0 from the second interval on
    # (identical replays), so their breach threshold is -1.0.
    n_watch = max(100_000, int(100_000 * scale))
    n_thr = int(n_watch * 0.60)
    n_delta = int(n_watch * 0.15)
    n_quant = int(n_watch * 0.20)
    n_card = n_watch - n_thr - n_delta - n_quant
    thr_b = (n_thr + 1) // 2
    delta_b = (n_delta + 1) // 2
    quant_b = (n_quant + 1) // 2

    sink = DebugMetricSink()
    glob = _mk_server([sink], grpc_address="127.0.0.1:0",
                      http_address="127.0.0.1:0",
                      tpu_counter_capacity=1 << 12,
                      tpu_histo_capacity=1 << 9,
                      watch_enabled=True,
                      watch_max_active=n_watch + 16)
    try:
        eng = glob.watch_engine
        _warm(glob, [b"warm.c:1|c", b"warm.t:1.0|ms"], sinks=[sink])
        client = ForwardClient(f"127.0.0.1:{glob.grpc_port}")

        def feed_interval(timeout=FLUSH_WAIT):
            """One full replay of the load, consumed end to end: the
            watch determinism above needs every interval identical, so
            wait on imported_total (exact), not just queue-empty."""
            want = glob.imported_total + n_metrics
            for e in exports:
                client.send_metrics(e, timeout=30.0)
            t1 = time.time()
            while glob.imported_total < want and time.time() - t1 < timeout:
                time.sleep(0.01)
            if glob.imported_total < want:
                raise RuntimeError(
                    "forward feed not absorbed: %d of %d imports after "
                    "%.0fs" % (glob.imported_total - want + n_metrics,
                               n_metrics, timeout))

        def wait_evaluated(target, timeout):
            t1 = time.time()
            done = lambda: (eng.intervals_evaluated
                            + eng.intervals_skipped) >= target
            while not done() and time.time() - t1 < timeout:
                time.sleep(0.005)
            if not done():
                raise RuntimeError(
                    "watch engine did not finish interval %d within "
                    "%.0fs" % (target, timeout))

        phase("compile_cycle")            # flush-program size buckets
        feed_interval(timeout=WARM_TIMEOUT)
        _flush_checked(glob, timeout=3 * WARM_TIMEOUT)

        flush_base = []
        for cycle in range(K_BASE):       # watches-off flush baseline
            phase(f"base_cycle{cycle}")
            feed_interval()
            tf = time.perf_counter()
            _flush_checked(glob)
            flush_base.append(time.perf_counter() - tf)

        phase("register")
        http_registered = 0

        def admit(body, via_http):
            nonlocal http_registered
            if via_http:                  # prove the public API path
                req = urllib.request.Request(
                    f"http://127.0.0.1:{glob.http_port}/watch",
                    data=_json.dumps(body).encode(), method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30.0) as resp:
                    if resp.status != 201:
                        raise RuntimeError(
                            f"POST /watch -> {resp.status}")
                http_registered += 1
            else:
                eng.register(body)

        t0 = time.perf_counter()
        for i in range(n_thr):
            admit({"kind": "threshold",
                   "name": f"merged.counter.{i % counters}", "op": ">",
                   "threshold": 0.5 if i % 2 == 0 else 1e18},
                  via_http=i == 0)
        for i in range(n_delta):
            admit({"kind": "delta",
                   "name": f"merged.counter.{i % counters}", "op": ">",
                   "threshold": -1.0 if i % 2 == 0 else 1e18},
                  via_http=i == 0)
        for i in range(n_quant):
            admit({"kind": "quantile", "quantile": 0.99,
                   "name": f"merged.timer.{i % histos}", "op": ">",
                   "threshold": 0.0 if i % 2 == 0 else 1e18},
                  via_http=i == 0)
        for i in range(n_card):
            admit({"kind": "cardinality", "prefix": f"w13.sets.{i}.",
                   "op": ">", "threshold": 0.5, "no_data_intervals": 2},
                  via_http=i == 0)
        reg_dt = time.perf_counter() - t0
        if eng.n_active != n_watch:
            raise RuntimeError(
                f"registered {eng.n_active} of {n_watch} watches")

        def kind_sum(counter):
            return sum(counter.value(kind=k) for k in WATCH_KINDS)

        ev0 = kind_sum(glob._c_watch_evaluated)
        f0 = kind_sum(glob._c_watch_fired)
        s0 = kind_sum(glob._c_watch_suppressed)
        d0 = kind_sum(glob._c_watch_notify_dropped)
        iv0, sk0, ln0 = (eng.intervals_evaluated, eng.intervals_skipped,
                         eng.launches_total)
        # a subscriber that never drains: its losses are the exact-drop
        # accounting under a transition storm
        sub = eng.hub.subscribe()
        if sub is None:
            raise RuntimeError("SSE subscribe refused below the cap")

        flush_watch, notify_lat = [], []
        for cycle in range(K_WATCH):
            phase(f"watch_cycle{cycle}")
            feed_interval(timeout=WARM_TIMEOUT if cycle == 0
                          else FLUSH_WAIT)
            tf = time.perf_counter()
            _flush_checked(glob, timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            flush_dt = time.perf_counter() - tf
            tn = time.perf_counter()
            wait_evaluated(iv0 + sk0 + cycle + 1,
                           timeout=WARM_TIMEOUT if cycle == 0
                           else FLUSH_WAIT)
            lat = time.perf_counter() - tn
            if cycle == 0:   # absorbs the packed-evaluation compile
                absorb_flush, absorb_lat = flush_dt, lat
            else:
                flush_watch.append(flush_dt)
                notify_lat.append(lat)

        received = 0
        while True:
            ev = sub.get(timeout=0.2)
            if ev is None:
                break
            received += 1
        eng.hub.unsubscribe(sub)

        evaluated = kind_sum(glob._c_watch_evaluated) - ev0
        fired = kind_sum(glob._c_watch_fired) - f0
        suppressed = kind_sum(glob._c_watch_suppressed) - s0
        dropped = kind_sum(glob._c_watch_notify_dropped) - d0
        intervals = eng.intervals_evaluated - iv0
        skipped = eng.intervals_skipped - sk0
        launches = eng.launches_total - ln0

        # closed-form expectations from the breach pattern: breaching
        # threshold/quantile watches fire on interval 1 then hold
        # (suppressed x3); breaching delta watches prime on interval 1,
        # fire on 2, hold (x2); every cardinality watch posts exactly
        # one NO_DATA transition on interval 2
        fired_exp = thr_b + quant_b + delta_b
        supp_exp = (thr_b + quant_b) * (K_WATCH - 1) \
            + delta_b * (K_WATCH - 2)
        events_exp = fired_exp + n_card
        exact = (evaluated == n_watch * K_WATCH
                 and fired == fired_exp and suppressed == supp_exp
                 and received + dropped == events_exp and skipped == 0)

        phase("checkpoint_roundtrip")
        blob1 = _json.dumps(eng.snapshot(), separators=(",", ":"))
        srv2 = _mk_server([DebugMetricSink()], watch_enabled=True,
                          watch_max_active=n_watch + 16,
                          tpu_counter_capacity=1 << 8,
                          tpu_histo_capacity=1 << 6)
        try:
            srv2.watch_engine.restore(_json.loads(blob1))
            blob2 = _json.dumps(srv2.watch_engine.snapshot(),
                                separators=(",", ":"))
        finally:
            srv2.shutdown()
        client.close()

        base_p99 = float(np.percentile(flush_base, 99))
        watch_p99 = float(np.percentile(flush_watch, 99))
        on_tpu = jax.default_backend() == "tpu"
        return {
            "config": 13, "name": "watch_storm",
            "n_watches": n_watch, "n_watches_http": http_registered,
            "watch_kinds": {"threshold": n_thr, "delta": n_delta,
                            "quantile": n_quant, "cardinality": n_card},
            "register_seconds": round(reg_dt, 3),
            "registrations_per_sec": round(n_watch / reg_dt, 1),
            "watch_intervals": int(intervals),
            "intervals_skipped": int(skipped),
            "device_launches": int(launches),
            "one_fused_launch_per_interval": bool(
                launches == intervals == K_WATCH and skipped == 0),
            "evaluations_per_interval": n_watch,
            "fired": int(fired), "suppressed": int(suppressed),
            "notify_received": int(received),
            "notify_dropped": int(dropped),
            "transitions_expected": int(events_exp),
            "accounting_exact": bool(exact),
            "watch_state_ckpt_byte_exact": bool(blob1 == blob2),
            "flush_seconds_baseline": [round(s, 3) for s in flush_base],
            "flush_seconds": [round(s, 3) for s in flush_watch],
            "flush_p99_seconds_baseline": round(base_p99, 3),
            "flush_p99_seconds": round(watch_p99, 3),
            "flush_p99_interference_free": bool(
                watch_p99 <= base_p99 * 1.5 + 0.5),
            "eval_absorb_seconds": round(absorb_lat, 3),
            "flush_absorb_seconds": round(absorb_flush, 3),
            "notify_latency_seconds": [round(s, 3) for s in notify_lat],
            "on_chip_gate_notify_armed": on_tpu,
            "notify_p99_within_interval": (
                bool(notify_lat)
                and float(np.percentile(notify_lat, 99)) <= interval_s)
            if on_tpu else None,
        }
    finally:
        glob.shutdown()


def config14_range_dashboard(scale=1.0):
    """The history tier under dashboard load (README §History): replay
    a deterministic per-interval load into a history-enabled server,
    flush K intervals, then hammer POST /query with a concurrent
    range-query storm while verifying three always-on gates. (1) BYTE
    EXACTNESS: the ring the flush program filled is byte-identical to
    re-writing the archived (table, result, raw) flush frames into a
    fresh ring via the standalone write/roll programs — so every range
    answer equals re-merging the archive — and the HTTP per-interval
    points match the closed-form per-interval sums. (2) ZERO FLUSH
    INTERFERENCE: flush p99 with the ring armed stays inside the
    history-off band measured on an identical server minutes earlier in
    the SAME process (bench.py adds the cross-config band vs config4).
    (3) HBM BUDGET: the production `for_table` derivation at K=90
    windows / 3 decimation tiers over the kernel benchmark's ~1M-key
    TableSpec is measured per kind and capped at 6 GiB — the analytic
    number IS the allocation (tests pin hbm_bytes == sum of device
    array nbytes), so the budget gate is exact without touching the
    chip. The range-query throughput gate arms on TPU only (standing
    constraint): the CPU smoke records qps/latency but a compile-bound
    first launch would gate XLA wall time, not the serving path."""
    import json as _json
    import urllib.request

    import jax

    from veneur_tpu.aggregation.state import TableSpec
    from veneur_tpu.history.spec import HistorySpec
    from veneur_tpu.history.writer import HistoryWriter
    from veneur_tpu.sinks.debug import DebugMetricSink

    counters = max(8, int(200 * scale))
    gauges = max(4, int(50 * scale))
    timers = max(4, int(50 * scale))
    sets = max(4, int(25 * scale))
    histo_samples = 10
    # The ring's tier-roll program compiles per roll SHAPE: 1 tier rolls
    # at seq 2, 2 at seq 4, 3 at seq 8 — so the timed window starts at
    # cycle 8, after every shape the steady state revisits has compiled
    # (cycle-1/3/7 walls would otherwise gate XLA, not the ring write).
    K_ABSORB = 8
    K_TIMED = 4
    K_TOT = K_ABSORB + K_TIMED
    interval_s = 600.0        # _mk_server's manual-flush interval
    rng = np.random.default_rng(14)

    def interval_lines(i):
        """Interval i's wire load. Counter key c receives ONE sample of
        c + i + 1, so its archived window value is closed-form — the
        HTTP range check below needs no replay to know the answer."""
        lines = []
        for c in range(counters):
            lines.append(b"c14.counter.%d:%d|c" % (c, c + i + 1))
        for g in range(gauges):
            lines.append(b"c14.gauge.%d:%d|g" % (g, 10 * i + g))
        for h in range(timers):
            for v in rng.lognormal(2.0, 0.8, histo_samples):
                lines.append(b"c14.timer.%d:%.4f|ms" % (h, v))
        for s in range(sets):
            lines.append(b"c14.set.%d:m%d|s" % (s, i))
        lines.append(b"c14.marker.%d:1|c" % i)
        return lines

    def post_query(srv, body, timeout=30.0):
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.http_port}/query",
            data=_json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return _json.loads(resp.read())

    def feed_interval(srv, i, timeout=FLUSH_WAIT):
        """Feed interval i and wait for the trailing MARKER key to
        answer a live instant query. The pipeline queue is FIFO, so a
        staged marker proves the whole interval is staged — the
        cumulative `processed` counter can't (flush intermetrics ride
        the same pipeline and inflate it)."""
        _feed_queue(srv, interval_lines(i))
        t1 = time.time()
        probe = {"queries": [{"name": f"c14.marker.{i}",
                              "kinds": ["counter"]}]}
        while time.time() - t1 < timeout:
            out = post_query(srv, probe)
            if out["results"][0]["matches"]:
                return
            time.sleep(0.02)
        raise RuntimeError(f"interval {i} marker never staged "
                           f"within {timeout:.0f}s")

    srv_kw = dict(http_address="127.0.0.1:0", query_enabled=True,
                  tpu_counter_capacity=1 << 12,
                  tpu_histo_capacity=1 << 9)

    # -- phase A: history-OFF flush baseline (the interference oracle) --
    phase("baseline_server")
    base = _mk_server([DebugMetricSink()], **srv_kw)
    flush_base = []
    try:
        _warm(base, [b"warm.c:1|c", b"warm.t:1.0|ms"])
        rng = np.random.default_rng(14)   # identical timer draws
        for i in range(K_TOT):
            phase(f"base_cycle{i}")
            feed_interval(base, i, timeout=WARM_TIMEOUT if i == 0
                          else FLUSH_WAIT)
            tf = time.perf_counter()
            _flush_checked(base, timeout=WARM_TIMEOUT if i == 0
                           else FLUSH_WAIT)
            dt = time.perf_counter() - tf
            if i >= K_ABSORB:             # early cycles absorb compiles
                flush_base.append(dt)
    finally:
        base.shutdown()

    # -- phase B: history-ON, frames archived for the replay oracle ----
    phase("history_server")
    glob = _mk_server([DebugMetricSink()], history_enabled=True,
                      **srv_kw)
    try:
        frames = []
        orig = glob.aggregator.compute_flush

        def archiving(state, table, percentiles, want_raw=False,
                      history=None):
            out = orig(state, table, percentiles, want_raw=True,
                       history=history)
            result, tbl, raw = out
            frames.append((tbl,
                           {k: np.copy(v) for k, v in result.items()},
                           {k: np.copy(v) for k, v in raw.items()}))
            return out if want_raw else (result, tbl)

        glob.aggregator.compute_flush = archiving
        _warm(glob, [b"warm.c:1|c", b"warm.t:1.0|ms"])
        rng = np.random.default_rng(14)   # identical timer draws
        flush_hist = []
        for i in range(K_TOT):
            phase(f"hist_cycle{i}")
            feed_interval(glob, i, timeout=WARM_TIMEOUT if i == 0
                          else FLUSH_WAIT)
            tf = time.perf_counter()
            _flush_checked(glob, timeout=WARM_TIMEOUT if i == 0
                           else FLUSH_WAIT)
            dt = time.perf_counter() - tf
            if i >= K_ABSORB:
                flush_hist.append(dt)
        if glob.history.seq != K_TOT:
            raise RuntimeError(
                f"ring advanced {glob.history.seq} of {K_TOT} windows")

        # gate 1a: ring bytes == replaying the archived frames
        phase("replay_oracle")
        wr = HistoryWriter(glob.history.spec,
                           interval_s=glob.history.interval_s)
        for tbl, result, raw in frames:
            wr.record_frame(tbl, result, raw)
        sa, sb = glob.history.snapshot(), wr.snapshot()
        byte_exact = (sa["meta"]["seq"] == sb["meta"]["seq"]
                      and sa["meta"]["keys"] == sb["meta"]["keys"])
        for name in sa["arrays"]:
            byte_exact = byte_exact and bool(np.array_equal(
                sa["arrays"][name], sb["arrays"][name], equal_nan=True))

        # gate 1b: HTTP per-interval points match the closed form
        def range_ok(c):
            out = post_query(glob, {"queries": [
                {"name": f"c14.counter.{c}",
                 "range": int(K_TOT * interval_s),
                 "step": int(interval_s)}]})
            pts = out["results"][0]["matches"][0]["points"]
            want = [float(c + i + 1) for i in range(K_TOT)]
            return ([p["value"] for p in pts] == want
                    and all(p["complete"] for p in pts))

        values_exact = all(range_ok(c) for c in (0, counters - 1))

        # -- concurrent range-query storm over live HTTP ---------------
        phase("range_storm")
        n_threads = max(2, min(8, int(8 * scale)))
        per_thread = max(10, int(100 * scale))
        errors = []
        lat = []
        lat_lock = threading.Lock()
        ln0 = glob.query_engine.launches_total

        def storm(t):
            try:
                for j in range(per_thread):
                    c = (t * per_thread + j) % counters
                    body = {"queries": [
                        {"name": f"c14.counter.{c}",
                         "range": int(K_TOT * interval_s),
                         "step": int(interval_s)},
                        {"name": f"c14.gauge.{c % gauges}",
                         "range": int(K_TOT * interval_s)},
                        {"name": f"c14.counter.{c}",
                         "kinds": ["counter"]},      # instant, same launch
                    ]}
                    tq = time.perf_counter()
                    out = post_query(glob, body)
                    dt = time.perf_counter() - tq
                    pts = out["results"][0]["matches"][0]["points"]
                    if len(pts) != K_TOT or not all(
                            p["complete"] for p in pts):
                        raise RuntimeError(
                            f"storm range answer malformed for key {c}: "
                            f"{len(pts)} points")
                    with lat_lock:
                        lat.append(dt)
            except Exception as e:
                errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=storm, args=(t,))
                   for t in range(n_threads)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        storm_dt = time.perf_counter() - t0
        n_queries = n_threads * per_thread
        launches = glob.query_engine.launches_total - ln0
        qps = n_queries / storm_dt if storm_dt > 0 else 0.0

        ring_bytes_live = glob.history.spec.hbm_bytes()
    finally:
        glob.shutdown()

    # -- gate 3: K=90 @ ~1M keys HBM budget (analytic == allocated) ----
    kernel_1m = TableSpec(counter_capacity=1 << 19,
                          gauge_capacity=1 << 18,
                          status_capacity=1 << 10,
                          set_capacity=1 << 14,
                          histo_capacity=1 << 17)
    h90 = HistorySpec.for_table(kernel_1m, windows=90, tiers=3,
                                max_keys=1 << 20)
    w = h90.total_cols
    hbm_cap = 6 * (1 << 30)
    hbm_by_kind = {
        "counter": h90.counter_rows * w * 2 * 4,
        "gauge": h90.gauge_rows * w * 4,
        "status": h90.status_rows * w * 4,
        "set": h90.set_rows * w * h90.hll_words * 4,
        "histo": h90.histo_rows * w * (2 * h90.centroids + 6) * 4,
    }

    base_p99 = float(np.percentile(flush_base, 99))
    hist_p99 = float(np.percentile(flush_hist, 99))
    on_tpu = jax.default_backend() == "tpu"
    return {
        "config": 14, "name": "range_dashboard",
        "intervals": K_TOT,
        "ring_windows": 90, "ring_tiers": 3,
        "range_byte_exact": bool(byte_exact),
        "range_values_exact": bool(values_exact),
        "storm_threads": n_threads,
        "storm_queries": n_queries,
        "storm_errors": errors[:5],
        "storm_ok": not errors,
        "range_queries_per_sec": round(qps, 1),
        "range_query_p99_ms": round(
            float(np.percentile(lat, 99)) * 1e3, 2) if lat else None,
        "device_launches": int(launches),
        "flush_seconds_baseline": [round(s, 3) for s in flush_base],
        "flush_seconds": [round(s, 3) for s in flush_hist],
        "flush_p99_seconds_baseline": round(base_p99, 3),
        "flush_p99_seconds": round(hist_p99, 3),
        # same noise band as config13: CPU flush walls jitter ~2x run
        # to run; a per-window device write that actually interfered
        # would cost far more than the band
        "flush_p99_interference_free": bool(
            hist_p99 <= base_p99 * 1.5 + 0.5),
        "ring_hbm_bytes_live": int(ring_bytes_live),
        "hbm_k90_1m_bytes": int(h90.hbm_bytes()),
        "hbm_k90_1m_gib": round(h90.hbm_bytes() / (1 << 30), 3),
        "hbm_k90_1m_by_kind": {k: int(v) for k, v in
                               hbm_by_kind.items()},
        "hbm_cap_gib": round(hbm_cap / (1 << 30), 3),
        "hbm_gate_ok": bool(h90.hbm_bytes() <= hbm_cap),
        "gate_range_qps_armed": on_tpu,
        "gate_range_qps_ok": bool(qps >= 100.0) if on_tpu else None,
    }


# -- config 15: multi-tenant storm — fairness, quarantine, restart -----------

def config15_tenant_storm(scale=1.0):
    """Seeded production-replay tenant storm (README §Multi-tenancy).
    Two same-seed passes of identical traffic (steady + diurnal ramp +
    one tenant flash-crowding to ~5x its share), baseline vs fairness
    armed, then a tag explosion, a rolling restart mid-storm, and
    quarantine decay. Gates, all booleans: the byte streams are
    identical (seeded-reproducible); per-tenant sent == admitted + shed
    EXACTLY in both passes, folded across all rings, and across the
    restart; isolated tenants shed nothing in either pass and their
    p99 value error is unchanged vs baseline while the noisy tenant is
    throttled; /healthz stays 200 and /readyz flips/recovers on
    interval during the flash crowd; the runaway tenant demotes, K
    post-demotion rows count EXACTLY K, quarantine state survives the
    restart, and decay re-admits it."""
    import shutil
    import tempfile
    import urllib.error
    import urllib.request

    from benchmarks.replay import ReplayGenerator
    from veneur_tpu.reliability.overload import HEALTHY, SHEDDING
    from veneur_tpu.sinks.debug import DebugMetricSink

    NOISY = "acme"            # DEFAULT_TENANTS[0]: the flash-crowd tenant
    RUNAWAY = "crux"          # the tag-explosion tenant
    ISOLATED = ("blue", "dex", "default")
    seed = 150_150
    steady_n = max(2_000, int(10_000 * scale))
    diurnal_n = max(1_000, int(4_000 * scale))
    flash_n = max(4_000, int(20_000 * scale))
    post_n = max(1_000, int(3_000 * scale))
    interval_s = 2.0
    # above any legitimate tenant's steady key count (<= 512 names x 4
    # kinds) so only the explosion can demote
    q_max_keys = 3_500
    explode_n = q_max_keys + 1_500
    exact_k = 250

    cfg = dict(
        http_address="127.0.0.1:0", num_readers=1, reader_rings=2,
        tenant_enabled=True,
        # per-tenant burst = rate x mult = 0.3 x flash_n: the largest
        # isolated tenant sends ~0.1 x flash_n in the flash segment, so
        # its burst covers it outright at ANY injection speed, while the
        # noisy tenant's ~0.77 x flash_n cannot fit even with refill —
        # isolation is structural, not timing-dependent
        tenant_fair_rate=flash_n / 10.0, tenant_fair_burst_mult=3.0,
        tenant_quarantine_max_keys=q_max_keys,
        tenant_quarantine_decay=0.25,
        tenant_quarantine_readmit_frac=0.5,
        overload_enabled=True, overload_native_admission=True,
        overload_poll_interval_s=0.05, overload_hold_s=0.3,
        tpu_counter_capacity=1 << 14, tpu_batch_counter=1 << 14,
        tpu_histo_capacity=1 << 14, tpu_batch_histo=1 << 13,
        tpu_gauge_capacity=1 << 13, tpu_batch_gauge=1 << 12,
        tpu_set_capacity=1 << 12, tpu_batch_set=1 << 11)

    def _inject(srv, grams):
        """Lossless feed through the REAL admission choke point
        (ring_push), deterministic round-robin placement. A full ring
        answers INJECT_BACKPRESSURE — nothing counted — so the retry
        loop is exact; the depth check keeps the pacing coarse."""
        from veneur_tpu.native import INJECT_BACKPRESSURE
        eng = srv.aggregator.eng
        nr = max(1, eng.n_rings)
        counters = srv.aggregator.reader_counters
        for i, g in enumerate(grams):
            while eng.rings_inject(i % nr, g) == INJECT_BACKPRESSURE:
                time.sleep(0.002)
            if (i & 0xFFF) == 0xFFF and counters()["ring_depth"] > 32_000:
                while counters()["ring_depth"] > 8_000:
                    time.sleep(0.005)

    def _settle(srv, timeout=DRAIN_TIMEOUT):
        """Wait until the rings are empty and parse counts stop moving,
        then give the overload poller a few ticks to fold the per-ring
        per-tenant deltas into the tenancy ledger."""
        deadline = time.time() + timeout
        last = -1
        while time.time() < deadline:
            done = srv.aggregator.processed
            if srv.aggregator.reader_counters()["ring_depth"] == 0 \
                    and done == last:
                break
            last = done
            time.sleep(0.05)
        time.sleep(0.35)

    def _totals(ten):
        return ({t: n for (t,), n in ten.admitted_snapshot()},
                {t: n for (t,), n in ten.shed_snapshot()})

    def _delta(now, base):
        return {t: now.get(t, 0) - base.get(t, 0)
                for t in set(now) | set(base)}

    def _timer_oracle(grams):
        vals: dict = {}
        for g in grams:
            head, _, rest = g.partition(b":")
            v, _, kind_tags = rest.partition(b"|")
            if kind_tags.split(b"|", 1)[0] == b"ms":
                vals.setdefault(head.decode(), []).append(float(v))
        return vals

    def _p99_errs(sink, oracle):
        """Worst per-tenant relative p99 error across that tenant's
        well-sampled timer names."""
        flushed = {m.name: m.value for m in sink.flushed}
        errs: dict = {}
        for name, v in oracle.items():
            if len(v) < 30:
                continue
            got = flushed.get(name + ".99percentile")
            if got is None:
                continue
            exact = midpoint_quantile(np.asarray(v), 0.99)
            if exact > 0:
                errs.setdefault(name.split(".")[1], []).append(
                    abs(got - exact) / exact)
        return {t: float(np.max(e)) for t, e in errs.items() if e}

    def _accounting_exact(ledger, adm, shd, tenants=None):
        names = tenants if tenants is not None else ledger.keys()
        return all(ledger.get(t, 0) == adm.get(t, 0) + shd.get(t, 0)
                   for t in names)

    # -- pass A: baseline — same traffic, admission held HEALTHY -------------
    phase("baseline")
    gen_a = ReplayGenerator(seed)
    sink_a = DebugMetricSink()
    srv = _mk_server([sink_a], udp=True, **cfg)
    try:
        srv._overload._signals = lambda: {}
        _warm(srv, [b"replay.warm.m0:1.0|ms"], sinks=[sink_a])
        grams_a = (gen_a.steady(steady_n) + gen_a.diurnal(diurnal_n)
                   + gen_a.flash_crowd(flash_n))
        adm0, shd0 = _totals(srv.tenancy)
        _inject(srv, grams_a)
        _settle(srv)
        _flush_checked(srv, timeout=WARM_TIMEOUT)
        time.sleep(0.3)
        adm_a, shd_a = _totals(srv.tenancy)
        adm_a, shd_a = _delta(adm_a, adm0), _delta(shd_a, shd0)
        errs_a = _p99_errs(sink_a, _timer_oracle(grams_a))
    finally:
        srv.shutdown()
    checksum_a = gen_a.checksum()
    ledger_storm = gen_a.ledger()

    # -- pass B: fairness armed — flash crowd under forced SHEDDING ----------
    phase("noisy")
    ckpt_root = tempfile.mkdtemp(prefix="veneur-tenant-ckpt-")
    gen = ReplayGenerator(seed)
    sink_b = DebugMetricSink()
    srv = _mk_server([sink_b], udp=True, checkpoint_dir=ckpt_root,
                     checkpoint_interval_flushes=100_000,
                     checkpoint_on_shutdown=True, **cfg)
    restarted = False
    try:
        ov = srv._overload
        ov._signals = lambda: {}
        port = srv.http_port

        def probe(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                    return r.status
            except urllib.error.HTTPError as e:
                return e.code

        _warm(srv, [b"replay.warm.m0:1.0|ms"], sinks=[sink_b])
        health_codes, ready_log = [], []
        poll_stop = threading.Event()

        def poll_http():
            while not poll_stop.is_set():
                t = time.monotonic()
                health_codes.append(probe("/healthz"))
                ready_log.append((t, probe("/readyz")))
                poll_stop.wait(0.05)

        poller = threading.Thread(target=poll_http, daemon=True)
        poller.start()

        adm0, shd0 = _totals(srv.tenancy)
        grams_b1 = gen.steady(steady_n) + gen.diurnal(diurnal_n)
        _inject(srv, grams_b1)
        _settle(srv)

        phase("flash")
        ov._signals = lambda: {"tenant_storm": 0.90}
        t_force = time.monotonic()
        while ov.state < SHEDDING \
                and time.monotonic() - t_force < 5.0:
            time.sleep(0.01)
        flash = gen.flash_crowd(flash_n)
        # spread the crowd over ~1.5 flush intervals so the readyz
        # latency gates measure against a sustained storm, not a blip
        chunk = max(1, len(flash) // 30)
        t0f = time.monotonic()
        for i in range(0, len(flash), chunk):
            _inject(srv, flash[i:i + chunk])
            target = t0f + 1.5 * interval_s * min(
                1.0, (i + chunk) / len(flash))
            now = time.monotonic()
            if target > now:
                time.sleep(target - now)
        _settle(srv)
        t_load_off = time.monotonic()
        ov._signals = lambda: {}
        while ov.state > HEALTHY \
                and time.monotonic() - t_load_off < 4 * interval_s:
            time.sleep(0.02)
        time.sleep(0.25)
        poll_stop.set()
        poller.join()

        _flush_checked(srv, timeout=WARM_TIMEOUT)
        time.sleep(0.3)
        checksum_b_storm = gen.checksum()   # same point as checksum_a
        adm_b, shd_b = _totals(srv.tenancy)
        adm_b, shd_b = _delta(adm_b, adm0), _delta(shd_b, shd0)
        errs_b = _p99_errs(sink_b, _timer_oracle(grams_b1 + flash))

        # readiness latency vs the controller's own transition stamps
        t_shed = next((ts for ts, _f, to in ov.transitions
                       if to >= SHEDDING and ts >= t_force - 1), None)
        t_flip = next((t for t, c in ready_log if c != 200), None)
        t_back = next((t for t, c in ready_log
                       if t > t_load_off and c == 200), None)
        flip_s = (t_flip - t_shed) if t_shed and t_flip else None
        recover_s = (t_back - t_load_off) if t_back else None

        # -- quarantine: explosion -> demotion -> exact-K accounting ---------
        phase("quarantine")
        _inject(srv, gen.tag_explosion(explode_n, RUNAWAY))
        _settle(srv)
        table = srv.aggregator.tenant_table()
        demoted = bool(table.get(RUNAWAY, {}).get("demoted"))
        rows0 = dict(srv.tenancy.demoted_rows_snapshot())
        _inject(srv, gen.tag_explosion(exact_k, RUNAWAY))
        _settle(srv)
        rows1 = dict(srv.tenancy.demoted_rows_snapshot())
        exact_rows_ok = (rows1.get((RUNAWAY,), 0)
                         - rows0.get((RUNAWAY,), 0)) == exact_k
        healthy_demotions = sum(n for (t,), n in rows1.items()
                                if t != RUNAWAY)

        # -- rolling restart mid-storm ---------------------------------------
        phase("restart")
        srv.shutdown()   # final fold + shutdown checkpoint (tenants chunk)
        restarted = True
        adm_b1, shd_b1 = _totals(srv.tenancy)
        adm_b1, shd_b1 = _delta(adm_b1, adm0), _delta(shd_b1, shd0)
        rows_b1 = dict(srv.tenancy.demoted_rows_snapshot()) \
            .get((RUNAWAY,), 0)

        sink_c = DebugMetricSink()
        srv = _mk_server([sink_c], udp=True, checkpoint_dir=ckpt_root,
                         checkpoint_interval_flushes=100_000,
                         checkpoint_on_shutdown=False,
                         restore_on_start=True, **cfg)
        srv._overload._signals = lambda: {}
        survived = bool(srv.aggregator.tenant_table()
                        .get(RUNAWAY, {}).get("demoted"))
        rows_restored = (dict(srv.tenancy.demoted_rows_snapshot())
                         .get((RUNAWAY,), 0) == rows_b1)
        adm0c, shd0c = _totals(srv.tenancy)
        _inject(srv, gen.steady(post_n))
        _settle(srv)

        # -- decay re-admission (no runaway traffic across flushes) ----------
        phase("readmit")
        readmitted = False
        for _ in range(4):
            _flush_checked(srv, timeout=WARM_TIMEOUT)
            time.sleep(0.25)
            if not srv.aggregator.tenant_table() \
                    .get(RUNAWAY, {}).get("demoted", True):
                readmitted = True
                break
        srv.shutdown()
        adm_c, shd_c = _totals(srv.tenancy)
        adm_c, shd_c = _delta(adm_c, adm0c), _delta(shd_c, shd0c)
    finally:
        if not restarted:
            srv.shutdown()
        shutil.rmtree(ckpt_root, ignore_errors=True)

    ledger_all = gen.ledger()
    noisy_sent_b = (adm_b.get(NOISY, 0) + shd_b.get(NOISY, 0))
    # unchanged = same worst relative p99 error, to 1% absolute slack
    # (device scatter order is not bit-stable between runs); armed only
    # when every isolated tenant had a well-sampled timer in BOTH passes
    # (reduced --scale runs can leave the oracle too sparse)
    p99_gate_armed = all(t in errs_a and t in errs_b for t in ISOLATED)
    iso_p99_unchanged = all(
        abs(errs_a.get(t, 0.0) - errs_b.get(t, 0.0)) <= 0.01
        for t in ISOLATED if t in errs_a and t in errs_b)
    return {
        "config": 15, "name": "tenant_storm",
        "seed": seed,
        "datagrams_storm": sum(ledger_storm.values()),
        "sent": ledger_all,
        "replay_reproducible": checksum_b_storm == checksum_a,
        "accounting_exact_baseline": _accounting_exact(
            ledger_storm, adm_a, shd_a),
        "accounting_exact_noisy": noisy_sent_b == ledger_storm.get(NOISY, 0),
        "baseline_all_admitted": sum(shd_a.values()) == 0,
        "noisy_shed": shd_b.get(NOISY, 0),
        "noisy_throttled": shd_b.get(NOISY, 0) > 0,
        "isolated_shed": {t: shd_b.get(t, 0) for t in ISOLATED},
        "isolated_zero_shed": all(shd_b.get(t, 0) == 0 for t in ISOLATED),
        "isolated_p99_err_baseline": {t: round(errs_a.get(t, 0.0), 5)
                                      for t in ISOLATED},
        "isolated_p99_err_noisy": {t: round(errs_b.get(t, 0.0), 5)
                                   for t in ISOLATED},
        "isolated_p99_unchanged": iso_p99_unchanged,
        "p99_gate_armed": p99_gate_armed,
        "healthz_all_200": all(c == 200 for c in health_codes),
        "readyz_flip_seconds": round(flip_s, 3)
        if flip_s is not None else None,
        "readyz_flip_within_interval": flip_s is not None
        and flip_s <= interval_s,
        "readyz_recover_seconds": round(recover_s, 3)
        if recover_s is not None else None,
        "readyz_recover_within_2_intervals": recover_s is not None
        and recover_s <= 2 * interval_s,
        "runaway_demoted": demoted,
        "demoted_rows_exact_k": exact_rows_ok,
        "healthy_tenant_demotions": healthy_demotions,
        "quarantine_survived_restart": survived,
        "demoted_rows_restored": rows_restored,
        "accounting_exact_across_restart": all(
            ledger_all.get(t, 0)
            == adm_b1.get(t, 0) + shd_b1.get(t, 0)
            + adm_c.get(t, 0) + shd_c.get(t, 0)
            for t in ledger_all),
        "readmitted_after_decay": readmitted,
    }


CONFIGS = {1: config1_counter_replay, 2: config2_zipf_timers,
           3: config3_set_cardinality, 4: config4_global_merge,
           5: config5_span_firehose, 6: config6_cardinality_stress,
           7: config7_checkpoint_restore, 8: config8_overload_storm,
           9: config9_duplicate_storm, 10: config10_wire_to_flush_firehose,
           11: config11_collective_merge, 12: config12_elastic_resize,
           13: config13_watch_storm, 14: config14_range_dashboard,
           15: config15_tenant_storm}

# Per-config subprocess budget: backend init + first XLA compiles of the
# config's size buckets + the run itself. Config 6 gets a larger budget:
# its cycle-0 flush compiles the flush program at multi-million-key
# buckets.
SUBPROC_TIMEOUT = float(os.environ.get("E2E_CONFIG_TIMEOUT", "1500"))


def _config_budget(n: int) -> float:
    # config 6's parent budget must DOMINATE the sum of its child's
    # sanctioned waits — which are absolute constants, NOT scaled by
    # E2E_CONFIG_TIMEOUT — or the parent kills the child in exactly the
    # slow-flush scenario the child budgets tolerate: init + cycle-0
    # flush compile + cycle-1 flush + the four 10M-name feed passes.
    if n != 6:
        return SUBPROC_TIMEOUT
    child_waits = INIT_TIMEOUT + 3 * WARM_TIMEOUT + 300.0 \
        + 4 * DRAIN_TIMEOUT  # feed/drain passes (2 cycles x 2 passes)
    return max(SUBPROC_TIMEOUT * 3.0, child_waits + 300.0)
# Backend-init budget inside each child (mirrors bench.py's kernel-stage
# watchdog): a backend whose client creation hangs must fail fast with a
# diagnostic instead of burning SUBPROC_TIMEOUT x 5.
INIT_TIMEOUT = float(os.environ.get("BENCH_INIT_TIMEOUT", "600"))


def parse_last_json_line(stdout: str):
    """Last '{'-prefixed stdout line as a dict, or None (shared by this
    orchestrator and bench.py so truncation handling can't diverge)."""
    for line in reversed(stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue  # truncated tail from a killed child: the line
                #           above may be a complete earlier checkpoint
    return None


def phase(name: str) -> None:
    """Progress marker on stderr (`BENCHPHASE <name>`). The subprocess
    orchestrators scrape the LAST marker out of a timed-out child's
    captured stderr, turning an opaque "timeout after 1500s" into
    "timeout ... at phase=timed_loop step 40/100", which can be
    diagnosed.
    Markers are cheap (one line per pipeline phase, not per step)."""
    print(f"BENCHPHASE {name}", file=sys.stderr, flush=True)


def last_phase(stderr) -> str:
    """Extract the last BENCHPHASE marker from captured child stderr
    (str, bytes, or None — subprocess.TimeoutExpired.stderr is bytes)."""
    if not stderr:
        return "none"
    if isinstance(stderr, bytes):
        stderr = stderr.decode("utf-8", "replace")
    marks = [ln[len("BENCHPHASE "):].strip()
             for ln in stderr.splitlines() if ln.startswith("BENCHPHASE ")]
    return marks[-1] if marks else "none"


def _arm_init_watchdog(diag: dict):
    """os._exit(2) with one JSON diagnostic line if the backend doesn't
    come up inside INIT_TIMEOUT. Returns the timer to cancel on success."""
    import threading

    def _fire():
        print(json.dumps(dict(diag, error=(
            f"device backend init exceeded {INIT_TIMEOUT:.0f}s"))),
            flush=True)
        os._exit(2)

    t = threading.Timer(INIT_TIMEOUT, _fire)
    t.daemon = True
    t.start()
    return t


def cache_env(force_cpu: bool = False) -> dict:
    """Child-process env with ONE persistent XLA compilation cache shared
    by every benchmark stage (kernel + the config children): each child
    otherwise pays every compile cold. The placement rule is the
    package's (veneur_tpu/utils/compile_cache.py): an outside
    JAX_COMPILATION_CACHE_DIR is left alone, unset means
    <checkout>/.xla_cache.

    With force_cpu (or a parent env already requesting cpu), the child
    is a host-only stage and must stay off the chip: a chip belongs to
    one process at a time."""
    from veneur_tpu.utils import compile_cache
    env = dict(os.environ)
    compile_cache.configure(env)
    if force_cpu or env.get("JAX_PLATFORMS", "").split(",")[0].strip() \
            == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _run_config_subprocess(n, scale, force_cpu=False, budget_cap=None):
    """One config per subprocess. Two reasons: (a) the reference's own
    perf story is per-benchmark processes (`go test -bench` spawns a
    fresh process per package), and (b) each config gets a fresh
    backend session and its own device memory for its own table spec."""
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, "-m", "benchmarks.e2e",
           "--config", str(n), "--in-process"]
    if scale is not None:
        cmd += ["--scale", str(scale)]
    # scale=None is resolved by the CHILD (where jax.devices() is safe);
    # resolving it here would initialize the backend in the parent and
    # block every child from acquiring the chip
    env = cache_env(force_cpu=force_cpu)
    if n == 11:
        # the collective config needs a multi-device mesh; on a CPU-only
        # host, force 8 host devices (the flag is a no-op for real
        # accelerator platforms, so it is safe to add unconditionally)
        flags = env.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    budget = _config_budget(n)
    if budget_cap is not None:
        # the orchestrator's wall-clock guard wins over per-config
        # budgets: a partial e2e block inside the driver's budget beats
        # a complete one that ships as rc=124 (the r04 failure class)
        budget = min(budget, max(60.0, budget_cap))
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              cwd=repo, timeout=budget, env=env)
    except subprocess.TimeoutExpired as e:
        return {"config": n, "error":
                f"timeout after {budget:.0f}s at "
                f"phase={last_phase(e.stderr)}"}
    parsed = parse_last_json_line(proc.stdout)
    if parsed is not None:
        return parsed
    return {"config": n, "error":
            f"rc={proc.returncode}: {proc.stderr.strip()[-400:]}"}


def main(configs=None, scale=None, in_process=False, force_cpu=False,
         on_result=None, deadline=None):
    """`configs` runs in the GIVEN order when passed explicitly (the
    bench orchestrator front-loads the headline configs so a wall-clock
    guard truncates the tail, not the head); default remains all configs
    in numeric order. `deadline` (time.monotonic() absolute) skips
    configs that can't start and caps the budget of the one in flight."""
    if in_process:
        # only the in-process (child) path may touch the backend; the
        # subprocess orchestrator must stay off the chip entirely
        watchdog = _arm_init_watchdog(
            {"config": sorted(configs or CONFIGS)[0]})
        import jax
        on_tpu = jax.devices()[0].platform != "cpu"
        watchdog.cancel()
        if scale is None:
            scale = 1.0 if on_tpu else 0.02
    results = []
    seq = list(configs) if configs else sorted(CONFIGS)
    for n in seq:
        left = None if deadline is None else deadline - time.monotonic()
        if left is not None and left < 90.0:
            results.append({"config": n,
                            "skipped": "bench wall-clock guard"})
            if on_result is not None:
                on_result(results)
            continue
        if in_process:
            phase(f"config{n}_start")
            results.append(CONFIGS[n](scale))
        else:
            results.append(_run_config_subprocess(
                n, scale, force_cpu=force_cpu, budget_cap=left))
        if on_result is not None:
            on_result(results)   # caller checkpoints partial artifacts
    return results


if __name__ == "__main__":
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, action="append",
                    help="config number 1-5 (repeatable; default all)")
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--in-process", action="store_true",
                    help="run configs in this process instead of one "
                         "subprocess per config")
    args = ap.parse_args()
    for r in main(args.config, args.scale, in_process=args.in_process):
        print(json.dumps(r))
