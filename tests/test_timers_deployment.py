"""The 100,000-timer agent (BASELINE configuration 2) at a size the CPU
holds: the benchmark's own configuration file with the digest table cut to
4096 rows, a timers-only Zipf(1.0) stream over real UDP through the native
readers, the packed ingest program with its in-band compaction and the live
flush, held to the benchmark's plain NumPy reference.

What the deployment stresses is here at scale 1/32: hot names overflow their
192 raw temp cells many times between two compactions, so their digests
compress for real; tail names with a handful of samples stay raw, so their
percentiles are exact; every name flushes six rows. The chip run at the
published width is the cell `agent-100k-timers` (PERF.md).
"""

import json
import os
import socket
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")

HISTO_ROWS = 4096
TRAFFIC = {"prefix": "pb", "lines_per_datagram": 30, "kinds": {
    "timer": {"names": 3000, "samples": 150000, "zipf_s": 1.0}}}
# two intervals: one pool cycle, then a cycle and a bit (other boundaries,
# every value tied twice for the names the bit reaches)
BOUNDS = (0, 5000, 10400)
PERCENTILES = (0.5, 0.75, 0.99)
# datagrams the sender may be ahead of the engine: as the harness, a
# quarter of the 4 MiB socket buffer at ~2.3 KB of accounting a datagram
CREDIT = 455

# Rank-space limits at this size (reference.rank_errors), each between the
# program's largest and the control's smallest reading over four seeds (11,
# 7, 99, 2147485931; this file's stream is deterministic for a seed: a step
# is cut where the 8192-sample lane fills). The control is the same stream
# at tpu_digest_compression 20, the benchmark configuration's own control.
LIMITS = {
    # mean over the timers, weighted by their samples: the hot names, whose
    # digests compress, carry it. Program 6.4e-4..8.2e-4, control
    # 3.2e-3..3.7e-3
    "p50_rank_wmean": 1.6e-3,
    # program 5.0e-4..7.7e-4, control 2.8e-3..3.4e-3
    "p75_rank_wmean": 1.5e-3,
    # the tail is held by the 64 protected extremes of each row: program
    # 3.2e-5..7.0e-5, control 1.7e-4..4.2e-4
    "p99_rank_wmean": 1.4e-4,
    # the widest of 3,000 timers: about one k-cell at the median of a row
    # compressed at delta 100. Program 6.9e-3..9.9e-3, control
    # 2.6e-2..3.6e-2
    "p50_rank_max": 2.0e-2,
}
# the numbers whose control reads more than three times the program
SEPARATED = {"p50_rank_wmean", "p75_rank_wmean"}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own harness, traffic generator and reference, by
    the plain names run.py imports them under."""
    sys.path.insert(0, BENCH)
    try:
        import harness
        import reference
        import traffic
        yield harness, reference, traffic
    finally:
        while BENCH in sys.path:
            sys.path.remove(BENCH)


def send_interval(agg, out, pool, datagrams, sizes, base, sent, lo, hi):
    """Stream positions [lo, hi) of the cycled pool over the connected
    socket `out`, never more than CREDIT datagrams ahead of what the
    engine has parsed beyond `base`, then wait until it has parsed them
    all. `sent` is the samples sent so far; returns it with these."""
    for pos in range(lo, hi):
        d = pos % pool.n_datagrams
        deadline = time.monotonic() + 60
        while sent - (agg.eng.stats()["processed"] - base) \
                > CREDIT * pool.lines:
            assert time.monotonic() < deadline, "the engine stalled"
            time.sleep(0.0005)
        out.send(datagrams[d])
        sent += int(sizes[d])
    deadline = time.monotonic() + 60
    while agg.eng.stats()["processed"] - base < sent:
        assert time.monotonic() < deadline, "the engine did not drain"
        time.sleep(0.001)
    return sent


def serve_stream(bench, tmp_path, seed, overrides):
    """The deployment's server (config.read_config + new_from_config
    through the harness's build_server) fed BOUNDS' intervals over UDP;
    returns the reference's numbers over both intervals and the rows of
    each flush."""
    harness, reference, traffic = bench
    with open(os.path.join(BENCH, "configs", "agent-timers-1chip.json")) as f:
        cfgf = json.load(f)
    pool = traffic.build_pool(TRAFFIC, seed)
    # the shape the deployment is about: between two compactions (8 steps
    # of 8192 samples) the hottest name overflows its 192 raw temp cells
    # many times over, and most names never fill them in a whole interval
    per_name = np.bincount(pool.name, minlength=TRAFFIC["kinds"]["timer"][
        "names"])
    assert per_name.max() * 8 * 8192 / pool.n_samples > 10 * 192
    assert np.median(per_name) * 2 < 192
    datagrams = pool.datagrams()
    sizes = pool.datagram_sizes()
    sink = harness.make_sink()
    server = harness.build_server(
        cfgf, str(tmp_path), sink,
        dict(overrides, tpu_histo_capacity=HISTO_ROWS))
    server.start()
    numbers, examples, rows = reference.new_numbers(PERCENTILES), [], []
    try:
        assert server._native and server._native_readers_active
        agg = server.aggregator
        assert agg.spec.histo_capacity == HISTO_ROWS
        base = agg.eng.stats()["processed"]
        out = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        out.connect(("127.0.0.1", server.local_addr()[1]))
        sent = 0
        for k in range(1, len(BOUNDS)):
            steps0, compactions0 = agg.steps_total, agg.compactions
            sent = send_interval(agg, out, pool, datagrams, sizes, base,
                                 sent, BOUNDS[k - 1], BOUNDS[k])
            assert server.trigger_flush(wait=True, timeout=300)
            assert agg.compactions - compactions0 >= 2, (
                agg.steps_total - steps0, agg.compactions - compactions0)
            got, tags, twice = harness.frame_rows(sink.handed[-1][1],
                                                  pool.prefix)
            want, timers = reference.expected(pool, BOUNDS[k - 1], BOUNDS[k],
                                              PERCENTILES)
            reference.compare(got, tags, twice, want, timers, PERCENTILES,
                              pool.prefix, numbers, examples)
            rows.append(len(got))
        out.close()
        stats = agg.eng.stats()
        assert stats["dropped"] == 0 and stats["parse_errors"] == 0
        # where the benchmark reads compact_rows_per_step: the device's
        # count of the rows that took samples, never the table's height
        assert 0 < agg.ring_stats()["compact_rows"] <= (
            agg.compactions * TRAFFIC["kinds"]["timer"]["names"])
        assert server.internal_errors == 0
    finally:
        server.shutdown()
    return numbers, rows, examples


@pytest.mark.parametrize("seed", [11, 2147485931])
def test_timers_deployment_agrees_with_the_reference(bench, tmp_path, seed):
    reference = bench[1]
    numbers, rows, examples = serve_stream(bench, tmp_path, seed, {})
    # every name flushes its six rows in both intervals, each once, and
    # count, min and max are exact
    names = TRAFFIC["kinds"]["timer"]["names"]
    assert rows == [names * (len(PERCENTILES) + 3)] * 2
    assert {k: numbers[k] for k in reference.EXACT} == dict.fromkeys(
        reference.EXACT, 0), examples
    over = {k: numbers[k] for k, limit in LIMITS.items()
            if numbers[k] > limit}
    assert not over, numbers


def test_compression_20_fails_the_same_limits(bench, tmp_path):
    """The control: the same stream through the program's own
    lower-precision digest path. Exact numbers still hold; the rank
    errors do not."""
    reference = bench[1]
    numbers, rows, examples = serve_stream(
        bench, tmp_path, 11, {"tpu_digest_compression": 20.0})
    assert {k: numbers[k] for k in reference.EXACT} == dict.fromkeys(
        reference.EXACT, 0), examples
    over = {k for k, limit in LIMITS.items() if numbers[k] > limit}
    assert over >= SEPARATED, numbers
