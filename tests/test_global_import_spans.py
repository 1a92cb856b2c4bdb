"""The global tier (BASELINE configuration 4) at a size the CPU holds: the
benchmark's own forward configuration with its digest table cut to 4,096
rows, eight locals forwarding 2,000 mixed timers as t-digests and 500
counters over real gRPC (forwardrpc.Forward/SendMetrics) into the Server,
two bursts of the fleet a pool cycle, two intervals of two and two and a
half cycles, so that the global's digests compress between compactions as
the cell's do. Each flush is held to the
benchmark's plain NumPy reference (`reference.expected_forward`: counters
exact, a mixed timer's percentiles only, in rank space against the raw
samples its digests summarise), and the import path's counters and spans
to what was sent: `import_rpcs` the requests, `import_rows` their
centroids and counters, and `import.decode`, `import.fallback` and
`import.stats` inside the request's `pipeline.item`. The chip run at the
deployment's size is the benchmark's forward cell (PERF.md).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")

HISTO_ROWS = 4096
TRAFFIC = {"ingress": "forward", "prefix": "pb", "locals": 8, "bursts": 2,
           "compression": 100, "metrics_per_rpc": 100, "kinds": {
               "counter": {"names": 500, "names_per_local": 80,
                           "samples_per_local": 400, "zipf_s": 1.0},
               "timer": {"names": 2000, "names_per_local": 300,
                         "samples_per_local": 3000, "zipf_s": 1.0,
                         "scope": "mixed"}}}
PERCENTILES = (0.5, 0.75, 0.99)
SEEDS = (11, 2147485931)
# Rank-space limits at this size (reference.rank_errors), each the
# geometric mean of the program's largest and the control's
# (tpu_digest_compression 20) smallest reading over three seeds (11, 5,
# 2147485931), the same intervals: most timers stay a few raw centroids,
# the hot ones compress.
LIMITS = {
    # program 3.8e-4..6.2e-4, control 2.0e-3..3.0e-3
    "p50_rank_wmean": 1.1e-3,
    # the widest of 2,000 timers: program 4.7e-3..6.8e-3, control
    # 2.1e-2..3.2e-2
    "p50_rank_max": 1.2e-2,
    # program 7.0e-5..1.2e-4, control 5.1e-4..7.7e-4
    "p99_rank_wmean": 2.4e-4,
}
ITEM, TAG = "pipeline.item", "_ImportBytes"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's forwarder, harness, reference and traffic
    generator, by the plain names run.py imports them under."""
    sys.path.insert(0, BENCH)
    try:
        import forwarder
        import harness
        import reference
        import traffic
        yield forwarder, harness, reference, traffic
    finally:
        while BENCH in sys.path:
            sys.path.remove(BENCH)


def forward_config() -> dict:
    """The configuration of the benchmark's cell whose traffic comes in
    forwarded."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            if json.load(f).get("ingress") == "forward":
                with open(os.path.join(ROOT, files[w["config"]])) as g:
                    return json.load(g)
    raise AssertionError("no cell of BENCHMARK.json comes in forwarded")


def fallback_rpc(precision: int) -> bytes:
    """One set metric, which the engine hands to the Python import path;
    outside the pool's prefix, so the reference never sees its row."""
    from veneur_tpu.ops import hll
    from veneur_tpu.proto import forwardrpc_pb2, metricpb_pb2
    regs = np.zeros(hll.num_registers(precision), np.uint8)
    regs[::97] = 3
    ml = forwardrpc_pb2.MetricList()
    m = ml.metrics.add()
    m.name, m.type = "fb.s.members", metricpb_pb2.Set
    m.set.hyper_log_log = hll.serialize(regs, precision)
    return ml.SerializeToString()


def serve(bench, tmp_path, seed, overrides) -> dict:
    """The deployment's server (harness.build_server, as the benchmark
    builds it) fed `bounds`' intervals over gRPC, the first with the
    fallback request after its RPCs. Per interval: the
    reference's verdict numbers, what was sent, the import counters'
    deltas; and the pipeline thread's records of the run."""
    import grpc

    from veneur_tpu.observability import hostspans
    forwarder, harness, reference, traffic = bench
    pool = traffic.build_forward_pool(TRAFFIC, seed)
    dg = forwarder.Digests(pool)
    rpcs, sizes = forwarder.encode(pool, dg)
    # rows the engine stages for a metric: a digest's centroids, else one
    rows = np.where(np.diff(pool.s_start) > 0, np.diff(dg.c_start), 1)
    rpc_rows = np.add.reduceat(rows, pool.rpc_start[:-1])
    # RPC positions of the cycled pool: two cycles, then two and a half
    n = pool.n_rpcs
    bounds = ((0, 2 * n), (2 * n, 4 * n + n // TRAFFIC["bursts"]))
    sink = harness.make_sink()
    server = harness.build_server(
        forward_config(), str(tmp_path), sink,
        dict(overrides, tpu_histo_capacity=HISTO_ROWS))
    t0 = time.monotonic_ns()
    server.start()
    numbers, examples, intervals = reference.new_numbers(PERCENTILES), [], []
    try:
        agg = server.aggregator
        assert agg.spec.histo_capacity == HISTO_ROWS
        channel = grpc.insecure_channel(f"127.0.0.1:{server.grpc_port}")
        send = channel.unary_unary(forwarder.METHOD)
        for k, (b0, b1) in enumerate(bounds):
            ring0, steps0 = agg.ring_stats(), agg.steps_total
            at = np.arange(b0, b1) % pool.n_rpcs
            imported0, errors0 = server.imported_total, server.import_errors
            for r in at.tolist():
                send(rpcs[r], timeout=60)
            extra = 0 if k else 1
            if extra:
                send(fallback_rpc(agg.spec.hll_precision), timeout=60)
            # the imports wait ahead of the flush request in the FIFO queue
            assert server.trigger_flush(wait=True, timeout=300)
            ring = agg.ring_stats()
            intervals.append({
                "sent": {"import_rpcs": b1 - b0 + extra,
                         "import_rows": int(rpc_rows[at].sum()),
                         "imported_total": int(np.asarray(sizes)[at].sum())
                         + extra},
                "read": {"import_rpcs": ring["import_rpcs"]
                         - ring0["import_rpcs"],
                         "import_rows": ring["import_rows"]
                         - ring0["import_rows"],
                         "imported_total": server.imported_total - imported0},
                "steps": agg.steps_total - steps0,
                "import_steps": ring["import_steps"] - ring0["import_steps"],
                "lane_stops": (ring["import_lane_stops"]
                               - ring0["import_lane_stops"]),
                "stat_steps": (ring["import_stat_steps"]
                               - ring0["import_stat_steps"]),
                "import_errors": server.import_errors - errors0})
            got, tags, twice = harness.frame_rows(sink.handed[-1][1],
                                                  pool.prefix)
            want, timers = reference.expected_forward(pool, b0, b1,
                                                      PERCENTILES)
            reference.compare(got, tags, twice, want, timers, PERCENTILES,
                              pool.prefix, numbers, examples)
        channel.close()
        assert server.internal_errors == 0
        thread = server._pipeline_thread.name
    finally:
        server.shutdown()
    t1 = time.monotonic_ns()
    records = [r for r in hostspans.records()
               if r.thread == thread and t0 <= r.start_ns and r.end_ns <= t1]
    return {"numbers": numbers, "examples": examples,
            "intervals": intervals, "records": records}


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    """serve(seed, control), each made once for the module."""
    made = {}

    def get(seed, control=False):
        if (seed, control) not in made:
            made[seed, control] = serve(
                bench, tmp_path_factory.mktemp("global"), seed,
                {"tpu_digest_compression": 20.0} if control else {})
        return made[seed, control]
    return get


@pytest.mark.parametrize("seed", SEEDS)
def test_global_agrees_with_the_reference(bench, served, seed):
    reference = bench[2]
    run = served(seed)
    numbers = run["numbers"]
    assert {k: numbers[k] for k in reference.EXACT} == dict.fromkeys(
        reference.EXACT, 0), run["examples"]
    over = {k: numbers[k] for k, limit in LIMITS.items()
            if numbers[k] > limit}
    assert not over, numbers
    assert all(i["import_errors"] == 0 for i in run["intervals"])


def test_compression_20_fails_the_same_limits(bench, served):
    """The control: the same RPCs into the program's own lower-precision
    digest. Counters stay exact; the rank errors do not."""
    reference = bench[2]
    numbers = served(SEEDS[0], control=True)["numbers"]
    assert {k: numbers[k] for k in reference.EXACT} == dict.fromkeys(
        reference.EXACT, 0)
    assert {k for k, limit in LIMITS.items() if numbers[k] > limit} == set(
        LIMITS), numbers


@pytest.mark.parametrize("counter", ["import_rpcs", "import_rows",
                                     "imported_total"])
def test_import_counters_equal_what_was_sent(served, counter):
    for interval in served(SEEDS[0])["intervals"]:
        assert interval["read"][counter] == interval["sent"][counter]


def test_import_steps_are_the_steps_of_the_imports(served):
    """Every step of an interval but the swap's own last emits is
    dispatched inside an import; a lane stop is followed by one."""
    for i in served(SEEDS[0])["intervals"]:
        assert 0 < i["lane_stops"] <= i["import_steps"] <= i["steps"]
        assert i["steps"] - i["import_steps"] <= 2, i


def test_a_request_pays_no_step_for_its_stats(served):
    """The global's stats lane is as wide as its histo lane, so the
    digests' stats ride the lane stops' steps: an import step is a lane
    stop's, and none is the stats lane's own."""
    for i in served(SEEDS[0])["intervals"]:
        assert i["stat_steps"] == 0, i
        assert i["import_steps"] == i["lane_stops"], i


@pytest.mark.parametrize("name, per_request", [
    ("import.decode", None), ("import.stats", 1), ("import.fallback", 0)])
def test_import_spans_nest_in_the_request_item(served, name, per_request):
    run = served(SEEDS[0])
    records = run["records"]
    items = {r.index for r in records if r.name == ITEM and r.tag == TAG}
    mine = [r for r in records if r.name == name]
    requests = sum(i["sent"]["import_rpcs"] for i in run["intervals"])
    assert len(items) == requests
    assert mine and all(r.parent in items for r in mine)
    if per_request is None:
        # one an engine call: a request's first and one after each stop
        stops = sum(i["lane_stops"] for i in run["intervals"])
        assert len(mine) >= requests + stops
    elif per_request:
        assert len(mine) == requests
    else:
        assert len(mine) == 1       # the fallback request's set metric
