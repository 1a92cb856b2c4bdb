"""The shipped agent on a four-chip host at a size the CPU holds: the
benchmark's own four-chip configuration file with every table and batch
lane cut by 64 and `tpu_n_shards` 4 on the conftest's virtual CPU devices
(on the CPU the shipped `tpu_n_shards: 0` stays one shard), a mixed Zipf
stream in `mixed-zipf`'s ratios over real UDP through the native readers,
held to the benchmark's plain NumPy reference.

What the deployment runs is the sharded backend: the engine's staged lanes
split by owner shard into one Python batcher a shard, a step of four tiles
whenever one shard's lane fills (the others go out as they stand), the
XLA-chain ingest under two vmaps and the merged flush. Its staging counts
(`ring_stats()`: `shard_rows_staged`, `shard_rows_max`,
`shard_lane_rows_dispatched`, `shard_forced_emits`) and spans
(`pipeline.shard_split`, `pipeline.shard_pack`) are what the cell's
per-layer metrics read; here they are held to what was sent. The chip run
at the published size is the cell `agent-mixed-4chip` (PERF.md).
"""

import json
import os
import socket
import sys

import numpy as np
import pytest

from tests.test_timers_deployment import send_interval

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CONFIG = os.path.join(BENCH, "configs", "agent-4chip.json")
MIX = os.path.join(BENCH, "traffic", "mixed-zipf.json")

SHARDS = 4
CUT = 64
TABLES = ("tpu_counter_capacity", "tpu_gauge_capacity", "tpu_status_capacity",
          "tpu_set_capacity", "tpu_histo_capacity")
LANES = ("tpu_batch_counter", "tpu_batch_gauge", "tpu_batch_status",
         "tpu_batch_set", "tpu_batch_histo")
# the mix's names and samples over 64, rounded down
TRAFFIC = {"prefix": "pb", "lines_per_datagram": 30, "kinds": {
    "counter": {"names": 1250, "samples": 3750, "zipf_s": 1.0,
                "half_rate_share": 0.1},
    "gauge": {"names": 312, "samples": 625, "zipf_s": 1.0},
    "timer": {"names": 156, "samples": 3125, "zipf_s": 1.0},
    "set": {"names": 31, "samples": 625, "zipf_s": 1.0}}}
PERCENTILES = (0.5, 0.75, 0.99)
NAMES = sum(k["names"] for k in TRAFFIC["kinds"].values())
ROWS = NAMES + TRAFFIC["kinds"]["timer"]["names"] * (len(PERCENTILES) + 2)
# a pool cycle is 271 datagrams. Two intervals: half a cycle, then five
# cycles, in which the lanes fill a few dozen times and every tile
# compacts
BOUNDS = (0, 135, 1490)
# Limits at this size, each between the program's largest reading and the
# control's smallest over five seeds (11, 7, 99, 2147483659, 2147485931;
# the control: tpu_digest_compression 20, and the reference's counters in
# one float and its sets from 2^10 registers put in the program's place,
# as harness.Run._judge does). No counter passes 2^24 here, so the exact
# numbers hold in the control too. p50_rank_wmean is printed, not held:
# program 2.8e-4..6.6e-4, control 7.7e-4..3.6e-3.
LIMITS = {
    # the widest of 156 timers. Program 1.6e-3..3.3e-3, control
    # 7.3e-3..1.8e-2
    "p50_rank_max": 5e-3,
    # 31 sets. Program 2.2e-4..4.6e-4, control 1.2e-3..2.4e-3
    "set_err_mean": 7.5e-4,
}


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


def small_overrides(cfgf):
    """The configuration's tables and lanes over 64, four shards."""
    out = {key: cfgf["expect"][key] // CUT for key in TABLES + LANES}
    out["tpu_n_shards"] = SHARDS
    return out


def serve_stream(bench, tmp_path, monkeypatch, seed, control=False):
    """The deployment's server (config.read_config + new_from_config
    through the harness's build_server) at the small size, fed BOUNDS'
    intervals over UDP. Returns the reference's numbers over the
    intervals, the rows each flush emitted, the aggregator's staging
    counts over the run, the rows the engine's emits handed over, the
    steps dispatched, and the program's span records of the run."""
    harness, reference, traffic = bench
    from veneur_tpu.observability import hostspans
    with open(CONFIG) as f:
        cfgf = json.load(f)
    ctl = cfgf["control"]
    pool = traffic.build_pool(TRAFFIC, seed)
    datagrams = pool.datagrams()
    sizes = pool.datagram_sizes()
    sink = harness.make_sink()
    server = harness.build_server(
        cfgf, str(tmp_path), sink,
        dict(small_overrides(cfgf), **(ctl["overrides"] if control else {})))
    first_index = max((r.index for r in hostspans.records()), default=-1)
    server.start()
    numbers, examples, rows = reference.new_numbers(PERCENTILES), [], []
    emitted = []
    try:
        assert server._native and server._native_readers_active
        agg = server.aggregator
        info = harness.describe(server)
        assert (info["aggregator"], info["shards"], info["ingest_path"]) == (
            "NativeShardedAggregator", SHARDS, "xla")
        assert agg.spec.counter_capacity == cfgf["expect"][
            "tpu_counter_capacity"] // CUT
        eng_emit = agg.eng.emit_into

        def counted_emit(lanes):
            counts = eng_emit(lanes)
            emitted.append(sum(counts))
            return counts

        monkeypatch.setattr(agg.eng, "emit_into", counted_emit)
        ring0, steps0 = agg.ring_stats(), agg.steps_total
        base = agg.eng.stats()["processed"]
        out = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        out.connect(("127.0.0.1", server.local_addr()[1]))
        sent = 0
        for k in range(1, len(BOUNDS)):
            sent = send_interval(agg, out, pool, datagrams, sizes, base,
                                 sent, BOUNDS[k - 1], BOUNDS[k])
            assert server.trigger_flush(wait=True, timeout=300)
            got, tags, twice = harness.frame_rows(sink.handed[-1][1],
                                                  pool.prefix)
            want, timers = reference.expected(pool, BOUNDS[k - 1], BOUNDS[k],
                                              PERCENTILES)
            if control:
                low, _ = reference.expected(
                    pool, BOUNDS[k - 1], BOUNDS[k], PERCENTILES,
                    counter_dtype=getattr(np, ctl["counter_dtype"]))
                low.update(reference.hll_estimates(
                    pool, BOUNDS[k - 1], BOUNDS[k], ctl["hll_precision"]))
                for name in low.keys() & got.keys():
                    if name.startswith((pool.prefix + ".c.",
                                        pool.prefix + ".s.")):
                        got[name] = low[name]
            reference.compare(got, tags, twice, want, timers, PERCENTILES,
                              pool.prefix, numbers, examples)
            rows.append(len(got))
        out.close()
        ring1, steps1 = agg.ring_stats(), agg.steps_total
        stats = agg.eng.stats()
        assert stats["dropped"] == 0 and stats["parse_errors"] == 0
        assert stats["processed"] - base == sent
        assert server.internal_errors == 0
        lanes = sum(cfgf["expect"][key] // CUT for key in LANES
                    if key != "tpu_batch_status")
        counts = {key: ring1[key] - ring0[key] for key in (
            "shard_rows_staged", "shard_lane_rows_dispatched",
            "shard_forced_emits")}
        counts["shard_rows_max"] = ring1["shard_rows_max"]
        counts["lane_rows"] = SHARDS * lanes
    finally:
        server.shutdown()
    records = [r for r in hostspans.records() if r.index > first_index]
    return (numbers, rows, examples, counts, sum(emitted), steps1 - steps0,
            sent, records)


def _ancestors(rec, by_index):
    out = []
    while rec.parent is not None and rec.parent in by_index:
        rec = by_index[rec.parent]
        out.append(rec.name)
    return out


@pytest.mark.parametrize("seed", [11, 2147483659])
def test_agent_4chip_deployment_agrees_with_the_reference(bench, tmp_path,
                                                          monkeypatch, seed):
    reference = bench[1]
    (numbers, rows, examples, counts, emitted, steps, sent,
     records) = serve_stream(bench, tmp_path, monkeypatch, seed)
    # every name of the second interval (five cycles) flushes its rows,
    # each once, from whichever shard owns it, and the exact numbers are
    # exact
    assert rows[1] == ROWS
    assert {k: numbers[k] for k in reference.EXACT} == dict.fromkeys(
        reference.EXACT, 0), examples
    over = {k: numbers[k] for k, limit in LIMITS.items()
            if numbers[k] > limit}
    assert not over, numbers

    # the staging counts hold what was sent: every row the engine's emits
    # handed over was staged into a shard batcher, every dispatched step
    # carried four tiles of the four engine lanes' widths, and the
    # busiest shard took between a quarter and all of the rows
    staged = counts["shard_rows_staged"]
    assert staged == emitted == sent
    assert counts["shard_lane_rows_dispatched"] == steps * counts[
        "lane_rows"]
    assert counts["shard_lane_rows_dispatched"] >= staged
    assert staged / SHARDS <= counts["shard_rows_max"] <= staged
    # a step dispatched by one shard's full lane, the others as they
    # stood: all but the swaps' own (one an interval)
    assert 0 < counts["shard_forced_emits"] <= steps - 1

    # the spans nest: a split inside an emit, a step's packing inside the
    # split whose lane filled (or the swap's last emit), its dispatch
    # inside the packing
    by_index = {r.index: r for r in records}
    splits = [r for r in records if r.name == "pipeline.shard_split"]
    packs = [r for r in records if r.name == "pipeline.shard_pack"]
    dispatches = [r for r in records if r.name == "pipeline.dispatch"]
    assert splits and packs and len(dispatches) == len(packs) == steps
    assert all(by_index[r.parent].name == "pipeline.emit" for r in splits)
    assert all(by_index[r.parent].name in ("pipeline.shard_split",
                                           "swap.emit_staged")
               for r in packs)
    assert sum(by_index[r.parent].name == "pipeline.shard_split"
               for r in packs) == counts["shard_forced_emits"]
    assert all(by_index[r.parent].name == "pipeline.shard_pack"
               for r in dispatches)
    assert all("pipeline.emit" in _ancestors(r, by_index)
               or "swap" in _ancestors(r, by_index) for r in packs)


@pytest.mark.parametrize("seed", [7, 99])
def test_the_control_fails_the_same_limits(bench, tmp_path, monkeypatch,
                                           seed):
    """The configuration's control on the same stream: every row is still
    there once and exact; the sketches are not inside the limits."""
    reference = bench[1]
    numbers, rows, examples = serve_stream(bench, tmp_path, monkeypatch,
                                           seed, control=True)[:3]
    assert rows[1] == ROWS
    assert {k: numbers[k] for k in reference.EXACT} == dict.fromkeys(
        reference.EXACT, 0), examples
    assert all(numbers[k] > limit for k, limit in LIMITS.items()), numbers


@pytest.mark.parametrize("per_step,compact_every", [(60, 1), (45, 2)])
def test_sharded_roofline_counts_a_step_by_hand(bench, per_step,
                                                compact_every):
    """sharded_ingest_roofline's least bytes a step, against a count by
    hand over this file's pool: a record a sample (8 B a counter or
    gauge, 12 a timer, 9 a set member), a state cell read and written a
    distinct name in the step (8 B a counter, 10 a gauge, 8 a set word, 48
    a timer's six accumulators), a timer sample's temp cell (8 B), and a
    digest row of 472 columns read and written once a group of
    `compact_every` steps (472 x 16 B)."""
    import importlib.util
    traffic = bench[2]
    path = os.path.join(BENCH, "layer_metrics", "sharded_ingest_roofline.py")
    spec = importlib.util.spec_from_file_location("sharded_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    pool = traffic.build_pool(TRAFFIC, 11)
    kinds = [traffic.KINDS[k] for k in pool.kind]
    names = [int(n) for n in pool.name]
    record = {"counter": 8, "gauge": 8, "timer": 12, "set": 9}
    cell = {"counter": 8, "gauge": 10, "timer": 48, "set": 8}
    n_steps = len(kinds) // per_step
    total = 0
    for s in range(n_steps):
        seen = set()
        for i in range(s * per_step, (s + 1) * per_step):
            total += record[kinds[i]] + 8 * (kinds[i] == "timer")
            seen.add((kinds[i], names[i]))
        total += sum(cell[k] for k, _ in seen)
    group = per_step * compact_every
    for lo in range(0, n_steps * per_step, group):
        timers = {names[i] for i in range(lo, min(len(kinds), lo + group))
                  if kinds[i] == "timer"}
        total += len(timers) * 472 * 16
    assert mod.step_min_bytes(pool, per_step, compact_every, 472) == \
        pytest.approx(total / n_steps, rel=1e-12)


def test_configuration_and_traffic_files_agree(bench):
    """The four-chip configuration is the one-chip agent's on four chips
    (the same YAML, four shards, nothing else changed), its cell runs the
    one-chip cell's own stream, and this file's stream is that stream's
    ratios over 64."""
    import yaml
    traffic = bench[2]
    with open(CONFIG) as f:
        cfgf = json.load(f)
    with open(os.path.join(BENCH, "configs", "agent-1chip.json")) as f:
        one = json.load(f)
    differs = {k for k in one.keys() | cfgf.keys()
               if one.get(k) != cfgf.get(k)}
    assert differs == {"name", "source", "deployment", "state", "assumed"}
    assert cfgf["state"] == dict(one["state"], shards=SHARDS)
    assert cfgf["assumed"][:len(one["assumed"])] == one["assumed"]
    assert cfgf["reduced"] == [] and cfgf["expect"]["tpu_n_shards"] == 0
    assert "native_preshard_enabled" not in cfgf["overrides"]
    with open(os.path.join(ROOT, cfgf["base"])) as f:
        shipped = yaml.safe_load(f)
    assert shipped["native_preshard_enabled"] is False
    for key, want in cfgf["expect"].items():
        assert shipped.get(key) == want, key
    # every table splits evenly over the shards, at the published size
    # and at this file's
    for key in TABLES:
        assert cfgf["expect"][key] % (SHARDS * CUT) == 0, key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    (cell,) = [w for w in declared["workloads"]
               if w["config"] == cfgf["name"]]
    assert cell["chips"] == SHARDS
    (one_chip,) = [w for w in declared["workloads"]
                   if w["config"] == one["name"]
                   and w["traffic"] == cell["traffic"]]
    assert os.path.join(BENCH, "traffic", cell["traffic"] + ".json") == MIX
    mix = traffic.load(MIX)
    assert TRAFFIC["lines_per_datagram"] == mix["lines_per_datagram"]
    for kind, k in mix["kinds"].items():
        small = TRAFFIC["kinds"][kind]
        assert (small["names"], small["samples"]) == (
            k["names"] // CUT, k["samples"] // CUT), kind
        assert small.get("half_rate_share") == k.get("half_rate_share")
