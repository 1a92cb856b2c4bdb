"""The forward way in: the forwarder's wire and digests, the reference for
a global, the planted faults it must catch, the result line of a run that
could not be made, and `correct` through the real Server at a size the CPU
holds (program correct; control, a lost step and an altered answer not)."""

import json

import numpy as np
import pytest

import conftest
import forwarder
import harness
import reference
import run
import traffic

SEED = 2 ** 31 + 3
PERCENTILES = (0.5, 0.75, 0.99)
TINY = {"ingress": "forward", "prefix": "pb", "locals": 4, "bursts": 2,
        "compression": 100, "metrics_per_rpc": 50, "kinds": {
            "counter": {"names": 60, "names_per_local": 20,
                        "samples_per_local": 80, "zipf_s": 1.0},
            "timer": {"names": 40, "names_per_local": 12,
                      "samples_per_local": 600, "zipf_s": 1.0,
                      "scope": "mixed"}}}
# at the server: 8 locals, 2 bursts, 48 RPCs a pool cycle of 9,600
# metrics; a 4 s interval on the CPU holds about two cycles
SMALL = {"ingress": "forward", "prefix": "pb", "locals": 8, "bursts": 2,
         "compression": 100, "metrics_per_rpc": 200, "kinds": {
             "counter": {"names": 2000, "names_per_local": 400,
                         "samples_per_local": 2000, "zipf_s": 1.0},
             "timer": {"names": 1000, "names_per_local": 200,
                       "samples_per_local": 4000, "zipf_s": 1.0,
                       "scope": "mixed"}}}


def tiny(scope="mixed", **kw):
    spec = json.loads(json.dumps(TINY))
    spec["kinds"]["timer"]["scope"] = scope
    spec.update(kw)
    return traffic.build_forward_pool(spec, SEED)


# -- the forwarder ------------------------------------------------------------

@pytest.mark.parametrize("scope", ["mixed", "global"])
def test_rpcs_decode_with_the_wire_schema(scope):
    from veneur_tpu.proto import forwardrpc_pb2, metricpb_pb2
    pool = tiny(scope)
    rpcs, sizes = forwarder.encode(pool, forwarder.Digests(pool))
    assert len(rpcs) == pool.n_rpcs and sum(sizes) == pool.n_metrics
    i = 0
    for data, size in zip(rpcs, sizes):
        metrics = forwardrpc_pb2.MetricList.FromString(data).metrics
        assert len(metrics) == size
        for m in metrics:
            lens = np.diff(pool.s_start)
            name = int(pool.m_name[i])
            if pool.m_kind[i] == traffic.KINDS.index("counter"):
                assert m.name == f"pb.c.{name:07d}" and m.type == metricpb_pb2.Counter
                assert list(m.tags) == [f"k:{name % 8}"]
                assert m.scope == metricpb_pb2.Global
                assert m.counter.value == pool.m_value[i] > 0
            else:
                td = m.histogram.t_digest
                raw = pool.s_value[pool.s_start[i]:pool.s_start[i + 1]]
                assert m.name == f"pb.t.{name:07d}" and m.type == metricpb_pb2.Timer
                assert m.scope == (metricpb_pb2.Global if scope == "global"
                                   else metricpb_pb2.Mixed)
                assert sum(c.weight for c in td.main_centroids) == lens[i]
                assert (td.min, td.max) == (raw.min(), raw.max())
                assert td.reciprocalSum == pytest.approx(np.sum(1 / raw))
                assert td.compression == 100
            i += 1


def merge_loop(x, compression):
    """upstream merging_digest.go mergeOne, sample by sample."""
    centroids, before = [], 0.0
    for j, v in enumerate(x):
        nxt = forwarder.k1((j + 1) / len(x), compression)
        if not centroids or nxt - before > 1:
            centroids.append([v, 1.0])
            before = forwarder.k1(j / len(x), compression)
        else:
            c = centroids[-1]
            c[1] += 1.0
            c[0] += (v - c[0]) / c[1]
    return centroids


@pytest.mark.parametrize("compression", [100.0, 20.0])
def test_each_digest_is_valid(compression):
    pool = tiny(compression=compression)
    dg = forwarder.Digests(pool)
    lens = np.diff(pool.s_start)
    for i in np.flatnonzero(lens > 0):
        raw = pool.s_value[pool.s_start[i]:pool.s_start[i + 1]]
        mean = dg.mean[dg.c_start[i]:dg.c_start[i + 1]]
        weight = dg.weight[dg.c_start[i]:dg.c_start[i + 1]]
        assert weight.sum() == len(raw) and np.all(weight == np.round(weight))
        assert np.all(np.diff(mean) >= 0)
        q = np.concatenate([[0], np.cumsum(weight)]) / len(raw)
        span = forwarder.k1(q[1:], compression) - forwarder.k1(q[:-1],
                                                               compression)
        assert np.all((span <= 1) | (weight == 1))
        assert (dg.min[i], dg.max[i]) == (raw.min(), raw.max())
        loop = np.asarray(merge_loop(raw, compression))
        np.testing.assert_array_equal(loop[:, 1], weight)
        np.testing.assert_allclose(loop[:, 0], mean, rtol=1e-12)


def test_every_fleet_name_in_every_burst_and_the_sizes_fixed():
    a, b = tiny(), traffic.build_forward_pool(TINY, SEED + 1)
    assert (a.n_metrics, a.n_rpcs, len(a.s_value)) == (
        b.n_metrics, b.n_rpcs, len(b.s_value))
    assert a.digest() != b.digest() and a.digest() == tiny().digest()
    per_burst = a.n_metrics // TINY["bursts"]
    for kind, k in TINY["kinds"].items():
        sel = a.m_kind[:per_burst] == traffic.KINDS.index(kind)
        assert set(a.m_name[:per_burst][sel].tolist()) == set(range(k["names"]))


@pytest.mark.parametrize("kind", ["set", "gauge"])
def test_load_refuses_what_is_not_forwarded_yet(tmp_path, kind):
    spec = json.loads(json.dumps(TINY))
    spec["kinds"][kind] = {"names": 4, "names_per_local": 1,
                           "samples_per_local": 4, "zipf_s": 1.0}
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="future work"):
        traffic.load(str(path))


# -- the reference ------------------------------------------------------------

def hand_pool(scope):
    """Three RPCs: counter 1 (5) and timer 0 ([1, 2]); counter 1 (7) and
    counter 2 (1); timer 0 ([3]) and timer 1 ([10, 20, 30])."""
    return traffic.ForwardPool(
        prefix="pb", compression=100.0, timer_scope=scope,
        m_kind=np.asarray([0, 2, 0, 0, 2, 2], np.int8),
        m_name=np.asarray([1, 0, 1, 2, 0, 1], np.int32),
        m_value=np.asarray([5, 0, 7, 1, 0, 0], np.int64),
        s_start=np.asarray([0, 0, 2, 2, 2, 3, 6], np.int64),
        s_value=np.asarray([1.0, 2.0, 3.0, 10.0, 20.0, 30.0]),
        rpc_start=np.asarray([0, 2, 4, 6], np.int64))


@pytest.mark.parametrize("b0, b1, c1, c2, t0, t1", [
    (0, 3, 12, 1, [1, 2, 3], [10, 20, 30]),
    (1, 2, 7, 1, None, None),
    # across the cycle's edge: RPCs 2, 0, 1, 2 -> RPC 2 twice
    (2, 6, 12, 1, [1, 2, 3, 3], [10, 10, 20, 20, 30, 30]),
])
@pytest.mark.parametrize("scope", ["mixed", "global"])
def test_expected_forward_by_hand(scope, b0, b1, c1, c2, t0, t1):
    want, timers = reference.expected_forward(hand_pool(scope), b0, b1,
                                              PERCENTILES)
    assert want["pb.c.0000001"] == c1 and want["pb.c.0000002"] == c2
    assert "pb.c.0000000" not in want
    for i, samples in ((0, t0), (1, t1)):
        base = f"pb.t.{i:07d}"
        if samples is None:
            assert not any(k.startswith(base) for k in want)
            continue
        assert want[base + ".50percentile"] == np.quantile(
            samples, 0.5, method="hazen")
        assert (base + ".count" in want) == (scope == "global")
        if scope == "global":
            assert want[base + ".count"] == len(samples)
            assert (want[base + ".min"], want[base + ".max"]) == (
                min(samples), max(samples))
    if t0 is not None:
        assert timers.lens.tolist() == [len(t0), len(t1)]


def brute_forward(pool, b0, b1):
    """Walk the RPC positions one by one, metric by metric."""
    counters, timers = {}, {}
    for pos in range(b0, b1):
        r = pos % pool.n_rpcs
        for i in range(pool.rpc_start[r], pool.rpc_start[r + 1]):
            name = int(pool.m_name[i])
            if pool.m_kind[i] == 0:
                counters[name] = counters.get(name, 0) + int(pool.m_value[i])
            else:
                timers.setdefault(name, []).extend(
                    pool.s_value[pool.s_start[i]:pool.s_start[i + 1]])
    return counters, timers


@pytest.mark.parametrize("span", [(0, 1.0), (3, 2.5)])
def test_expected_forward_against_a_loop(span):
    pool = tiny("global")
    b0 = span[0]
    b1 = b0 + int(span[1] * pool.n_rpcs)
    want, _ = reference.expected_forward(pool, b0, b1, PERCENTILES)
    counters, timers = brute_forward(pool, b0, b1)
    assert {k for k in want if ".c." in k} == {f"pb.c.{i:07d}" for i in counters}
    for i, v in counters.items():
        assert want[f"pb.c.{i:07d}"] == v
    for i, vals in timers.items():
        base = f"pb.t.{i:07d}"
        assert want[base + ".count"] == len(vals)
        assert want[base + ".max"] == float(np.float32(max(vals)))
        assert want[base + ".99percentile"] == pytest.approx(
            np.quantile(vals, 0.99, method="hazen"), rel=1e-12)


LIMITS = {"p50_rank_wmean": 0.0016, "p50_rank_max": 0.016,
          "p75_rank_wmean": 0.0014, "p99_rank_wmean": 0.0002,
          "p99_rank_max": 0.0011}


def judged(got, want, timers):
    numbers, examples = reference.new_numbers(PERCENTILES), []
    tags = {k: [f"k:{int(k[5:]) % 8}"] for k in got if k.startswith("pb.c.")}
    reference.compare(got, tags, 0, want, timers, PERCENTILES, "pb",
                      numbers, examples)
    rows, ok = reference.verdict(numbers, LIMITS)
    return ok, {name for name, _v, _l, good in rows if not good}


def fault_rpc_left_out(pool, want, b0, b1):
    return reference.expected_forward(pool, b0 + 1, b1, PERCENTILES)[0]


def fault_counter_altered(pool, want, b0, b1):
    got = dict(want)
    name = next(k for k in got if k.startswith("pb.c."))
    got[name] += 1
    return got


def fault_percentile_moved(pool, want, b0, b1):
    got = dict(want)
    name = next(k for k in got if k.endswith("50percentile"))
    got[name] = got[name.replace("50percentile", "99percentile")]
    return got


@pytest.mark.parametrize("fault, over", [
    (fault_rpc_left_out, {"exact_mismatch"}),
    (fault_counter_altered, {"exact_mismatch"}),
    (fault_percentile_moved, {"p50_rank_max"}),
])
def test_planted_fault_is_not_correct(fault, over):
    pool = tiny()
    b0, b1 = 2, 2 + 2 * pool.n_rpcs
    want, timers = reference.expected_forward(pool, b0, b1, PERCENTILES)
    ok, _ = judged({k: float(np.float32(v)) if k.endswith("percentile")
                    else v for k, v in want.items()}, want, timers)
    assert ok
    ok, failed = judged(fault(pool, want, b0, b1), want, timers)
    assert not ok and over <= failed, failed


# -- the result line of a run that could not be made ----------------------------

def test_run_error_gives_a_last_line_and_exit_code_4(monkeypatch, capsys):
    import jax

    from veneur_tpu.utils import compile_cache

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    class Refused:
        def __init__(self, *a, **kw):
            pass

        def execute(self):
            raise harness.RunError("the sender built no pool " + "x" * 400)

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    monkeypatch.setattr(compile_cache, "configure", lambda: "not used")
    monkeypatch.setattr(harness, "Run", Refused)
    cell = conftest._cells()[0]["name"]
    code = run.main(["--workload", cell, "--seed", str(SEED), "--seconds",
                     "30", "--trace", "0"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert code == 4
    assert line["correct"] is False and line["metrics"] == {}
    assert (line["attempted"], line["failed"]) == (0, 0)
    assert line["error"].startswith("the sender built no pool")
    assert len(line["error"]) == run.ERROR_CHARS


# -- through the real Server ------------------------------------------------------

@pytest.fixture()
def forward_cell(tmp_path, monkeypatch):
    """The first cell's configuration serving gRPC imports, the small
    forward mix in its traffic's place, 4 s intervals."""
    cell = harness.load_cell(conftest._cells()[0]["name"])
    path = tmp_path / "forward.json"
    path.write_text(json.dumps(SMALL))
    cell["traffic_path"] = str(path)
    cell["traffic_file"] = traffic.load(str(path))
    cfgf = cell["config_file"]
    cell["config_file"] = dict(cfgf, overrides=dict(
        cfgf["overrides"], grpc_address="127.0.0.1:0"))
    monkeypatch.setattr(harness, "INTERVAL_S", 4.0)
    return cell


def numbers(out):
    return {name: value for name, value, _limit, _ok in out["compared"]}


def test_forward_program_is_correct(forward_cell):
    out = conftest.run(forward_cell, 11)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    # every metric forwarded in the window was imported
    ctx = out["ctx"]
    assert (ctx["counters_end"]["imported_total"]
            - ctx["counters_start"]["imported_total"]) == out["attempted"]


def test_forward_control_is_not_correct(forward_cell):
    """The global's digest at compression 20 and one-float counters."""
    out = conftest.run(forward_cell, 12, control=True)
    assert not out["correct"], out["compared"]
    over = {name for name, value, limit, ok in out["compared"] if not ok}
    assert over & {"p99_rank_wmean", "p99_rank_max", "p50_rank_max"}, over


def test_forward_altered_counter_is_not_correct(forward_cell, monkeypatch):
    """An answer altered where it is produced: one counter of the window's
    first flush."""
    make = harness.make_sink

    def altered():
        sink = make()
        flush = sink.flush_frame

        def flush_frame(frame):
            if len(sink.handed) == 2:
                for seg in frame.segments:
                    hit = [i for i, n in enumerate(seg.names)
                           if n.startswith("pb.c.")]
                    if hit:
                        seg.values[hit[0]] *= 2.0
                        break
            flush(frame)
        sink.flush_frame = flush_frame
        return sink

    monkeypatch.setattr(harness, "make_sink", altered)
    out = conftest.run(forward_cell, 13)
    assert not out["correct"]
    assert {n for n, v, lim, ok in out["compared"] if not ok} == {
        "exact_mismatch"}


def test_forward_step_left_out_is_not_correct(forward_cell, monkeypatch):
    """A step that returns its state unchanged: every fifth ingest step
    the imports stage drops its batch (as test_correct.py's)."""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.server import native_aggregator
    real, calls = native_aggregator.ingest_step_packed, [0]

    def lossy(state, flat, *a, **kw):
        calls[0] += 1
        if calls[0] % 5:
            return real(state, flat, *a, **kw)
        kept = jax.tree_util.tree_map(jnp.copy, state)
        _state, rows = real(state, flat, *a, **kw)
        return kept, rows

    monkeypatch.setattr(native_aggregator, "ingest_step_packed", lossy)
    out = conftest.run(forward_cell, 14)
    assert calls[0] >= 5
    assert not out["correct"]
    assert numbers(out)["exact_mismatch"] + numbers(out)["rows_missing"] >= 1
