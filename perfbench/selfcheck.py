#!/usr/bin/env python3
"""Checks of the yardstick itself, run by hand (`python perfbench/selfcheck.py`),
needing no chip and not collected by the repo's tests:

- the reference against a brute-force loop at a tiny pool, and its rank
  errors against a loop over one timer's sorted samples; the same for the
  forward reference over RPC positions;
- the forwarder imports nothing of JAX and nothing of the program but the
  generated wire-schema modules (`veneur_tpu/proto/*_pb2`), and what it
  sends decodes with them;
- the interval arithmetic of trace_reduce.py on hand-made events;
- the roofline byte count on a hand-counted batch;
- every file BENCHMARK.json names exists, and every name and unit uses
  only the allowed characters.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import readers          # noqa: E402
import reference        # noqa: E402
import roofline         # noqa: E402
import trace_reduce     # noqa: E402
import traffic          # noqa: E402

TINY = {"prefix": "pb", "lines_per_datagram": 7, "kinds": {
    "counter": {"names": 40, "samples": 200, "zipf_s": 1.0,
                "half_rate_share": 0.2},
    "gauge": {"names": 10, "samples": 50, "zipf_s": 1.0},
    "timer": {"names": 8, "samples": 300, "zipf_s": 1.0},
    "set": {"names": 4, "samples": 60, "zipf_s": 1.0}}}


def brute(pool, b0, b1, percentiles):
    """Walk the stream position by position, line by line."""
    counters, gauges, timers, sets = {}, {}, {}, {}
    n = pool.n_datagrams
    for pos in range(b0, b1):
        d = pos % n
        for i in range(d * pool.lines, min((d + 1) * pool.lines,
                                           pool.n_samples)):
            kind = traffic.KINDS[pool.kind[i]]
            name, v = int(pool.name[i]), float(pool.value[i])
            if kind == "counter":
                counters[name] = counters.get(name, 0.0) + (
                    2 * v if pool.half_rate[i] else v)
            elif kind == "gauge":
                gauges[name] = v
            elif kind == "timer":
                timers.setdefault(name, []).append(v)
            else:
                sets.setdefault(name, set()).add(int(v))
    out = {f"pb.c.{k:07d}": v for k, v in counters.items()}
    out.update({f"pb.g.{k:07d}": v for k, v in gauges.items()})
    out.update({f"pb.s.{k:07d}": float(len(v)) for k, v in sets.items()})
    for k, vals in timers.items():
        base = f"pb.t.{k:07d}"
        a32 = np.asarray(vals, np.float32)
        out[base + ".min"] = float(a32.min())
        out[base + ".max"] = float(a32.max())
        out[base + ".count"] = float(len(vals))
        for q in percentiles:
            out[f"{base}.{int(round(q * 100))}percentile"] = float(
                np.quantile(np.asarray(vals), q, method="hazen"))
    return out


def check_reference():
    qs = (0.5, 0.75, 0.99)
    for seed in (0, 1, 2 ** 31 + 11):
        pool = traffic.build_pool(TINY, seed)
        assert pool.digest() == traffic.build_pool(TINY, seed).digest()
        n = pool.n_datagrams
        for b0, b1 in ((0, n), (n + 3, 3 * n + 5), (5, 9), (2 * n - 1, 2 * n + 1)):
            want, (got, timers) = brute(pool, b0, b1, qs), reference.expected(
                pool, b0, b1, qs)
            check_ranks(timers, want, seed)
            assert want.keys() == got.keys(), (seed, b0, b1)
            for k, v in want.items():
                assert abs(got[k] - v) <= 1e-9 * max(1.0, abs(v)), (k, got[k], v)
        # the datagrams carry exactly the pool's lines
        lines = b"\n".join(pool.datagrams()).split(b"\n")
        assert len(lines) == pool.n_samples
        assert sum(pool.datagram_sizes()) == pool.n_samples
    # the control's float32 counters must differ once sums pass 2^24
    numbers, ex = reference.new_numbers(qs), []
    pool = traffic.build_pool(TINY, 3)
    want, timers = reference.expected(pool, 0, 4000 * pool.n_datagrams, qs)
    low, _ = reference.expected(pool, 0, 4000 * pool.n_datagrams, qs,
                                counter_dtype=np.float32)
    tags = {k: [f"k:{int(k[5:]) % 8}"] for k in want if k.startswith("pb.c.")}
    reference.compare(low, tags, 0, want, timers, qs, "pb", numbers, ex)
    assert numbers["exact_mismatch"] > 0, numbers
    # the reference's own rows read nought in every number, 4000 ties or not
    numbers = reference.new_numbers(qs)
    as_emitted = {k: float(np.float32(v)) if k.endswith("percentile") else v
                  for k, v in want.items()}
    reference.compare(as_emitted, tags, 0, want, timers, qs, "pb", numbers,
                      ex)
    rows, ok = reference.verdict(numbers, {k: 1e-6 for k in numbers})
    assert ok and len(rows) == len(numbers), rows


FORWARD_TINY = {"ingress": "forward", "prefix": "pb", "locals": 3, "bursts": 2,
                "compression": 100, "metrics_per_rpc": 7, "kinds": {
                    "counter": {"names": 9, "names_per_local": 4,
                                "samples_per_local": 12, "zipf_s": 1.0},
                    "timer": {"names": 6, "names_per_local": 3,
                              "samples_per_local": 40, "zipf_s": 1.0,
                              "scope": "global"}}}


def check_forward_reference():
    qs = (0.5, 0.75, 0.99)
    for seed in (0, 2 ** 31 + 11):
        pool = traffic.build_forward_pool(FORWARD_TINY, seed)
        n = pool.n_rpcs
        for b0, b1 in ((0, n), (n + 3, 3 * n + 2), (2, 4)):
            counters, timers = {}, {}
            for pos in range(b0, b1):
                r = pos % n
                for i in range(pool.rpc_start[r], pool.rpc_start[r + 1]):
                    name = int(pool.m_name[i])
                    if traffic.KINDS[pool.m_kind[i]] == "counter":
                        counters[name] = (counters.get(name, 0.0)
                                          + float(pool.m_value[i]))
                    else:
                        timers.setdefault(name, []).extend(
                            pool.s_value[pool.s_start[i]:pool.s_start[i + 1]])
            want = {f"pb.c.{k:07d}": v for k, v in counters.items()}
            for k, vals in timers.items():
                base = f"pb.t.{k:07d}"
                a32 = np.asarray(vals, np.float32)
                want[base + ".min"] = float(a32.min())
                want[base + ".max"] = float(a32.max())
                want[base + ".count"] = float(len(vals))
                for q in qs:
                    want[f"{base}.{int(round(q * 100))}percentile"] = float(
                        np.quantile(np.asarray(vals), q, method="hazen"))
            got, _ = reference.expected_forward(pool, b0, b1, qs)
            assert want.keys() == got.keys(), (seed, b0, b1)
            for k, v in want.items():
                assert abs(got[k] - v) <= 1e-9 * max(1.0, abs(v)), (k, got[k], v)


def check_forwarder():
    """In a child of its own: what forwarder.py and its pool pull in."""
    code = ("import sys, json; sys.path.insert(0, sys.argv[1]); "
            "import forwarder, traffic; "
            "pool = traffic.build_forward_pool(json.loads(sys.argv[2]), 5); "
            "rpcs, _ = forwarder.encode(pool, forwarder.Digests(pool)); "
            "print(json.dumps(sorted(sys.modules)))")
    out = subprocess.run([sys.executable, "-c", code, HERE,
                          json.dumps(FORWARD_TINY)], capture_output=True,
                         text=True, check=True, cwd=ROOT)
    mods = json.loads(out.stdout)
    assert not [m for m in mods if m == "jax" or m.startswith("jax.")], mods
    ours = [m for m in mods if m.split(".")[0] == "veneur_tpu"]
    assert all(m in ("veneur_tpu", "veneur_tpu.proto")
               or re.fullmatch(r"veneur_tpu\.proto\.\w+_pb2", m)
               for m in ours), ours
    sys.path.insert(1, ROOT)
    import forwarder
    from veneur_tpu.proto import forwardrpc_pb2
    pool = traffic.build_forward_pool(FORWARD_TINY, 5)
    rpcs, sizes = forwarder.encode(pool, forwarder.Digests(pool))
    assert [len(forwardrpc_pb2.MetricList.FromString(r).metrics)
            for r in rpcs] == sizes


def brute_rank_error(x, g, q):
    """x: one timer's sorted samples. See reference.rank_errors."""
    n = len(x)
    tol = 4.0 * float(np.spacing(np.float32(abs(g))))
    near = min(x, key=lambda v: (abs(v - g), -v))
    if abs(near - g) <= tol:
        g = near
    below = [i for i in range(n) if x[i] < g]
    equal = [i for i in range(n) if x[i] == g]
    if equal:
        lo, hi = equal[0] + 0.5, equal[-1] + 0.5
    elif not below:
        lo = hi = 0.0
    elif len(below) == n:
        lo = hi = float(n)
    else:
        a, b = x[below[-1]], x[len(below)]
        f = (g - a) / (b - a)
        first_a = min(i for i in range(n) if x[i] == a)
        last_b = max(i for i in range(n) if x[i] == b)
        lo = first_a + f * (len(below) - first_a) + 0.5
        hi = below[-1] + f * (last_b - below[-1]) + 0.5
    if not below:
        lo = 0.0
    if len(below) + len(equal) == n:
        hi = float(n)
    return max(0.0, lo / n - q, q - hi / n)


def check_ranks(timers, want, seed):
    rng = np.random.default_rng(seed)
    for q in (0.5, 0.75, 0.99):
        rows = [f"pb.t.{i:07d}.{int(q * 100)}percentile"
                for i in timers.ids.tolist()]
        # as the program emits it: a float32
        exact = np.asarray([want[r] for r in rows], np.float32).astype(
            np.float64)
        # the reference's own answer, answers off by a little and by a lot,
        # and answers that are samples of the timer
        picks = timers.values[timers.starts + rng.integers(0, timers.lens)]
        # ... a sample one float32 unit up or two down (it is that sample)
        p32 = picks.astype(np.float32)
        up = np.nextafter(p32, np.float32(np.inf)).astype(np.float64)
        down = np.nextafter(np.nextafter(p32, np.float32(-np.inf)),
                            np.float32(-np.inf)).astype(np.float64)
        for got in (exact, exact * 1.003, exact + rng.normal(0, 8, len(rows)),
                    picks, up, down, np.full(len(rows), np.nan)):
            errs = reference.rank_errors(timers, got, q)
            for j, (s0, n) in enumerate(zip(timers.starts, timers.lens)):
                x = timers.values[s0:s0 + n].tolist()
                g = got[j] if np.isfinite(got[j]) else 1e9
                assert abs(errs[j] - brute_rank_error(x, g, q)) < 1e-12, (
                    seed, q, j, got[j], errs[j], brute_rank_error(x, g, q))
        assert reference.rank_errors(timers, exact, q).max() < 1e-6
        on = reference.rank_errors(timers, picks, q)
        assert np.array_equal(reference.rank_errors(timers, up, q), on)
        largest = timers.values[timers.starts + timers.lens - 1]
        assert reference.rank_errors(
            timers, np.nextafter(largest.astype(np.float32),
                                 np.float32(np.inf)).astype(np.float64),
            0.99).max() < 1 - 0.99


def check_trace_reduce():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert u == [(0, 3), (5, 8), (10, 11)], u
    assert trace_reduce.busy([(0, 2), (1, 3), (5, 6)]) == 4
    g = trace_reduce.gaps([(1, 3), (2, 4), (6, 7)], 0, 10)
    assert g == [(0, 1), (4, 6), (7, 10)], g
    assert trace_reduce.gaps([], 0, 5) == [(0, 5)]
    assert trace_reduce.clip([(0, 4), (6, 9)], 2, 7) == [(2, 4), (6, 7)]
    phases = [("tick_pause", 10, 20), ("flush_in_flight", 15, 40)]
    assert trace_reduce.phase_of(12, phases) == "tick_pause"
    assert trace_reduce.phase_of(30, phases) == "flush_in_flight"
    assert trace_reduce.phase_of(50, phases) == "steady_ingest"
    # a hand-made trace: two devices, the mark 1000 ns into the trace when
    # the host clock read 5000
    loaded = {"mark_ns": 1000, "devices": {
        0: {"ops": [("a", 1000, 1400), ("b", 1300, 1500), ("a", 1800, 1900)],
            "modules": [("jit_step(1)", 1000, 1500), ("jit_step(1)", 1800, 1900)]},
        1: {"ops": [("a", 1000, 1100)], "modules": []}}}
    r = trace_reduce.reduce(loaded, (5000, 6000), 5000, [("tick_pause", 5500, 5800)])
    assert r["busiest_device"] == 0 and abs(r["busiest_busy_s"] - 600e-9) < 1e-15
    assert abs(r["busy_s"] - 350e-9) < 1e-15 and abs(r["window_s"] - 1e-6) < 1e-15
    assert r["programs"]["jit_step(1)"]["calls"] == 2
    assert r["device_ops"][0][0] == "a" and abs(r["device_ops"][0][1] - 500e-9) < 1e-15
    assert r["idle_gaps"][0] == ["tick_pause", 300e-9], r["idle_gaps"]
    assert trace_reduce.reduce({"mark_ns": None, "devices": {}}, (0, 1), 0, []) == {}
    ctx = {"trace": r}
    assert readers.program_time(ctx, ["step"]) == (2, 600e-9)
    assert readers.trace_idle(ctx, {}) == 100.0 * (1 - 0.6)
    assert readers.trace_idle({"trace": {}}, {}) is None


def check_roofline():
    # by hand: 3 counter samples on 2 names, 2 timer samples on 1 name, in
    # one step; compaction every 2 steps; 10 digest columns
    pool = traffic.Pool(prefix="pb", lines=5,
                        kind=np.asarray([0, 0, 2, 0, 2], np.int8),
                        name=np.asarray([1, 1, 0, 2, 0], np.int32),
                        value=np.ones(5), half_rate=np.zeros(5, bool),
                        names_per_kind={"counter": 3, "timer": 1})
    records = 3 * 8 + 2 * 12
    cells = 2 * 8 + 1 * 48
    temp = 2 * 8
    compaction = 1 * 10 * 2 * 4 * 2      # one row, once in the group
    got = roofline.ingest_min_bytes(pool, 5, 2, 10)
    assert got == records + cells + temp + compaction, got


def check_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    name_re = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit_re = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"] for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert name_re.match(c["name"]) and len(c["source"]) <= 200
        assert os.path.exists(os.path.join(ROOT, c["file"])), c["file"]
        with open(os.path.join(ROOT, c["file"])) as f:
            cf = json.load(f)
        assert cf["reduced"] == c["reduced"] and cf["source"] == c["source"]
        assert cf["limits"] and set(cf["limits"]) <= {
            k for k in reference.new_numbers(cf["expect"]["percentiles"])
            if k not in reference.EXACT}
    for w in b["workloads"]:
        assert name_re.match(w["name"]) and name_re.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        traffic.load(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 2)
    assert "setup_s" in e2e
    for m in b["end_to_end"] + b["per_layer"]:
        assert name_re.match(m["name"]), m["name"]
        assert unit_re.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        spec = readers.spec_of(m["name"])
        assert spec["kind"] in readers.KINDS, m["name"]
        if spec["kind"] == "python":
            assert os.path.exists(os.path.join(readers.METRICS_DIR,
                                               spec["module"] + ".py"))
    # no cell, configuration or mix is named in the code
    names = cells | configs | {w["traffic"] for w in b["workloads"]}
    for root, _dirs, files in os.walk(HERE):
        for fn in files:
            rel = os.path.relpath(os.path.join(root, fn), ROOT)
            if "__pycache__" in rel:
                continue
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
            if fn.endswith(".py"):
                with open(os.path.join(root, fn)) as f:
                    text = f.read()
                for n in names:
                    assert n not in text, (fn, n)


def main() -> int:
    for check in (check_reference, check_forward_reference, check_forwarder,
                  check_trace_reduce, check_roofline, check_files):
        check()
        print(f"ok  {check.__name__}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
