#!/usr/bin/env python3
"""Quickest proof that the served path still starts and answers right
on a TPU: UDP datagram -> C++ ring/parse/key table -> packed h2d ->
ingest program -> swap -> flush program -> d2h -> sink, at the shipped
default widths and capacities (example.yaml), compared with a plain
NumPy reference.

    python chip_smoke.py             one chip: device, serve, kernels
    python chip_smoke.py --chips 4   one four-chip host: the sharded phase

One process, no JAX-touching children, no CPU mode and no size option:
with no TPU it exits non-zero and prints no result. The first failed
check raises. The last line of a passing run is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.

To rehearse a phase off the chip, call its function from a throw-away
snippet (the traffic scale is an argument; widths are never scaled):

    JAX_PLATFORMS=cpu python -c \\
        "import chip_smoke; chip_smoke.serve_phase(seed=0, scale=0.01)"
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# per flush window, at scale 1.0: ~61 % of the counter, gauge and
# histogram tables and half of the set table
COUNTERS = 80_000
GAUGES = 20_000
TIMERS = 10_000
TIMER_SAMPLES = 20
SETS = 2_000
SET_MEMBERS = 50
REPEAT_SHARE = 0.1          # names that get a second sample in a window
LINES_PER_DATAGRAM = 30

PERCENTILES = (0.5, 0.75, 0.99)
# relative budgets vs the midpoint-rank quantile (verify skill: <=2 %
# median, <=1 % p99)
Q_BUDGET = {0.5: 0.02, 0.75: 0.02, 0.99: 0.01}
# set cardinality within 3 % of the distinct count — or within 3 members:
# below ~100 members the estimator is linear counting, which loses exactly
# one per register collision, and among 6,000 sets of ~45 members at
# 2^14 registers a dozen are expected to collide twice (4.4 %). The mean
# error over a window's sets is held to 1 %.
SET_BUDGET = 0.03
SET_SLACK = 3.0
SET_MEAN_BUDGET = 0.01

DEFAULT_CAPACITIES = dict(
    tpu_counter_capacity=131072, tpu_gauge_capacity=32768,
    tpu_status_capacity=1024, tpu_set_capacity=4096,
    tpu_histo_capacity=16384, tpu_batch_counter=8192, tpu_batch_gauge=2048,
    tpu_batch_status=256, tpu_batch_set=4096, tpu_batch_histo=8192)


def fail(msg):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def check(cond, msg):
    if not cond:
        fail(msg)


# -- traffic ------------------------------------------------------------------

class Window:
    """One flush window of seeded traffic, plus what a plain reading of
    the DogStatsD semantics says must come out of it."""

    def __init__(self, rng, first_id_share: float, scale: float):
        def ids(n):
            # name ids: window 1 uses [0, n); later windows start half a
            # window further on, so half of the names are new
            n = max(1, int(n * scale))
            off = int(n * first_id_share)
            return np.arange(off, off + n)

        self.c_ids = ids(COUNTERS)
        self.c_val = rng.integers(1, 1000, len(self.c_ids))
        self.c_rep = rng.random(len(self.c_ids)) < REPEAT_SHARE
        self.c_val2 = rng.integers(1, 1000, len(self.c_ids))

        self.g_ids = ids(GAUGES)
        # quarter steps: exact in f32 and in the decimal wire text
        self.g_val = rng.integers(-4000, 4000, len(self.g_ids)) / 4.0
        self.g_rep = rng.random(len(self.g_ids)) < REPEAT_SHARE
        self.g_val2 = rng.integers(-4000, 4000, len(self.g_ids)) / 4.0

        self.t_ids = ids(TIMERS)
        lat = rng.gamma(2.0, 15.0, (len(self.t_ids), TIMER_SAMPLES)) + 0.5
        self.t_val = np.round(lat, 3)

        self.s_ids = ids(SETS)
        # members drawn with repeats: the distinct count is below the
        # number of inserts
        self.s_mem = rng.integers(0, 4 * SET_MEMBERS,
                                  (len(self.s_ids), SET_MEMBERS))

    def lines(self, rng):
        first = [f"smoke.c.{i:07d}:{v}|c|#k:{i % 8}"
                 for i, v in zip(self.c_ids.tolist(), self.c_val.tolist())]
        first += [f"smoke.g.{i:07d}:{v}|g"
                  for i, v in zip(self.g_ids.tolist(), self.g_val.tolist())]
        first += [f"smoke.t.{i:07d}:{v:.3f}|ms"
                  for i, row in zip(self.t_ids.tolist(),
                                    self.t_val.tolist()) for v in row]
        first += [f"smoke.s.{i:07d}:m{m}|s"
                  for i, row in zip(self.s_ids.tolist(),
                                    self.s_mem.tolist()) for m in row]
        order = rng.permutation(len(first))
        first = [first[j] for j in order.tolist()]
        # second samples go last, so a gauge's second write is its last
        second = [f"smoke.c.{i:07d}:{v}|c|@0.5|#k:{i % 8}"
                  for i, v in zip(self.c_ids[self.c_rep].tolist(),
                                  self.c_val2[self.c_rep].tolist())]
        second += [f"smoke.g.{i:07d}:{v}|g"
                   for i, v in zip(self.g_ids[self.g_rep].tolist(),
                                   self.g_val2[self.g_rep].tolist())]
        return first + second

    def datagrams(self, rng):
        lines = self.lines(rng)
        n = LINES_PER_DATAGRAM
        return [("\n".join(lines[i:i + n])).encode()
                for i in range(0, len(lines), n)]

    def expected(self):
        """name -> value for every row the sink must receive."""
        out = {}
        c = self.c_val + np.where(self.c_rep, 2 * self.c_val2, 0)
        for i, v in zip(self.c_ids.tolist(), c.tolist()):
            out[f"smoke.c.{i:07d}"] = float(v)
        g = np.where(self.g_rep, self.g_val2, self.g_val)
        for i, v in zip(self.g_ids.tolist(), g.tolist()):
            out[f"smoke.g.{i:07d}"] = float(v)
        t32 = self.t_val.astype(np.float32)
        mn, mx = t32.min(axis=1), t32.max(axis=1)
        # the digest's (and the reference Quantile's) midpoint-rank
        # convention is NumPy's "hazen" method: sample i sits at
        # cumulative mass i + 0.5
        qs = np.quantile(self.t_val, PERCENTILES, axis=1, method="hazen")
        for j, i in enumerate(self.t_ids.tolist()):
            base = f"smoke.t.{i:07d}"
            out[base + ".min"] = float(mn[j])
            out[base + ".max"] = float(mx[j])
            out[base + ".count"] = float(TIMER_SAMPLES)
            for p, col in zip(PERCENTILES, qs):
                out[f"{base}.{int(round(p * 100))}percentile"] = float(col[j])
        for j, i in enumerate(self.s_ids.tolist()):
            out[f"smoke.s.{i:07d}"] = float(len(np.unique(self.s_mem[j])))
        return out


def compare(got: dict, want: dict, tags: dict):
    """Hold the sink's rows to the reference; returns the worst errors."""
    missing = want.keys() - got.keys()
    extra = got.keys() - want.keys()
    check(not missing and not extra,
          f"{len(missing)} rows missing (e.g. {sorted(missing)[:3]}), "
          f"{len(extra)} unexpected (e.g. {sorted(extra)[:3]})")
    worst = {"p50": 0.0, "p75": 0.0, "p99": 0.0, "set": 0.0}
    set_errs = []
    for name, w in want.items():
        g = got[name]
        kind = name[6]
        # messages are built on failure only: 162k rows a window
        if kind == "s":
            set_errs.append(abs(g - w) / w)
            if abs(g - w) > max(SET_BUDGET * w, SET_SLACK):
                fail(f"{name}: estimate {g} vs {w} distinct")
        elif name.endswith("percentile"):
            p = int(name[-12:-10])
            err = abs(g - w) / abs(w)
            worst[f"p{p}"] = max(worst[f"p{p}"], err)
            if err > Q_BUDGET[p / 100.0]:
                fail(f"{name}: {g} vs {w} (rel {err:.4f})")
        elif g != w:
            # counters, gauges, timer count/min/max: exact
            fail(f"{name}: {g} != {w}")
        if kind == "c" and tags[name] != [f"k:{int(name[8:]) % 8}"]:
            fail(f"{name}: tags {tags[name]}")
    worst["set"] = max(set_errs)
    worst["set_mean"] = float(np.mean(set_errs))
    check(worst["set_mean"] <= SET_MEAN_BUDGET,
          f"mean set error {worst['set_mean']:.4f}")
    return worst


# -- phases -------------------------------------------------------------------

def device_phase(cache_dir: str) -> dict:
    import jax
    import jaxlib
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - version print only
        libtpu = "unknown"
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"compile_cache={cache_dir}", flush=True)
    return info


def _memory_sink():
    from veneur_tpu.sinks.base import MetricSink

    class MemorySink(MetricSink):
        """In-memory metric sink: keeps the last flush's smoke.* rows."""
        name = "chip_smoke"

        def __init__(self):
            self.values, self.tags = {}, {}

        def flush_frame(self, frame):
            for name, value, _t, _msg, tags, _sinks, _host in frame.rows():
                if name.startswith("smoke."):
                    check(name not in self.values,
                          f"row {name} flushed twice")
                    self.values[name] = value
                    self.tags[name] = list(tags)

        def take(self):
            out = self.values, self.tags
            self.values, self.tags = {}, {}
            return out

    return MemorySink()


def _write_config(tmpdir: str, n_shards: int) -> str:
    """example.yaml as shipped, with the listener on an ephemeral port
    and an interval longer than the run: windows end on trigger_flush,
    never on the ticker."""
    import yaml
    with open(os.path.join(REPO, "example.yaml")) as f:
        raw = yaml.safe_load(f)
    raw["statsd_listen_addresses"] = ["udp://127.0.0.1:0"]
    raw["interval"] = "3600s"
    raw["tpu_n_shards"] = n_shards
    path = os.path.join(tmpdir, "chip_smoke.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(raw, f)
    return path


def _dump_stall(server) -> None:
    """Where the datagrams stopped, and what every thread is doing."""
    import faulthandler
    agg = server.aggregator
    print(f"stall: packets_received={server.packets_received} "
          f"reader_counters={agg.reader_counters()} "
          f"ring_stats={agg.ring_stats()} engine={agg.eng.stats()}",
          flush=True)
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)


def _send_closed_loop(server, sock, addr, datagrams, base, chunk):
    """Send `chunk` datagrams at a time and wait for the engine to have
    parsed them all: nothing ever queues beyond the socket buffer, so no
    datagram is lost and the comparison can be exact."""
    eng = server.aggregator.eng
    sent = 0
    for i in range(0, len(datagrams), chunk):
        for d in datagrams[i:i + chunk]:
            sock.sendto(d, addr)
            sent += d.count(b"\n") + 1
        # parsing waits on the device only while a program compiles
        # (minutes, cold); without any progress at all it never started
        seen, since = -1, time.monotonic()
        while True:
            done = eng.stats()["processed"] - base
            if done >= sent:
                break
            check(server._pipeline_thread.is_alive(),
                  "the pipeline thread died (see the traceback above)")
            if done != seen:
                seen, since = done, time.monotonic()
            elif time.monotonic() - since > (30.0 if base + done == 0
                                             else 300.0):
                _dump_stall(server)
                fail(f"engine parsed {done} of {sent} samples sent, then "
                     "stalled")
            time.sleep(0.0005)
    return sent


def serve_phase(seed: int, scale: float = 1.0, windows: int = 3,
                n_shards: int = 0) -> None:
    """The served path, end to end, `windows` flush windows. `n_shards`
    is the config's tpu_n_shards (0 = as shipped: one shard per attached
    chip); a rehearsal on virtual CPU devices passes it explicitly."""
    import jax

    from veneur_tpu.config import read_config
    from veneur_tpu.observability import jaxruntime
    from veneur_tpu.ops import pallas_digest, pallas_ingest
    from veneur_tpu.server.factory import new_from_config

    rng = np.random.default_rng(seed)
    sink = _memory_sink()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
        cfg = read_config(_write_config(tmp, n_shards))
        for key, want in DEFAULT_CAPACITIES.items():
            check(getattr(cfg, key) == want,
                  f"example.yaml {key}={getattr(cfg, key)}, not the "
                  f"default {want}")
        check(list(cfg.percentiles) == list(PERCENTILES)
              and list(cfg.aggregates) == ["min", "max", "count"],
              "example.yaml percentiles/aggregates are not the defaults")
        check(cfg.native_ingest and cfg.native_udp_readers,
              "example.yaml does not ship the native engine on")
        server = new_from_config(cfg, extra_metric_sinks=[sink])
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    server.start()
    try:
        agg = server.aggregator
        check(server._native and server._native_readers_active,
              "the Python parser is serving: the native engine did not "
              "build or load")
        shards = agg.n_shards
        want_shards = n_shards or (
            len(jax.devices()) if jax.devices()[0].platform != "cpu" else 1)
        check(shards == want_shards,
              f"{shards} shards, expected {want_shards}")
        ingest_path = ("pallas" if shards == 1 and pallas_ingest.active()
                       else "xla")
        quantile_path = "pallas" if pallas_digest.enabled() else "xla"
        print(f"serve: aggregator={type(agg).__name__} shards={shards} "
              f"parse_engine=native(C++) udp_readers=native "
              f"ingest_path={ingest_path} quantile_path={quantile_path}",
              flush=True)

        addr = server.local_addr()
        rcvbuf = server._sockets[0].getsockopt(socket.SOL_SOCKET,
                                               socket.SO_RCVBUF)
        # a datagram of ~30 lines costs the kernel up to ~2.3 KB of
        # buffer accounting; stay a factor of four inside the buffer
        chunk = max(4, rcvbuf // (4 * 2304))
        eng = agg.eng
        for w in range(1, windows + 1):
            # window 2 renames half of window 1's keys; window 3 keeps
            # window 2's names and sends new values
            win = Window(np.random.default_rng([seed, w]),
                         0.0 if w == 1 else 0.5, scale)
            dgrams = win.datagrams(rng)
            want = win.expected()
            c0 = jaxruntime.compiles_total()
            s0 = jaxruntime.compile_time_ns_total()
            base = eng.stats()["processed"]
            recv0 = server.packets_received
            t0 = time.perf_counter()
            sent = _send_closed_loop(server, sock, addr, dgrams, base,
                                     chunk)
            ingest_wall = time.perf_counter() - t0
            st = eng.stats()
            processed = st["processed"] - base
            check(processed == sent, f"processed {processed} != sent {sent}")
            check(server.packets_received - recv0 == len(dgrams),
                  f"{server.packets_received - recv0} datagrams received, "
                  f"{len(dgrams)} sent")
            dropped = (st["dropped"] + server.packets_dropped
                       + server.packets_toolong)
            check(dropped == 0, f"{dropped} drops (table/ring/too-long)")
            perr = server.parse_errors + agg.extra_parse_errors()
            check(perr == 0, f"{perr} parse errors")
            t0 = time.perf_counter()
            check(server.trigger_flush(timeout=900.0),
                  "flush failed (see the log above)")
            flush_wall = time.perf_counter() - t0
            check(server.internal_errors == 0,
                  f"internal_errors={server.internal_errors}")
            got, tags = sink.take()
            worst = compare(got, want, tags)
            compiles = jaxruntime.compiles_total() - c0
            compile_s = (jaxruntime.compile_time_ns_total() - s0) / 1e9
            print(f"window {w}: datagrams={len(dgrams)} sent={sent} "
                  f"processed={processed} dropped=0 internal_errors=0 "
                  f"ingest_wall_s={ingest_wall:.3f} "
                  f"flush_wall_s={flush_wall:.3f} rows_flushed={len(got)} "
                  f"compiles={compiles} compile_s={compile_s:.2f} "
                  f"worst_rel_err p50={worst['p50']:.2e} "
                  f"p75={worst['p75']:.2e} p99={worst['p99']:.2e} "
                  f"set={worst['set']:.2e} set_mean={worst['set_mean']:.2e}",
                  flush=True)
            if w == 3:
                check(compiles == 0,
                      f"window 3 compiled {compiles} programs")
        if shards > 1:
            leaves = jax.tree.leaves(agg.state)
            placed = [sorted(s.device.id for s in a.addressable_shards)
                      for a in leaves]
            check(all(len(set(p)) == shards for p in placed),
                  f"state leaves not on {shards} distinct devices: {placed}")
            print(f"sharded: every one of {len(leaves)} state leaves has "
                  f"addressable shards on devices {placed[0]}", flush=True)
        for label, s in sorted(jaxruntime.hbm_stats().items()):
            print(f"memory {label}: bytes_in_use={s['bytes_in_use']} "
                  f"peak_bytes_in_use={s['peak_bytes_in_use']}", flush=True)
    finally:
        sock.close()
        server.shutdown()
    print("serve: clean shutdown", flush=True)


def _timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, first, time.perf_counter() - t0


def kernels_phase(seed: int, interpret: bool = False) -> None:
    """Each Pallas kernel that is on: compiled (not interpreted) at the
    production widths and compared on the device with its XLA twin.
    `interpret` is for a rehearsal off the chip only."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from veneur_tpu.aggregation import step
    from veneur_tpu.aggregation.state import empty_state
    from veneur_tpu.config import Config
    from veneur_tpu.history import merge as hmerge
    from veneur_tpu.ops import (hll, pallas_digest, pallas_history,
                                pallas_ingest, tdigest)
    from veneur_tpu.server.server import spec_from_config

    rng = np.random.default_rng([seed, 99])
    spec = spec_from_config(Config())

    if pallas_digest.ENABLED:
        r, c = spec.histo_capacity, spec.total_cells
        mean = rng.gamma(2.0, 15.0, (r, c)).astype(np.float32)
        weight = rng.integers(0, 4, (r, c)).astype(np.float32)
        weight[rng.random(r) < 0.05] = 0.0          # empty digests
        mn = np.where(weight > 0, mean, np.inf).min(axis=1)
        mx = np.where(weight > 0, mean, -np.inf).max(axis=1)
        qs = jnp.asarray([0.5, 0.75, 0.99, 0.5], jnp.float32)
        args = tuple(jnp.asarray(a, jnp.float32)
                     for a in (mean, weight, mn, mx)) + (qs,)
        got, c1, t1 = _timed(jax.jit(partial(
            pallas_digest.quantiles_rows, interpret=interpret)), *args)
        ref, c2, t2 = _timed(jax.jit(jax.vmap(
            tdigest._quantiles_one, in_axes=(0, 0, 0, 0, None))), *args)
        got, ref = np.asarray(got), np.asarray(ref)
        check(got.shape == (r, 4), f"quantile kernel shape {got.shape}")
        # the parity suite's tolerance (tests/test_pallas_digest.py)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5,
                                   equal_nan=True)
        print(f"kernel digest_quantiles: pallas == xla on [{r}, {c}] x 4 "
              f"quantiles (rtol 1e-5); first call {c1:.2f}s / {c2:.2f}s, "
              f"second {t1 * 1e3:.2f}ms / {t2 * 1e3:.2f}ms (pallas / xla)",
              flush=True)
    else:
        print("kernel digest_quantiles: off by module constant", flush=True)

    if pallas_ingest.ENABLED:
        cfg = Config()

        def batch(exact: bool):
            """A full default-size batch. `exact`: timer values are
            powers of two, so every f32 sum (and reciprocal sum) is
            exact whatever order the adds are applied in."""
            def slots(cap, n):
                s = rng.integers(0, cap, n).astype(np.int32)
                s[-n // 16:] = cap                   # padding tail
                return s
            nh = cfg.tpu_batch_histo
            val = (2.0 ** rng.integers(-2, 9, nh) if exact
                   else rng.gamma(2.0, 15.0, nh))
            return jax.device_put(step.Batch(
                counter_slot=slots(spec.counter_capacity,
                                   cfg.tpu_batch_counter),
                counter_inc=rng.integers(1, 9, cfg.tpu_batch_counter)
                .astype(np.float32),
                gauge_slot=slots(spec.gauge_capacity, cfg.tpu_batch_gauge),
                gauge_val=rng.normal(size=cfg.tpu_batch_gauge)
                .astype(np.float32),
                status_slot=slots(spec.status_capacity,
                                  cfg.tpu_batch_status),
                status_val=rng.integers(0, 4, cfg.tpu_batch_status)
                .astype(np.float32),
                set_slot=slots(spec.set_capacity, cfg.tpu_batch_set),
                set_reg=rng.integers(0, spec.registers, cfg.tpu_batch_set)
                .astype(np.int32),
                set_rho=rng.integers(1, 51, cfg.tpu_batch_set)
                .astype(np.uint8),
                histo_slot=slots(spec.histo_capacity // 8, nh),
                histo_val=val.astype(np.float32),
                histo_wt=np.ones(nh, np.float32)))

        def fused(state, b):
            return step._fold_core(pallas_ingest.fused_ingest_core(
                state, b, spec=spec, interpret=interpret))

        chain = partial(step.ingest_core, spec=spec, allow_pallas=False)
        f_fused = jax.jit(fused, donate_argnums=(0,))
        f_chain = jax.jit(chain, donate_argnums=(0,))
        exact = [batch(True) for _ in range(3)]
        rounded = batch(False)
        timed = [batch(False) for _ in range(4)]

        def run(fn):
            state = empty_state(spec)
            for b in exact:
                state = fn(state, b)
            after_exact = [np.asarray(a) for a in state]
            state = fn(state, rounded)
            after_rounded = [np.asarray(a) for a in state]
            t0 = time.perf_counter()
            for b in timed:
                state = fn(state, b)
            jax.block_until_ready(state)
            return (after_exact, after_rounded,
                    (time.perf_counter() - t0) / len(timed))

        got_e, got_r, t1 = run(f_fused)
        ref_e, ref_r, t2 = run(f_chain)
        names = step.DeviceState._fields
        for name, a, b in zip(names, got_e, ref_e):
            check(np.array_equal(a, b, equal_nan=True),
                  f"fused ingest leaf {name} differs from the XLA chain "
                  f"in {int((a != b).sum())} of {a.size} elements")
        # One more batch, with values whose sums round, onto that
        # identical state. XLA's TPU scatter-add applies duplicate
        # updates of one (row, cell) in an order of its own, the kernel
        # in stream order, so an f32 sum of three or more addends may
        # differ in its last bits — and in nothing else.
        sums = {"h_wm", "h_sum_hi", "h_sum_lo", "h_recip_hi", "h_recip_lo"}
        off = 0
        for name, a, b in zip(names, got_r, ref_r):
            if name in sums:
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                           err_msg=name)
                off += int((a != b).sum())
            else:
                check(np.array_equal(a, b, equal_nan=True),
                      f"fused ingest leaf {name} differs from the XLA "
                      f"chain in {int((a != b).sum())} of {a.size} elements")
        print(f"kernel fused_ingest: pallas == xla byte for byte on "
              f"{len(names)} state leaves over 3 exact-sum batches; after a "
              f"rounding batch {off} f32 sums differ in the last bits "
              f"(rtol 1e-5), all else identical; a step then takes "
              f"{t1 * 1e3:.2f}ms / {t2 * 1e3:.2f}ms (pallas / xla)",
              flush=True)
    else:
        print("kernel fused_ingest: off by module constant", flush=True)

    if pallas_history.ENABLED:
        from veneur_tpu.history.spec import HistorySpec
        hs = HistorySpec()
        n, w, s, p = 64, hs.total_cols, 8, hs.hll_precision
        regs = rng.integers(0, 52, (n, w, 1 << p)).astype(np.uint8)
        regs[rng.random((n, w)) < 0.5] = 0
        rows = jnp.asarray(hll.pack_registers_np(regs, p))
        sel = jnp.asarray((rng.random((s, w)) < 0.3).astype(np.float32))
        got, c1, t1 = _timed(jax.jit(partial(
            pallas_history.merge_windows_packed, precision=p,
            interpret=interpret)), rows, sel)
        ref, c2, t2 = _timed(jax.jit(partial(
            hmerge._merge_windows_xla, precision=p)), rows, sel)
        check(np.array_equal(np.asarray(got), np.asarray(ref)),
              "history merge kernel differs from the XLA chain")
        print(f"kernel history_merge: pallas == xla byte for byte on "
              f"[{n}, {w}, {rows.shape[2]}] x {s} steps; second call "
              f"{t1 * 1e3:.2f}ms / {t2 * 1e3:.2f}ms (pallas / xla)",
              flush=True)
    else:
        print("kernel history_merge: off by module constant", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on a four-chip host")
    args = ap.parse_args(argv)

    from veneur_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX reports {platform!r}); "
                         "this script has no CPU mode")
    info = device_phase(cache_dir)
    check(info["count"] == args.chips,
          f"{info['count']} devices attached, --chips {args.chips}")
    if args.chips == 4:
        serve_phase(args.seed, windows=1)
    else:
        serve_phase(args.seed)
        kernels_phase(args.seed)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
