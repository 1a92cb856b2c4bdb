"""One run of one cell: the server in this process, the load generator in
a child, the tick protocol between them, and the comparison after the
window.

The tick protocol (README.md has it in ten lines): pause the generator
and note where the stream stopped; wait until the server has taken in all
that was sent; enqueue the flush request the server's own ticker would
send; wait until the swap has happened; resume. Interval boundaries are
then known to the reference although ingest and flush overlap.

The traffic file's `ingress` picks the way in (`Udp`, `Forward`): the
child, what counts as taken in, the credit, the pool and what the
reference expects of an interval. The protocol is the same for both.

From the program this module takes the system under test (built the way
cli/server.py builds it) and its counters and phase timers. Nothing here
is imported by the program.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

import reference
import sender as S
import traffic as T

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
INTERVAL_S = 10.0
STALL_NS = 250_000_000      # a round of the credit's publisher this long is said


class RunError(RuntimeError):
    """The run could not be made (not: it was made and is not correct)."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the cell's files ---------------------------------------------------------

def load_cell(name: str) -> dict:
    """The cell's entry, configuration, traffic and per-layer metrics, all
    found by name: BENCHMARK.json names them, the directories hold them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has: {sorted(cells)})")
    cell = dict(cells[name])
    with open(os.path.join(HERE, "configs", cell["config"] + ".json")) as f:
        cell["config_file"] = json.load(f)
    cell["traffic_path"] = os.path.join(HERE, "traffic",
                                        cell["traffic"] + ".json")
    cell["traffic_file"] = T.load(cell["traffic_path"])

    def mine(metric):
        return name in metric.get("workloads", [name])

    cell["end_to_end"] = [m for m in bench["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in bench["per_layer"] if mine(m)]
    return cell


# -- the system under test ----------------------------------------------------

def make_sink():
    from veneur_tpu.sinks.base import MetricSink

    class FrameSink(MetricSink):
        """Stamps the moment it is handed each flush's frame and keeps the
        frame; the rows are read out once the window has closed."""
        name = "perfbench"

        def __init__(self):
            self.handed = []          # (monotonic ns, frame)

        def flush_frame(self, frame):
            self.handed.append((time.monotonic_ns(), frame))

    return FrameSink()


def frame_rows(frame, prefix: str):
    """name -> value, name -> tags (counters only) and the number of rows
    that came twice, for the rows of the pool's own names."""
    values, tags, twice = {}, {}, 0
    head, counter = prefix + ".", prefix + ".c."
    for name, value, _t, _msg, tg, _sinks, _host in frame.rows():
        if name.startswith(head):
            if name in values:
                twice += 1
            values[name] = value
            if name.startswith(counter):
                tags[name] = list(tg)
    return values, tags, twice


def build_server(config_file: dict, tmpdir: str, sink, overrides=None):
    """example.yaml with the configuration's overrides, through
    config.read_config and server.factory.new_from_config, as
    cli/server.py does; the listener on an ephemeral port and the
    server's own ticker set long: the harness issues the tick."""
    import yaml

    from veneur_tpu.config import read_config
    from veneur_tpu.server.factory import new_from_config
    with open(os.path.join(ROOT, config_file["base"])) as f:
        raw = yaml.safe_load(f)
    for key, want in config_file["expect"].items():
        if raw.get(key) != want:
            raise RunError(f"{config_file['base']} {key}={raw.get(key)!r}, "
                           f"the configuration states {want!r}")
    raw.update(config_file["overrides"])
    raw.update(overrides or {})
    path = os.path.join(tmpdir, "server.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(raw, f)
    cfg = read_config(path)
    return new_from_config(cfg, extra_metric_sinks=[sink])


def describe(server) -> dict:
    import jax

    from veneur_tpu.ops import pallas_digest, pallas_ingest
    agg = server.aggregator
    if not (server._native and server._native_readers_active):
        raise RunError("the Python parser is serving: the native engine "
                       "or the native readers did not build or load")
    shards = agg.n_shards
    return {
        "aggregator": type(agg).__name__, "shards": shards,
        "ingest_path": ("pallas" if shards == 1 and pallas_ingest.active()
                        else "xla"),
        "quantile_path": "pallas" if pallas_digest.enabled() else "xla",
        "devices": len(jax.devices()),
    }


def counters(server) -> dict:
    """Every count the per-layer readers may name, flat. eng.stats() is
    read from this thread as chip_smoke.py reads it (three u64 loads)."""
    from veneur_tpu.observability import jaxruntime
    agg = server.aggregator
    out = {"steps_total": agg.steps_total, "h2d_bytes": agg.h2d_bytes,
           "dispatch_ns": agg.dispatch_ns,
           "packets_dropped": server.packets_dropped,
           "packets_toolong": server.packets_toolong,
           "parse_errors_py": server.parse_errors,
           "internal_errors": server.internal_errors,
           "imported_total": server.imported_total,
           "import_errors": server.import_errors,
           "intervals_deferred": server.flush_intervals_deferred,
           "compiles_total": jaxruntime.compiles_total(),
           "compile_ns": jaxruntime.compile_time_ns_total()}
    out.update({f"ring.{k}": v for k, v in agg.ring_stats().items()})
    out.update({f"eng.{k}": v for k, v in agg.eng.stats().items()})
    out.update({f"reader.{k}": v for k, v in agg.reader_counters().items()})
    return out


def phase_totals(server) -> dict:
    """phase -> (count, sum in ns) of veneur.flush.phase_duration_ns. Read
    from the timer's own state: its public snapshot() folds the buffered
    samples through a device program, which a harness thread must not
    start inside the window."""
    timer = server._t_flush_phase
    with timer._lock:
        return {key[0]: (st.count, st.sum)
                for key, st in timer._states.items()}


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


# -- the ways in ----------------------------------------------------------------

class Ingress:
    """A way in, made from the traffic file."""

    def __init__(self, spec: dict):
        self.spec = spec


class Udp(Ingress):
    """DogStatsD over UDP: `sender.py` streams the pool's datagrams; taken
    in is what the engine has parsed, in samples."""
    child, unit, position = "sender.py", "samples", "datagrams"

    def build_pool(self, seed: int):
        return T.build_pool(self.spec, seed)

    @staticmethod
    def positions(pool) -> int:
        return pool.n_datagrams

    @staticmethod
    def second_warmup(n: int) -> int:
        """Positions sent between the warm-up's tick and tick 0: a cycle."""
        return n

    @staticmethod
    def taken_in(server) -> int:
        return server.aggregator.eng.stats()["processed"]

    @staticmethod
    def queue_empty(server) -> bool:
        return server.aggregator.eng.reader_counters()["ring_depth"] == 0

    def open(self, server):
        """(port, credit in samples, what was chosen)."""
        rcvbuf = server._sockets[0].getsockopt(socket.SOL_SOCKET,
                                               socket.SO_RCVBUF)
        # a datagram of ~30 lines costs the kernel up to ~2.3 KB of buffer
        # accounting; stay a factor of four inside the socket buffer, so
        # that nothing is dropped even if the reader thread is not run
        credit_d = max(4, rcvbuf // (4 * 2304))
        return (server.local_addr()[1],
                credit_d * self.spec["lines_per_datagram"],
                f"credit {credit_d} datagrams (socket buffer {rcvbuf} B)")

    @staticmethod
    def stalled(server) -> str:
        agg = server.aggregator
        return f"engine parsed {agg.eng.stats()}; ring {agg.ring_stats()}"

    @staticmethod
    def expected(pool, b0, b1, percentiles):
        return reference.expected(pool, b0, b1, percentiles)

    @staticmethod
    def control_rows(pool, b0, b1, percentiles, control) -> dict:
        """The reference's counters kept in the control's lower precision,
        and its sets from a plain HyperLogLog."""
        low, _ = reference.expected(
            pool, b0, b1, percentiles,
            counter_dtype=getattr(np, control["counter_dtype"]))
        low.update(reference.hll_estimates(pool, b0, b1,
                                           control["hll_precision"]))
        return low

    @staticmethod
    def lost(c_after: dict, c_start: dict, pool) -> int:
        """Samples dropped, refused or unparsed over the window."""
        return ((c_after["eng.dropped"] - c_start["eng.dropped"])
                + lines_of(c_after, c_start, "ring.ring_dropped", pool)
                + lines_of(c_after, c_start, "packets_dropped", pool)
                + lines_of(c_after, c_start, "packets_toolong", pool)
                + lines_of(c_after, c_start, "reader.toolong", pool)
                + (c_after["eng.parse_errors"] - c_start["eng.parse_errors"])
                + (c_after["parse_errors_py"] - c_start["parse_errors_py"]))


class Forward(Ingress):
    """Sketches forwarded by N locals over gRPC: `forwarder.py` sends the
    pool's RPCs to the global's `grpc_port`; taken in is the server's
    `imported_total`, in metrics, counted on the pipeline thread as each
    import is folded. The imports wait in the server's FIFO packet queue
    ahead of the tick's flush request."""
    child, unit, position = "forwarder.py", "metrics", "RPCs"
    # RPCs sent ahead of what is taken in: the pipeline thread's queue at a
    # pause, so tick_to_sink_mean_s holds its drain (8 RPCs is ~0.1 s of
    # work at 74k metrics/s, and outlasts the publisher's and an RPC's
    # round trips, ~1 ms each, at ten times that)
    CREDIT_RPCS = 8

    def build_pool(self, seed: int):
        return T.build_forward_pool(self.spec, seed)

    @staticmethod
    def positions(pool) -> int:
        return pool.n_rpcs

    def second_warmup(self, n: int) -> int:
        """One burst: the first cycle has made every key and compiled every
        shape, and a cycle at the rate a global takes its imports is tens
        of seconds of set-up."""
        return n // int(self.spec["bursts"])

    @staticmethod
    def taken_in(server) -> int:
        return server.imported_total

    @staticmethod
    def queue_empty(server) -> bool:
        return server.packet_queue.empty()

    def open(self, server):
        if server.grpc_port is None:
            raise RunError("a forward mix needs a configuration that sets "
                           "grpc_address: the server serves no gRPC import")
        credit = self.CREDIT_RPCS * int(self.spec["metrics_per_rpc"])
        return server.grpc_port, credit, f"credit {credit} metrics"

    @staticmethod
    def stalled(server) -> str:
        return (f"server imported {server.imported_total} "
                f"({server.import_errors} import errors); packet queue "
                f"{server.packet_queue.qsize()}")

    @staticmethod
    def expected(pool, b0, b1, percentiles):
        return reference.expected_forward(pool, b0, b1, percentiles)

    @staticmethod
    def control_rows(pool, b0, b1, percentiles, control) -> dict:
        """The reference's counters kept in the control's lower precision."""
        low, _ = reference.expected_forward(
            pool, b0, b1, percentiles,
            counter_dtype=getattr(np, control["counter_dtype"]))
        return low

    @staticmethod
    def lost(c_after: dict, c_start: dict, pool) -> int:
        """Metrics the server refused as it imported them."""
        return c_after["import_errors"] - c_start["import_errors"]


INGRESS = {"udp": Udp, "forward": Forward}


# -- the run ------------------------------------------------------------------

class Run:
    """State of one run. `t_process` is the process's start on the
    monotonic clock, for setup_s."""

    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 t_process: float, control: bool = False):
        self.cell, self.seed, self.trace = cell, int(seed), bool(trace)
        self.ticks = max(1, int(seconds // INTERVAL_S))
        self.t_process = t_process
        self.control = cell["config_file"]["control"] if control else None
        # the control switches on the program's own lower-precision path
        self.overrides = dict(self.control["overrides"]) if control else {}
        self.prefix = cell["traffic_file"].get("prefix", "pb")
        self.ingress = INGRESS[cell["traffic_file"].get("ingress", "udp")](
            cell["traffic_file"])
        self.phases = []              # (name, start ns, end ns), host clock
        self.tick_log = []            # per tick: b, sent, t_ns, req, ...
        self.trace_dir = self.trace_mark_ns = self.trace_span = None
        # what _cleanup releases, whatever point the run reached
        self.server = self.child = self.ctl = self.mm = None
        self._publisher = None
        self._stop_publish = threading.Event()
        self.pub = {"n": 0, "gap_max_ns": 0, "ring_empty": 0, "ahead": 0}
        self._shut = True

    # the control block
    def _wait(self, cond, what: str, timeout: float, poll=0.0005):
        end = time.monotonic() + timeout
        while not cond():
            if self.child.poll() is not None:
                raise RunError(f"the {self.ingress.child} child exited "
                               f"({self.child.returncode}) while waiting "
                               f"for {what}")
            if not self.server._pipeline_thread.is_alive():
                raise RunError("the pipeline thread died")
            if self._publisher is not None and not self._publisher.is_alive():
                raise RunError("the credit's publisher died")
            if time.monotonic() > end:
                return False
            time.sleep(poll)
        return True

    def _publish(self):
        """Copies the engine's count into the control block about once a
        millisecond, and keeps for the per-interval line what the credit's
        round trip looked like while the sender was running: how many
        times it published, the longest time between two, how often the
        ring (forward: the packet queue) stood empty and how far ahead the
        sender was. A round of over STALL_NS starves the sender of credit
        for a real part of a stretch, so it is said at once, with where the
        time went: asleep or waiting for the interpreter, or in the count
        (UDP: eng.stats(), the engine's key lock, and the interpreter again
        on the way back)."""
        server, ctl, base = self.server, self.ctl, self.base
        taken_in, queue_empty = self.ingress.taken_in, self.ingress.queue_empty
        pub, clock = self.pub, time.monotonic_ns
        last = clock()
        while not self._stop_publish.is_set():
            t_woke = clock()
            done = taken_in(server) - base
            ctl[S.PROCESSED] = done
            now = clock()
            if ctl[S.CMD] == S.RUN:
                pub["n"] += 1
                pub["gap_max_ns"] = max(pub["gap_max_ns"], now - last)
                pub["ring_empty"] += queue_empty(server)
                pub["ahead"] += ctl[S.SENT] - done
                if now - last > STALL_NS:
                    # in the warm-up's first stretch no tick has resumed
                    # the sender yet: an IndexError here killed this
                    # thread and starved the sender for good
                    since = (f"{(now - self.phases[-1][2]) / 1e9:.2f} s "
                             "after the last resume" if self.phases
                             else "before the first tick")
                    say(f"credit: not published for {(now - last) / 1e6:.0f} "
                        f"ms ({(t_woke - last) / 1e6:.0f} asleep or waiting "
                        f"for the interpreter, {(now - t_woke) / 1e6:.0f} "
                        f"reading the count and back), {since}")
            last = clock()
            time.sleep(0.001)

    def _processed(self) -> int:
        return self.ingress.taken_in(self.server) - self.base

    def _drained(self):
        return self._processed() >= self.ctl[S.SENT]

    def _pause(self):
        ctl = self.ctl
        ctl[S.SEQ] += 1
        ctl[S.CMD] = S.PAUSE
        if not self._wait(lambda: ctl[S.ACK] == ctl[S.SEQ], "the pause", 30):
            raise RunError("the sender did not pause")
        return ctl[S.POS], ctl[S.SENT], ctl[S.LAST_SEND_NS]

    def _tick(self, wait_flush: bool):
        """pause -> drained -> flush request -> swap -> resume. Returns the
        tick's record; the sender is running again when this returns."""
        t_pause = time.monotonic_ns()
        b, sent, t_last = self._pause()
        blocked_ns, pub = self.ctl[S.BLOCKED_NS], dict(self.pub)
        pub["poll_max_ns"], self.ctl[S.POLL_MAX_NS] = self.ctl[S.POLL_MAX_NS], 0
        self.pub["gap_max_ns"] = 0
        drained = self._wait(self._drained, "the engine to drain", 20)
        over = self._processed() - self.ctl[S.SENT]
        if over:
            # the server counts only what the sender sent, or the drain
            # test means nothing
            raise RunError(f"the server has taken in {over} "
                           f"{self.ingress.unit} more than were sent")
        table = self.server.aggregator.table
        t_req = time.monotonic_ns()
        req = self.server.trigger_flush(wait=False)
        # the swap has happened once the aggregator holds a fresh key
        # table (NativeAggregator.swap replaces it after eng.reset(), on
        # the pipeline thread, which parses nothing more until the
        # request is handled); a refused or failed request finishes
        swapped = self._wait(
            lambda: self.server.aggregator.table is not table
            or req.done.is_set(), "the swap", 900 if wait_flush else 120)
        t_swap = time.monotonic_ns()
        rec = {"b": b, "sent": sent, "t_ns": t_last, "req": req,
               "drained": drained, "swapped": swapped, "t_pause": t_pause,
               "t_req": t_req, "t_swap": t_swap, "blocked_ns": blocked_ns,
               "pub": pub}
        if wait_flush and not req.wait(900):
            raise RunError(f"the warm-up flush failed: {req.detail}")
        self.ctl[S.CMD] = S.RUN
        rec["t_resume"] = time.monotonic_ns()
        self.phases.append(("tick_pause", t_pause, rec["t_resume"]))
        return rec

    def _send_until(self, limit: int, timeout: float):
        ctl = self.ctl
        ctl[S.LIMIT] = limit
        ctl[S.CMD] = S.RUN
        ok = self._wait(lambda: ctl[S.POS] >= limit and self._drained(),
                        f"{limit} {self.ingress.position} to be taken in",
                        timeout, 0.002)
        if not ok:
            raise RunError(
                f"{ctl[S.SENT]} {self.ingress.unit} sent, then stalled: "
                + self.ingress.stalled(self.server))

    # tracing a slice of the window
    def _trace_start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        self.trace_dir = os.path.join(self.tmp, "trace")
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        t0 = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("perfbench.mark"):
            self.trace_mark_ns = time.monotonic_ns()
            time.sleep(0.001)
        return t0

    def _trace_stop(self, t0):
        import jax
        t1 = time.monotonic_ns()
        jax.profiler.stop_trace()
        self.trace_span = (t0, t1)

    def execute(self) -> dict:
        with tempfile.TemporaryDirectory(prefix="perfbench-") as tmp:
            self.tmp = tmp
            try:
                return self._execute()
            finally:
                self._cleanup()

    def _cleanup(self):
        self._stop_publish.set()
        if self.ctl is not None:
            self.ctl[S.CMD] = S.STOP
        if self.child is not None:
            try:
                self.child.wait(10)
            except subprocess.TimeoutExpired:
                self.child.kill()
                self.child.wait()
        if self._publisher is not None:
            self._publisher.join(5)
        if not self._shut:
            self.server.shutdown()
            self._shut = True
        if self.ctl is not None:
            self.ctl.release()
            self.mm.close()
            self.ctl = None

    def _execute(self) -> dict:
        cell, cfgf = self.cell, self.cell["config_file"]
        # the sender first: it builds its pool while the server starts
        ctl_path = os.path.join(self.tmp, "control")
        self.mm, self.ctl = S.open_block(ctl_path, create=True)
        ctl = self.ctl
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, self.ingress.child), ctl_path,
             cell["traffic_path"], str(self.seed)])
        self.sink = make_sink()
        self.server = build_server(cfgf, self.tmp, self.sink, self.overrides)
        self._shut = False
        self.server.start()
        self.info = describe(self.server)
        say("serve: " + " ".join(f"{k}={v}" for k, v in self.info.items()))
        self.base = self.ingress.taken_in(self.server)
        port, ctl[S.CREDIT], chosen = self.ingress.open(self.server)
        ctl[S.LIMIT] = 0
        ctl[S.PORT] = port
        self._publisher = threading.Thread(target=self._publish, daemon=True,
                                           name="perfbench-credit")
        self._publisher.start()
        if not self._wait(lambda: ctl[S.STATE] >= S.READY,
                          "the sender's pool", 120, 0.005):
            raise RunError("the sender built no pool")
        n_d = ctl[S.N_DATAGRAMS]
        say(f"{self.ingress.child}: pool of {n_d} {self.ingress.position}, "
            + chosen)

        # warm-up: one pool cycle, a tick, its emission (compiles or loads
        # the ingest program with its compaction branch, the swap and the
        # flush program at this cell's own row count); then a second
        # cycle (forward: a burst) and tick 0, which is not waited for
        c0 = counters(self.server)
        self._send_until(n_d, 1100)
        self._tick(wait_flush=True)
        self._send_until(n_d + self.ingress.second_warmup(n_d), 300)
        ctl[S.LIMIT] = 2 ** 62
        tick0 = self._tick(wait_flush=False)
        t0 = tick0["t_swap"]
        setup_s = t0 / 1e9 - self.t_process
        self.tick_log.append(tick0)
        c_start = counters(self.server)
        p_start = phase_totals(self.server)
        say(f"setup: {setup_s:.3f} s, compiles {c_start['compiles_total']} "
            f"({(c_start['compile_ns'] - c0['compile_ns']) / 1e9:.2f} s "
            f"compiling or loading)")

        # the window: tick k at t0 + 10 k seconds. A traced run profiles
        # the last tick, from 3 s before it until its flush is out, and
        # stops the profiler once the window has closed: stop_trace holds
        # the interpreter for seconds (4 to 20 by the cell), and inside the
        # window that made the next tick late and its interval half as
        # long again as the others (PERF.md, section 6)
        trace_t0 = None
        for k in range(1, self.ticks + 1):
            due = t0 + int(k * INTERVAL_S * 1e9)
            if self.trace and k == self.ticks:
                time.sleep(max(0.0, (due - 3e9 - time.monotonic_ns()) / 1e9))
                trace_t0 = self._trace_start()
            time.sleep(max(0.0, (due - time.monotonic_ns()) / 1e9))
            rec = self._tick(wait_flush=False)
            self.tick_log.append(rec)
        c_end = counters(self.server)
        if self.trace:
            rec["req"].done.wait(8)
            time.sleep(0.5)
            self._trace_stop(trace_t0)
        # The window ends as it began, at a swap: the engine has parsed
        # all that was sent and the device has worked off its queue (the
        # swap waits for it), so the samples counted are the work done in
        # the span, K sending stretches and K pauses. The sender keeps
        # sending until the last flush has been emitted, so that it runs
        # under load like the others; that tail is not counted.
        window_s = (self.tick_log[-1]["t_swap"] - t0) / 1e9
        for rec in self.tick_log:
            if not rec["req"].done.wait(120):
                rec["req"].detail = rec["req"].detail or "timed out"
        p_end = phase_totals(self.server)
        c_after = counters(self.server)
        ctl[S.CMD] = S.STOP
        self.child.wait(20)
        digest_child = ctl[S.POOL_DIGEST]
        peak = memory_peak()
        self._stop_publish.set()
        self.server.shutdown()
        self._shut = True
        say("serve: clean shutdown")

        return self._judge(t0, window_s, setup_s, c_start, c_end, c_after,
                           p_start, p_end, peak, digest_child)

    def _judge(self, t0, window_s, setup_s, c_start, c_end, c_after, p_start,
               p_end, peak, digest_child) -> dict:
        cell, cfgf = self.cell, self.cell["config_file"]
        log, frames = self.tick_log, self.sink.handed
        attempted = log[-1]["sent"] - log[0]["sent"]
        ingress = self.ingress
        pool = ingress.build_pool(self.seed)
        if int(pool.digest()[:15], 16) != digest_child:
            raise RunError("the sender's pool is not the reference's pool")
        percentiles = cfgf["expect"]["percentiles"]
        numbers, examples = reference.new_numbers(percentiles), []
        widest = {}                   # per percentile: the timer behind _rank_max
        failed, latencies = 0, []
        # flushes are emitted in order: the frames are those of the
        # warm-up, of tick 0 and of each tick whose request succeeded
        at = 2 if log[0]["req"].ok else 1
        for k in range(1, len(log)):
            rec, prev = log[k], log[k - 1]
            sent_k = rec["sent"] - prev["sent"]
            cycles = (rec["b"] - prev["b"]) / ingress.positions(pool)
            req = rec["req"]
            if not (req.done.is_set() and req.ok) or len(frames) <= at:
                failed += sent_k
                examples.append(f"flush {k} not emitted: {req.detail}")
                say(f"interval {k}: {sent_k} {ingress.unit}, {cycles:.2f} "
                    f"pool cycles, NOT EMITTED ({req.detail})")
                continue
            e_ns, frame = frames[at]
            at += 1
            latencies.append((e_ns - rec["t_ns"]) / 1e9)
            self.phases.append(("flush_in_flight", rec["t_req"], e_ns))
            got, tags, twice = frame_rows(frame, self.prefix)
            want, timers = ingress.expected(pool, prev["b"], rec["b"],
                                            percentiles)
            if self.control:
                # the reference, put in the program's place, with counters
                # kept in the control's lower precision
                low = ingress.control_rows(pool, prev["b"], rec["b"],
                                           percentiles, self.control)
                for name in low.keys() & got.keys():
                    if name.startswith((self.prefix + ".c.",
                                        self.prefix + ".s.")):
                        got[name] = low[name]
            here = {}
            reference.compare(got, tags, twice, want, timers, percentiles,
                              self.prefix, numbers, examples, here)
            for name, w in here.items():
                if w["err"] >= widest.get(name, w)["err"]:
                    widest[name] = dict(w, interval=k)
            say(f"interval {k}: {sent_k} {ingress.unit}, {cycles:.2f} pool "
                f"cycles, "
                f"{len(got)} rows, pause "
                f"{(rec['t_resume'] - rec['t_pause']) / 1e6:.1f} ms (drain "
                f"{(rec['t_req'] - rec['t_pause']) / 1e6:.1f}, swap "
                f"{(rec['t_swap'] - rec['t_req']) / 1e6:.1f}), "
                f"tick to sink {latencies[-1]:.3f} s; "
                + stretch_line(prev, rec, sent_k, ingress.unit))
        dropped = ingress.lost(c_after, c_start, pool)
        undrained = sum(1 for rec in log[1:] if not rec["drained"])
        failed = min(attempted, failed + dropped)
        numbers["rows_missing"] += undrained
        rows, ok = reference.verdict(numbers, cfgf["limits"])
        ok = ok and failed == 0 and c_after["internal_errors"] == 0
        say("worst sketch errors over the window's intervals: "
            + " ".join(f"{k}={v:.3e}" for k, v in numbers.items()
                       if k not in reference.EXACT))
        for line in examples[:8]:
            say("  " + line)
        for name, w in widest.items():
            say(f"  {name} widest: interval {w['interval']}, timer {w['timer']} "
                f"of {w['n']} samples emitted {w['got']!r} for an exact "
                f"{w['exact']!r} (its largest sample {w['max']!r}), rank "
                f"error {w['err']:.3e}; {w['timers_near']} timers over half "
                f"of that, {100 * w['their_sample_share']:.2f} % of the "
                "samples")

        harness = {
            "samples_per_s": attempted / window_s if window_s > 0 else None,
            "tick_to_sink_max_s": max(latencies) if latencies else None,
            "tick_to_sink_mean_s": (sum(latencies) / len(latencies)
                                    if latencies else None),
            "setup_s": setup_s,
        }
        # the window is its K sending stretches (resume to pause) and what
        # lies between them: the ticks' pauses, as far as they are inside
        t1 = log[-1]["t_swap"]
        paused_ns = sum(max(0, min(end, t1) - max(start, t0))
                        for name, start, end in self.phases
                        if name == "tick_pause")
        pseudo = {"window_samples": attempted, "window_ns": window_s * 1e9,
                  "sender_blocked_ns": (log[-1]["blocked_ns"]
                                        - log[0]["blocked_ns"]),
                  "tick_pause_ns": paused_ns,
                  "send_stretch_ns": window_s * 1e9 - paused_ns}
        ctx = {
            "pool": pool, "harness": harness, "info": self.info,
            "counters_start": {**c_start, **{k: 0 for k in pseudo}},
            "counters_end": {**c_end, **pseudo},
            "phases_start": p_start, "phases_end": p_end,
            "memory_peak_bytes": peak, "trace": None, "config": cfgf,
        }
        if self.trace_dir:
            import trace_reduce
            loaded = trace_reduce.load(self.trace_dir)
            say(trace_reduce.summary(loaded))
            ctx["trace"] = trace_reduce.reduce(
                loaded, self.trace_span, self.trace_mark_ns, self.phases)
        return {"ctx": ctx, "correct": bool(ok), "attempted": int(attempted),
                "failed": int(failed), "compared": rows, "numbers": numbers,
                "widest": widest,
                "harness": harness,
                "memory_peak_bytes": peak}


def stretch_line(prev: dict, rec: dict, sent: int, unit: str) -> str:
    """The interval's sending stretch, resume to pause: its own rate, how
    long the sender waited for credit in it, and the credit's round trip
    as the publisher saw it (Run._publish)."""
    stretch_ns = rec["t_pause"] - prev["t_resume"]
    blocked_ns = rec["blocked_ns"] - prev["blocked_ns"]
    n = max(1, rec["pub"]["n"] - prev["pub"]["n"])
    empty = rec["pub"]["ring_empty"] - prev["pub"]["ring_empty"]
    ahead = rec["pub"]["ahead"] - prev["pub"]["ahead"]
    return (f"stretch {stretch_ns / 1e9:.3f} s at "
            f"{sent / (stretch_ns / 1e9):.0f} {unit}/s, sender blocked "
            f"{blocked_ns / 1e6:.1f} ms ({100 * blocked_ns / stretch_ns:.1f} %), "
            f"credit published {n} times, longest gap "
            f"{rec['pub']['gap_max_ns'] / 1e6:.1f} ms (the sender's longest "
            f"look for credit {rec['pub']['poll_max_ns'] / 1e6:.1f} ms), ring "
            f"empty at {100 * empty / n:.1f} % of them, sender ahead "
            f"{ahead / n:.0f} {unit} on average")


def lines_of(after: dict, before: dict, key: str, pool) -> int:
    """A count of datagrams, as samples (a lost datagram loses its lines)."""
    return (after.get(key, 0) - before.get(key, 0)) * pool.lines
