"""One run of one cell: the server in this process, the sender in a child,
the tick protocol between them, and the comparison after the window.

The tick protocol (README.md has it in ten lines): pause the sender and
note where the stream stopped; wait until the engine has parsed all that
was sent; enqueue the flush request the server's own ticker would send;
wait until the swap has happened; resume. Interval boundaries are then
known to the reference although ingest and flush overlap.

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
                raise RunError(f"the sender exited ({self.child.returncode}) "
                               f"while waiting for {what}")
            if not self.server._pipeline_thread.is_alive():
                raise RunError("the pipeline thread died")
            if time.monotonic() > end:
                return False
            time.sleep(poll)
        return True

    def _publish(self):
        """Copies the engine's count into the control block about once a
        millisecond, and keeps for the per-interval line what the credit's
        round trip looked like while the sender was running: how many
        times it published, the longest time between two, how often the
        ring stood empty and how far ahead the sender was. A round of
        over STALL_NS starves the sender of credit for a real part of a
        stretch, so it is said at once, with where the time went: asleep
        or waiting for the interpreter, or in eng.stats() (the engine's key
        lock, and the interpreter again on the way back)."""
        eng, ctl, base = self.server.aggregator.eng, self.ctl, self.base
        pub, clock = self.pub, time.monotonic_ns
        last = clock()
        while not self._stop_publish.is_set():
            t_woke = clock()
            done = eng.stats()["processed"] - base
            ctl[S.PROCESSED] = done
            now = clock()
            if ctl[S.CMD] == S.RUN:
                pub["n"] += 1
                pub["gap_max_ns"] = max(pub["gap_max_ns"], now - last)
                pub["ring_empty"] += eng.reader_counters()["ring_depth"] == 0
                pub["ahead"] += ctl[S.SENT] - done
                if now - last > STALL_NS:
                    say(f"credit: not published for {(now - last) / 1e6:.0f} "
                        f"ms ({(t_woke - last) / 1e6:.0f} asleep or waiting "
                        f"for the interpreter, {(now - t_woke) / 1e6:.0f} in "
                        "eng.stats() and back), "
                        f"{(now - self.phases[-1][2]) / 1e9:.2f} s after the "
                        "last resume")
            last = clock()
            time.sleep(0.001)

    def _processed(self) -> int:
        return self.server.aggregator.eng.stats()["processed"] - self.base

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
            # the engine counts only what the sender sent, or the drain
            # test means nothing
            raise RunError(f"the engine has parsed {over} samples more than "
                           "were sent")
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
                        f"{limit} datagrams to be parsed", timeout, 0.002)
        if not ok:
            raise RunError(
                f"engine parsed {self.server.aggregator.eng.stats()} of "
                f"{ctl[S.SENT]} samples sent, then stalled; ring "
                f"{self.server.aggregator.ring_stats()}")

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
            [sys.executable, os.path.join(HERE, "sender.py"), ctl_path,
             cell["traffic_path"], str(self.seed)])
        self.sink = make_sink()
        self.server = build_server(cfgf, self.tmp, self.sink, self.overrides)
        self._shut = False
        self.server.start()
        self.info = describe(self.server)
        say("serve: " + " ".join(f"{k}={v}" for k, v in self.info.items()))
        agg = self.server.aggregator
        self.base = agg.eng.stats()["processed"]
        rcvbuf = self.server._sockets[0].getsockopt(socket.SOL_SOCKET,
                                                    socket.SO_RCVBUF)
        lines = cell["traffic_file"]["lines_per_datagram"]
        # a datagram of ~30 lines costs the kernel up to ~2.3 KB of buffer
        # accounting; stay a factor of four inside the socket buffer, so
        # that nothing is dropped even if the reader thread is not run
        credit_d = max(4, rcvbuf // (4 * 2304))
        ctl[S.CREDIT] = credit_d * lines
        ctl[S.LIMIT] = 0
        ctl[S.PORT] = self.server.local_addr()[1]
        self._publisher = threading.Thread(target=self._publish, daemon=True,
                                           name="perfbench-credit")
        self._publisher.start()
        if not self._wait(lambda: ctl[S.STATE] >= S.READY,
                          "the sender's pool", 120, 0.005):
            raise RunError("the sender built no pool")
        n_d = ctl[S.N_DATAGRAMS]
        say(f"sender: pool of {n_d} datagrams, credit {credit_d} datagrams "
            f"(socket buffer {rcvbuf} B)")

        # warm-up: one pool cycle, a tick, its emission (compiles or loads
        # the ingest program with its compaction branch, the swap and the
        # flush program at this cell's own row count); then a second
        # cycle and tick 0, which is not waited for
        c0 = counters(self.server)
        self._send_until(n_d, 1100)
        self._tick(wait_flush=True)
        self._send_until(2 * n_d, 300)
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
        pool = T.build_pool(cell["traffic_file"], self.seed)
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
            cycles = (rec["b"] - prev["b"]) / pool.n_datagrams
            req = rec["req"]
            if not (req.done.is_set() and req.ok) or len(frames) <= at:
                failed += sent_k
                examples.append(f"flush {k} not emitted: {req.detail}")
                say(f"interval {k}: {sent_k} samples, {cycles:.2f} pool "
                    f"cycles, NOT EMITTED ({req.detail})")
                continue
            e_ns, frame = frames[at]
            at += 1
            latencies.append((e_ns - rec["t_ns"]) / 1e9)
            self.phases.append(("flush_in_flight", rec["t_req"], e_ns))
            got, tags, twice = frame_rows(frame, self.prefix)
            want, timers = reference.expected(pool, prev["b"], rec["b"],
                                              percentiles)
            if self.control:
                # the reference, put in the program's place, with counters
                # kept in the control's lower precision
                low, _ = reference.expected(
                    pool, prev["b"], rec["b"], percentiles,
                    counter_dtype=getattr(np, self.control["counter_dtype"]))
                low.update(reference.hll_estimates(
                    pool, prev["b"], rec["b"], self.control["hll_precision"]))
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
            say(f"interval {k}: {sent_k} samples, {cycles:.2f} pool cycles, "
                f"{len(got)} rows, pause "
                f"{(rec['t_resume'] - rec['t_pause']) / 1e6:.1f} ms (drain "
                f"{(rec['t_req'] - rec['t_pause']) / 1e6:.1f}, swap "
                f"{(rec['t_swap'] - rec['t_req']) / 1e6:.1f}), "
                f"tick to sink {latencies[-1]:.3f} s; "
                + stretch_line(prev, rec, sent_k))
        dropped = ((c_after["eng.dropped"] - c_start["eng.dropped"])
                   + lines_of(c_after, c_start, "ring.ring_dropped", pool)
                   + lines_of(c_after, c_start, "packets_dropped", pool)
                   + lines_of(c_after, c_start, "packets_toolong", pool)
                   + lines_of(c_after, c_start, "reader.toolong", pool)
                   + (c_after["eng.parse_errors"] - c_start["eng.parse_errors"])
                   + (c_after["parse_errors_py"] - c_start["parse_errors_py"]))
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


def stretch_line(prev: dict, rec: dict, sent: int) -> str:
    """The interval's sending stretch, resume to pause: its own rate, how
    long the sender waited for credit in it, and the credit's round trip
    as the publisher saw it (Run._publish)."""
    stretch_ns = rec["t_pause"] - prev["t_resume"]
    blocked_ns = rec["blocked_ns"] - prev["blocked_ns"]
    n = max(1, rec["pub"]["n"] - prev["pub"]["n"])
    empty = rec["pub"]["ring_empty"] - prev["pub"]["ring_empty"]
    ahead = rec["pub"]["ahead"] - prev["pub"]["ahead"]
    return (f"stretch {stretch_ns / 1e9:.3f} s at "
            f"{sent / (stretch_ns / 1e9):.0f} samples/s, sender blocked "
            f"{blocked_ns / 1e6:.1f} ms ({100 * blocked_ns / stretch_ns:.1f} %), "
            f"credit published {n} times, longest gap "
            f"{rec['pub']['gap_max_ns'] / 1e6:.1f} ms (the sender's longest "
            f"look for credit {rec['pub']['poll_max_ns'] / 1e6:.1f} ms), ring "
            f"empty at {100 * empty / n:.1f} % of them, sender ahead "
            f"{ahead / n:.0f} samples on average")


def lines_of(after: dict, before: dict, key: str, pool) -> int:
    """A count of datagrams, as samples (a lost datagram loses its lines)."""
    return (after.get(key, 0) - before.get(key, 0)) * pool.lines
