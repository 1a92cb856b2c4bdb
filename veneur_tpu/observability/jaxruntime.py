"""JAX runtime telemetry: compile events, synced step timing, HBM gauges,
and on-demand profiler captures.

The single biggest silent perf cliff in this codebase is an accidental
recompile of the ingest/flush programs (a shape-static argument that
isn't, a new batch geometry) — the whole TPU-first design is "one
resident executable per batch". jax.monitoring fires a duration event
(`.../backend_compile_duration`) every time XLA actually compiles, so a
recompile storm shows up as a climbing counter instead of a mysterious
10x flush-latency regression.

The listener is process-global and idempotent (jax.monitoring has no
unregister; multiple Server instances in one process — the test suite —
must not stack listeners). Servers export the accumulators through
registry callbacks, so every server's /metrics reports the same
process-wide truth.

This module is also the ONE sanctioned device-sync site: XLA dispatch is
async, so `perf_counter_ns` around a bare step call measures dispatch
latency, not device time. sync_and_time() times a block_until_ready on
the result token; aggregators sample it every N steps (and at every
swap) into `step_ns` while `dispatch_ns` keeps the cheap always-on
host-side number. The token is ready only when everything queued ahead
of it has run, so with a backlog on the device a sample reads the
queue's drain, not one step (PERF.md §5; the profile has per-step device
time). The vtlint timer-sync pass enforces the split everywhere else.
"""

from __future__ import annotations

import tempfile
import threading
import time

_lock = threading.Lock()
_installed = False
_compiles_total = 0
_compile_seconds_total = 0.0

# fires once per program handed to the backend compiler, persistent-cache
# hits included (their duration is the cache read)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    global _compiles_total, _compile_seconds_total
    if event != _COMPILE_EVENT:
        return
    with _lock:
        _compiles_total += 1
        _compile_seconds_total += float(duration_secs)


def install() -> None:
    """Register the compile listener once per process; safe to call from
    every Server.__init__."""
    global _installed
    with _lock:
        if _installed:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True


def compiles_total() -> int:
    with _lock:
        return _compiles_total


def compile_time_ns_total() -> float:
    with _lock:
        return _compile_seconds_total * 1e9


# -- synced step timing -------------------------------------------------------

def sync_and_time(token) -> int:
    """Wall nanoseconds until `token` (a donated step result / pytree of
    device arrays) is actually ready. XLA dispatch is async, so timing a
    bare step call measures host-side dispatch, not device work; this is
    the ONE production sync point — aggregators sample it every
    _SYNC_EVERY steps and at swap() into `step_ns` (the wait covers every
    step still queued ahead of the token), while `dispatch_ns` stays the
    cheap per-step number."""
    import jax
    t0 = time.perf_counter_ns()
    # the sanctioned sampled sync point: callers time device completion
    # here instead of around dispatch
    # vtlint: disable=jax-hot-path -- deliberate sampled device sync
    jax.block_until_ready(token)
    return time.perf_counter_ns() - t0


class SampledSync:
    """Sampled device-sync bookkeeping for dispatch sites that launch
    many small programs (the query tier's batched reads): every
    `every`-th token is synced through sync_and_time() so `sync_ns`
    means device time, while the other N-1 launches pay only enqueue
    cost. Same cadence contract as the aggregators' `_SYNC_EVERY`
    sampling — one shared shape for the vtlint timer-sync rule."""

    def __init__(self, every: int = 64) -> None:
        self.every = max(1, int(every))
        self.count = 0
        self.synced = 0
        self.sync_ns = 0

    def tick(self, token) -> int:
        """Count one launch; on the sampling edge, block on `token` and
        accumulate the wait. Returns the sampled nanoseconds (0 when
        this launch was not sampled)."""
        self.count += 1
        if self.count % self.every:
            return 0
        dt = sync_and_time(token)
        self.synced += 1
        self.sync_ns += dt
        return dt


# -- HBM accounting -----------------------------------------------------------

def hbm_stats() -> dict:
    """{device_label: {"bytes_in_use": n, "peak_bytes_in_use": n}} from
    each local device's allocator. Empty on backends that expose no
    memory_stats (CPU) — callers treat absence as 'no series'."""
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return {}
    out = {}
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        out[f"{d.platform}:{d.id}"] = {
            "bytes_in_use": int(ms.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
        }
    return out


def hbm_bytes_in_use() -> dict:
    return {(label,): s["bytes_in_use"] for label, s in hbm_stats().items()}


def hbm_bytes_peak() -> dict:
    return {(label,): s["peak_bytes_in_use"]
            for label, s in hbm_stats().items()}


# -- on-demand profiler capture ----------------------------------------------

_profile_lock = threading.Lock()


def capture_profile(seconds: float, base_dir: str = None) -> str:
    """Run jax.profiler for `seconds` and return the trace directory.
    One capture at a time per process (the profiler is a global
    resource); a concurrent request raises RuntimeError — the HTTP layer
    maps it to 409."""
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("profile capture already in progress")
    try:
        import jax
        trace_dir = tempfile.mkdtemp(prefix="veneur-trace-", dir=base_dir)
        jax.profiler.start_trace(trace_dir)
        try:
            time.sleep(max(0.0, float(seconds)))
        finally:
            jax.profiler.stop_trace()
        return trace_dir
    finally:
        _profile_lock.release()
