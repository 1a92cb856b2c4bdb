"""From the profiler's trace to numbers: device busy time, time per
program, the top device operations and the longest idle gaps.

The interval arithmetic (`union`, `gaps`, `clip`) is plain and is checked
on hand-made events by selfcheck.py. `load` reads the `.xplane.pb` with
`jax.profiler.ProfileData`: device planes are those named `/device:TPU:n`;
on each, the line `XLA Ops` holds one event per operation run on the
device and the line `XLA Modules` one per program (named after the jitted
function). All times are nanoseconds on the trace's own clock; the
harness's `perfbench.mark` annotation ties that clock to the host's.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MARK = "perfbench.mark"


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy(intervals) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(intervals, lo, hi):
    """The idle stretches of [lo, hi] between the intervals' union."""
    out, at = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def short_name(op: str, limit: int = 96) -> str:
    """`%cond.8 = (f32[16384,472]{...}, ...) conditional(...)` as
    `%cond.8 conditional f32[16384,472]...`: the profile prints whole HLO
    instructions, hundreds of characters each."""
    m = re.match(r"^(%\S+) = (.*?)\s([a-z][a-z0-9\-]*)\(", op)
    if m:
        op = f"{m.group(1)} {m.group(3)} {m.group(2)}"
    return op[:limit]


def phase_of(t, phases, default="steady_ingest"):
    """The harness phase that holds time t; the first listed wins."""
    for name, a, b in phases:
        if a <= t < b:
            return name
    return default


def _profile(trace_dir: str):
    import jax
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(sorted(paths)[-1])


def load(trace_dir: str) -> dict:
    """{"devices": {id: {"ops": [(name, start, end)], "modules": [...]}},
    "mark_ns": start of the harness's mark on the trace's clock}."""
    data = _profile(trace_dir)
    devices, mark = {}, None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
        elif mark is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        mark = e.start_ns
                        break
                if mark is not None:
                    break
    return {"devices": devices, "mark_ns": mark}


def reduce(loaded: dict, span_host, mark_host_ns, phases_host) -> dict:
    """`span_host` is the traced slice and `phases_host` the harness's
    phases, on the host's monotonic clock; `mark_host_ns` is when the mark
    was made on that clock."""
    devices = loaded["devices"]
    if not devices or loaded["mark_ns"] is None:
        return {}
    shift = loaded["mark_ns"] - mark_host_ns      # host clock -> trace clock
    lo, hi = span_host[0] + shift, span_host[1] + shift
    phases = [(n, a + shift, b + shift) for n, a, b in phases_host]
    per_device, busiest = {}, None
    for dev, d in sorted(devices.items()):
        ops = clip([(a, b) for _n, a, b in d["ops"]], lo, hi)
        per_device[dev] = busy(ops)
        if busiest is None or per_device[dev] > per_device[busiest]:
            busiest = dev
    if not any(per_device.values()):
        return {}
    d = devices[busiest]
    by_op = {}
    for name, a, b in d["ops"]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            by_op[name] = by_op.get(name, 0) + (b - a)
    programs = {}
    for name, a, b in d["modules"]:
        if a >= lo and b <= hi:
            t = programs.setdefault(name, [0, 0])
            t[0] += 1
            t[1] += b - a
    idle = sorted(gaps([(a, b) for _n, a, b in d["ops"]], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(per_device.values()) / len(per_device) / 1e9,
        "busiest_device": busiest,
        "busiest_busy_s": per_device[busiest] / 1e9,
        "n_devices": len(per_device),
        "programs": {n: {"calls": c, "seconds": t / 1e9}
                     for n, (c, t) in programs.items()},
        "device_ops": [[short_name(n), t / 1e9] for n, t in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[phase_of((a + b) / 2, phases), (b - a) / 1e9]
                      for a, b in idle],
    }


def summary(loaded: dict, limit: int = 12) -> str:
    """What a profile holds, for reading one by hand."""
    out = []
    for dev, d in sorted(loaded["devices"].items()):
        out.append(f"device {dev}: {len(d['ops'])} op events, "
                   f"{len(d['modules'])} program events")
        names = {}
        for n, a, b in d["modules"]:
            c = names.setdefault(n, [0, 0])
            c[0] += 1
            c[1] += b - a
        for n, (c, t) in sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]:
            out.append(f"  program {n}: {c} calls, {t / 1e6:.3f} ms")
    return "\n".join(out)
