"""The general readers of per-layer metrics. A metric is a file
`layer_metrics/<name>.json` that names one of these kinds and what it
reads; `python` names a module beside it with one `read(ctx)` function.
A reader that finds nothing to read returns None and the metric is left
out of the line: never 0 for a share.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
METRICS_DIR = os.path.join(HERE, "layer_metrics")


def spec_of(name: str) -> dict:
    with open(os.path.join(METRICS_DIR, name + ".json")) as f:
        return json.load(f)


def program_time(ctx, patterns):
    """(calls, device seconds) of the traced programs whose name matches
    any of the patterns, on the busiest device."""
    trace = ctx.get("trace") or {}
    calls = seconds = 0
    for name, p in trace.get("programs", {}).items():
        if any(re.search(pat, name) for pat in patterns):
            calls += p["calls"]
            seconds += p["seconds"]
    return calls, seconds


def _delta(ctx, key):
    a, b = ctx["counters_start"].get(key), ctx["counters_end"].get(key)
    return None if a is None or b is None else b - a


def counter_delta(ctx, spec):
    d = _delta(ctx, spec["counter"])
    return None if d is None else d * spec.get("scale", 1)


def counter_last(ctx, spec):
    v = ctx["counters_end"].get(spec["counter"])
    return None if v is None else v * spec.get("scale", 1)


def counter_ratio(ctx, spec):
    num, den = _delta(ctx, spec["num"]), _delta(ctx, spec["den"])
    if num is None or not den:
        return None
    return num / den * spec.get("scale", 1)


def phase_timer_mean(ctx, spec):
    c0, s0 = ctx["phases_start"].get(spec["phase"], (0, 0.0))
    c1, s1 = ctx["phases_end"].get(spec["phase"], (0, 0.0))
    if c1 <= c0:
        return None
    return (s1 - s0) / (c1 - c0) * spec.get("scale", 1)


def trace_program_time(ctx, spec):
    calls, seconds = program_time(ctx, spec["programs"])
    if not calls:
        return None
    per = seconds / calls if spec.get("per") == "call" else seconds
    return per * spec.get("scale", 1)


def trace_idle(ctx, spec):
    trace = ctx.get("trace") or {}
    if not trace.get("window_s") or not trace.get("busiest_busy_s"):
        return None
    # the fullest device: on four chips the one that was busy longest
    return 100.0 * (1.0 - trace["busiest_busy_s"] / trace["window_s"])


def memory_stat(ctx, spec):
    return ctx.get("memory_peak_bytes") or None


def harness(ctx, spec):
    return ctx["harness"].get(spec["key"])


def python(ctx, spec):
    path = os.path.join(METRICS_DIR, spec["module"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + spec["module"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


KINDS = {f.__name__: f for f in (
    counter_delta, counter_last, counter_ratio, phase_timer_mean,
    trace_program_time, trace_idle, memory_stat, harness, python)}


def read(name: str, ctx):
    spec = spec_of(name)
    return KINDS[spec["kind"]](ctx, spec)
