#!/usr/bin/env python3
"""The benchmark's one command.

    python perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and prints
one JSON object as the last line of its standard output. There is no CPU
mode: with no TPU, or another number of chips than the cell asks for, it
exits non-zero and prints no result. A run that could not be made (a
`harness.RunError`) exits with 4 and its last line is a result that is not
correct, with the reason under `error`. The traffic file's `ingress` picks
the way in (`harness.INGRESS`). To rehearse off the chip, call
`harness.Run(...).execute()` from a throw-away snippet (README.md).
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


ERROR_CHARS = 300


def error_line(error: Exception) -> dict:
    """The last line of a run that could not be made."""
    return {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
            "error": str(error)[:ERROR_CHARS]}


def result_line(cell: dict, out: dict, device: dict, trace: bool) -> dict:
    """The last line: end-to-end metrics with --trace 0, per-layer metrics
    with --trace 1; the numbers compared, each beside its limit, last."""
    import readers
    ctx = out["ctx"]
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            value = readers.read(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            value = out["harness"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    reduced = ctx.get("trace")
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    # the timer behind each percentile's widest rank error, short, so that
    # the end of the line says what a reading over its limit was made of
    line["widest"] = {
        name: [w["interval"], w["timer"], w["n"], w["got"], w["exact"],
               w["max"], w["timers_near"], round(w["their_sample_share"], 5)]
        for name, w in out["widest"].items()}
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ok in out["compared"]}
    return line


def main(argv=None, control: bool = False) -> int:
    """`control` (control.py) runs the configuration's control in the
    program's place; the benchmark's own runs never do."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    try:
        cell = harness.load_cell(args.workload)
        from veneur_tpu.utils import compile_cache
    except (harness.RunError, ImportError, OSError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    cache_dir = compile_cache.configure()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"perfbench: no TPU (JAX reports {devs[0].platform!r}); the "
              "benchmark has no CPU mode", file=sys.stderr)
        return 3
    if len(devs) != cell["chips"]:
        print(f"perfbench: {len(devs)} chips attached, the cell asks for "
              f"{cell['chips']}", file=sys.stderr)
        return 3
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    harness.say(f"device: {device} jax={jax.__version__} "
                f"compile_cache={cache_dir}")
    try:
        out = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                          _T_PROCESS, control=control).execute()
    except harness.RunError as e:
        print(f"perfbench: the run could not be made: {e}", file=sys.stderr)
        print(json.dumps(error_line(e)), flush=True)
        return 4
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    out["ctx"]["device_kind"] = device["kind"]
    line = result_line(cell, out, device, bool(args.trace))
    for name, c in line["compared"].items():
        harness.say(f"compared {name}: {c['value']:.6g} (limit {c['limit']:g})"
                    + ("" if c["value"] <= c["limit"] else "  <-- over"))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
