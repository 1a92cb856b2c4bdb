"""Drives the rest of a run, skipping only run.py's look for a chip, on the
CPU at a size a test run can hold.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q

Not collected by the repo's tier-1 command (which runs `tests/`).
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))

import pytest               # noqa: E402

import harness              # noqa: E402
import traffic              # noqa: E402

# a sixteenth of the cells' mixed pool: the same kinds and skew
SMALL = {"prefix": "pb", "lines_per_datagram": 30, "kinds": {
    "counter": {"names": 5000, "samples": 15000, "zipf_s": 1.0,
                "half_rate_share": 0.1},
    "gauge": {"names": 1250, "samples": 2500, "zipf_s": 1.0},
    "timer": {"names": 625, "samples": 12500, "zipf_s": 1.0},
    "set": {"names": 125, "samples": 2500, "zipf_s": 1.0}}}
# The cell's own limits are held at this size too, where an interval holds
# some thirty pool cycles: the percentiles are judged in rank space, which
# does not see the ties. Only set_err_mean is given more room: over 125
# sets, not 2,000, the program's mean read 2.3e-4 to 4.2e-4 (three seeds)
# and the control's 1.3e-3 to 2.0e-3.
SMALL_LIMITS = {"set_err_mean": 8e-4}


def _small(name, tmp_path, monkeypatch):
    cell = harness.load_cell(name)
    path = tmp_path / "small.json"
    path.write_text(json.dumps(SMALL))
    cell["traffic_path"] = str(path)
    cell["traffic_file"] = traffic.load(str(path))
    cell["config_file"] = dict(
        cell["config_file"],
        limits=dict(cell["config_file"]["limits"], **SMALL_LIMITS))
    monkeypatch.setattr(harness, "INTERVAL_S", 4.0)
    return cell


def _cells():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)["workloads"]


@pytest.fixture()
def small_cell(tmp_path, monkeypatch):
    """The first cell of BENCHMARK.json with the small pool in its place
    and 4 s intervals."""
    return _small(_cells()[0]["name"], tmp_path, monkeypatch)


def run(cell, seed, **kw):
    return harness.Run(cell, seed, 4.0, False, time.monotonic(),
                       **kw).execute()
