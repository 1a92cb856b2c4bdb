"""The benchmark's load generator alone, among the tier-1 tests: the
eleven cases of perfbench/tests/test_sender.py (the sender child against a
UDP socket and a control block the test owns; no JAX, no server, a time
limit each), which that directory's own conftest keeps out of the repo's
tier-1 run. They need only perfbench/ on sys.path, by the plain names the
benchmark's files import each other under.
"""

import importlib.util
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


def _load():
    sys.path.insert(0, BENCH)
    try:
        spec = importlib.util.spec_from_file_location(
            "perfbench_tests_test_sender",
            os.path.join(BENCH, "tests", "test_sender.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module
    finally:
        sys.path.remove(BENCH)


# its tests with their fixtures (the time limit of each, `bench`) and
# helpers, collected here under their own names
globals().update({name: value for name, value in vars(_load()).items()
                  if not name.startswith("__")})
