"""The benchmark's forward way in, among the tier-1 tests: the CPU cases of
perfbench/tests/test_forward.py (the forwarder's wire against the schema,
its digests against upstream's merge loop, `reference.expected_forward` by
hand and against a loop, the planted faults it must catch, the last line
of a run that could not be made, `traffic.load` refusing sets and gauges),
which that directory's own conftest keeps out of the repo's tier-1 run.
Its cases through the real Server are left there;
tests/test_global_import_spans.py runs the deployment's shape here.

test_forward.py imports the benchmark's files by the plain names run.py
imports them under and that directory's conftest as `conftest`, which in
this process is tests/conftest.py: that one is put back once the module
is loaded, and so is sys.path.
"""

import importlib.util
import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
TESTS = os.path.join(BENCH, "tests")
# the cases that build the Server: perfbench/tests runs them
SERVED = {"forward_cell", "test_forward_program_is_correct",
          "test_forward_control_is_not_correct",
          "test_forward_altered_counter_is_not_correct",
          "test_forward_step_left_out_is_not_correct"}


def _module(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load():
    path, ours = sys.path[:], sys.modules.get("conftest")
    sys.path.insert(0, BENCH)
    try:
        sys.modules["conftest"] = _module(
            "perfbench_tests_conftest", os.path.join(TESTS, "conftest.py"))
        return _module("perfbench_tests_test_forward",
                       os.path.join(TESTS, "test_forward.py"))
    finally:
        sys.path[:] = path
        if ours is None:
            sys.modules.pop("conftest", None)
        else:
            sys.modules["conftest"] = ours


# its CPU tests with their helpers, collected here under their own names
globals().update({name: value for name, value in vars(_load()).items()
                  if not name.startswith("__") and name not in SERVED})
