"""``benchmark/trace_reduce.py`` inside tier-1: the cases of
``benchmark/tests/test_trace_reduce.py`` (busy time and counts, the clock
that lays the device's timeline on the host's, the idle time by the
program's spans), imported, not copied.  The bracket in which the
occupancy account's readings must lie on a traced run leans on
``idle_gaps``, and ``pytest benchmark/tests`` is not part of tier-1."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _cases():
    """The benchmark's test module, loaded by path (``benchmark/tests`` is
    no package, and its ``conftest.py`` pins another number of devices).
    Its modules import each other by bare name."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_test_trace_reduce",
        os.path.join(BENCH, "tests", "test_trace_reduce.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {name: case for name, case in vars(module).items()
            if name.startswith("test_")}


globals().update(_cases())
