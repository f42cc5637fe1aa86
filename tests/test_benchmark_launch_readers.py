"""The readers of the launch taken apart (``benchmark/launch_window.py``,
``benchmark/launch_events.py`` and the seven ``layer_metrics`` on them)
inside tier-1: the cases of ``benchmark/tests/test_launch_readers.py``,
imported, not copied, as ``test_benchmark_trace_reduce.py`` takes the
reduction's.  They feed a stage clock by hand and read a synthetic profile:
no device, no cluster."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _cases():
    """The benchmark's test module, loaded by path (``benchmark/tests`` is
    no package, and its ``conftest.py`` pins another number of devices).
    Its modules import each other, and the synthetic trace's shapes of
    ``benchmark/tests/test_trace_reduce.py``, by bare name."""
    for path in (BENCH, os.path.join(BENCH, "tests")):
        if path not in sys.path:
            sys.path.append(path)
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_test_launch_readers",
        os.path.join(BENCH, "tests", "test_launch_readers.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return {name: case for name, case in vars(module).items()
            if name.startswith("test_") or name == "clock"}


globals().update(_cases())
