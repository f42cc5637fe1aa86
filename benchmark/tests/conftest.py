"""benchmark/tests: the yardstick's own tests and the rehearsals that cost
no chip time.  Run with ``pytest benchmark/tests`` (not part of tier-1).
They run on four virtual CPU devices with kernels interpreted; a CPU run
proves values and counts, never a speed."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

# Tests compile fresh: the persistent cache is for programs compiled for
# the chip.  Set before jax is imported.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
os.environ.setdefault("PS_CHECK_FATAL", "0")

from pslite_tpu.utils.platform_pin import pin_cpu

pin_cpu(4)

import pytest


@pytest.fixture(autouse=True)
def _loopback_isolation(request):
    """Each test boots its own in-process cluster: give it its own
    loopback namespace and a clean registry (as tests/conftest.py does)."""
    os.environ["PS_LOOPBACK_NS"] = request.node.nodeid
    yield
    from pslite_tpu.vans import loopback_van

    loopback_van.reset_registry()
    os.environ.pop("PS_LOOPBACK_NS", None)
