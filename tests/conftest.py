"""Test bootstrap: force the CPU backend with 8 virtual devices.

Sharding/collective tests run on a virtual 8-device CPU mesh; what the
chip does is measured by ``benchmark/run.py`` (which does NOT import this).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Unit tests compile fresh: the persistent compilation cache
# (pslite_tpu/utils/compile_cache.py) is for programs compiled for the
# chip.  Set before jax is imported, and inherited by every child process.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

# Unit tests always run on the virtual 8-device CPU mesh, whatever
# JAX_PLATFORMS says.
try:
    from pslite_tpu.utils.platform_pin import pin_cpu

    pin_cpu(8)
except ImportError:  # jax-less host: non-jax tests still run
    pass

import pytest

# Best-effort build of the native transport core so the suite exercises the
# C++ path; tests still pass on the pure-Python fallback if g++ is missing.
# A build that fails where g++ exists fails one test, by name:
# test_native_plane.py::test_core_builds_where_a_toolchain_exists.
_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not os.path.exists(os.path.join(_repo, "cpp", "libpslite_core.so")):
    import subprocess

    subprocess.run(
        ["make", "-C", os.path.join(_repo, "cpp")],
        capture_output=True,
        check=False,
    )


# In-process test clusters host many logical nodes in one interpreter; a
# CHECK failure in one node's pump must not os._exit the whole pytest run.
# Multi-process tests that assert the abort behavior override this.
os.environ.setdefault("PS_CHECK_FATAL", "0")


@pytest.fixture(autouse=True)
def _loopback_isolation(request):
    """Give each test its own loopback namespace and clean registry."""
    os.environ["PS_LOOPBACK_NS"] = request.node.nodeid
    yield
    from pslite_tpu.vans import loopback_van

    loopback_van.reset_registry()
    os.environ.pop("PS_LOOPBACK_NS", None)
