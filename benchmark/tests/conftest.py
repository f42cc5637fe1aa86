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


@pytest.fixture(scope="session")
def appended_root(tmp_path_factory):
    """A root whose ``BENCHMARK.json`` is the committed one with what an
    addition PR brings appended: a directory of ``paths`` with a
    configuration, a traffic mix and a reader in it, and an entry of
    ``configs``, ``workloads`` and ``per_layer`` each at the end of its
    list.  The committed files are named by absolute path, so nothing is
    copied but the two data files the new cell runs on."""
    import json

    root = tmp_path_factory.mktemp("appended")
    extra = root / "extra"
    for kind in ("configs", "traffic", "layer_metrics"):
        (extra / kind).mkdir(parents=True)
    cells = os.path.join(HERE, "cells")
    for src, dst, name in (
            ("tiny-sparse.json", extra / "configs" / "appended-table.json",
             "appended-table"),
            ("tiny-zipf.json", extra / "traffic" / "appended-zipf.json",
             "appended-zipf")):
        with open(os.path.join(cells, src)) as fh:
            data = json.load(fh)
        dst.write_text(json.dumps(dict(
            data, name=name, reduced=[], chips=1,
            source="benchmark/tests/conftest.py")))
    (extra / "layer_metrics" / "appended_steps.py").write_text(
        '"""Steps in the profiler-off window: a count any run can read."""\n'
        "def read(ctx):\n    return float(len(ctx.spans)) or None\n")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["paths"] = [BENCH, str(extra)]
    for config in bench["configs"]:
        config["file"] = os.path.join(ROOT, config["file"])
    bench["configs"].append({
        "name": "appended-table", "source": "benchmark/tests/conftest.py",
        "file": str(extra / "configs" / "appended-table.json"),
        "reduced": [], "why": "what an addition PR appends"})
    bench["workloads"].append({
        "name": "appended-table.zipf", "config": "appended-table",
        "traffic": "appended-zipf", "chips": 1,
        "why": "what an addition PR appends"})
    bench["per_layer"].append({
        "name": "appended_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "dense and sparse engines",
        "moves": "goodput"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


@pytest.fixture(params=["committed", "appended"])
def bench_root(request):
    """The repository's root, and the root with an entry of each kind
    appended: what a test asserts by name holds under both."""
    if request.param == "committed":
        return ROOT
    return request.getfixturevalue("appended_root")
