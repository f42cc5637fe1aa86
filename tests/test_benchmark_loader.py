"""Every cell of ``BENCHMARK.json`` resolves by file, with nothing booted:
the configuration, the traffic file, the driver's file and class, and every
per-layer reader the cell reports (ISSUE 26's loader test, which
``benchmark/tests/test_loader.py`` holds outside tier-1)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCHMARK = json.load(_fh)


@pytest.fixture()
def harness(monkeypatch):
    """The benchmark's modules import each other by bare name."""
    monkeypatch.syspath_prepend(BENCH)
    import boot
    import harness

    def booted(*a, **kw):
        raise AssertionError("the cluster was booted")

    monkeypatch.setattr(boot, "Cluster", booted)
    before = set(sys.path)
    yield harness
    # ``harness._load`` makes the directories of ``paths`` importable.
    sys.path[:] = [p for p in sys.path if p in before]


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCHMARK["workloads"]])
def test_every_workload_resolves_by_file(workload, harness):
    cell = harness.load_cell(workload)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == workload)
    assert cell.chips == entry["chips"] == cell.config["chips"]
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["name"] == entry["traffic"]
    assert cell.config["server_handle"] and cell.config["limits"]
    driver = harness.resolve(cell)
    assert driver is harness.load_driver(cell.search, cell.traffic["driver"])
    assert os.path.exists(os.path.join(
        BENCH, "drivers", cell.traffic["driver"] + ".py"))
    for member in harness.DRIVER_CALLS:
        assert callable(getattr(driver, member)), member
    for member in harness.DRIVER_READS:
        assert hasattr(driver, member), member
    assert cell.per_layer
    names = {m["name"] for m in cell.per_layer}
    for metric in BENCHMARK["per_layer"]:
        listed = metric.get("workloads")
        assert (metric["name"] in names) == (listed is None
                                             or workload in listed)
    for metric in cell.per_layer:
        assert callable(harness.load_reader(cell.search, metric["name"]))


def test_the_stateful_sparse_cell_names_its_handle_and_driver(harness):
    cell = harness.load_cell("dlrm-criteo-rowadagrad.zipf")
    assert cell.config["kind"] == "sparse"
    assert cell.config["server_handle"] == "row_adagrad:0.004,1e-8"
    assert (cell.config["rows"], cell.config["dim"]) == (20_000_000, 128)
    assert cell.traffic["driver"] == "sparse_handle_pull_push"
    driver = harness.resolve(cell)
    base = harness.load_driver(cell.search, "sparse_pull_push")
    assert issubclass(driver, base) and driver is not base
    assert {"combine_ms", "table_write_ms"} <= {
        m["name"] for m in cell.per_layer}


def test_the_lane_packed_cell_names_its_table_traffic_and_driver(harness):
    """``dlrm-terabyte-emb64.zipf``: the upstream DLRM repository's Criteo
    Terabyte run whole, a 64-wide table (two rows to a 128-lane physical
    row) under the plain sum, the sparse driver as it stands, and the two
    per-layer metrics that only this cell reports."""
    cell = harness.load_cell("dlrm-terabyte-emb64.zipf")
    config, traffic = cell.config, cell.traffic
    assert config["kind"] == "sparse" and config["server_handle"] == "sum"
    assert (config["rows"], config["dim"]) == (54_000_000, 64)
    assert config["reduced"] == [] and config["dtype"] == "float32"
    sizes = config["sizes"]
    assert sizes["mini_batch"] * sizes["categorical_features"] \
        == traffic["lookups_per_worker"] == 53_248
    assert sizes["embedding_width"] == config["dim"]
    assert traffic["name"] == "zipf-rows-2048x26"
    assert traffic["driver"] == "sparse_pull_push"
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "dlrm-terabyte-emb64")
    assert entry["source"] == config["source"] and entry["reduced"] == []
    # The whole table on one chip: over the floor of a quarter of it.
    assert 0.25 * 16e9 < config["rows"] * config["dim"] * 4 < 16e9
    names = {m["name"] for m in cell.per_layer}
    assert {"packed_write_ms", "packed_combine_ms", "roofline_share",
            "busy_ms", "launches_per_step"} <= names
    assert not {"combine_ms", "table_write_ms", "route_ms"} & names
    # The sum cell's guarantees word for word, and one more of its own.
    emb = harness.load_cell("dlrm-criteo-emb.zipf").config
    assert config["guarantees"].startswith(emb["guarantees"])
