"""Drivers are found by file, as traffic and readers are: the lookup, what
it refuses and when (before anything boots), and the room it leaves a later
PR (a driver that builds on one that exists, its reference beside it)."""

import json
import os
import time

import pytest

import boot
import harness
from conftest import BENCH, HERE, ROOT
from tiny import cell as _cell

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCHMARK = json.load(_fh)


@pytest.fixture
def no_boot(monkeypatch):
    """Whatever is refused has to be refused before ``Cluster`` boots."""
    def booted(*a, **kw):
        raise AssertionError("the cluster was booted")

    monkeypatch.setattr(boot, "Cluster", booted)


def _driver_file(tmp_path, name, body):
    directory = tmp_path / "drivers"
    directory.mkdir(exist_ok=True)
    (directory / (name + ".py")).write_text(body)
    return [BENCH, str(tmp_path)]


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCHMARK["workloads"]])
def test_every_workload_resolves_by_file(workload, bench_root, no_boot):
    """No boot, no device: the configuration, the traffic, the driver's
    file and class, and every reader the cell reports; the same with an
    entry of each kind appended to ``BENCHMARK.json``."""
    cell = harness.load_cell(workload, root=bench_root)
    assert cell.config["server_handle"] and cell.config["limits"]
    driver = harness.resolve(cell)
    assert driver is harness.load_driver(cell.search,
                                         cell.traffic["driver"])
    for member in harness.DRIVER_CALLS:
        assert callable(getattr(driver, member))
    for member in harness.DRIVER_READS:
        assert hasattr(driver, member)
    assert cell.per_layer
    for metric in cell.per_layer:
        assert callable(harness.load_reader(cell.search, metric["name"]))


def test_an_appended_configuration_cell_and_metric_are_taken(appended_root,
                                                            no_boot):
    """What an addition PR brings (``conftest.py`` ``appended_root``: a
    configuration, a cell and a per-layer metric, each the last of its
    list, their files in a directory of ``paths`` of their own) loads by
    name, and every cell that was there keeps every metric it had and
    gains the one that lists no cell."""
    with open(os.path.join(appended_root, "BENCHMARK.json")) as fh:
        appended = json.load(fh)
    for group in ("configs", "workloads", "per_layer"):
        assert len(appended[group]) == len(BENCHMARK[group]) + 1
    cell = harness.load_cell("appended-table.zipf", root=appended_root)
    assert cell.config["name"] == "appended-table" and cell.chips == 1
    assert cell.traffic["name"] == "appended-zipf"
    assert harness.resolve(cell) is harness.load_driver(cell.search,
                                                        "sparse_pull_push")
    assert len(cell.search) == 2 and cell.search[0] == BENCH
    names = [m["name"] for m in cell.per_layer]
    assert "appended_steps" in names and "lamb_norm_ms" not in names
    ctx = harness.LayerContext(spans=[(0.0, 0.1, 0.2)] * 3,
                               compiles_in_window=0, reduction=None,
                               least={}, peaks={})
    assert harness.load_reader(cell.search, "appended_steps")(ctx) == 3.0
    for w in BENCHMARK["workloads"]:
        before = [m["name"] for m in harness.load_cell(w["name"]).per_layer]
        after = [m["name"] for m in harness.load_cell(
            w["name"], root=appended_root).per_layer]
        assert after == before + ["appended_steps"]
    with pytest.raises(KeyError, match="appended-table.zipf"):
        harness.load_cell("appended-table.zipf")      # not in the committed


def test_an_appended_counter_reports_in_the_cpu_rehearsal(appended_root):
    """A tiny cell under the appended file, traced on the CPU: the metric a
    later PR appended is read beside those that were there, and the
    rehearsals' check of a run's metrics (``tiny.check_metrics``) takes
    it, since it is named in the file."""
    from tiny import check_metrics

    ok, result = harness.run_cell(_cell("sparse", root=appended_root), 5, 0.3,
                                  True, time.perf_counter(),
                                  require_tpu=False)
    assert ok
    got = check_metrics(result, "per_layer",
                        {"issue_ms", "wait_ms", "compiles_in_window",
                         "appended_steps"}, root=appended_root)
    assert "issue_exposed_ms" not in got     # no device plane, no clock
    assert result["metrics"]["appended_steps"]["value"] >= 1
    with pytest.raises(AssertionError):      # the committed file lacks it
        check_metrics(result, "per_layer", {"issue_ms"})


def test_a_traffic_file_naming_no_driver_file(tmp_path, no_boot):
    """A cell loaded from a root of its own, and ``run_cell`` on a
    hand-made cell: the searched directories are in the message."""
    extra = tmp_path / "extra"
    (extra / "traffic").mkdir(parents=True)
    (extra / "traffic" / "t.json").write_text(
        json.dumps({"name": "t", "driver": "nowhere"}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "paths": [BENCH, "extra"],
        "configs": [{"name": "c", "file": os.path.join(
            HERE, "cells", "tiny-sparse.json")}],
        "workloads": [{"name": "w", "config": "c", "traffic": "t",
                       "chips": 4}],
        "end_to_end": BENCHMARK["end_to_end"], "per_layer": []}))
    with pytest.raises(FileNotFoundError) as exc:
        harness.resolve(harness.load_cell("w", root=str(tmp_path)))
    assert "drivers/nowhere" in str(exc.value)
    assert BENCH in str(exc.value) and str(extra) in str(exc.value)

    cell = _cell("sparse")
    cell.traffic["driver"] = "nowhere"
    with pytest.raises(FileNotFoundError, match="drivers/nowhere"):
        harness.run_cell(cell, 1, 0.1, False, time.perf_counter(),
                         require_tpu=False)
    del cell.traffic["driver"]
    with pytest.raises(KeyError, match="names no driver"):
        harness.run_cell(cell, 1, 0.1, False, time.perf_counter(),
                         require_tpu=False)


HALF = (
    "class Driver:\n"
    "    tracing = False\n"
    "    payload_bytes_per_step = 8\n"
    "    def __init__(self, cluster, config, traffic, seed): pass\n"
    "    def setup(self): return {'register': 0.0, 'inputs': 0.0}\n"
    "    def step(self): return 0.0, 0.0, 0.0\n"
    "    def checked_steps(self): pass\n"
    "    def counters(self): return 0, 0\n"
    "    def expected_counters(self, steps): return 0, 0\n"
    "    def least_bytes(self): return {'hbm': 1.0, 'ici': 0.0}\n"
)


@pytest.mark.parametrize("body, lacks", [
    (HALF, ["compare", "steps_done"]),
    (HALF.replace("traffic, seed", "traffic")
     + "    steps_done = 0\n    def compare(self, rounding=None): return []\n",
     ["__init__(cluster, config, traffic, seed)"]),
    (HALF.replace("class Driver:", "class HalfDriver:"),
     ["no class named Driver"]),
])
def test_a_driver_lacking_a_member_is_refused_by_name(body, lacks, tmp_path,
                                                      no_boot):
    search = _driver_file(tmp_path, "half", body)
    with pytest.raises(TypeError) as exc:
        harness.load_driver(search, "half")
    for member in lacks:
        assert member in str(exc.value)
    assert "setup" not in str(exc.value)       # what is there is not named
    cell = _cell("sparse")
    cell.traffic["driver"], cell.search = "half", search
    with pytest.raises(TypeError, match="half"):
        harness.run_cell(cell, 1, 0.1, False, time.perf_counter(),
                         require_tpu=False)


def test_a_whole_driver_file_is_taken(tmp_path):
    search = _driver_file(
        tmp_path, "whole", HALF + "    steps_done = 0\n"
        "    def compare(self, rounding=None): return []\n")
    cls = harness.load_driver(search, "whole")
    assert cls.__name__ == "Driver"
    assert harness.load_driver(search, "whole") is cls    # executed once


def test_a_file_only_driver_builds_on_one_that_exists():
    """The rehearsal's driver takes the sparse driver's class through the
    same loader and overrides the step and the comparison, nothing else;
    its reference lies beside ``drivers/`` in its own directory."""
    search = _cell("row-adagrad").search
    base = harness.load_driver(search, "sparse_pull_push")
    cls = harness.load_driver(search, "row_adagrad_pull_push")
    assert issubclass(cls, base) and cls is not base
    assert {k for k in vars(cls) if not k.startswith("__")} \
        == {"step", "compare"}
    assert not os.path.exists(os.path.join(BENCH, "drivers",
                                           "row_adagrad_pull_push.py"))
    import row_adagrad_reference

    assert os.path.dirname(row_adagrad_reference.__file__) \
        == os.path.join(HERE, "cells")
