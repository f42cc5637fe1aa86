"""Every cell of ``BENCHMARK.json`` resolves by file, with nothing booted:
the configuration, the traffic file, the driver's file and class, and every
per-layer reader the cell reports (ISSUE 26's loader test, which
``benchmark/tests/test_loader.py`` holds outside tier-1), and what an
addition PR appends is taken by name (ISSUE 36's, whose first real case
are the three metrics of the occupancy account that PR 37 appended)."""

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


def test_the_many_tables_cell_names_its_tables_traffic_and_driver(harness):
    """``dlrm-terabyte-26tables.zipf`` (PR 50): the same Terabyte run with
    its 26 tables kept as 26 tables, under a driver of its own whose step is
    one grouped pull and one grouped push, and the four per-layer metrics
    that only this cell reports."""
    cell = harness.load_cell("dlrm-terabyte-26tables.zipf")
    config, traffic = cell.config, cell.traffic
    sibling = harness.load_cell("dlrm-terabyte-emb64.zipf")
    assert config["kind"] == "sparse" and config["server_handle"] == "sum"
    assert config["reduced"] == [] and config["dtype"] == "float32"
    tables = config["tables"]
    assert [name for name, _ in tables] == [f"emb{i:02d}" for i in range(26)]
    rows = [r for _, r in tables]
    assert sum(rows) == config["rows"] == 54_063_992
    assert (min(rows), max(rows), rows.count(10_000_000)) == (3, 10**7, 5)
    # No width, batch, skew or guarantee of the sibling is changed.
    assert config["dim"] == sibling.config["dim"] == 64
    assert config["sizes"] == sibling.config["sizes"]
    assert config["guarantees"].startswith(sibling.config["guarantees"])
    assert config["limits"]["first3_err"] <= 1.5e-4
    assert config["limits"]["final_err"] <= 2e-2
    assert traffic["name"] == "zipf-tables-2048x26"
    assert traffic["driver"] == "sparse_tables_pull_push"
    assert traffic["lookups_per_table"] == config["sizes"]["mini_batch"]
    assert traffic["lookups_per_table"] * len(tables) \
        == traffic["lookups_per_worker"] \
        == sibling.traffic["lookups_per_worker"] == 53_248
    for key in ("pool_batches", "zipf_constant", "gradient_scale",
                "warm_steps", "step_deadline_s", "trace"):
        assert traffic[key] == sibling.traffic[key], key
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "dlrm-terabyte-26tables")
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert len(config["source"]) <= 200
    # The whole deployment on one chip, two rows to a 128-lane physical row.
    held = sum(-(-r // 2) for r in rows) * 512
    assert held == 13_840_384_000 and 0.25 * 16e9 < held < 16e9
    driver = harness.resolve(cell)
    assert driver is not harness.load_driver(cell.search, "sparse_pull_push")
    names = {m["name"] for m in cell.per_layer}
    assert {"sparse_tables_per_op", "tables_combine_ms", "tables_write_ms",
            "sparse_device_ops_per_step", "roofline_share", "busy_ms",
            "launches_per_step", "ops_per_step"} <= names
    assert not {"combine_ms", "table_write_ms", "packed_write_ms",
                "packed_combine_ms", "route_ms"} & names


def test_the_bags_cell_names_its_tables_bag_sizes_traffic_and_driver(harness):
    """``dlrm-dcnv2-multihot.bags`` (PR 54): MLPerf's DLRM-DCNv2 tables, in
    which a lookup is a bag of ``multi_hot_sizes[t]`` ids, under a driver of
    its own built on the many-tables driver, and the five per-layer metrics
    that only this cell reports."""
    cell = harness.load_cell("dlrm-dcnv2-multihot.bags")
    config, traffic = cell.config, cell.traffic
    sibling = harness.load_cell("dlrm-criteo-rowadagrad.zipf").config
    assert config["kind"] == "sparse" and config["dtype"] == "float32"
    assert config["server_handle"] == sibling["server_handle"] \
        == "row_adagrad:0.004,1e-8"
    assert config["reduced"] == ["max_ind_range"]
    tables, hs = config["tables"], config["bag_sizes"]
    assert [name for name, _ in tables] == [f"emb{i:02d}" for i in range(26)]
    rows, sizes = [r for _, r in tables], config["sizes"]
    cap = sizes["max_ind_range"]
    assert rows == [min(c, cap) for c in sizes["num_embeddings_per_feature"]]
    assert hs == sizes["multi_hot_sizes"] and len(hs) == 26
    assert (sum(hs), max(hs), hs.count(1)) == (214, 100, 11)
    assert sum(rows) == config["rows"]
    assert (min(rows), max(rows), rows.count(cap)) == (3, cap, 5)
    # No width of the source is changed; the sibling's guarantee word for
    # word up to the bag's lines.
    assert config["dim"] == sizes["embedding_width"] == sibling["dim"] == 128
    assert sizes["max_ind_range_published"] == 40_000_000
    head = sibling["guarantees"].split("(KVWorker.push_sparse ")[0]
    assert config["guarantees"].startswith(head)
    for phrase in ("a row that lies twice in a bag added twice",
                   "each slot exactly once",
                   "A table's rows are touched by its own ids alone",
                   "No cell may weaken this."):
        assert phrase in config["guarantees"], phrase
    assert set(config["limits"]) == {"first3_err", "final_err", "acc_err"}
    assert traffic["name"] == "zipf-bags-4096x214"
    assert traffic["driver"] == "sparse_bags_pull_push"
    B = traffic["bags_per_table"]
    assert B == sizes["samples_per_worker"] == 4096
    assert B * sum(hs) == traffic["lookups_per_worker"] == 876_544
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "dlrm-dcnv2-multihot")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"]
    assert len(config["source"]) <= 200
    # The whole deployment on one chip: the tables and an f32 accumulator a
    # row, above the floor of a quarter of the chip.
    held = sum(rows) * (512 + 4)
    assert 0.25 * 16e9 < held < 16e9
    driver = harness.resolve(cell)
    base = harness.load_driver(cell.search, "sparse_tables_pull_push")
    assert issubclass(driver, base) and driver is not base
    names = {m["name"] for m in cell.per_layer}
    assert {"bag_lookups_per_bag", "bag_pull_ms", "bag_pull_roofline",
            "bag_combine_ms", "bag_write_ms", "roofline_share", "busy_ms",
            "launches_per_step", "ops_per_step"} <= names
    assert not {"combine_ms", "table_write_ms", "tables_combine_ms",
                "tables_write_ms", "sparse_tables_per_op", "launch_pull_ms",
                "launch_push_ms"} & names
    for name in ("bag_lookups_per_bag", "bag_pull_ms", "bag_pull_roofline",
                 "bag_combine_ms", "bag_write_ms"):
        entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
        assert entry["workloads"] == ["dlrm-dcnv2-multihot.bags"]
        assert callable(harness.load_reader(cell.search, name))


# What an addition PR may do: append entries, each at the end of its list,
# and add files under ``paths``.  The occupancy account's three metrics
# (PR 37) came that way.
OCCUPANCY_METRICS = ("starved_prelaunch_ms", "starved_ms",
                     "ready_at_wait_share")


@pytest.mark.parametrize("name", OCCUPANCY_METRICS)
def test_an_appended_metric_that_lists_no_cell_is_every_cells(name, harness):
    entry, = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert "workloads" not in entry
    assert entry["layer"] == "app api and engine host side"
    assert entry["moves"] == "step_p50" and entry["better"] == "lower"
    assert entry["source"] in ("program_span", "program_counter")
    for w in BENCHMARK["workloads"]:
        cell = harness.load_cell(w["name"])
        assert name in [m["name"] for m in cell.per_layer]
    # A reader is given spans and finds the program's clock itself: with
    # no spans it reads nothing and does not raise.
    read = harness.load_reader(harness.search_dirs(), name)
    ctx = harness.LayerContext(spans=[], compiles_in_window=0,
                               reduction=None, least={}, peaks={})
    assert read(ctx) is None


def test_an_appended_configuration_cell_and_metric_are_taken(harness,
                                                            tmp_path):
    """The appended-entry case of ``benchmark/tests/test_loader.py`` (its
    ``conftest.py`` ``appended_root``), inside tier-1: a root whose
    ``BENCHMARK.json`` is the committed one with a configuration, a cell
    and a per-layer metric appended, their files in a directory of
    ``paths`` of their own.  They load by name, and every cell that was
    there keeps every metric it had, in its order, and gains the one that
    lists no cell."""
    cells = os.path.join(BENCH, "tests", "cells")
    extra = tmp_path / "extra"
    for kind in ("configs", "traffic", "layer_metrics"):
        (extra / kind).mkdir(parents=True)
    for src, kind, name in (("tiny-sparse.json", "configs", "appended-table"),
                            ("tiny-zipf.json", "traffic", "appended-zipf")):
        with open(os.path.join(cells, src)) as fh:
            data = json.load(fh)
        (extra / kind / (name + ".json")).write_text(json.dumps(dict(
            data, name=name, reduced=[], chips=1, source=__file__)))
    (extra / "layer_metrics" / "appended_steps.py").write_text(
        "def read(ctx):\n    return float(len(ctx.spans)) or None\n")
    bench = json.loads(json.dumps(BENCHMARK))
    bench["paths"] = [BENCH, str(extra)]
    for config in bench["configs"]:
        config["file"] = os.path.join(ROOT, config["file"])
    bench["configs"].append({
        "name": "appended-table", "source": __file__, "reduced": [],
        "file": str(extra / "configs" / "appended-table.json"),
        "why": "what an addition PR appends"})
    bench["workloads"].append({
        "name": "appended-table.zipf", "config": "appended-table",
        "traffic": "appended-zipf", "chips": 1,
        "why": "what an addition PR appends"})
    bench["per_layer"].append({
        "name": "appended_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "dense and sparse engines",
        "moves": "goodput"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    root = str(tmp_path)

    cell = harness.load_cell("appended-table.zipf", root=root)
    assert cell.config["name"] == "appended-table" and cell.chips == 1
    assert cell.traffic["name"] == "appended-zipf"
    assert harness.resolve(cell) is harness.load_driver(cell.search,
                                                        "sparse_pull_push")
    assert cell.search == [BENCH, str(extra)]
    names = [m["name"] for m in cell.per_layer]
    assert "appended_steps" in names and "lamb_norm_ms" not in names
    assert set(OCCUPANCY_METRICS) <= set(names)
    ctx = harness.LayerContext(spans=[(0.0, 0.1, 0.2)] * 3,
                               compiles_in_window=0, reduction=None,
                               least={}, peaks={})
    assert harness.load_reader(cell.search, "appended_steps")(ctx) == 3.0
    for w in BENCHMARK["workloads"]:
        before = [m["name"] for m in harness.load_cell(w["name"]).per_layer]
        after = [m["name"] for m in harness.load_cell(
            w["name"], root=root).per_layer]
        assert after == before + ["appended_steps"]
    with pytest.raises(KeyError, match="appended-table.zipf"):
        harness.load_cell("appended-table.zipf")      # not in the committed
