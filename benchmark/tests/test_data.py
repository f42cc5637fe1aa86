"""The data files and the pure functions of the yardstick."""

import json
import os
import re

import numpy as np
import pytest

import buckets
import least_bytes
import reference
import zipf
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


BENCHMARK = _json(ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("config, tensors, params, n_buckets, full", [
    ("bert-large-adam", 398, 336_226_108, 467, 319),
    ("gpt2-large-adam", 436, 774_030_080, 820, 674),
])
def test_tensor_lists_sum_and_cut(config, tensors, params, n_buckets, full):
    cfg = _json(BENCH, "configs", config + ".json")
    sizes = buckets.expand_tensors(cfg["tensors"])
    assert len(sizes) == tensors
    assert len({name for name, _ in sizes}) == tensors
    assert sum(n for _, n in sizes) == params == cfg["parameters"]
    limit = _json(BENCH, "traffic", "device-buckets.json")["bucket_elements"]
    cut = buckets.make_buckets([n for _, n in sizes], limit)
    assert sum(cut) == params and max(cut) == limit
    assert len(cut) == n_buckets
    assert cut.count(limit) == full
    assert len(set(cut)) == 7  # the shapes a run warms


def test_bucket_rule_is_the_programs_on_resnet50():
    from pslite_tpu.models.resnet_trace import (make_buckets,
                                                resnet50_param_sizes)

    theirs = [n for _, n in make_buckets()]
    ours = buckets.make_buckets([n for _, n in resnet50_param_sizes()],
                                (4 << 20) // 4)
    assert len(theirs) == 36 and ours == theirs


def test_bucket_rule_small_cases():
    assert buckets.make_buckets([3, 3, 3], 8) == [6, 3]      # fuse, then overflow
    assert buckets.make_buckets([20], 8) == [8, 8, 4]        # split
    assert buckets.make_buckets([5, 8, 2], 8) == [5, 8, 2]   # flush before a full one
    assert buckets.make_buckets([], 8) == []


def test_zipf_head_share_and_repeat():
    rows = _json(BENCH, "configs", "dlrm-criteo-emb.json")["rows"]
    z = zipf.BoundedZipf(rows, 0.99)
    assert abs(z.head_share() - 1 / 18.883448) < 1e-6
    a = zipf.zipf_rows(7, (4, 131072), rows, 0.99)
    b = zipf.zipf_rows(7, (4, 131072), rows, 0.99)
    assert a.dtype == np.int32 and (a == b).all()
    assert 0 <= a.min() and a.max() < rows
    share = (a == zipf.HOTTEST_ROW).mean()
    assert abs(share - z.head_share()) < 0.01 * z.head_share() * 5  # 524,288 draws
    assert (zipf.zipf_rows(8, (4, 131072), rows, 0.99) != a).any()
    # Hot rows are not neighbours: ranks 0..9 land far apart.
    top = (np.arange(10) * zipf.SCRAMBLE) % rows
    assert np.diff(np.sort(top)).min() > 1000


def test_zipf_tail_matches_the_exact_table():
    n = 300_000  # past the head table, small enough for the exact CDF
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -0.99)
    z = zipf.BoundedZipf(n, 0.99)
    assert abs(z.harmonic - cdf[-1]) / cdf[-1] < 1e-10
    u = np.random.default_rng(1).random(200_000)
    exact = np.searchsorted(cdf, u * cdf[-1], side="right")
    assert (z.ranks(u) == exact).all()


def test_scramble_is_a_bijection_on_the_cells_rows():
    rows = _json(BENCH, "configs", "dlrm-criteo-emb.json")["rows"]
    assert np.gcd(rows, zipf.SCRAMBLE) == 1


def test_adam_reference_by_hand():
    ref = reference.AdamReference(1, lr=0.1, b1=0.9, b2=0.999, eps=1e-8)
    g = np.array([[1.0], [1.0]])  # two workers, summed: 2
    p1 = ref.step(g)[0]
    # m=0.2, v=0.004, alpha=0.1*sqrt(0.001)/0.1 -> p = -alpha*0.2/sqrt(0.004)
    assert p1 == pytest.approx(-0.1, rel=1e-6)
    p2 = ref.step(2 * g)[0]
    m2, v2 = 0.9 * 0.2 + 0.1 * 4, 0.999 * 0.004 + 0.001 * 16
    alpha2 = 0.1 * np.sqrt(1 - 0.999 ** 2) / (1 - 0.9 ** 2)
    assert p2 == pytest.approx(p1 - alpha2 * m2 / (np.sqrt(v2) + 1e-8),
                               rel=1e-12)
    assert ref.t == 2


def test_adam_handle_parses():
    assert reference.parse_adam_handle("adam:1e-4,0.9,0.999,1e-8") == {
        "lr": 1e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    assert reference.parse_adam_handle("adam:0.5")["b2"] == 0.999
    with pytest.raises(ValueError):
        reference.parse_adam_handle("sgd:0.1")


def test_row_sum_reference_by_hand():
    watch = np.array([[1, 5, 5, 9]])
    ref = reference.RowSumReference(watch, 2)
    idx = np.array([[5, 5, 7, 1]])            # 7 is not watched
    grads = np.arange(8.0).reshape(1, 4, 2)
    c = ref.contribution(idx, grads)
    assert c.tolist() == [[6.0, 7.0], [2.0, 4.0], [0.0, 0.0]]  # rows 1, 5, 9
    ref.push(c, 3)
    assert ref.pull(watch)[0].tolist() == [[18, 21], [6, 12], [6, 12], [0, 0]]
    with pytest.raises(KeyError):
        ref.pull(np.array([[7]]))


def test_bf16_rounds_to_nearest_even():
    got = reference.bf16(np.array([1.0, 1.00390625, 1.001, 3.14159]))
    assert got.tolist() == [1.0, 1.0, 1.0, 3.140625]


def test_scaled_errors():
    assert reference.scaled_error([1.0, 0.0], [1.0, 1e-9], 1e-4) == \
        pytest.approx(1e-5)
    assert reference.scaled_error([np.nan], [0.0], 1.0) == float("inf")
    assert reference.scaled_error([1.0], [1.0, 2.0], 1.0) == float("inf")
    # By the row: a small element beside a large one is the row's noise.
    assert reference.row_scaled_error([[100.0, 0.1]], [[100.0, 0.0]], 1.0) \
        == pytest.approx(1e-3)


def test_least_bytes():
    one = least_bytes.dense_adam_step(1000, 1)
    assert one == {"hbm": 4 * 1000 + 24 * 1000, "ici": 0.0}
    four = least_bytes.dense_adam_step(1000, 4)
    assert four["hbm"] == 4000 + 6000 + 3000 and four["ici"] == 6000
    sp = least_bytes.sparse_pull_push_step(100, 256, 128, 1)
    assert sp["hbm"] == 3 * 100 * 512 + 2 * 256 * 4 + 2 * 256 * 512
    peaks = _json(BENCH, "peaks.json")["TPU v5 lite"]
    assert least_bytes.least_seconds(four, peaks)["bound"] == "ici"
    assert least_bytes.least_seconds(one, peaks) == {
        "seconds": 28000 / 819e9, "bound": "hbm"}


def test_benchmark_json_names_units_and_files(bench_root):
    """Under the committed file and under a copy with an entry of each
    kind appended (``conftest.py``): nothing here counts entries or asks
    where one stands."""
    import harness

    BENCHMARK = _json(bench_root, "BENCHMARK.json")
    search = harness.search_dirs(bench_root)
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCHMARK[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCHMARK["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        harness._find(search, "layer_metrics", m["name"], (".py",))
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    four = 0
    for w in BENCHMARK["workloads"]:
        assert NAME.match(w["traffic"]) and w["config"] in configs
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        four += w["chips"] == 4
        cfg = _json(bench_root, configs[w["config"]]["file"])
        assert cfg["chips"] == w["chips"]
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert len(cfg["source"]) <= 200
        assert cfg["source"] == configs[w["config"]]["source"]
        harness._find(search, "traffic", w["traffic"], (".json",))
    assert four <= max(1, len(BENCHMARK["workloads"]) // 4)
    assert 1 <= BENCHMARK["run_seconds"] <= 51


def test_run_py_names_no_cell_no_model_and_no_driver():
    drivers = [f[:-3] for f in os.listdir(os.path.join(BENCH, "drivers"))
               if f.endswith(".py")]
    assert len(drivers) >= 2
    for name in ("run.py", "harness.py", "sets.py", "readings.py"):
        with open(os.path.join(BENCH, name)) as fh:
            text = fh.read().lower()
        for word in ["bert", "gpt2", "gpt-2", "dlrm", "criteo"] + drivers:
            assert word not in text, (name, word)
