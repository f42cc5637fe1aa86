"""``lamb_update_ms``, ``lamb_update_roofline`` and ``lamb_norm_ms`` on a
synthetic trace of the shape the chip's has (``test_trace_reduce.py``) with
the operations the full-size program compiles to for a v5e
(``test_compile_fullsize_lamb.py``): which operations each counts, and that
they read nothing where there is no device plane or no such operation (a
program from before the handle).  ``lamb_bytes.py``'s counts against hand
sums; the loader on the entries PR 33 adds to ``BENCHMARK.json``."""

import json
import os

import pytest

import harness
import lamb_bytes
import lamb_ops
import trace_reduce as tr
from conftest import BENCH, ROOT
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "bert-large-lamb.tree"
T = "{1,0:T(8,128)}"
VEC = "f32[2627072,128]"

# One step of ``jit__push_pull`` on one chip, nanoseconds.
STEP = [
    ("%pad_maximum_fusion = f32[2]{0:T(128)S(1)} fusion(%div.11, %div.10)", 900),
    ("%lamb_moments.1 = (" + VEC + T + ", " + VEC + T + ", f32[796]{0:T(1024)S(1)})"
     " custom-call(%pad_maximum_fusion, %constant.9)", 12_000_000),
    ("%reshape.23 = f32[398,2]{1,0:T(8,128)S(1)} reshape(%jit_lamb_moments_.7)", 1_000),
    ("%multiply_reduce_fusion = f32[398]{0:T(512)S(1)} fusion(%reshape.23)", 2_000),
    ("%lamb_apply.1 = " + VEC + T + " custom-call(%pad_maximum_fusion)", 8_000_000),
    ("%copy.16 = f32[336265216]{0:T(1024)} copy(%bitcast.4)", 4_000_000),
]
# What a program under Adam leaves in a trace: none of the above.
ADAM_STEP = [
    ("%adam_update.1 = (f32[8192,128]" + T + ") custom-call(%a)", 44_000),
    ("%copy.16 = f32[1048576]{0:T(1024)} copy(%b)", 12_000),
]


def _profile(ops, steps=2):
    host, mods, opl = Line("python3"), Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
    width = 30_000_000
    for s in range(steps):
        base = 100_000 + s * width
        host.events += [Ev(tr.STEP, base, width), Ev(tr.ISSUE, base, 1000),
                        Ev(tr.WAIT, base + 1000, width - 1000)]
        at = base + 10
        for name, ns in ops:
            opl.events.append(Ev(name, at, ns))
            at += ns
        mods.events.append(Ev("jit__push_pull(1)", base + 10, at - base - 10))
    return Profile([Plane("/device:TPU:0", [mods, opl]),
                    Plane("/host:CPU", [host])])


def _ctx(reduction):
    cell = harness.load_cell(CELL)
    return harness.LayerContext(spans=[], compiles_in_window=0,
                                reduction=reduction,
                                least={"hbm": 1.0, "ici": 0.0},
                                peaks={"hbm_gb_s": 819},
                                config=cell.config, traffic=cell.traffic)


@pytest.fixture
def readers():
    search = harness.search_dirs()
    return [harness.load_reader(search, name) for name in
            ("lamb_update_ms", "lamb_update_roofline", "lamb_norm_ms")]


def test_the_readers_on_a_trace_of_the_lamb_step(readers):
    update, roofline, norm = readers
    ctx = _ctx(tr.reduce_trace(_profile(STEP)))
    assert ctx.reduction.steps == 2
    assert update(ctx) == pytest.approx(20.0)
    assert norm(ctx) == pytest.approx(0.003)
    # 28 B an element and 12 B more for emb.word, the one key whose p and
    # u pass VMEM: 9.789 GB at 819 GB/s is 11.95 ms of the 20.
    least_ms = (28 * 336226108 + 12 * 31254528) / 819e9 * 1e3
    assert roofline(ctx) == pytest.approx(100 * least_ms / 20.0)
    assert 59 < roofline(ctx) < 60


def test_the_readers_find_nothing_to_read(readers):
    for ctx in (_ctx(None), _ctx(tr.reduce_trace(_profile(ADAM_STEP)))):
        assert [r(ctx) for r in readers] == [None, None, None]


def test_the_cells_sizes_come_from_its_configuration():
    assert not hasattr(lamb_ops, "CONFIG")
    sizes = lamb_ops.cell_sizes(harness.load_cell(CELL).config)
    assert sizes["keys"] == 398 and sizes["chips"] == 1
    assert lamb_ops.norm_shapes(398) == ("f32[398]", "f32[398,2]",
                                         "f32[796]")
    assert sizes["update_bytes"] == 28 * 336226108 + 12 * 31254528


def test_least_bytes_against_hand_sums():
    # A key is charged a second pass only where 8 B an element of one
    # device's share pass 128 MiB: above 16,777,216 elements a device.
    assert lamb_bytes.over_vmem([16777216, 16777217, 2], 1) == 16777217
    assert lamb_bytes.over_vmem([16777217, 4 * 16777216 + 4], 4) \
        == 4 * 16777216 + 4
    assert lamb_bytes.lamb_update(1000, 1, 0) == 28000
    assert lamb_bytes.lamb_update(1000, 4, 400) == (28000 + 4800) / 4
    import least_bytes

    for w in (1, 4):
        adam = least_bytes.dense_adam_step(1000, w)
        lamb = lamb_bytes.dense_lamb_step(1000, w)
        assert lamb == adam                       # no key larger than VMEM
        more = lamb_bytes.dense_lamb_step(1000, w, over=400)
        assert more["hbm"] == adam["hbm"] + 12 * 400 / w
        assert more["ici"] == adam["ici"]
    # Today's two kernels move 40 B an element: the least is below it.
    n = 336226108
    assert lamb_bytes.lamb_update(n, 1, 31254528) < 40 * n


def test_the_loader_takes_the_new_entries(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = harness.load_cell(CELL, root=bench_root)
    assert cell.chips == 1 and cell.traffic["driver"] == "dense_tree_push_pull"
    assert cell.config["server_handle"].startswith("lamb:")
    names = [m["name"] for m in cell.per_layer]
    for name in ("lamb_update_ms", "lamb_update_roofline", "lamb_norm_ms",
                 "route_ms", "busy_ms", "roofline_share", "ops_per_step",
                 "launches_per_step", "compiles_in_window"):
        assert name in names
    for name in ("combine_ms", "table_write_ms", "packed_write_ms"):
        assert name not in names
    assert harness.resolve(cell).__name__ == "Driver"
    # The three metrics are this cell's alone, wherever they stand in
    # their list; the cell and its configuration are there by name.
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("lamb_update_ms", "lamb_update_roofline", "lamb_norm_ms"):
        assert entries[name]["workloads"] == [CELL]
    assert [w["config"] for w in bench["workloads"] if w["name"] == CELL] \
        == ["bert-large-lamb"]
    assert [os.path.relpath(os.path.join(bench_root, c["file"]), ROOT)
            for c in bench["configs"] if c["name"] == "bert-large-lamb"] \
        == ["benchmark/configs/bert-large-lamb.json"]
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    assert all(len(c["source"]) <= 200 for c in bench["configs"])
    assert os.path.exists(os.path.join(BENCH, "lamb_reference.py"))
