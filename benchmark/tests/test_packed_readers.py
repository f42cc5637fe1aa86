"""``packed_write_ms`` and ``packed_combine_ms`` on synthetic traces of the
shape the chip's has (``test_trace_reduce.py``), one of a program that keeps
XLA's scatter on a lane-packed table and one of a program that writes it by
distinct physical row: which operations each counts, by kind and result
shape worked out from the cell's own sizes, and that they read nothing
where there is no device plane or no operation of these shapes."""

import json
import os

import pytest

import harness
import packed_table_ops as ops
import trace_reduce as tr
from conftest import ROOT
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "dlrm-terabyte-emb64.zipf"
T = "{1,0:T(8,128)}"
TABLE = "f32[27000000,128]"

# The pull program, as both sides run it: the gather of physical rows and
# the selection of each row's slot.
PULL = [
    ("%fusion = f32[53248,128]" + T + " fusion(" + TABLE + " %p, %ix)", 500),
    ("%fusion.1 = f32[53248,64]" + T + " fusion(%rows, %slot)", 60),
]
# A push through XLA's scatter: the placement, then the scatter's fusion
# (which sorts its indices first).
SCATTER_PUSH = [
    ("%fusion.2 = f32[53248,128]" + T + " fusion(%g, %slot)", 70),
    ("%sort.1 = (s32[53248]{0}, s32[53248]{0}) sort(%a, %b)", 40),
    ("%fusion.3 = " + TABLE + T + " fusion(%st, %rows, %placed)", 3900),
]
# A push by distinct physical row: placement, sort, permutation, segment
# sum (XLA's scatter-add: before PR 32), the sort of the segments' rows,
# the kernel.
KERNEL_PUSH = [
    ("%copy.5 = f32[53248,128]" + T + " copy(%tiled)", 30),
    ("%compare_select_fusion = f32[53248,128]" + T + " fusion(%c, %s)", 45),
    ("%sort.16 = (s32[53248]{0:T(1024)}, s32[53248]{0}) sort(%k, %i)", 41),
    ("%fusion.9 = s32[53248]{0:T(1024)} fusion(%sort.18)", 7),
    ("%fusion.4 = f32[53248,128]" + T + " fusion(%placed, %order)", 100),
    ("%sort.17 = (s32[53248]{0}, s32[53248]{0}) sort(%seg, %j)", 42),
    ("%fusion.5 = f32[53248,128]" + T + " fusion(%zeros, %seg, %sg)", 680),
    ("%sort.19 = s32[53248]{0:T(1024)} sort(%first_rows)", 20),
    ("%reduce-window = s32[416,128]{0,1} reduce-window(%first)", 15),
    ("%row_add.1 = " + TABLE + T + " custom-call(%n, %rows, %G, %st)", 930),
]


def _profile(push, steps=2):
    per_step = PULL + push
    host, mods, opl = Line("python3"), Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
    for s in range(steps):
        base = 100_000 + s * 10_000
        host.events += [Ev(tr.STEP, base, 10_000), Ev(tr.ISSUE, base, 1000),
                        Ev(tr.WAIT, base + 1000, 9000)]
        at = base + 10
        for name, ns in per_step:
            opl.events.append(Ev(name, at, ns))
            at += ns
        mods.events += [Ev("jit__pull(1)", base + 10, 560),
                        Ev("jit__push(2)", base + 570, at - base - 570)]
    return Profile([Plane("/device:TPU:0", [mods, opl]),
                    Plane("/host:CPU", [host])])


def _ctx(reduction):
    cell = harness.load_cell(CELL)
    return harness.LayerContext(spans=[], compiles_in_window=0,
                                reduction=reduction,
                                least={"hbm": 1.0, "ici": 0.0}, peaks={},
                                config=cell.config, traffic=cell.traffic)


@pytest.fixture
def readers():
    search = harness.search_dirs()
    return (harness.load_reader(search, "packed_write_ms"),
            harness.load_reader(search, "packed_combine_ms"))


def test_shapes_follow_from_the_cells_sizes():
    ctx = _ctx(None)
    assert not hasattr(ops, "CONFIG") and not hasattr(ops, "cell_shapes")
    assert ops.shapes(ctx.config, ctx.traffic) == {
        "table": TABLE, "accumulator": "f32[54000000]",
        "batch_rows": "f32[53248,64]", "batch_phys_rows": "f32[53248,128]",
        "batch_ids": "s32[53248]", "batch_flags": "pred[53248]"}


def test_what_each_reader_counts_on_either_side(readers):
    packed_write_ms, packed_combine_ms = readers
    # XLA's scatter: the one table-shaped operation is its fusion; the
    # movers are the pull's two, the placement and the scatter's own sort.
    ctx = _ctx(tr.reduce_trace(_profile(SCATTER_PUSH)))
    assert ctx.reduction.steps == 2
    assert packed_write_ms(ctx) == pytest.approx(3900e-6)
    assert packed_combine_ms(ctx) == pytest.approx((500 + 60 + 70 + 40) * 1e-6)
    # By distinct physical row: the kernel alone has the table's shape;
    # sorts of any result and the fusions of the batch's four shapes are
    # the combine's, not the copy XLA tiles the rows with, the elementwise
    # placement it names after its operations or the cumulative sum.
    ctx = _ctx(tr.reduce_trace(_profile(KERNEL_PUSH)))
    assert packed_write_ms(ctx) == pytest.approx(930e-6)
    assert packed_combine_ms(ctx) == pytest.approx(
        (500 + 60 + 41 + 7 + 100 + 42 + 680 + 20) * 1e-6)


def test_the_segment_sums_kernel_is_the_combines(readers):
    """Since PR 32 the segment sum is a kernel of its own name: counted by
    that name, once, as XLA's scatter-add was by its shape; ``row_add`` is
    the write's by its name as by its shape, once."""
    packed_write_ms, packed_combine_ms = readers
    push = [(("%segment_sum.1 = (f32[53248,128]" + T + ", s32[1]{0})"
              " custom-call(%sorted)", 154) if name.startswith("%fusion.5")
             else (name, ns)) for name, ns in KERNEL_PUSH]
    ctx = _ctx(tr.reduce_trace(_profile(push)))
    assert packed_write_ms(ctx) == pytest.approx(930e-6)
    assert packed_combine_ms(ctx) == pytest.approx(
        (500 + 60 + 41 + 7 + 100 + 42 + 154 + 20) * 1e-6)
    assert packed_write_ms(ctx) + packed_combine_ms(ctx) \
        < ctx.reduction.busy_ms_per_step


def test_a_copy_of_the_donated_table_shows_in_packed_write_ms(readers):
    packed_write_ms, _ = readers
    copy = ("%copy.9 = " + TABLE + T + " copy(" + TABLE + " %st)", 35_000)
    ctx = _ctx(tr.reduce_trace(_profile(KERNEL_PUSH + [copy])))
    assert packed_write_ms(ctx) == pytest.approx((930 + 35_000) * 1e-6)


def test_nothing_is_read_without_a_device_plane_or_in_another_cell(readers):
    p = _profile(KERNEL_PUSH)
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert tr.reduce_trace(p) is None
    for read in readers:
        assert read(_ctx(None)) is None
    other = Profile([
        Plane("/device:TPU:0", [
            Line(tr.MODULES_LINE, [Ev("jit__push_pull(1)", 100_010, 100)]),
            Line(tr.OPS_LINE, [Ev("%adam_update.1 = (f32[8192,128]" + T
                                  + ", f32[8]) custom-call(%x)", 100_010,
                                  100)])]),
        Plane("/host:CPU", [Line("python3", [Ev(tr.STEP, 100_000, 1000)])])])
    ctx = _ctx(tr.reduce_trace(other))
    assert ctx.reduction is not None
    for read in readers:
        assert read(ctx) is None


def test_both_metrics_list_the_one_cell(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in ("packed_write_ms", "packed_combine_ms"):
        m = entries[name]
        assert m["workloads"] == [CELL] and m["source"] == "device_trace"
        assert m["layer"] == "xla programs and kernels"
        assert m["moves"] == "step_p50" and m["unit"] == "ms"
    cell = harness.load_cell(CELL, root=bench_root)
    names = {m["name"] for m in cell.per_layer}
    assert {"packed_write_ms", "packed_combine_ms"} <= names
    assert not {"combine_ms", "table_write_ms", "route_ms"} & names
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        assert not {"packed_write_ms", "packed_combine_ms"} & {
            m["name"] for m in harness.load_cell(
                other, root=bench_root).per_layer}
