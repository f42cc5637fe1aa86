"""``combine_ms`` and ``table_write_ms`` on synthetic traces of the shape
the chip's has (``test_trace_reduce.py``), one of a program that keeps XLA's
scatters and one of the program as the chip runs it since PRs 28-34 (the
three kernels): which operations each counts, by kind, by kernel name and by
result shape worked out from the sizes of the cell that is read
(``ctx.config`` / ``ctx.traffic``), and that they read nothing where there
is no device plane."""

import json
import os

import pytest

import harness
import sparse_handle_ops as ops
import trace_reduce as tr
from conftest import BENCH, ROOT
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "dlrm-criteo-rowadagrad.zipf"
SUM_CELL = "dlrm-criteo-emb.zipf"
T = "{1,0:T(8,128)}"

# The push as the chip runs it (PERF.md section 5): the combine with the
# segment sum's kernel, the accumulator's kernel (a tuple: the accumulator
# seen as whole 128-lane rows first), the table's.  ``ACC`` only under the
# handle.
PULL = [("%fusion = f32[131072,128]" + T + " fusion(f32[20000000,128] %p)",
         1300)]
COMBINE = [
    ("%sort.16 = (s32[131072]{0:T(1024)}, s32[131072]{0}) sort(%a, %b)", 94),
    ("%fusion.2 = f32[131072,128]" + T + " fusion(%g, %order)", 240),
    ("%segment_sum.1 = (f32[131072,128]" + T + ", s32[1]{0}) custom-call(%s)",
     385),
    ("%sort.19 = s32[131072]{0:T(1024)} sort(%first_rows)", 42),
    ("%copy.1 = f32[1,131072,128]{2,1,0:T(8,128)} copy(%g)", 204),
]
ACC = [
    ("%copy-done.2 = f32[131072,128]" + T + " copy-done(%cs)", 102),
    ("%acc_update.1 = (f32[156250,128]" + T + ", f32[512,1,256]{2,1,0})"
     " custom-call(%ids, %g2, %acc)", 878),
    ("%select_negate_fusion = f32[131072,128]" + T + " fusion(%x)", 207),
    ("%bitcast.3 = f32[20000000]{0:T(1024)} bitcast(%acc_update.1)", 0.5),
]
ROW_ADD = [("%row_add.1 = f32[20000000,128]" + T
            + " custom-call(%n, %rows, %G, %st)", 2006)]


def _profile(extra=(), steps=2, per_step=None):
    """Two steps of 10 us; a step runs the pull program (its gather) and
    the push program under the handle, by default as XLA alone compiles
    it: four sorts, the permutation and the segment sum, the elementwise
    step, and the two in-place scatters."""
    per_step = list(per_step) + list(extra) if per_step is not None else [
        # the pull program
        ("%fusion = f32[131072,128]" + T + " fusion(f32[20000000,128] %p)",
         1300),
        # the push program: combine
        ("%sort.0 = (s32[131072]{0:T(1024)}, s32[131072]{0}) sort(%a, %b)",
         200),
        ("%sort.2 = (s32[131072]{0}, s32[131072]{0}) sort(%c, %d)", 210),
        ("%fusion.2 = f32[131072,128]" + T + " fusion(%g, %order)", 1000),
        ("%fusion.4 = f32[131072,128]" + T + " fusion(%zeros, %seg)", 3000),
        ("%fusion.5 = s32[131072]{0} fusion(%seg)", 50),
        ("%fusion.1 = pred[131072]{0:T(1024)(128)(4,1)} fusion(%o, %order)",
         60),
        ("%reduce-window = s32[1024,128]{0,1} reduce-window(%first)", 30),
        # update
        ("%sort.3 = (s32[131072]{0}, f32[131072]{0}) sort(%e, %f)", 220),
        ("%fusion.3 = f32[131072]{0} fusion(%ac, %rows)", 40),
        ("%select_negate_fusion = f32[131072,128]" + T + " fusion(%x)", 90),
        # the whole table, the whole accumulator
        ("%fusion.6 = f32[20000000]{0:T(1024)} fusion(%ac, %r, %v)", 700),
        ("%fusion.7 = f32[20000000,128]" + T + " fusion(%st, %r, %s)", 2500),
    ] + list(extra)
    host, mods, opl = Line("python3"), Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
    for s in range(steps):
        base = 100_000 + s * 10_000
        host.events += [Ev(tr.STEP, base, 10_000), Ev(tr.ISSUE, base, 1000),
                        Ev(tr.WAIT, base + 1000, 9000)]
        at = base + 10
        for k, (name, ns) in enumerate(per_step):
            opl.events.append(Ev(name, at, ns))
            at += ns
        mods.events += [Ev("jit__pull(1)", base + 10, 1300),
                        Ev("jit__push_row_adagrad(2)", base + 1310, 8000)]
    return Profile([Plane("/device:TPU:0", [mods, opl]),
                    Plane("/host:CPU", [host])])


def _ctx(reduction, cell=CELL):
    cell = harness.load_cell(cell)
    return harness.LayerContext(spans=[], compiles_in_window=0,
                                reduction=reduction,
                                least={"hbm": 1.0, "ici": 0.0}, peaks={},
                                config=cell.config, traffic=cell.traffic)


@pytest.fixture
def readers():
    search = harness.search_dirs()
    return (harness.load_reader(search, "combine_ms"),
            harness.load_reader(search, "table_write_ms"))


@pytest.mark.parametrize("cell", [CELL, SUM_CELL])
def test_shapes_follow_from_the_cells_sizes(cell):
    ctx = _ctx(None, cell)
    assert ops.shapes(ctx.config, ctx.traffic) == {
        "table": "f32[20000000,128]", "accumulator": "f32[20000000]",
        "batch_rows": "f32[131072,128]", "batch_ids": "s32[131072]",
        "batch_flags": "pred[131072]"}
    assert not hasattr(ops, "CONFIG") and not hasattr(ops, "cell_shapes")


def test_kinds_shapes_and_the_kernels_names():
    # Four chips and a lane-packed width: a shard of each, rounded up.
    assert ops.shapes({"chips": 4, "rows": 4001, "dim": 32},
                      {"lookups_per_worker": 256}) == {
        "table": "f32[251,128]", "accumulator": "f32[1004]",
        "batch_rows": "f32[1024,32]", "batch_ids": "s32[1024]",
        "batch_flags": "pred[1024]"}
    assert ops.kind_and_shape("%fusion.7 f32[20000000,128]") == (
        "fusion", "f32[20000000,128]")
    assert ops.kind_and_shape("%scatter-add.1 f32[8,4]") == (
        "scatter-add", "f32[8,4]")
    assert ops.kind_and_shape("%select_negate_fusion f32[8,4]")[0] \
        == "select_negate_fusion"
    assert ops.kind_and_shape("jit__push_row_adagrad(2)") is None
    # A kernel's custom call carries its name: that is its kind.
    assert ops.KERNELS == ("segment_sum", "row_add", "acc_update")
    for kernel, shape in zip(ops.KERNELS, ("f32[131072,128]",
                                           "f32[20000000,128]",
                                           "f32[156250,128]")):
        assert ops.kind_and_shape(f"%{kernel}.1 {shape}") == (kernel, shape)


def test_what_each_reader_counts(readers):
    combine_ms, table_write_ms = readers
    ctx = _ctx(tr.reduce_trace(_profile()))
    assert ctx.reduction.steps == 2
    # Sorts of any result + fusions whose result is a workspace of the
    # batch (rows, ids, ownership), the pull program's gather among them
    # (see the reader's docstring); not the accumulator's gather
    # (f32[131072]), the elementwise step or the cumulative sum.
    assert combine_ms(ctx) == pytest.approx(
        (200 + 210 + 220 + 1000 + 3000 + 50 + 60 + 1300) * 1e-6)
    assert table_write_ms(ctx) == pytest.approx((700 + 2500) * 1e-6)


def test_the_three_kernels_are_counted_once_each(readers):
    """The row-adagrad push as the chip runs it: the segment sum's kernel
    is the combine's, the accumulator's and the table's are the write's,
    each by its name and once (the accumulator's bitcast back to
    ``f32[20000000]`` is of the accumulator's shape and costs nothing);
    what XLA moved off VMEM before the accumulator's kernel and the step
    are neither's."""
    combine_ms, table_write_ms = readers
    ctx = _ctx(tr.reduce_trace(_profile(
        per_step=PULL + COMBINE + ACC + ROW_ADD)))
    assert combine_ms(ctx) == pytest.approx(
        (1300 + 94 + 240 + 385 + 42) * 1e-6)
    assert table_write_ms(ctx) == pytest.approx((878 + 0.5 + 2006) * 1e-6)
    busy = ctx.reduction.busy_ms_per_step
    assert combine_ms(ctx) + table_write_ms(ctx) < busy


def test_the_sum_cell_reads_both_with_no_accumulator(readers):
    """``dlrm-criteo-emb.zipf`` runs the same combine and ``row_add``
    with no accumulator: its shapes come from its own files."""
    combine_ms, table_write_ms = readers
    ctx = _ctx(tr.reduce_trace(_profile(per_step=PULL + COMBINE + ROW_ADD)),
               SUM_CELL)
    assert ctx.config["server_handle"] == "sum"
    assert combine_ms(ctx) == pytest.approx(
        (1300 + 94 + 240 + 385 + 42) * 1e-6)
    assert table_write_ms(ctx) == pytest.approx(2006e-6)


@pytest.mark.parametrize("per_step, wrote", [
    (None, 700 + 2500), (PULL + COMBINE + ACC + ROW_ADD, 878 + 0.5 + 2006)])
def test_a_copy_of_a_donated_operand_shows_in_table_write_ms(readers,
                                                             per_step, wrote):
    _, table_write_ms = readers
    copy = ("%copy.9 = f32[20000000,128]" + T + " copy(f32[20000000,128] %st)",
            25_000)
    ctx = _ctx(tr.reduce_trace(_profile(extra=[copy], per_step=per_step)))
    assert table_write_ms(ctx) == pytest.approx((wrote + 25_000) * 1e-6)


def test_nothing_is_read_without_a_device_plane(readers):
    p = _profile()
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert tr.reduce_trace(p) is None
    for read in readers:
        assert read(_ctx(None)) is None
    # A trace of another cell: no operation of these shapes, no value.
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


def test_both_metrics_list_the_two_criteo_cells(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = {CELL, SUM_CELL}
    for name in ("combine_ms", "table_write_ms"):
        m = entries[name]
        assert cells <= set(m["workloads"])
        assert m["source"] == "device_trace"
        assert m["layer"] == "xla programs and kernels"
        assert m["moves"] == "step_p50" and m["unit"] == "ms"
    for cell in cells:
        assert {"combine_ms", "table_write_ms"} <= {
            m["name"] for m in harness.load_cell(
                cell, root=bench_root).per_layer}
    # Each reports them where it is listed, wherever else that is.
    for w in bench["workloads"]:
        names = {m["name"] for m in harness.load_cell(
            w["name"], root=bench_root).per_layer}
        for name in ("combine_ms", "table_write_ms"):
            assert (name in names) == (w["name"] in entries[name]["workloads"])
