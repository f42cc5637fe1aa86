"""The four readers of the cell ``dlrm-terabyte-26tables.zipf`` on a synthetic
step of the shape the chip's trace has (``test_trace_reduce.py``) and on a
stage clock fed by hand: ``tables_combine_ms`` and ``tables_write_ms`` tell a
step's operations by kind and by result shape worked out from the cell's own
26 tables and its lookups a table, ``sparse_device_ops_per_step`` counts the
operations a traced step executes, ``sparse_tables_per_op`` reads the grouped
ops' counter over the window; and each reads nothing where there is nothing
to read (a CPU run, a program from before the counter)."""

import json
import os

import pytest

import harness
import sparse_tables_ops as ops
import trace_reduce as tr
from conftest import ROOT
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "dlrm-terabyte-26tables.zipf"
READERS = ("sparse_tables_per_op", "tables_combine_ms", "tables_write_ms",
           "sparse_device_ops_per_step")
T = "{1,0:T(8,128)}"

# One table's body of the pull: the gather of physical rows, the selection
# of each row's slot.
PULL = [
    ("%fusion.60 = f32[2048,128]" + T + " fusion(%st, %ix)", 40),
    ("%fusion.61 = f32[2048,64]" + T + " fusion(%rows, %slot)", 12),
    ("%copy.7 = f32[1,2048,64]{1,2,0:T(8,128)} copy(%bitcast.3)", 9),
]


def _push(table: str, staged: bool):
    """One table's body of the push by distinct physical row; a small table
    the compiler stages through its alternate memory and back."""
    body = [
        ("%compare_select_fusion.3 = f32[2048,128]" + T + " fusion(%c)", 8),
        ("%sort.16 = (s32[2048]{0}, s32[2048]{0}) sort(%k, %i)", 30),
        ("%fusion.4 = f32[2048,128]" + T + " fusion(%placed, %order)", 11),
        ("%reduce-window.2 = s32[16,128]{0,1} reduce-window(%first)", 5),
        ("%segment_sum.9 = (f32[2048,128]" + T + ", s32[1]{0})"
         " custom-call(%sorted)", 25),
        ("%sort.17 = s32[2048]{0:T(1024)} sort(%first_rows)", 14),
        ("%row_add.9 = " + table + T + " custom-call(%n, %r, %G, %st)", 21),
    ]
    if staged:
        body.insert(0, ("%copy-start.4 = (" + table + "{1,0:T(8,128)S(1)}, "
                        + table + T + ", u32[]{:S(2)}) copy-start(%p)", 3))
        body.append(("%copy-done.5 = " + table + T
                     + " copy-done(%copy-start.5)", 6))
    return body


def _step():
    big = _push("f32[5000000,128]", staged=False)
    small = _push("f32[2,128]", staged=True)
    return PULL + PULL + big + small


def _profile(per_step, steps=2):
    host, mods, opl = Line("python3"), Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
    for s in range(steps):
        base = 100_000 + s * 10_000
        host.events += [Ev(tr.STEP, base, 10_000), Ev(tr.ISSUE, base, 1000),
                        Ev(tr.WAIT, base + 1000, 9000)]
        at = base + 10
        for name, ns in per_step:
            opl.events.append(Ev(name, at, ns))
            at += ns
        mods.events += [Ev("jit_body(1)", base + 10, 122),
                        Ev("jit_body(2)", base + 132, at - base - 132)]
    return Profile([Plane("/device:TPU:0", [mods, opl]),
                    Plane("/host:CPU", [host])])


class _Events:
    """``ProfileData``'s events: iterated, with no ``len``."""

    def __init__(self, events):
        self._events = events

    def __iter__(self):
        return iter(self._events)


def _as_the_profiler_hands_them(profile):
    for plane in profile.planes:
        for line in plane.lines:
            line.events = _Events(line.events)
    return profile


def _ctx(profile=None, spans=()):
    cell = harness.load_cell(CELL)
    return harness.LayerContext(
        spans=list(spans), compiles_in_window=0,
        reduction=tr.reduce_trace(profile) if profile is not None else None,
        least={"hbm": 1.0, "ici": 0.0}, peaks={}, config=cell.config,
        traffic=cell.traffic,
        profile=(_as_the_profiler_hands_them(profile)
                 if profile is not None else None))


@pytest.fixture
def readers():
    search = harness.search_dirs()
    return {name: harness.load_reader(search, name) for name in READERS}


def test_shapes_follow_from_the_cells_26_tables():
    ctx = _ctx()
    s = ops.shapes(ctx.config, ctx.traffic)
    assert (s["batch_rows"], s["batch_phys_rows"], s["batch_ids"]) == (
        "f32[2048,64]", "f32[2048,128]", "s32[2048]")
    tables = s["tables"]
    assert len(tables) == 26 and tables[0] == "f32[5000000,128]"
    assert tables[5] == "f32[2,128]" and tables[16] == "f32[2,128]"  # 3, 4
    assert tables[10] == "f32[1476773,128]" and tables[12] == "f32[5,128]"
    assert sum(int(t[4:].split(",")[0]) for t in tables) == 27_032_000
    # No table is as tall as a batch: the two classes of shapes are apart.
    assert not set(tables) & {s["batch_phys_rows"], s["batch_rows"]}


def test_what_the_three_trace_readers_count(readers):
    ctx = _ctx(_profile(_step()))
    assert ctx.reduction.steps == 2
    # Sorts, the segment sum and the movers of the batch's three shapes, in
    # both programs and both tables: not the placement XLA names after its
    # operations, the cumulative sum, the copy that re-lays a pulled batch
    # or anything of a table's shape.
    combine = 2 * (40 + 12) + 2 * (30 + 11 + 25 + 14)
    assert readers["tables_combine_ms"](ctx) == pytest.approx(combine * 1e-6)
    # ``row_add`` by name, and both ends of the compiler's staging of the
    # small table, by the table's shape.
    assert readers["tables_write_ms"](ctx) == pytest.approx(
        (21 + 21 + 3 + 6) * 1e-6)
    assert readers["sparse_device_ops_per_step"](ctx) == len(_step()) == 22
    assert (readers["tables_combine_ms"](ctx)
            + readers["tables_write_ms"](ctx)
            < ctx.reduction.busy_ms_per_step)


def test_a_copy_of_a_donated_table_shows_in_tables_write_ms(readers):
    copy = ("%copy.9 = f32[1476773,128]" + T + " copy(%st)", 5_000)
    ctx = _ctx(_profile(_step() + [copy]))
    assert readers["tables_write_ms"](ctx) == pytest.approx(
        (21 + 21 + 3 + 6 + 5_000) * 1e-6)
    # A table of another cell's shape is none of this cell's.
    other = ("%copy.9 = f32[27000000,128]" + T + " copy(%st)", 5_000)
    ctx = _ctx(_profile(_step() + [other]))
    assert readers["tables_write_ms"](ctx) == pytest.approx(51e-6)


def test_the_counter_is_read_over_the_windows_grouped_ops(readers,
                                                          monkeypatch):
    from pslite_tpu.utils import profiling

    clock = profiling.StageClock()
    monkeypatch.setattr(profiling, "_clock", clock)
    slot = 1 << clock.SLOT_SHIFT
    t0 = 50 * slot
    spans = []
    for k in range(6 * 4):              # four steps a slot, two ops a step
        start = t0 + k * slot // 4
        for op in range(2):
            end = start + (op + 1) * 1000
            clock.note((profiling.SPARSE_ROUTE, end, 53248, -1, -1))
            clock.note((profiling.SPARSE_GROUP, end, 26, -1, -1))
            clock.note((profiling.ENGINE_OP, end, 10, 20, 30))
        spans.append((start / 1e9, (start + 2000) / 1e9,
                      (start + 3000) / 1e9))
    assert readers["sparse_tables_per_op"](_ctx(spans=spans)) == 26.0
    assert ops.grouped_in_window(spans) == (26 * 8 * 5, 8 * 5)   # 5 slots
    # A one-table op in the window notes no group and moves nothing.
    clock.note((profiling.SPARSE_ROUTE, t0 + 2 * slot + 7, 64, -1, -1))
    clock.note((profiling.ENGINE_OP, t0 + 2 * slot + 7, 10, 20, 30))
    assert readers["sparse_tables_per_op"](_ctx(spans=spans)) == 26.0


def test_nothing_is_read_where_there_is_nothing_to_read(readers,
                                                        monkeypatch):
    from pslite_tpu.utils import profiling

    # A CPU run: no device plane, no reduction; no spans.
    p = _profile(_step())
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert tr.reduce_trace(p) is None
    for name in READERS:
        assert readers[name](_ctx()) is None, name
    # A window without a grouped op; a program from before the counter (its
    # clock has no ``grouped``); the no-op clock of PS_TELEMETRY=0.
    spans = [(50.0 + k, 50.1 + k, 50.2 + k) for k in range(8)]
    monkeypatch.setattr(profiling, "_clock", profiling.StageClock())
    assert readers["sparse_tables_per_op"](_ctx(spans=spans)) is None

    class Before:
        def routed(self, lo, hi):
            return (0, 0), 0, 0.0

    monkeypatch.setattr(profiling, "_clock", Before())
    assert readers["sparse_tables_per_op"](_ctx(spans=spans)) is None
    monkeypatch.setattr(profiling, "_clock", profiling._NullStageClock())
    assert readers["sparse_tables_per_op"](_ctx(spans=spans)) is None
    # Another cell's step holds none of this cell's shapes.
    other = Profile([
        Plane("/device:TPU:0", [
            Line(tr.MODULES_LINE, [Ev("jit__push_pull(1)", 100_010, 100)]),
            Line(tr.OPS_LINE, [Ev("%adam_update.1 = (f32[8192,128]" + T
                                  + ", f32[8]) custom-call(%x)", 100_010,
                                  100)])]),
        Plane("/host:CPU", [Line("python3", [Ev(tr.STEP, 100_000, 1000)])])])
    ctx = _ctx(other)
    assert ctx.reduction is not None
    assert readers["tables_combine_ms"](ctx) is None
    assert readers["tables_write_ms"](ctx) is None
    assert readers["sparse_device_ops_per_step"](ctx) == 1.0


def test_the_four_metrics_list_the_one_cell(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert m["workloads"] == [CELL] and m["moves"] == "step_p50"
    assert entries["sparse_tables_per_op"]["source"] == "program_counter"
    assert entries["sparse_tables_per_op"]["layer"] \
        == "dense and sparse engines"
    for name in READERS[1:]:
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["layer"] == "xla programs and kernels"
    cell = harness.load_cell(CELL, root=bench_root)
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) | {"roofline_share", "busy_ms", "launches_per_step",
                           "ops_per_step", "prep_ms"} <= names
    assert not {"combine_ms", "table_write_ms", "packed_write_ms",
                "packed_combine_ms", "route_ms",
                "sparse_slots_per_lookup"} & names
    for other in (w["name"] for w in bench["workloads"] if w["name"] != CELL):
        assert not set(READERS) & {m["name"] for m in harness.load_cell(
            other, root=bench_root).per_layer}
