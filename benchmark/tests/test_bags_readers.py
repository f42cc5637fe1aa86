"""The five readers of the cell ``dlrm-dcnv2-multihot.bags`` on a hand-made
trace of the form the chip's has (two programs a step on the device, the
issuing thread's ``ps.kv.op`` spans with their ``op``) and on a stage clock
fed by hand: ``bag_pull_ms``, ``bag_combine_ms`` and ``bag_write_ms`` put an
operation down to the op that launched its program and then tell it by kind
and result shape, ``bag_pull_roofline`` holds the pull's time to the bytes
the work needs whatever does it, ``bag_lookups_per_bag`` reads the pooled
ops' counter over the window; each reads nothing where there is nothing to
read (a CPU run, a trace without the program's spans, a program from before
the counter)."""

import json
import os

import pytest

import harness
import sparse_bags_ops as ops
import trace_reduce as tr
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "dlrm-dcnv2-multihot.bags"
READERS = ("bag_lookups_per_bag", "bag_pull_ms", "bag_pull_roofline",
           "bag_combine_ms", "bag_write_ms")
T = "{1,0:T(8,128)}"
def _pull(m=409600, h=100):
    """One table's body of the pooled pull: the gather of every slot's row,
    the sum over a bag, the placing in the group's one result."""
    return [
        (f"%copy.12 = s32[1,1,{h},4096]{{3,1,2,0}} copy(%ids)", 20),
        (f"%fusion.60 = f32[{m},128]" + T + " fusion(%st, %ix)", 4000),
        ("%reduce_sum.3 = f32[4096,128]" + T + " reduce(%rows)", 500),
        ("%constant_dynamic-update-slice_fusion.5 = f32[106496,128]" + T
         + " fusion(%r)", 80),
    ]


def _push(m=409600, table="f32[4000000,128]", acc="f32[4000000]",
          kernel=True):
    """One table's body of the pooled push under the handle.  The read
    through the bag has the pull's gather's result shape."""
    body = [
        (f"%sort.16 = (s32[{m}]{{0}}, s32[{m}]{{0}}) sort(%k, %i)", 300),
        (f"%fusion.4 = f32[{m},128]" + T + " fusion(%g, %order)", 900),
        (f"%segment_sum.9 = (f32[{m},128]" + T + ", s32[1]{0})"
         " custom-call(%sorted)", 1200),
        (f"%sort.17 = s32[{m}]{{0:T(1024)}} sort(%first_rows)", 100),
        (f"%multiply_reduce_fusion.2 = f32[{m}]{{0}} fusion(%G)", 70),
        (f"%select_negate_fusion = f32[{m},128]" + T + " fusion(%G)", 400),
        ("%row_add.9 = " + table + T + " custom-call(%n, %r)", 2000),
    ]
    if kernel:
        return body + [("%acc_update.3 = (f32[31250,128]" + T
                        + ", f32[1600,1,256]) custom-call(%acc)", 700)]
    # XLA's pair: the accumulator's rows gathered and stepped, then scattered.
    return body + [(f"%fusion.31 = f32[{m}]{{0}} fusion(%acc, %rows)", 250),
                   ("%fusion.32 = " + acc + "{0} fusion(%acc, %new)", 350)]


def _profile(pull, push, steps=2, devices=1, ops=("sparse.pull",
                                                  "sparse.push")):
    """``steps`` traced steps: the issuing thread's ``ps.kv.op`` a program
    with the op's kind, the two programs one after the other on a device."""
    host = Line("python3")
    planes = []
    length = 10_000 + sum(ns for _, ns in pull + push)
    for s in range(steps):
        base = 100_000 + s * length
        host.events += [Ev(tr.STEP, base, length), Ev(tr.ISSUE, base, 1000),
                        Ev(tr.WAIT, base + 1000, length - 1000)]
        for k, op in enumerate(ops):
            host.events.append(Ev(tr.OP, base + 10 + 300 * k, 250,
                                  (("ts", 2 * s + k), ("op", op))))
    for d in range(devices):
        mods, opl = Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
        for s in range(steps):
            at = 100_000 + s * length + 500
            for k, body in enumerate((pull, push)[:len(ops)]):
                start = at
                for name, ns in body:
                    opl.events.append(Ev(name, at, ns))
                    at += ns
                mods.events.append(Ev(f"jit_body({k})", start, at - start))
                at += 100
        planes.append(Plane(f"/device:TPU:{d}", [mods, opl]))
    return Profile(planes + [Plane("/host:CPU", [host])])


def _ctx(profile=None, spans=()):
    cell = harness.load_cell(CELL)
    return harness.LayerContext(
        spans=list(spans), compiles_in_window=0,
        reduction=tr.reduce_trace(profile) if profile is not None else None,
        least={"hbm": 1.0, "ici": 0.0}, peaks={"hbm_gb_s": 819},
        config=cell.config, traffic=cell.traffic, profile=profile)


@pytest.fixture
def readers():
    search = harness.search_dirs()
    return {name: harness.load_reader(search, name) for name in READERS}


def test_sizes_follow_from_the_cells_two_files():
    cell = harness.load_cell(CELL)
    s = ops.sizes(cell.config, cell.traffic)
    assert len(s["tables"]) == 26 and s["bags"] == 4096
    assert s["tables"][20] == ("emb20", 4_000_000, 100)
    assert (s["all_bags"], s["all_lookups"]) == (106_496, 876_544)
    # Every lookup's row read, every bag's pooled row written, every id read.
    assert ops.pooled_pull_bytes(cell.config, cell.traffic) \
        == 876_544 * 512 + 106_496 * 512 + 876_544 * 4 == 506_822_656
    # A step's least: a table's distinct rows three times and their
    # accumulators twice, ids twice, a row a bag out and in.
    least = ops.step_least_bytes([10.0] * 26, cell.config, cell.traffic)
    assert least["ici"] == 0.0
    assert least["hbm"] == (26 * (3 * 10 * 512 + 2 * 4 * 10)
                            + 2 * 876_544 * 4 + 2 * 106_496 * 512)


def test_what_the_three_trace_readers_count(readers):
    # Two tables a program: the 100-id table (acc_update's pass) and emb04 (h
    # 6, 20,265 rows: no multiple of 128, XLA's pair).
    pull = _pull(409600, 100) + _pull(24576, 6)
    push = (_push(409600) + _push(24576, "f32[20265,128]", "f32[20265]",
                                  kernel=False))
    ctx = _ctx(_profile(pull, push))
    assert ctx.reduction.steps == 2
    # The pull: every operation of its program.
    assert readers["bag_pull_ms"](ctx) == pytest.approx(2 * 4600e-6)
    # The combine: two sorts, the read through the bag, the segment sum.
    assert readers["bag_combine_ms"](ctx) == pytest.approx(2 * 2500e-6)
    # The write: row_add, and acc_update or XLA's pair.
    assert readers["bag_write_ms"](ctx) == pytest.approx(
        (2000 + 700 + 2000 + 250 + 350) * 1e-6)
    # What is in none: mean(G ** 2) and the rows' step.
    assert ctx.reduction.busy_ms_per_step == pytest.approx(
        (2 * 4600 + 2 * 2500 + 5300 + 2 * 470) * 1e-6)
    # The first device that shows programs answers.
    two = _ctx(_profile(pull, push, devices=2))
    assert readers["bag_pull_ms"](two) == pytest.approx(2 * 4600e-6)
    # The pull's gather is not the push's read through the bag, though their
    # results have one shape: a program at a time.
    by_op = ops.program_ops(ctx.profile)
    assert set(by_op) == {"sparse.pull", "sparse.push"}
    assert by_op["sparse.pull"]["%fusion.60 f32[409600,128]"] == 2 * 4000
    assert by_op["sparse.push"]["%fusion.4 f32[409600,128]"] == 2 * 900
    # Another table's shapes are none of this cell's.
    other = _push(131072, "f32[20000000,128]", "f32[20000000]", kernel=False)
    ctx = _ctx(_profile(pull, other))
    assert readers["bag_write_ms"](ctx) == pytest.approx(2000e-6)  # row_add
    assert readers["bag_combine_ms"](ctx) == pytest.approx(1600e-6)


def test_the_roofline_is_the_works_bytes_over_the_pulls_time(readers):
    # 506,822,656 B at 819 GB/s are 0.6188 ms: a pull of 1.2376 ms a step
    # reads 50%.
    pull = [("%fusion.60 = f32[409600,128]" + T + " fusion(%st)", 1_200_000),
            ("%reduce_sum.3 = f32[4096,128]" + T + " reduce(%rows)", 37_600)]
    ctx = _ctx(_profile(pull, _push()))
    assert readers["bag_pull_ms"](ctx) == pytest.approx(1.2376)
    assert readers["bag_pull_roofline"](ctx) == pytest.approx(
        100 * 506_822_656 / 819e9 * 1e3 / 1.2376)
    assert 49.9 < readers["bag_pull_roofline"](ctx) < 50.1


def test_the_counter_is_read_over_the_windows_pooled_ops(readers,
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
            clock.note((profiling.SPARSE_ROUTE, end, 876544, -1, -1))
            clock.note((profiling.SPARSE_GROUP, end, 26, -1, -1))
            clock.note((profiling.SPARSE_POOL, end, 106496, 876544, -1))
            clock.note((profiling.ENGINE_OP, end, 10, 20, 30))
        spans.append((start / 1e9, (start + 2000) / 1e9,
                      (start + 3000) / 1e9))
    got = readers["bag_lookups_per_bag"](_ctx(spans=spans))
    assert got == pytest.approx(876544 / 106496) and round(got, 2) == 8.23
    assert ops.pooled_in_window(spans) == (106496 * 40, 876544 * 40, 40)
    # An op that pools nothing notes none and moves nothing.
    clock.note((profiling.SPARSE_ROUTE, t0 + 2 * slot + 7, 64, -1, -1))
    clock.note((profiling.ENGINE_OP, t0 + 2 * slot + 7, 10, 20, 30))
    assert readers["bag_lookups_per_bag"](_ctx(spans=spans)) == got


def test_nothing_is_read_where_there_is_nothing_to_read(readers, monkeypatch):
    from pslite_tpu.utils import profiling

    # A CPU run: no device plane, no reduction; no spans.
    p = _profile(_pull(), _push())
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert tr.reduce_trace(p) is None
    for name in READERS:
        assert readers[name](_ctx()) is None, name
    # A trace without the program's spans (a program from before them): the
    # programs cannot be put down to their ops: nothing, not zero.
    bare = _profile(_pull(), _push())
    for plane in bare.planes:
        for line in plane.lines:
            line.events = [ev for ev in line.events if ev.name != tr.OP]
    bare = _ctx(bare)
    assert bare.reduction is not None
    for name in READERS[1:]:
        assert readers[name](bare) is None, name
    # More programs on the device than ops launched them.
    odd = _profile(_pull(), _push(), ops=("sparse.pull", "sparse.push"))
    odd.planes[0].lines[0].events.append(Ev("jit_reshape(9)", 90_000, 10))
    for name in READERS[1:]:
        assert readers[name](_ctx(odd)) is None, name
    # A step whose one op is a push: no pull to read, the push is read.
    push_only = _ctx(_profile(_pull(), _push(), ops=("sparse.push",)))
    assert readers["bag_pull_ms"](push_only) is None
    assert readers["bag_pull_roofline"](push_only) is None
    # A window in which nothing pooled; a program from before the counter
    # (its clock has no ``pooled``); the no-op clock of PS_TELEMETRY=0.
    spans = [(50.0 + k, 50.1 + k, 50.2 + k) for k in range(8)]
    monkeypatch.setattr(profiling, "_clock", profiling.StageClock())
    assert readers["bag_lookups_per_bag"](_ctx(spans=spans)) is None

    class Before:
        def grouped(self, lo, hi):
            return (0, 0), 0, 0.0

    monkeypatch.setattr(profiling, "_clock", Before())
    assert readers["bag_lookups_per_bag"](_ctx(spans=spans)) is None
    monkeypatch.setattr(profiling, "_clock", profiling._NullStageClock())
    assert readers["bag_lookups_per_bag"](_ctx(spans=spans)) is None


def test_the_five_metrics_list_the_one_cell(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert entries[name]["workloads"] == [CELL]
    assert entries["bag_lookups_per_bag"]["source"] == "program_counter"
    assert entries["bag_lookups_per_bag"]["layer"] \
        == "dense and sparse engines"
    for name in READERS[1:]:
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["layer"] == "xla programs and kernels"
    assert [entries[n]["moves"] for n in READERS] == [
        "goodput", "step_p50", "goodput", "step_p50", "step_p50"]
    assert entries["bag_pull_roofline"]["unit"] == "%"
    cell = harness.load_cell(CELL, root=bench_root)
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) | {"roofline_share", "busy_ms", "launches_per_step",
                           "ops_per_step", "launch_arrays_per_step"} <= names
    # The siblings' readers name their cells and stay theirs.
    assert not {"combine_ms", "table_write_ms", "tables_combine_ms",
                "tables_write_ms", "launch_pull_ms", "launch_push_ms",
                "launch_alloc_ms", "sparse_tables_per_op"} & names
