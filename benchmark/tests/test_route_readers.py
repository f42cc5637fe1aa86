"""``sparse_route_ms``, ``sparse_route_ici_share`` and
``sparse_slots_per_lookup`` on a synthetic trace of the shape the chip's has
(``test_trace_reduce.py``) and on a stage clock fed by hand: collectives are
told by kind, whatever their shapes and in their ``-start`` / ``-done``
forms; the share is the driver's least ICI bytes at the published link rate
over that time; the counter is read over the window's sparse ops; and each
reads nothing where there is nothing to read (a CPU run, one chip, a program
from before the counter)."""

import pytest

import harness
import least_bytes
import sparse_route_ops as ops
import trace_reduce as tr
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "dlrm-criteo-emb.zipf.4chip"
T = "{2,1,0:T(8,128)}"

# A step as a 2x2 compiles it today (ns): the pull's id all-gather, gather,
# the all-reduce that stands for its psum_scatter and a small permute in
# both forms; the push's two all-gathers, a sort and the kernels.
TODAY = [
    ("%all-gather.5 = s32[1,524288]{1,0:T(1,128)} all-gather(%p)", 100),
    ("%fusion = f32[524288,128]{1,0:T(8,128)} fusion(%st, %ids)", 5000),
    ("%all-reduce.1 = f32[65664,8,128]" + T + " all-reduce(%pad.1)", 3000),
    ("%collective-permute-start = (f32[96,8,128]" + T + ", f32[96,8,128]"
     + T + ", u32[], u32[]) collective-permute-start(%slice.2)", 10),
    ("%collective-permute-done = f32[96,8,128]" + T
     + " collective-permute-done(%collective-permute-start)", 30),
    ("%all-gather.10 = s32[1,524288]{1,0:T(1,128)} all-gather(%p)", 110),
    ("%all-gather.11 = f32[1,524288,128]" + T + " all-gather(%g)", 1750),
    ("%sort.16 = (s32[524288]{0}, s32[524288]{0}) sort(%a, %b)", 400),
    ("%segment_sum.1 = f32[524288,128]{1,0} custom-call(%s)", 1500),
    ("%row_add.1 = f32[20000000,128]{1,0} custom-call(%n, %r, %G, %st)",
     2000),
]
ROUTED_NS = 100 + 3000 + 10 + 30 + 110 + 1750
# The same all-reduce as the compiled text of a 2x2 has it: wrapped, with the
# cut to the device's own part, in a fusion that calls a computation named
# for it.
FUSED = [(op if "%all-reduce.1" not in op[0] else
          ("%fusion.1 = f32[16416,8,128]" + T + " fusion(%bsf), kind=kCustom,"
           " calls=%all-reduce-scatter, metadata={op_name=\"x\"}", op[1]))
         for op in TODAY]
# The same step routed by owner: other kinds, other shapes, asynchronous.
BY_OWNER = [
    ("%all-to-all-start.2 = (s32[4,40960]{1,0}, s32[4,40960]{1,0}) "
     "all-to-all-start(%ids)", 20),
    ("%all-to-all-done.2 = s32[4,40960]{1,0} all-to-all-done(%a)", 60),
    ("%all-to-all.7 = f32[4,40960,128]" + T + " all-to-all(%g)", 900),
    ("%reduce-scatter.3 = f32[131072,128]{1,0} reduce-scatter(%rows)", 700),
    ("%sort.16 = (s32[163840]{0}, s32[163840]{0}) sort(%a, %b)", 130),
    ("%all-reduce-sum_fusion = f32[8,128]{1,0} fusion(%x)", 5000),
]
BY_OWNER_NS = 20 + 60 + 900 + 700


def _profile(per_step, steps=2, devices=4):
    host = Line("python3")
    planes = []
    for s in range(steps):
        base = 100_000 + s * 20_000
        host.events += [Ev(tr.STEP, base, 20_000), Ev(tr.ISSUE, base, 1000),
                        Ev(tr.WAIT, base + 1000, 19_000)]
    for d in range(devices):
        mods, opl = Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
        for s in range(steps):
            at = 100_010 + s * 20_000
            mods.events.append(Ev("jit__pull(1)", at, 15_000))
            for name, ns in per_step:
                opl.events.append(Ev(name, at, ns))
                at += ns
        planes.append(Plane(f"/device:TPU:{d}", [mods, opl]))
    return Profile(planes + [Plane("/host:CPU", [host])])


def _ctx(profile, least=None, spans=(), cell=CELL):
    reduction = tr.reduce_trace(profile) if profile is not None else None
    cell = harness.load_cell(cell)
    W, lookups = cell.config["chips"], cell.traffic["lookups_per_worker"]
    if least is None:
        least = least_bytes.sparse_pull_push_step(
            60_000.0 * W, lookups, cell.config["dim"], W)
    return harness.LayerContext(
        spans=list(spans), compiles_in_window=0, reduction=reduction,
        least=least, peaks={"hbm_gb_s": 819, "ici_gbit_s": 1600},
        config=cell.config, traffic=cell.traffic, profile=profile)


def _reader(name):
    return harness.load_reader(harness.search_dirs(), name)


def test_the_kinds_are_the_five_collectives_in_three_forms():
    assert len(ops.KINDS) == 15
    assert {"all-gather", "all-reduce-start", "reduce-scatter-done",
            "all-to-all", "collective-permute-start"} <= ops.KINDS
    assert not {"fusion", "sort", "copy-start", "all-reduce-sum_fusion",
                "dynamic-slice"} & ops.KINDS


@pytest.mark.parametrize("per_step, ns", [(TODAY, ROUTED_NS),
                                          (FUSED, ROUTED_NS),
                                          (BY_OWNER, BY_OWNER_NS)],
                         ids=["today", "fused", "by-owner"])
def test_route_ms_counts_collectives_by_kind_whatever_their_shapes(per_step,
                                                                   ns):
    ctx = _ctx(_profile(per_step))
    # Mean over the four devices, a step: each device runs the same list.
    assert _reader("sparse_route_ms")(ctx) == pytest.approx(ns / 1e6)


def test_an_operation_shown_whole_and_by_its_parts_counts_once():
    profile = _profile(FUSED)
    for plane in profile.planes[:4]:
        ops_line = plane.lines[1]
        outer = next(ev for ev in ops_line.events if "%fusion.1" in ev.name)
        ops_line.events.append(Ev(
            "%all-reduce.1 = f32[65664,8,128]" + T + " all-reduce(%pad.1)",
            outer.start_ns + 100, outer.duration_ns - 200))
    assert _reader("sparse_route_ms")(_ctx(profile)) == pytest.approx(
        ROUTED_NS / 1e6)


def test_the_share_is_the_least_ici_bytes_at_the_link_rate_over_route_ms():
    ctx = _ctx(_profile(TODAY))
    # 2 * 131072 * 128 * 4 B * 3/4 = 100,663,296 B a chip a step at
    # 1,600 Gbit/s = 200 GB/s: 0.503 ms.
    assert ctx.least["ici"] == 100_663_296
    least_ms = 100_663_296 / 200e9 * 1e3
    share = _reader("sparse_route_ici_share")(ctx)
    assert share == pytest.approx(100 * least_ms / (ROUTED_NS / 1e6))
    assert least_bytes.least_seconds(ctx.least, ctx.peaks) == {
        "seconds": pytest.approx(least_ms / 1e3), "bound": "ici"}


@pytest.mark.parametrize("name", ["sparse_route_ms",
                                  "sparse_route_ici_share"])
def test_trace_readers_read_nothing_where_there_is_nothing(name):
    read = _reader(name)
    assert read(_ctx(None)) is None                     # a CPU run
    one_chip = [op for op in TODAY if "all-" not in op[0]
                and "collective" not in op[0]]
    assert read(_ctx(_profile(one_chip, devices=1),
                     least={"hbm": 1e8, "ici": 0.0})) is None
    # Collectives with no interconnect in the least bytes: no share.
    ctx = _ctx(_profile(TODAY), least={"hbm": 1e8, "ici": 0.0})
    assert (read(ctx) is None) == (name == "sparse_route_ici_share")


# -- the counter ---------------------------------------------------------------


@pytest.fixture
def clock(monkeypatch):
    from pslite_tpu.utils import profiling

    clock = profiling.StageClock()
    monkeypatch.setattr(profiling, "_clock", clock)
    return clock


def _steps(clock, t0_s, n, slots, step_s=0.02):
    """``n`` steps of one pull and one push from ``t0_s``: each op notes its
    slots, then its stages; returns the harness's spans."""
    from pslite_tpu.utils.profiling import ENGINE_OP, SPARSE_ROUTE

    spans = []
    for k in range(n):
        t = t0_s + k * step_s
        for op in range(2):
            end = int((t + 0.001 * (op + 1)) * 1e9)
            clock.note((SPARSE_ROUTE, end, slots, -1, -1))
            clock.note((ENGINE_OP, end, 1000, 2000, 300_000))
        spans.append((t, t + 0.002, t + step_s))
    return spans


def test_slots_per_lookup_over_the_windows_sparse_ops(clock):
    lookups = 131_072
    width = (1 << clock.SLOT_SHIFT) / 1e9
    # Warm steps of another batch size before the window do not count.
    _steps(clock, 10 * width + 0.01, 5, 4 * 999)
    spans = _steps(clock, 12 * width + 0.3, 200, 4 * lookups)
    read = _reader("sparse_slots_per_lookup")
    assert read(_ctx(None, spans=spans)) == 4.0
    assert clock.routed_totals() == (10 * 4 * 999 + 400 * 4 * lookups, 410)
    # No spans, and a window that holds no whole slot of the clock.
    assert read(_ctx(None)) is None
    assert read(_ctx(None, spans=spans[:3])) is None


def test_slots_per_lookup_reads_nothing_on_a_program_without_the_counter(
        clock, monkeypatch):
    from pslite_tpu.utils import profiling

    spans = _steps(clock, 12.3 * (1 << clock.SLOT_SHIFT) / 1e9, 200, 4 * 64)
    read = _reader("sparse_slots_per_lookup")
    assert read(_ctx(None, spans=spans)) == pytest.approx(4 * 64 / 131_072)
    # The parent's clock has no ``routed``; PS_TELEMETRY=0 keeps nothing.
    monkeypatch.delattr(profiling.StageClock, "routed")
    assert read(_ctx(None, spans=spans)) is None
    monkeypatch.undo()
    monkeypatch.setattr(profiling, "_clock", profiling._NullStageClock())
    assert read(_ctx(None, spans=spans)) is None
