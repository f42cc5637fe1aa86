"""The seven readers of the launch taken apart (``launch_window.py``,
``launch_events.py``), on a stage clock fed by hand and on a synthetic
profile of the shape the chip's has (``test_trace_reduce.py``): a host line
with ``bench_step``, two ``ps.kv.op`` a step with jax's nested pair of
``PjitFunction(`` events, the runtime's events inside them by time on the
thread's other line (``main/<tid>``: libtpu records through a tracer of its
own), a ``DoEnqueueProgram`` on another thread that starts after its op has
ended, a thread whose events lie across a launch's border, and a
``PjitFunction`` outside every op that must not count.  Each reads
nothing, and never raises, where there is nothing to read: a CPU run, a
program from before the account or the ``op`` stat, a tracer that does not
show the event."""

import json

import pytest

import harness
import launch_events
import trace_reduce as tr
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "dlrm-terabyte-26tables.zipf"
RUNTIME, ALLOC = launch_events.RUNTIME, launch_events.ALLOC
TUPLE = "tpu::System::AllocateAndFillTupleIndexTable"
INNER = "CommonPjRtLoadedExecutable::Execute"   # twice a launch, one inside the other


def _reader(name):
    return harness.load_reader(harness.search_dirs(), name)


def _ctx(profile=None, spans=()):
    cell = harness.load_cell(CELL)
    return harness.LayerContext(
        spans=list(spans), compiles_in_window=0, reduction=None, least={},
        peaks={}, config=cell.config, traffic=cell.traffic, profile=profile)


# -- the profile ---------------------------------------------------------------

# One op: (kind, its length, the outermost PjitFunction's, the runtime's
# call's, the allocation's, the tuple table's), ns.
PULL = ("sparse.pull", 3000, 2000, 1500, 400, 30)
PUSH = ("sparse.push", 4000, 3000, 2400, 600, 900)


def _op(host, main, at, kind, op_ns, pjit_ns, runtime_ns, alloc_ns, tuple_ns,
        stat=True, alloc=True, device_lines=()):
    """``device_lines``: over several chips the call hands a device's part
    to a thread of its own, and the allocations lie there."""
    stats = (("ts", 7), ("name", "emb00")) + ((("op", kind),) if stat else ())
    host.events += [
        Ev(tr.OP, at, op_ns, stats),
        # jax's pair: the outermost is taken once.
        Ev("PjitFunction(jit__pull)", at + 100, pjit_ns),
        Ev("PjitFunction(jit__pull)", at + 150, pjit_ns - 100),
        Ev("ParseArguments", at + 200, 50)]
    main.events += [
        Ev(RUNTIME, at + 300, runtime_ns),
        Ev(INNER, at + 310, runtime_ns - 20),
        Ev(INNER, at + 320, runtime_ns - 40)]
    for line in device_lines or (main,):
        line.events.append(
            Ev(TUPLE, at + 300 + runtime_ns - tuple_ns - 50, tuple_ns))
        if alloc:
            line.events.append(Ev(ALLOC, at + 350, alloc_ns))


def _profile(steps=2, stat=True, alloc=True, enqueue=True, ops=True,
             devices=1):
    host, main = Line("python3"), Line("main/298")
    per_device = [Line(f"py_xla_execute/{936 + d}")
                  for d in range(devices if devices > 1 else 0)]
    queue, waiter = Line("tfrt-non-blocking-queue/353"), Line("futex/435")
    for s in range(steps):
        base = 100_000 + s * 20_000
        host.events += [Ev(tr.STEP, base, 20_000), Ev(tr.ISSUE, base, 12_000),
                        Ev(tr.WAIT, base + 12_000, 8000)]
        if ops:
            for at, op in ((base + 1000, PULL), (base + 5000, PUSH)):
                _op(host, main, at, *op, stat=stat, alloc=alloc,
                    device_lines=per_device)
        # The driver's own jitted generator: a launch, but not the program's.
        host.events.append(Ev("PjitFunction(_draw)", base + 10_000, 1000))
        main.events.append(Ev(RUNTIME, base + 10_100, 800))
        # A thread of the runtime's own: long inside a launch, and across
        # the border of the next.
        waiter.events += [Ev("ReadSyncFlag", base + 1200, 1800),
                          Ev("ReadSyncFlag", base + 4900, 1000)]
        if enqueue:
            # PR 51's timeline: enqueued once ps.kv.op has returned.
            queue.events += [Ev(tr.ENQUEUE, base + 4500, 300),
                             Ev(tr.ENQUEUE, base + 9500, 200)]
    # Outside the traced steps: none of them counts.
    queue.events.append(Ev(tr.ENQUEUE, 50_000, 9999))
    host.events.append(Ev(tr.OP, 60_000, 5000, (("op", "dense.pull"),)))
    device = Plane("/device:TPU:0", [Line(tr.MODULES_LINE), Line(tr.OPS_LINE)])
    return Profile([device, Plane("/host:CPU", [waiter, main, *per_device,
                                               queue, host])])


def test_the_three_trace_metrics_read_the_sums_by_hand():
    ctx = _ctx(_profile())
    assert _reader("launch_runtime_ms")(ctx) == pytest.approx(
        (1500 + 2400) / 1e6)
    assert _reader("launch_alloc_ms")(ctx) == pytest.approx(
        (400 + 600) / 1e6)
    assert _reader("launch_enqueue_ms")(ctx) == pytest.approx(
        (300 + 200) / 1e6)
    found = launch_events.read(ctx.profile)
    assert found.steps == 2 and len(found.ops) == 4
    assert [(op.kind, op.op_ns, op.pjit_ns) for op in found.ops[:2]] == [
        ("sparse.pull", 3000, 2000), ("sparse.push", 4000, 3000)]
    # The outermost PjitFunction once; what is nested in it by name, on the
    # thread's own two lines (an event inside one of its name: the outer),
    # and nothing of the driver's own launch or of the thread beside it,
    # which lies across a launch's border as often as inside one.
    assert [op.nested for op in found.ops[:2]] == [
        {"ParseArguments": 50, RUNTIME: 1500, INNER: 1480, ALLOC: 400,
         TUPLE: 30},
        {"ParseArguments": 50, RUNTIME: 2400, INNER: 2380, ALLOC: 600,
         TUPLE: 900}]
    # alloc <= runtime <= PjitFunction under ps.kv.op, op by op.
    assert all(op.nested[ALLOC] <= op.nested[RUNTIME] <= op.pjit_ns
               for op in found.ops)


def test_over_several_chips_a_devices_part_lies_on_a_thread_of_its_own():
    ctx = _ctx(_profile(devices=4))
    # The call on the issuing thread; the allocations every device's.
    assert _reader("launch_runtime_ms")(ctx) == pytest.approx(3900 / 1e6)
    assert _reader("launch_alloc_ms")(ctx) == pytest.approx(4 * 1000 / 1e6)


def test_the_line_holds_a_kinds_medians_and_what_takes_2_percent(capsys):
    ctx = _ctx(_profile(steps=3))
    assert launch_events.of_run(ctx) is launch_events.of_run(ctx)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("launch: ")]
    assert len(lines) == 1                      # once a run
    said = json.loads(lines[0][len("launch: "):])
    assert said["steps"] == 3
    assert said["ops"]["sparse.pull"] == {
        "a_step": 1.0, "ps.kv.op_us": 3.0, "PjitFunction_us": 2.0,
        # 30 ns of 2000 is under 2%: the tuple table is not shown here.
        "nested_us": {RUNTIME: 1.5, INNER: 1.5, ALLOC: 0.4,
                      "ParseArguments": 0.1}}
    assert said["ops"]["sparse.push"]["nested_us"] == {
        RUNTIME: 2.4, INNER: 2.4, TUPLE: 0.9, ALLOC: 0.6}
    assert said[tr.ENQUEUE] == {"a_step": 2.0, "us": 0.2}


def test_a_program_from_before_the_stat_is_read_under_one_kind(capsys):
    ctx = _ctx(_profile(stat=False))
    assert _reader("launch_runtime_ms")(ctx) == pytest.approx(3900 / 1e6)
    said = json.loads(capsys.readouterr().out.split("launch: ")[1])
    assert list(said["ops"]) == ["op"] and said["ops"]["op"]["a_step"] == 2.0


@pytest.mark.parametrize("name", ["launch_runtime_ms", "launch_alloc_ms",
                                  "launch_enqueue_ms"])
def test_trace_readers_read_nothing_where_there_is_nothing(name):
    read = _reader(name)
    assert read(_ctx(None)) is None                         # a CPU run
    assert read(_ctx(Profile([Plane("/host:CPU", [Line("python3")])]))) \
        is None                                             # no traced step
    # A program without the spans (``Reduction.clock == "lead"``): the
    # runtime's events are there, under no ``ps.kv.op``.
    bare = read(_ctx(_profile(ops=False)))
    assert (bare is None) == (name != "launch_enqueue_ms")
    # A tracer that does not show the named event.
    without = read(_ctx(_profile(alloc=False, enqueue=False)))
    assert (without is None) == (name != "launch_runtime_ms")


# -- the clock -----------------------------------------------------------------


@pytest.fixture
def clock(monkeypatch):
    from pslite_tpu.utils import profiling

    clock = profiling.StageClock()
    monkeypatch.setattr(profiling, "_clock", clock)
    return clock


def _steps(clock, t0_s, n, step_s=0.02):
    """``n`` steps of a grouped pull and a grouped push of 26 tables from
    ``t0_s``, noted as ``SparseEngine`` notes them; returns the harness's
    spans."""
    from pslite_tpu.utils.profiling import ENGINE_OP, LAUNCH, launched

    spans = []
    for k in range(n):
        t = t0_s + k * step_s
        for at, kind, arrays, launch, call in (
                (0.002, "sparse.pull", 78, 1_400_000, 1_300_000),
                (0.003, "sparse.push", 105, 400_000, 350_000)):
            end = int((t + at) * 1e9)
            clock.note((LAUNCH, end, call, launch, launched(kind, arrays)))
            clock.note((ENGINE_OP, end, 1000, 2000, launch))
        spans.append((t, t + 0.019, t + step_s))
    return spans


def test_the_four_window_metrics_read_the_account(clock):
    width = (1 << clock.SLOT_SHIFT) / 1e9
    spans = _steps(clock, 12 * width + 0.3, 300)
    ctx = _ctx(spans=spans)
    launch_ms = _reader("launch_ms")(ctx)
    assert launch_ms == pytest.approx(1.8, rel=1e-2)
    pull, push = (_reader("launch_pull_ms")(ctx),
                  _reader("launch_push_ms")(ctx))
    assert pull == pytest.approx(1.4, rel=1e-2)
    assert pull + push == pytest.approx(launch_ms, rel=1e-12)
    call = _reader("launch_call_ms")(ctx)
    assert call == pytest.approx(1.65, rel=1e-2) and call <= launch_ms
    # Whole, though the window cuts its border steps by time.
    assert _reader("ops_per_step")(ctx) != 2.0
    assert _reader("launch_arrays_per_step")(ctx) == 183.0


@pytest.mark.parametrize("name", ["launch_call_ms", "launch_arrays_per_step",
                                  "launch_pull_ms", "launch_push_ms"])
def test_window_readers_read_nothing_where_there_is_nothing(
        name, clock, monkeypatch):
    from pslite_tpu.utils import profiling

    read = _reader(name)
    spans = _steps(clock, 12.3 * (1 << clock.SLOT_SHIFT) / 1e9, 300)
    assert read(_ctx(spans=spans)) is not None
    assert read(_ctx()) is None                     # no spans
    assert read(_ctx(spans=spans[:3])) is None      # no whole slot
    # The parent's clock has no ``launches``; PS_TELEMETRY=0 keeps nothing.
    monkeypatch.delattr(profiling.StageClock, "launches")
    assert read(_ctx(spans=spans)) is None
    monkeypatch.undo()
    monkeypatch.setattr(profiling, "_clock", profiling._NullStageClock())
    assert read(_ctx(spans=spans)) is None


def test_a_dense_window_has_no_sparse_part(clock):
    from pslite_tpu.utils.profiling import ENGINE_OP, LAUNCH, launched

    t0 = 12.3 * (1 << clock.SLOT_SHIFT) / 1e9
    spans = []
    for k in range(300):
        t = t0 + k * 0.02
        for b in range(5):
            end = int((t + 0.001 * (b + 1)) * 1e9)
            clock.note((LAUNCH, end, 250_000, 300_000,
                        launched("dense.push_pull", 10)))
            clock.note((ENGINE_OP, end, 1000, 2000, 300_000))
        spans.append((t, t + 0.006, t + 0.02))
    ctx = _ctx(spans=spans)
    assert _reader("launch_pull_ms")(ctx) is None
    assert _reader("launch_push_ms")(ctx) is None
    assert _reader("launch_call_ms")(ctx) == pytest.approx(1.25, rel=1e-3)
    assert _reader("launch_arrays_per_step")(ctx) == 50.0
