"""The reduction from a trace to numbers, on a synthetic trace of the
shape ``jax.profiler.ProfileData`` has on the chip (my chip run, PR 23:
planes ``/device:TPU:0`` with lines ``XLA Modules`` / ``XLA Ops``, and
``/host:CPU`` with the benchmark's annotations on the ``python3`` line;
my chip runs, PR 36: the program's spans and jax's ``PjitFunction`` on that
line too, the runtime's ``DoEnqueueProgram`` on lines of its own threads,
the device's clock ~0.5 ms behind the host's)."""

from dataclasses import dataclass, field
from typing import List

import pytest

import harness
import trace_reduce as tr


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: tuple = ()       # ((key, value), ...) as ProfileData's events


@dataclass
class Line:
    name: str
    events: List[Ev] = field(default_factory=list)


@dataclass
class Plane:
    name: str
    lines: List[Line] = field(default_factory=list)


@dataclass
class Profile:
    planes: List[Plane]


def _trace(lead_ns=0.0, devices=1, steps=2):
    """Steps of 1000 ns, each: issue [0, 600), wait [600, 1000); two
    programs a step, each one operation of 100 ns at +100 and +400; the
    device's clock leads the host's by ``lead_ns``."""
    host = Line("python3")
    planes = []
    for s in range(steps):
        base = 10_000 + s * 1000
        host.events += [Ev(tr.STEP, base, 1000), Ev(tr.ISSUE, base, 600),
                        Ev(tr.WAIT, base + 600, 400),
                        Ev("PjitFunction(_push_pull)", base + 10, 50)]
    for d in range(devices):
        mods, ops = Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
        for s in range(steps):
            base = 10_000 + s * 1000 - lead_ns
            for off in (100, 400):
                mods.events.append(Ev("jit__push_pull(1)", base + off, 120))
                ops.events.append(Ev(
                    "%adam_update.1 = (f32[8192,128]{1,0:T(8,128)}, f32[8]) "
                    "custom-call(f32[1] %div.2)", base + off, 100))
        planes.append(Plane(f"/device:TPU:{d}",
                            [mods, ops, Line("Async XLA Ops")]))
    planes.append(Plane("/host:CPU", [host]))
    planes.append(Plane("#Chip0 Misc"))
    return Profile(planes)


def test_busy_idle_launches():
    r = tr.reduce_trace(_trace())
    assert r.steps == 2 and r.devices == 1
    assert r.window_s == pytest.approx(2000e-9)
    assert r.busy_s == pytest.approx(400e-9)          # idle share 80%
    assert r.busy_ms_per_step == pytest.approx(200e-6)
    assert r.launches_per_step == 2.0 and r.launches_repeat
    assert r.device_ops == [["%adam_update.1 f32[8192,128]",
                             pytest.approx(400e-9)]]
    gaps = dict((k, v) for k, v in r.idle_gaps)
    # No span of the program's in this trace: the clocks are brought
    # together by the first operation (it starts after the first issue
    # opens: left where it is), and the benchmark's own spans are all there
    # is to name.  Per step: [0,100), [200,400) and [500,1000) idle, all
    # inside ``bench_issue`` or ``bench_wait``.
    assert r.clock == "lead" and r.clock_note == "no program spans in the trace"
    assert r.clock_offset_ns == 0.0 and r.clock_bracket_ns is None
    assert gaps == {"driver": pytest.approx(1600e-9)}


def test_counts_do_not_depend_on_the_clocks_agreeing():
    """On the chip the device's clock leads the host's by about a
    millisecond: counts and busy time must not move with it."""
    a, b = tr.reduce_trace(_trace()), tr.reduce_trace(_trace(lead_ns=150))
    assert a.launches_per_step == b.launches_per_step == 2.0
    assert a.busy_s == pytest.approx(b.busy_s)
    assert dict(map(tuple, b.idle_gaps)) == dict(map(tuple, a.idle_gaps))


def test_mean_over_devices_and_overlap():
    p = _trace(devices=4, steps=3)
    # An operation nested inside another adds nothing to the union.
    p.planes[0].lines[1].events.append(Ev("%fusion.2 = f32[4]{0} fusion()",
                                          10_120, 50))
    r = tr.reduce_trace(p)
    assert r.devices == 4 and r.steps == 3
    assert r.busy_s == pytest.approx(600e-9)
    assert r.launches_per_step == 2.0


def test_uneven_launches_are_said():
    p = _trace(steps=2)
    p.planes[0].lines[0].events.append(Ev("jit_extra(2)", 10_700, 10))
    r = tr.reduce_trace(p)
    assert r.launches_per_step == 2.5 and not r.launches_repeat


def test_nothing_to_read():
    assert tr.reduce_trace(Profile([Plane("/host:CPU", [Line("python3")])])) \
        is None
    p = _trace()
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    assert tr.reduce_trace(p) is None   # a CPU trace: no device plane


def test_every_operation_is_kept_and_the_ten_largest_are_printed():
    """``op_seconds`` holds every operation by name; ``device_ops``, what
    the result line prints, is its ten largest in falling order, as it was
    before the field existed."""
    p = _trace(devices=2)
    for dev in p.planes[:2]:
        for k in range(12):      # twelve more operations, 1..12 ns each
            dev.lines[1].events.append(
                Ev(f"%fusion.{k} = f32[{k + 1}]{{0}} fusion()", 10_250,
                   k + 1))
    r = tr.reduce_trace(p)
    assert len(r.op_seconds) == 13
    assert r.op_seconds["%fusion.0 f32[1]"] == pytest.approx(1e-9)
    assert r.op_seconds["%adam_update.1 f32[8192,128]"] == pytest.approx(
        400e-9)
    ranked = sorted(r.op_seconds.items(), key=lambda kv: -kv[1])
    assert r.device_ops == [[k, v] for k, v in ranked[:10]]
    assert [n for n, _ in r.device_ops][:3] == [
        "%adam_update.1 f32[8192,128]", "%fusion.11 f32[12]",
        "%fusion.10 f32[11]"]
    assert "%fusion.1 f32[2]" not in dict(map(tuple, r.device_ops))
    assert sum(r.op_seconds.values()) >= r.busy_s   # nested ones count here


def test_union_and_short_name():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert tr.short_name("%copy.16 = f32[1048576]{0:T(1024)} copy(f32[8])") \
        == "%copy.16 f32[1048576]"
    assert tr.short_name("jit__push_pull(123)") == "jit__push_pull(123)"


# -- the two clocks brought together by the program's spans --------------------

START = 1_000_000   # the first step's start on the host's clock, ns
PAUSE = 200         # between two steps


def _loop(offset=0.0, steps=3, ops=((0, 1, 8000),), enqueue=(300, 500, 400),
          wake=(900, 600, 700), run=20_000, gap=50, enqueue_events=0,
          threaded=False):
    """A closed loop on the host's clock, the device's ``offset`` ahead.

    ``ops``: an op a ``(start, launches, length)``, its launches of 2,000 ns
    each from +500 on, 3,000 apart; a program starts ``enqueue[step]``
    after its launch's entry, or ``gap`` after the program before it ended
    if that is later, and runs ``run``.  The driver waits for the ops in
    order: a ``ps.kv.complete.wait`` returns ``wake[step]`` after the op's
    last program ended (or 30 after it is asked, if later).  The step ends
    100 after the last wait and the next begins ``PAUSE`` later.  With
    ``enqueue_events`` the runtime's own event shows as many times a
    program, on a thread of its own, 200 ns into each launch."""
    host, runtime, other = Line("python3"), Line("main/1"), Line("worker/2")
    mods, dev_ops = Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
    ts, base = 100, START
    for s in range(steps):
        issue_end = base + ops[-1][0] + ops[-1][2] + 100
        host.events.append(Ev(tr.ISSUE, base, issue_end - base))
        done, ends = base, []
        for start, launches, length in ops:
            ts += 1
            host.events.append(Ev(tr.OP, base + start, length,
                                  (("ts", ts), ("name", "b"))))
            for k in range(launches):
                at = base + start + 500 + k * 3000
                # jax shows a jitted call twice, one inside the other.
                host.events += [Ev("PjitFunction(_f)", at, 2000),
                                Ev("PjitFunction(_f)", at + 10, 1980)]
                for _ in range(enqueue_events):
                    runtime.events.append(Ev(tr.ENQUEUE, at + 200, 100))
                begin = max(at + enqueue[s % len(enqueue)], done + gap)
                done = begin + run
                mods.events.append(Ev("jit__f(1)", begin + offset, run))
                dev_ops.events.append(Ev(
                    "%fusion.1 = f32[8,128]{1,0} fusion(f32[8] %p)",
                    begin + offset, run))
            ends.append((ts, done))
        wait_start = at = issue_end + 50
        for op_ts, done in ends:
            end = max(at + 30, done + wake[s % len(wake)])
            (other if threaded else host).events.append(
                Ev(tr.CWAIT, at, end - at, (("ts", op_ts), ("name", "b"))))
            host.events.append(Ev(tr.CCOPY, end + 10, 40))
            at = end + 60
        host.events.append(Ev(tr.WAIT, wait_start - 20, at + 50 - wait_start))
        host.events.append(Ev(tr.STEP, base, at + 100 - base))
        base = at + 100 + PAUSE
    return Profile([Plane("/device:TPU:0", [mods, dev_ops]),
                    Plane("/host:CPU", [host, runtime, other])])


@pytest.mark.parametrize("offset", [1e6, -1e6, 0.0])
def test_the_offset_is_the_fastest_enqueue_and_the_bracket_both(offset):
    """The device's clock a millisecond ahead of the host's, or behind
    it: the offset is recovered to the fastest enqueue of the steps, and
    the bracket is that and the fastest wake-up together."""
    r = tr.reduce_trace(_loop(offset))
    assert r.clock == "spans" and r.clock_note == "PjitFunction"
    assert r.clock_offset_ns == pytest.approx(offset + 300)
    assert r.clock_bracket_ns == pytest.approx(300 + 600)
    assert r.steps == 3 and r.launches_per_step == 1.0


def test_the_runtimes_own_event_tells_a_launch_where_it_is_there():
    """One ``DoEnqueueProgram`` a program lies 200 ns into each launch:
    the offset is off by 100 ns where ``PjitFunction`` left 300.  Two a
    program on one device do not pair, and are not used."""
    r = tr.reduce_trace(_loop(5e5, enqueue_events=1))
    assert r.clock_note == tr.ENQUEUE
    assert r.clock_offset_ns == pytest.approx(5e5 + 100)
    assert r.clock_bracket_ns == pytest.approx(100 + 600)
    r = tr.reduce_trace(_loop(5e5, enqueue_events=2))
    assert r.clock_note == "PjitFunction"
    assert r.clock_offset_ns == pytest.approx(5e5 + 300)
    four = _loop(5e5, enqueue_events=4)
    dev = four.planes[0]
    four.planes[1:1] = [Plane(f"/device:TPU:{d}", dev.lines)
                        for d in (1, 2, 3)]
    r = tr.reduce_trace(four)       # four devices, four events a program
    assert r.devices == 4 and r.clock_note == tr.ENQUEUE
    assert r.clock_offset_ns == pytest.approx(5e5 + 100)


def test_per_step_and_per_launch_bounds():
    """Three programs a step from two ops (the first launches two).  Where
    the launches are as many as the programs every program is held to its
    own launch and an op's completion to its own last program, by ``ts``.
    The third program starts only when the second has ended, so the first
    launch and the first wait are the tight ones; they are the per-step
    bounds' too."""
    ops = ((0, 2, 9000), (9500, 1, 4000))
    r = tr.reduce_trace(_loop(7e5, ops=ops, run=15_000))
    assert r.launches_per_step == 3.0 and r.clock == "spans"
    assert r.clock_offset_ns == pytest.approx(7e5 + 300)
    # Lower: the first op's wait returns 600 after its second program.
    assert r.clock_bracket_ns == pytest.approx(300 + 600)
    # One launch more than programs (a jitted call that launches nothing
    # on the device): only the per-step bounds are left, the step's last
    # program against the last wait seen: the same here, since the last
    # wait returns after the fastest wake-up too.
    p = _loop(7e5, ops=ops, run=15_000)
    host = p.planes[1].lines[0]
    for step in [e for e in host.events if e.name == tr.STEP]:
        host.events.append(Ev("PjitFunction(_noop)", step.start_ns + 8000,
                              100))
    r2 = tr.reduce_trace(p)
    assert r2.clock == "spans"
    assert r2.clock_offset_ns == pytest.approx(7e5 + 300)
    assert r2.clock_bracket_ns == pytest.approx(300 + 600)
    # Per launch is the tighter where a later launch is the fast one: the
    # second op's program enqueued 100 after its launch, on an idle device.
    fast = _loop(7e5, ops=((0, 1, 4000), (60_000, 1, 4000)), run=10_000)
    mods, dev_ops = fast.planes[0].lines
    for line in (mods, dev_ops):
        for k, ev in enumerate(line.events):
            if k % 2:
                ev.start_ns -= 200 + (k // 2 % 3) * 50   # enqueue 100..
    r3 = tr.reduce_trace(fast)
    assert r3.clock_offset_ns == pytest.approx(7e5 + 100)


@pytest.mark.parametrize("breaker, why", [
    ("no_spans", "no program spans in the trace"),
    ("uneven", "the launches do not repeat"),
    ("empty", "the bracket is empty"),
])
def test_falling_back_to_the_first_operation_is_said(breaker, why):
    p = _loop(-1e6)
    host = p.planes[1].lines[0]
    if breaker == "no_spans":
        _strip(p)
    elif breaker == "uneven":
        p.planes[0].lines[0].events.append(Ev("jit_extra(2)", 50_000, 10))
    else:   # a wait that returns before its program ended: not this order
        for e in host.events:
            if e.name == tr.CWAIT:
                e.duration_ns = 100
    r = tr.reduce_trace(p)
    assert r.clock == "lead" and r.clock_note.startswith(why)
    assert r.clock_bracket_ns is None
    # The first operation (800 into the first issue, on the device's clock
    # 1e6 earlier) drawn to where that issue opens; a device's clock that
    # is ahead is left where it is, as before this clock.
    assert r.clock_offset_ns == pytest.approx(-1e6 + 800)
    assert tr.reduce_trace(_strip(_loop(1e6))).clock_offset_ns == 0.0
    assert sum(v for _, v in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s)
    search = harness.search_dirs()
    read = harness.load_reader(search, "issue_exposed_ms")
    assert read(_ctx(r)) is None
    assert read(_ctx(None)) is None


def _strip(profile):
    """The trace a program from before the spans leaves."""
    host = profile.planes[1].lines[0]
    host.events = [e for e in host.events
                   if e.name in (tr.STEP, tr.ISSUE, tr.WAIT)]
    return profile


def _ctx(reduction):
    return harness.LayerContext(spans=[], compiles_in_window=0,
                                reduction=reduction, least={}, peaks={})


def _gaps(r):
    return {k: v * 1e9 / r.steps for k, v in r.idle_gaps}


def test_a_closed_loop_of_three_programs_a_step():
    """Device-bound: the device idles from its last operation to the next
    step's first program.  That spell is cut by what the issuing thread is
    in: the wake-up under ``complete.wait``, the copy, the driver's own
    loop, the next op up to its launch, the launch up to the program's
    start; nothing of it falls to the later ops, which the device
    covers."""
    ops = ((0, 2, 9000), (9500, 1, 4000))
    p = _loop(-4e5, ops=ops, run=25_000, enqueue=(300,), wake=(600,))
    r = tr.reduce_trace(p)
    assert r.clock_offset_ns == pytest.approx(-4e5 + 300)
    g = _gaps(r)
    idle = (r.window_s - r.busy_s) * 1e9 / r.steps
    assert sum(g.values()) == pytest.approx(idle)
    # The offset is late by the 300 of the enqueue, so a program shows at
    # its launch's entry: no idle time under ``op.launch``, the 500 of the
    # op before it under ``op.other``, and nothing under the second and
    # third launch, which the device covers.  The wake-up of 600 shows as
    # 900, and the two gaps of 50 between programs lie under the first
    # op's wait.
    assert g["op.other"] == pytest.approx(500)
    assert "op.launch" not in g and tr.IN_FLIGHT not in g
    assert g["complete.wait"] == pytest.approx(900 + 2 * 50)
    assert g["complete.wait"] > 0.5 * idle
    assert g["between_steps"] == pytest.approx(PAUSE * 2 / 3)
    read = harness.load_reader(harness.search_dirs(), "issue_exposed_ms")
    assert read(_ctx(r)) == pytest.approx(500e-6)
    # The same loop with the runtime's event 200 into each launch: the
    # device starts 100 after it, so 200 of the first launch show idle.
    r = tr.reduce_trace(_loop(-4e5, ops=ops, run=25_000, enqueue=(300,),
                              wake=(600,), enqueue_events=1))
    g = _gaps(r)
    assert g["op.launch"] == pytest.approx(200)
    assert g["complete.wait"] == pytest.approx(700 + 2 * 50)
    assert read(_ctx(r)) == pytest.approx(700e-6)


def test_a_launch_in_flight_is_not_the_wake_up():
    """A program that starts only after the host has left its launch and
    waits (the runtime enqueues a program with a tuple result from a
    thread of its own): that part of the idle spell under
    ``complete.wait`` is the launch's, the part after the device's last
    operation the wake-up's."""
    p = _loop(3e5, ops=((0, 1, 3000),), run=60_000, enqueue=(9000,),
              wake=(2000,))
    # Told by the launch's entry the offset is late by the 9,000.
    assert tr.reduce_trace(p).clock_offset_ns == pytest.approx(3e5 + 9000)
    # The enqueue itself, on the runtime's thread, 8,900 after that entry.
    host, runtime = p.planes[1].lines[:2]
    for step in (e for e in host.events if e.name == tr.STEP):
        runtime.events.append(Ev(tr.ENQUEUE, step.start_ns + 500 + 8900, 50))
    r = tr.reduce_trace(p)
    assert r.clock_note == tr.ENQUEUE
    assert r.clock_offset_ns == pytest.approx(3e5 + 100)
    g = _gaps(r)
    # The launch [500, 2500) and the rest of the op to 3000 and the
    # driver's 150 are the host's; from the wait's start at 3150 to the
    # program's at 9400 the launch is in flight.
    assert g["op.launch"] == pytest.approx(2000)
    assert g["op.other"] == pytest.approx(1000)
    assert g[tr.IN_FLIGHT] == pytest.approx(9400 - 3150)
    # 60,000 later the device has ended; the wait returns 2,000 after
    # (less the 100 the offset is late by).
    assert g["complete.wait"] == pytest.approx(2000 + 100)
    assert sum(g.values()) == pytest.approx(
        (r.window_s - r.busy_s) * 1e9 / r.steps)


def test_a_host_bound_step_of_many_ops():
    """Forty ops a step, each of 2,600 ns with a launch of 2,000 in it and
    a program of 300: the device idles under the launches and between
    them, and what the waits hold is next to nothing."""
    ops = tuple((k * 2700, 1, 2600) for k in range(40))
    r = tr.reduce_trace(_loop(-5e5, ops=ops, run=300, enqueue=(600, 700),
                              wake=(80, 50)))
    assert r.launches_per_step == 40.0 and r.clock == "spans"
    assert r.clock_offset_ns == pytest.approx(-5e5 + 600)
    # The waits come after every launch, so none is tight: the last op's
    # returns 4,790 after its program ended (39 waits of 90 before it).
    assert r.clock_bracket_ns == pytest.approx(600 + 4790)
    g = _gaps(r)
    assert sum(g.values()) == pytest.approx(
        (r.window_s - r.busy_s) * 1e9 / r.steps)
    assert tr.IN_FLIGHT not in g
    assert g["op.launch"] == pytest.approx(40 * 1700, rel=0.02)
    assert g["op.other"] == pytest.approx(40 * 600, rel=0.02)
    assert g["driver"] == pytest.approx(40 * 100, rel=0.3)
    assert g.get("complete.wait", 0.0) < 0.05 * sum(g.values())
    read = harness.load_reader(harness.search_dirs(), "issue_exposed_ms")
    assert read(_ctx(r)) == pytest.approx(
        (g["op.launch"] + g["op.other"]) * 1e-6)


def test_a_completion_on_a_thread_of_the_programs_own():
    """An op with ``out`` or ``callback`` completes on the program's
    ``kv-engine-complete`` thread: its ``ps.kv.complete.wait`` holds the
    offset from below all the same, and the issuing thread, which is in
    the driver's ``wait`` meanwhile, is labelled by what it is in."""
    a = tr.reduce_trace(_loop(2e5))
    b = tr.reduce_trace(_loop(2e5, threaded=True))
    assert (b.clock, b.clock_offset_ns, b.clock_bracket_ns) == (
        a.clock, a.clock_offset_ns, a.clock_bracket_ns)
    ga, gb = _gaps(a), _gaps(b)
    assert "complete.wait" in ga and "complete.wait" not in gb
    assert gb["driver"] == pytest.approx(ga["driver"] + ga["complete.wait"]
                                         + ga.get(tr.IN_FLIGHT, 0.0))


def test_the_host_timeline_by_the_innermost_span():
    spans = {name: [] for name, _ in tr.LABELS}
    spans[tr.STEP] = [(100.0, 1000.0, None)]
    spans[tr.ISSUE] = [(100.0, 500.0, None)]
    spans[tr.WAIT] = [(520.0, 990.0, None)]
    spans[tr.OP] = [(150.0, 450.0, 7)]
    spans[tr.PJIT] = [(200.0, 400.0, None), (210.0, 390.0, None)]
    spans[tr.CWAIT] = [(530.0, 900.0, 7)]
    spans[tr.CCOPY] = [(900.0, 950.0, None)]
    assert tr._host_timeline(spans, 0.0, 1100.0) == [
        (0.0, 100.0, "between_steps"), (100.0, 150.0, "driver"),
        (150.0, 200.0, "op.other"), (200.0, 400.0, "op.launch"),
        (400.0, 450.0, "op.other"), (450.0, 500.0, "driver"),
        (500.0, 520.0, "in_step_other"), (520.0, 530.0, "driver"),
        (530.0, 900.0, "complete.wait"), (900.0, 950.0, "complete.copy"),
        (950.0, 990.0, "driver"), (990.0, 1000.0, "in_step_other"),
        (1000.0, 1100.0, "between_steps")]
    # Clipped to the window asked for.
    assert tr._host_timeline(spans, 300.0, 420.0) == [
        (300.0, 400.0, "op.launch"), (400.0, 420.0, "op.other")]


def test_a_launch_outside_any_op_is_the_drivers():
    """``PjitFunction`` counts as a launch only inside ``ps.kv.op``: a
    jitted call of the driver's own is the driver's time."""
    p = _loop(0.0)
    host = p.planes[1].lines[0]
    host.events.append(Ev("PjitFunction(_mine)", START + 8100, 50))
    _, spans, anywhere = tr.read_planes(p)
    assert len(spans[tr.PJIT]) == 6      # two a launch, three steps
    assert all(ts is not None for _, _, ts in spans[tr.OP])
    assert [ts for _, _, ts in anywhere[tr.CWAIT]] == [101, 102, 103]
