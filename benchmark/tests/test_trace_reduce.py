"""The reduction from a trace to numbers, on a synthetic trace of the
shape ``jax.profiler.ProfileData`` has on the chip (my chip run, PR 23:
planes ``/device:TPU:0`` with lines ``XLA Modules`` / ``XLA Ops``, and
``/host:CPU`` with the benchmark's annotations on the ``python3`` line)."""

from dataclasses import dataclass, field
from typing import List

import pytest

import trace_reduce as tr


@dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


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
    # Per step: [0,100) and [200,400) and [500,600) under issue, [600,1000) wait.
    assert gaps["issue"] == pytest.approx(800e-9)
    assert gaps["wait"] == pytest.approx(800e-9)


def test_counts_do_not_depend_on_the_clocks_agreeing():
    """On the chip the device's clock leads the host's by about a
    millisecond: counts and busy time must not move with it."""
    a, b = tr.reduce_trace(_trace()), tr.reduce_trace(_trace(lead_ns=150))
    assert a.launches_per_step == b.launches_per_step == 2.0
    assert a.busy_s == pytest.approx(b.busy_s)
    assert dict(map(tuple, b.idle_gaps))["wait"] == pytest.approx(
        800e-9, rel=0.3)


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
