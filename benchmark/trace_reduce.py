"""From a profiler trace to numbers.

The one reduction every PR uses, so that no PR that claims a gain can
change it.  It reads ``jax.profiler.ProfileData`` (planes -> lines ->
events, each with ``name``, ``start_ns``, ``duration_ns``) or anything of
that shape; ``benchmark/tests`` drives it with a synthetic trace.

What it looks for:

- device planes named ``/device:TPU:<n>``, with a line of executed
  operations (``XLA Ops``) and a line of executed programs
  (``XLA Modules``);
- the benchmark's own ``TraceAnnotation`` spans on a host thread:
  ``bench_step`` around each traced step, ``bench_issue`` and
  ``bench_wait`` inside it.  They are on the trace's clock, so a gap on the
  device can be attributed to what the host was doing.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP, ISSUE, WAIT = "bench_step", "bench_issue", "bench_wait"

Interval = Tuple[float, float]  # start_ns, end_ns


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


@dataclass
class DevicePlane:
    index: int
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Reduction:
    """What the per-layer readers and the result line take."""

    steps: int
    window_s: float            # first traced step's start to the last's end
    busy_s: float              # union of the operations' intervals, mean over devices
    busy_ms_per_step: float    # the same, per traced step
    launches_per_step: Optional[float]   # programs on the first device, per step
    launches_repeat: bool      # the programs divide evenly among the steps
    device_ops: List[List[object]]       # [[name, seconds], ...] top 10
    idle_gaps: List[List[object]]        # [[what the host was doing, seconds], ...]
    devices: int
    # Every device operation's seconds over the traced steps (mean over
    # devices) by its short name: ``device_ops`` is the ten largest of
    # these.  A reader takes one program's or kernel's time from here,
    # whether or not it is among the ten.
    op_seconds: Dict[str, float] = field(default_factory=dict)


def _events(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def read_planes(profile) -> Tuple[List[DevicePlane],
                                  Dict[str, List[Interval]]]:
    """Device planes, and the benchmark's host spans by name."""
    devices: List[DevicePlane] = []
    spans: Dict[str, List[Interval]] = {STEP: [], ISSUE: [], WAIT: []}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DevicePlane(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(_events(line))
                elif line.name == MODULES_LINE:
                    dev.modules.extend(_events(line))
            devices.append(dev)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in spans:
                    spans[ev.name].append(
                        (float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns)))
    devices.sort(key=lambda d: d.index)
    for v in spans.values():
        v.sort()
    return devices, spans


def _host_timeline(spans: Dict[str, List[Interval]], lo: float, hi: float
                   ) -> List[Tuple[float, float, str]]:
    """``[lo, hi)`` cut into what the host was doing: inside a step's
    issue span, its wait span, elsewhere in the step, or between steps."""
    out: List[Tuple[float, float, str]] = []
    edge = lo
    for s, e in spans[STEP]:
        if s > edge:
            out.append((edge, s, "between_steps"))
        inner = sorted(
            [(a, b, "issue") for a, b in spans[ISSUE] if s <= a < e]
            + [(a, b, "wait") for a, b in spans[WAIT] if s <= a < e])
        at = s
        for a, b, label in inner:
            if a > at:
                out.append((at, a, "in_step_other"))
            out.append((a, b, label))
            at = max(at, b)
        if e > at:
            out.append((at, e, "in_step_other"))
        edge = max(edge, e)
    if hi > edge:
        out.append((edge, hi, "between_steps"))
    return out


_HLO = re.compile(r"^(%[\w.\-]+) = \(?([a-z]+[0-9]*\[[0-9,]*\])?")


def short_name(name: str) -> str:
    """The trace prints an operation as its whole HLO line; keep the
    operation's name and the shape of its (first) result."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def reduce_trace(profile) -> Optional[Reduction]:
    """None when the trace holds no device operation or no traced step
    (nothing to read: the readers then return nothing).

    The profiler runs around the traced steps and nothing else, so every
    operation on a device plane belongs to one of them.  Counts and busy
    time are therefore taken over the whole plane: the device's clock leads
    the host's by about a millisecond in these traces (the first program of
    a step shows before the host's span opens), and a count cut at the
    host's step borders would not repeat.  Only the attribution of idle
    gaps needs the two clocks together; it shifts the device's by the lead
    seen at the first program, and gaps are much longer than what is left.
    """
    devices, spans = read_planes(profile)
    steps = spans[STEP]
    devices = [d for d in devices if d.ops or d.modules]
    if not steps or not devices:
        return None
    n_steps = len(steps)
    window_ns = steps[-1][1] - steps[0][0]
    busy, names = [], {}
    for dev in devices:
        # A device with no per-operation line still shows its programs.
        source = dev.ops or dev.modules
        busy.append(total(union((s, e) for _, s, e in source)))
        for name, s, e in source:
            key = short_name(name)
            names[key] = names.get(key, 0.0) + (e - s)
    n_dev = len(devices)
    if sum(busy) <= 0:
        return None

    first = devices[0]
    launches = len(first.modules) / n_steps if first.modules else None

    source0 = first.ops or first.modules
    merged0 = union((s, e) for _, s, e in source0)
    issue0 = spans[ISSUE][0][0] if spans[ISSUE] else steps[0][0]
    lead = max(0.0, issue0 - merged0[0][0])
    lo, hi = steps[0][0], steps[-1][1]
    shifted = clip([(a + lead, b + lead) for a, b in merged0], lo, hi)
    idle = [(a, b) for a, b in zip([lo] + [e for _, e in shifted],
                                   [s for s, _ in shifted] + [hi]) if b > a]
    gaps: Dict[str, float] = {}
    timeline = _host_timeline(spans, lo, hi)
    k = 0
    for a, b in idle:   # both lists are sorted: one sweep
        while k and timeline[k][0] > a:
            k -= 1
        while k < len(timeline) and timeline[k][1] <= a:
            k += 1
        j = k
        while j < len(timeline) and timeline[j][0] < b:
            s0, e0, label = timeline[j]
            gaps[label] = gaps.get(label, 0.0) + (min(b, e0) - max(a, s0))
            j += 1

    def top(d: Dict[str, float]) -> List[List[object]]:
        ranked = sorted(d.items(), key=lambda kv: -kv[1])[:10]
        return [[k, v] for k, v in ranked]

    op_seconds = {k: v / n_dev / 1e9 for k, v in names.items()}
    return Reduction(
        steps=n_steps,
        window_s=window_ns / 1e9,
        busy_s=sum(busy) / n_dev / 1e9,
        busy_ms_per_step=sum(busy) / n_dev / n_steps / 1e6,
        launches_per_step=launches,
        launches_repeat=bool(first.modules)
        and len(first.modules) % n_steps == 0,
        device_ops=top(op_seconds),
        idle_gaps=top({k: v / 1e9 for k, v in gaps.items()}),
        devices=n_dev,
        op_seconds=op_seconds,
    )


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``ProfileData``."""
    import glob
    import os

    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                  recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        return None
    return ProfileData.from_file(files[-1])
