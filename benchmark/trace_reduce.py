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
  ``bench_wait`` inside it;
- the program's own spans, which it makes only while a profiler session
  runs (``pslite_tpu/utils/profiling.py``): ``ps.kv.op`` around the
  issuing thread's part of an op, ``ps.kv.complete.wait`` / ``.copy``
  around its completion, each with the op's ``ts``, and jax's
  ``PjitFunction(<program>)`` inside ``ps.kv.op``, the launch itself.

Host spans are on the host's clock and device events on the device's, which
differ by about half a millisecond in these traces.  ``align`` brings the two
together by causality (a program starts after the host entered the call
that launched it and ends before the host saw it ready), so that a gap on
the device can be put down to what the issuing thread was in.
"""

from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP, ISSUE, WAIT = "bench_step", "bench_issue", "bench_wait"
try:
    from pslite_tpu.utils.profiling import COMPLETE_SPANS, OP_SPAN as OP
    CWAIT, CCOPY = COMPLETE_SPANS
except ImportError:     # a program from before the spans: nothing is found
    OP, CWAIT, CCOPY = "ps.kv.op", "ps.kv.complete.wait", "ps.kv.complete.copy"
PJIT = "PjitFunction("      # jax's own event around a jitted call, by prefix
# The TPU runtime's own event (libtpu 0.0.34, host tracer level 2) around
# handing one program to one device, on whatever thread it does it: the
# innermost thing the trace shows of a launch.  Read where it is there.
ENQUEUE = "DoEnqueueProgram"

Interval = Tuple[float, float]  # start_ns, end_ns
HostSpan = Tuple[float, float, Optional[int]]   # start_ns, end_ns, the op's ts

# What the issuing thread is in, outermost first: a span further down
# covers one further up.  Outside every one of them: ``between_steps``.
LABELS = ((STEP, "in_step_other"), (ISSUE, "driver"), (WAIT, "driver"),
          (OP, "op.other"), (CWAIT, "complete.wait"),
          (CCOPY, "complete.copy"), (PJIT, "op.launch"))
ISSUE_LABELS = ("op.launch", "op.other")    # under ``ps.kv.op``
# Idle time under ``ps.kv.complete.wait`` is of two kinds.  After the
# device's last operation it is the runtime's and the waiting thread's
# wake-up, and keeps the label.  Where the same idle spell has already
# passed a launch, the host has left the call and waits while the program
# has not started (the runtime enqueues a program with a tuple result
# from a thread of its own, once the tuple's index table is on the
# device): that part is the launch's.
IN_FLIGHT = "launch.in_flight"


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
    # The first device's idle seconds inside the traced steps by what the
    # issuing thread was in (``LABELS``), largest first: they add up to
    # the idle time, ``window_s - busy_s`` on one device.
    idle_gaps: List[List[object]]
    devices: int
    # Every device operation's seconds over the traced steps (mean over
    # devices) by its short name: ``device_ops`` is the ten largest of
    # these.  A reader takes one program's or kernel's time from here,
    # whether or not it is among the ten.
    op_seconds: Dict[str, float] = field(default_factory=dict)
    # How the device's timeline was laid on the host's: ``"spans"`` (by the
    # program's spans, ``align``; ``clock_note`` names the event a launch
    # was told by) or ``"lead"`` (the first operation drawn to the first
    # issue, where ``align`` found nothing; ``clock_note`` says why).  The
    # offset is what is subtracted from a device time; the bracket is the
    # room causality leaves it (None under ``"lead"``).
    clock: str = "lead"
    clock_offset_ns: float = 0.0
    clock_bracket_ns: Optional[float] = None
    clock_note: str = ""


def _events(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for ev in line.events]


_NAMED = (STEP, ISSUE, WAIT, OP, CWAIT, CCOPY, ENQUEUE)


def _by_time(span: HostSpan) -> Interval:
    return span[:2]     # a ``ts`` may be None: never compared


def _host_line(line) -> Dict[str, List[HostSpan]]:
    """One thread's spans of the names above; an op's and a completion's
    carry the op's ``ts`` where the event has that stat."""
    found: Dict[str, List[HostSpan]] = {}
    for ev in line.events:
        name = ev.name
        if name.startswith(PJIT):
            name = PJIT
        elif name not in _NAMED:
            continue
        ts = None
        if name in (OP, CWAIT):
            ts = next((v for k, v in getattr(ev, "stats", ()) if k == "ts"),
                      None)
        start = float(ev.start_ns)
        found.setdefault(name, []).append(
            (start, start + float(ev.duration_ns), ts))
    return found


def read_planes(profile) -> Tuple[List[DevicePlane],
                                  Dict[str, List[HostSpan]],
                                  Dict[str, List[HostSpan]]]:
    """Device planes; the spans of the issuing thread (the one that holds
    ``bench_step``) by name, a ``PjitFunction`` only where it lies inside a
    ``ps.kv.op``; and what counts on whatever thread it happens: every
    ``ps.kv.complete.wait`` (an op with ``out`` or ``callback`` completes
    on a thread of the program's own) and every ``ENQUEUE``."""
    devices: List[DevicePlane] = []
    lines: List[Dict[str, List[HostSpan]]] = []
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
        else:
            lines.extend(_host_line(line) for line in plane.lines)
    devices.sort(key=lambda d: d.index)
    spans: Dict[str, List[HostSpan]] = {
        name: [] for name in (STEP, ISSUE, WAIT, OP, CWAIT, CCOPY, PJIT)}
    for found in lines:
        if STEP in found:
            for name in spans:
                spans[name].extend(found.get(name, ()))
    for got in spans.values():
        got.sort(key=_by_time)
    ops = union((s, e) for s, e, _ in spans[OP])
    starts = [s for s, _ in ops]
    inside = []
    for span in spans[PJIT]:
        k = bisect.bisect_right(starts, span[0]) - 1
        if k >= 0 and span[0] < ops[k][1]:
            inside.append(span)
    spans[PJIT] = inside
    anywhere = {name: sorted((s for found in lines
                              for s in found.get(name, ())), key=_by_time)
                for name in (CWAIT, ENQUEUE)}
    return devices, spans, anywhere


def _within(spans: Sequence[HostSpan], lo: float, hi: float
            ) -> List[HostSpan]:
    """Those of the sorted ``spans`` that start in ``[lo, hi)``."""
    return spans[bisect.bisect_left(spans, (lo,)):
                 bisect.bisect_left(spans, (hi,))]


def align(devices: Sequence[DevicePlane], spans: Dict[str, List[HostSpan]],
          anywhere: Dict[str, List[HostSpan]]
          ) -> Tuple[Optional[float], Optional[float], str]:
    """The offset to subtract from the first device's times so that they
    lie on the host's clock: ``(offset_ns, bracket_ns, the launch's
    marker)``, or ``(None, None, why not)``.

    A device runs a process's programs in the order they were launched, and
    the programs divide evenly among the traced steps, so step *s* owns
    programs ``[s*L, (s+1)*L)``.  A program cannot end after a
    ``ps.kv.complete.wait`` on it returned: the offset is at least end -
    return.  Per step that is its last program against the last completion
    seen in the step; where the step's launches (``PjitFunction``) are as
    many as its programs, also an op's last program against the completion
    that carries the op's ``ts``.  A program cannot start before the host
    entered the call that launched it: the offset is at most start -
    entry.  Per step that is its first program against its first launch,
    and every program against its own where they are as many; where the
    trace holds the runtime's own ``ENQUEUE`` events, one a program and
    device, every program against its own of those, which lie 0.1-0.2 ms
    further into the launch.

    The upper bound is taken.  It is off by the fastest start of a program
    after its enqueue (tens of microseconds: with the runtime's completion
    events against it, a trace of 7,472 small programs leaves 47 us for
    that and the fastest completion together; my chip run, PR 36).  The
    lower is off by the fastest wake-up of the waiting thread, which after
    a program of milliseconds is 0.26-0.62 ms (the runtime's
    ``ReadSyncFlag`` alone is 0.18-0.25).  The bracket (upper - lower) is
    the two together.
    """
    steps, ready, enqueues = spans[STEP], anywhere[CWAIT], anywhere[ENQUEUE]
    modules = devices[0].modules
    if not spans[OP] or not spans[PJIT] or not ready:
        return None, None, "no program spans in the trace"
    if not modules or len(modules) % len(steps):
        return None, None, "the launches do not repeat"
    per = len(modules) // len(steps)
    modules = sorted((s, e) for _, s, e in modules)
    by_ts = {ts: e for _, e, ts in ready if ts is not None}
    lower, upper = [], []
    for k, (lo, hi, _) in enumerate(steps):
        mods = modules[k * per:(k + 1) * per]
        launches = union((s, e) for s, e, _ in _within(spans[PJIT], lo, hi))
        seen = [e for _, e, _ in _within(ready, lo, hi)]
        if not launches or not seen:
            return None, None, f"traced step {k} holds no launch or no wait"
        lower.append(mods[-1][1] - max(seen))
        if len(launches) != per:
            upper.append(mods[0][0] - launches[0][0])
            continue
        upper.extend(m[0] - l[0] for m, l in zip(mods, launches))
        starts = [l[0] for l in launches]
        for s, e, ts in _within(spans[OP], lo, hi):
            last = bisect.bisect_left(starts, e) - 1    # its last launch
            if ts in by_ts and last >= 0 and starts[last] >= s:
                lower.append(mods[last][1] - by_ts[ts])
    marker = PJIT.rstrip("(")
    n_dev = sum(1 for d in devices if d.modules)
    if enqueues and len(enqueues) == len(modules) * n_dev:
        marker = ENQUEUE
        upper.extend(m[0] - enqueues[k * n_dev][0]
                     for k, m in enumerate(modules))
    offset, bracket = min(upper), min(upper) - max(lower)
    if bracket < 0:
        return None, None, (f"the bracket is empty by {-bracket:.0f} ns: "
                            f"programs and spans do not pair in order")
    return offset, bracket, marker


def _host_timeline(spans: Dict[str, List[HostSpan]], lo: float, hi: float
                   ) -> List[Tuple[float, float, str]]:
    """``[lo, hi)`` cut by the innermost span the issuing thread is in
    (``LABELS``; ``between_steps`` outside every one)."""
    marks = []      # (time, depth or -depth, label)
    for depth, (name, label) in enumerate(LABELS, 1):
        for s, e, _ in spans[name]:
            if e > s:
                marks.append((s, depth, label))
                marks.append((e, -depth, label))
    marks.sort(key=lambda m: m[0])
    open_: Dict[Tuple[int, str], int] = {}
    out: List[Tuple[float, float, str]] = []
    edge, label = lo, "between_steps"
    for t, depth, name in marks:
        key = (abs(depth), name)
        open_[key] = open_.get(key, 0) + (1 if depth > 0 else -1)
        if not open_[key]:
            del open_[key]
        now = max(open_)[1] if open_ else "between_steps"
        if now != label:
            t = min(max(t, lo), hi)
            if t > edge:
                out.append((edge, t, label))
                edge = t
            label = now
    if hi > edge:
        out.append((edge, hi, label))
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
    time are therefore taken over the whole plane and need no clock.  Only
    the attribution of idle gaps needs the device's clock and the host's
    together: ``align`` gives the offset where the trace holds the
    program's spans; where it does not, the device's timeline is shifted
    until its first operation starts where the first ``bench_issue`` opens,
    which draws every step's device work earlier by the whole of the
    launch (gaps much longer than that are still put down rightly).
    """
    devices, spans, anywhere = read_planes(profile)
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
    offset, bracket, note = align(devices, spans, anywhere)
    clock = "spans"
    if offset is None:
        clock = "lead"
        issue0 = spans[ISSUE][0][0] if spans[ISSUE] else steps[0][0]
        offset = min(0.0, merged0[0][0] - issue0)
    lo, hi = steps[0][0], steps[-1][1]
    shifted = clip([(a - offset, b - offset) for a, b in merged0], lo, hi)
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
        j, launched = k, False
        while j < len(timeline) and timeline[j][0] < b:
            s0, e0, label = timeline[j]
            if label == "op.launch" and s0 >= a:    # entered in this spell
                launched = True
            elif label == "complete.wait" and launched:
                label = IN_FLIGHT
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
        clock=clock,
        clock_offset_ns=offset,
        clock_bracket_ns=bracket,
        clock_note=note,
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
