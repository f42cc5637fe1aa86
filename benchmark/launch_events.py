"""A launch taken apart by the runtime's own host events, from the traced
section's whole profile (``LayerContext.profile``): what lies under jax's
``PjitFunction(<program>)`` inside the program's ``ps.kv.op`` span.

On the issuing thread (the line that holds ``bench_step``) every ``ps.kv.op``
inside a traced step is one op, of the kind its ``op`` stat names
(``pslite_tpu/utils/profiling.py`` ``LAUNCH_OPS``; a program from before the
stat: ``op``).  jax emits two nested ``PjitFunction(`` events a jitted call:
the outermost is the launch, and every other host event nested in it by time
on the same thread is summed by the name the trace gives it (an event inside
one of its own name once: the outer).  A ``PjitFunction`` outside every
``ps.kv.op`` (the driver's own jitted generator) is none of the program's and
does not count.

The same thread lies on TWO lines of the host plane: jax's events and the
``TraceAnnotation`` spans on one named for the process (``python3``), the
TPU runtime's own (libtpu 0.0.34 records through a tracer of its own:
``PJRT_LoadedExecutable_Execute``) on one named for the thread
(``main/<tid>``).  On one chip the whole call lies there
(``ExecutePrepare``, ``AllocateOutputBuffersWithInputReuse``,
``ExecuteLaunch`` ...); over several the call hands a device's part to a
thread a device (``py_xla_execute/<tid>``) and waits for them, so what is
nested in a launch is summed over those lines too: thread time, every device.
Such lines are told by their shape, not their names: events of theirs lie in
a launch and next to none lies across a launch's border, as those of the
threads beside the call do (``tfrt-non-blocking-queue``, which enqueues the
program once the call has returned; ``futex-*``, which waits for it to end).
``trace_reduce.ENQUEUE`` is taken on whatever thread it lies, inside the
traced steps.

Host events lie on the host's clock, the one ``trace_reduce.align`` lays the
device's timeline on; nothing here needs the device's.

The first of the three trace readers that runs prints one line a run, ``launch: {...}``: per op
kind the launches a step and the median microseconds of one in ``ps.kv.op``,
in ``PjitFunction`` and in each event nested in it that takes 2% of it or
more; and the enqueues a step with their median.  From a checkout:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds 30 --trace 1 | grep '^launch:'
"""

from __future__ import annotations

import bisect
import json
import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from trace_reduce import DEVICE_PLANE, ENQUEUE, OP, PJIT, STEP

# The runtime's execute call under ``PjitFunction`` and, inside it, the
# allocation of the result buffers, as libtpu 0.0.34 names them at the
# harness's ``host_tracer_level`` 2 (PERF.md section 5 has every name).
RUNTIME = "PJRT_LoadedExecutable_Execute"
ALLOC = "AllocateOutputBuffersWithInputReuse"
SHOWN_SHARE = 0.02      # of a kind's median PjitFunction
CROSSING = 50           # a line inside the launches: one event in so many across


@dataclass
class Op:
    kind: str
    op_ns: float
    pjit_ns: float = 0.0    # its outermost PjitFunction events
    nested: Dict[str, float] = field(default_factory=dict)  # under them, by name

    def add(self, name: str, ns: float) -> None:
        self.nested[name] = self.nested.get(name, 0.0) + ns


@dataclass
class Launches:
    steps: int
    ops: List[Op]
    enqueue_ns: List[float]     # every DoEnqueueProgram inside the traced steps


def _stat(ev, key: str):
    return next((v for k, v in getattr(ev, "stats", ()) if k == key), None)


Launch = Tuple[float, float, Op]    # an outermost PjitFunction: start, end, its op


def _sorted(line):
    """A line's events by start, the longer first where two start together
    (it is the outer one)."""
    return sorted(((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                    ev) for ev in line.events), key=lambda e: (e[0], -e[1]))


def _issuing_line(line) -> Tuple[List[Op], List[Launch],
                                 List[Tuple[float, float]]]:
    """The ops of one thread's line, their launches and the traced steps, by
    one sweep in time with the open spans as a stack."""
    ops: List[Op] = []
    launches: List[Launch] = []
    steps: List[Tuple[float, float]] = []
    open_: List[Tuple[float, str]] = []     # (end, what it is)
    op = None
    for start, end, ev in _sorted(line):
        while open_ and open_[-1][0] <= start:
            open_.pop()
        name = ev.name
        inside = [what for _, what in open_]
        what = name
        if name == STEP:
            steps.append((start, end))
        elif name == OP:
            if STEP not in inside:
                continue
            op = Op(str(_stat(ev, "op") or "op"), end - start)
            ops.append(op)
        elif OP not in inside:
            continue
        elif name.startswith(PJIT):
            if PJIT in inside:
                continue        # jax's inner event of the same call
            op.pjit_ns += end - start
            launches.append((start, end, op))
            what = PJIT
        elif PJIT in inside and name not in inside:
            op.add(name, end - start)
        open_.append((end, what))
    return ops, launches, steps


def _runtime_lines(lines, launches: List[Launch]):
    """Of ``lines``, those of the runtime's threads that work INSIDE the
    launches: events of theirs lie in a launch, and next to none crosses a
    launch's border (a thread beside the call, which waits for a program or
    enqueues the last one, has events across them).  For each, ``[(start,
    end, event, its launch)]`` of its events inside a launch."""
    starts = [launch[0] for launch in launches]
    for line in lines:
        inside, crossing = [], 0
        for start, end, ev in _sorted(line):
            k = bisect.bisect_right(starts, start) - 1
            into = k >= 0 and start < launches[k][1]
            if (into and end > launches[k][1]) or (
                    k + 1 < len(starts) and end > starts[k + 1]):
                crossing += 1
            elif into:
                inside.append((start, end, ev, launches[k]))
        if inside and crossing <= len(inside) // CROSSING:
            yield inside


def read(profile) -> Optional[Launches]:
    """None where nothing was traced or no thread holds a traced step."""
    if profile is None:
        return None
    lines = [line for plane in profile.planes
             if not DEVICE_PLANE.match(plane.name) for line in plane.lines]
    for at, line in enumerate(lines):
        ops, launches, steps = _issuing_line(line)
        if steps:
            break
    else:
        return None
    for inside in _runtime_lines(lines[:at] + lines[at + 1:], launches):
        open_: List[Tuple[float, str]] = []
        for start, end, ev, (_, _, op) in inside:
            while open_ and open_[-1][0] <= start:
                open_.pop()
            if ev.name not in [name for _, name in open_]:
                op.add(ev.name, end - start)
            open_.append((end, ev.name))
    lo, hi = steps[0][0], steps[-1][1]
    enqueue = [float(ev.duration_ns) for line in lines for ev in line.events
               if ev.name == ENQUEUE and lo <= ev.start_ns < hi]
    return Launches(len(steps), ops, enqueue)


def summary(found: Launches) -> dict:
    """What the ``launch:`` line holds."""
    us = lambda ns: round(ns / 1e3, 1)  # noqa: E731
    kinds: Dict[str, dict] = {}
    for kind in sorted({op.kind for op in found.ops}):
        of = [op for op in found.ops if op.kind == kind]
        pjit = statistics.median(op.pjit_ns for op in of)
        nested = {name: statistics.median(op.nested.get(name, 0.0)
                                          for op in of)
                  for name in {n for op in of for n in op.nested}}
        kinds[kind] = {
            "a_step": round(len(of) / found.steps, 2),
            "ps.kv.op_us": us(statistics.median(op.op_ns for op in of)),
            "PjitFunction_us": us(pjit),
            "nested_us": {name: us(ns) for name, ns in sorted(
                nested.items(), key=lambda kv: -kv[1])
                if ns >= SHOWN_SHARE * pjit and ns > 0}}
    out = {"steps": found.steps, "ops": kinds}
    if found.enqueue_ns:
        out[ENQUEUE] = {
            "a_step": round(len(found.enqueue_ns) / found.steps, 2),
            "us": us(statistics.median(found.enqueue_ns))}
    return out


_last: Tuple[object, Optional[Launches]] = (None, None)


def of_run(ctx) -> Optional[Launches]:
    """:func:`read` of the run's profile, made and printed once a run."""
    global _last
    profile = getattr(ctx, "profile", None)
    if profile is None:
        return None
    if _last[0] is not profile:
        _last = (profile, read(profile))
        if _last[1] is not None and _last[1].ops:
            print("launch: " + json.dumps(summary(_last[1])), flush=True)
    return _last[1]


def nested_ms(ctx, name: str) -> Optional[float]:
    """Milliseconds a traced step in the events ``name`` nested in a launch
    under ``ps.kv.op``; None where the trace holds no op or no such event."""
    found = of_run(ctx)
    if found is None or not any(name in op.nested for op in found.ops):
        return None
    return sum(op.nested.get(name, 0.0)
               for op in found.ops) / 1e6 / found.steps


def enqueue_ms(ctx) -> Optional[float]:
    """Milliseconds a traced step in ``DoEnqueueProgram``, every thread and
    every device; None where the trace shows none."""
    found = of_run(ctx)
    if found is None or not found.enqueue_ns:
        return None
    return sum(found.enqueue_ns) / 1e6 / found.steps
