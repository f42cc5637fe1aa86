"""What the program's stage ``launch`` is made of, by the kind of op that
paid it, over the profiler-off window: read after the run from its
``StageClock`` (``pslite_tpu/utils/profiling.py``, ``LAUNCH``), as
``stage_window.py`` reads the stages and over the same whole 1.07 s slots.

An op notes, beside its stages, the nanoseconds inside its jitted call alone
(``call``: ``launch - call`` is the program's own Python under the stage:
the lock, first-time state, rebinding store and state, the cut of the pulled
array, the byte counters), how many arrays that call was handed and returned
(``arrays``) and which of ``LAUNCH_OPS`` it is.  The kinds' launches and
launch nanoseconds add up to the stage's exactly (the same notes), so the
steps the window holds are the kinds' launches over the stage's calls a
step, as ``stage_window.per_step`` counted them.
"""

from typing import Dict, Optional

from stage_window import per_step as stages_per_step


def _window(spans):
    """``({op kind: (launches, launch ns, call ns, arrays)}, steps)`` of the
    kinds that launched in the window of ``spans``, or None: no spans, a
    program without the account, the no-op clock of ``PS_TELEMETRY=0``, or
    stages that read nothing (fewer than ``stage_window.MIN_SLOTS`` whole
    slots)."""
    stages = stages_per_step(spans)
    if not stages or not stages["launch"][1]:
        return None
    from pslite_tpu.utils.profiling import stage_clock

    launches = getattr(stage_clock(), "launches", None)
    if launches is None:
        return None
    kinds, slots, _ = launches(spans[0][0], spans[-1][2])
    total = sum(kind[0] for kind in kinds.values()) if slots else 0
    if not total:
        return None
    return ({op: kind for op, kind in kinds.items() if kind[0]},
            total / stages["launch"][1])


def per_step(spans) -> Optional[Dict[str, Dict[str, float]]]:
    """``{op kind: {"launches", "launch_ms", "call_ms", "arrays"}}``, each a
    step; None where :func:`_window` reads nothing."""
    found = _window(spans)
    if found is None:
        return None
    kinds, steps = found
    return {op: {"launches": n / steps, "launch_ms": launch / 1e6 / steps,
                 "call_ms": call / 1e6 / steps, "arrays": arrays / steps}
            for op, (n, launch, call, arrays) in kinds.items()}


def summed(spans, key: str, ops: str = "") -> Optional[float]:
    """``key`` a step over the kinds whose name starts with ``ops`` (all of
    them by default); None where :func:`per_step` reads nothing or no such
    kind launched."""
    found = [kind[key] for op, kind in (per_step(spans) or {}).items()
             if op.startswith(ops)]
    return sum(found) if found else None


def arrays_per_step(spans) -> Optional[float]:
    """Arrays the step's programs were handed and returned.  A step's ops
    are whole, and every op of a kind here carries as many arrays as the
    next, but the window cuts its border steps by time (``ops_per_step``
    reads 467.02 of 467): a kind's arrays a launch, two integers of the
    same notes, go by its launches a step as a whole number where they lie
    within a fiftieth of one."""
    found = _window(spans)
    if found is None:
        return None
    kinds, steps = found
    total = 0.0
    for n, _, _, arrays in kinds.values():
        a_step = n / steps
        whole = round(a_step)
        if whole and abs(a_step - whole) <= whole / 50:
            a_step = whole
        total += arrays * a_step / n
    return total
