"""Host time of a step by stage of the program's own engine path, read after
the run from the program's ``StageClock``
(``pslite_tpu/utils/profiling.py``: ``route``, ``select``, ``prep``,
``launch``, ``dispatch`` on the issuing thread, ``complete.wait`` and
``complete.copy`` on the completion thread).

The clock is always on and keeps its totals per 1.07 s slot of the
``time.perf_counter`` clock (slots begin at whole multiples of their width),
which is the clock of the harness's spans: a reader is given only the
profiler-off window's spans, and asks the clock for the whole slots between
the window's first issue and its last wait.  What the stages cost inside
those slots, over the steps issued inside them (a step cut by a border counts
by the part of its issue time that lies inside), is a stage's mean host
milliseconds a step.
"""

import math
from typing import Dict, Optional, Tuple

MIN_SLOTS = 3  # fewer whole slots inside the window say nothing steady


def per_step(spans) -> Optional[Dict[str, Tuple[float, float]]]:
    """``{stage: (host ms a step, calls a step)}`` over the window of
    ``spans``, or None: no spans, a program without the clock, the no-op
    clock of ``PS_TELEMETRY=0``, or fewer than ``MIN_SLOTS`` whole slots."""
    if not spans:
        return None
    try:
        from pslite_tpu.utils.profiling import stage_clock
    except ImportError:
        return None
    stages, slots, seconds = stage_clock().window(spans[0][0], spans[-1][2])
    if slots < MIN_SLOTS:
        return None
    lo = math.ceil(spans[0][0] * slots / seconds) * seconds / slots
    hi = lo + seconds
    steps = sum(max(0.0, min(t1, hi) - max(t0, lo)) / (t1 - t0)
                for t0, t1, _ in spans if t1 > t0)
    if not steps:
        return None
    return {name: (ns / 1e6 / steps, calls / steps)
            for name, (ns, calls) in stages.items()}


def stage_ms(spans, stage: str) -> Optional[float]:
    """One stage's host milliseconds a step; None where the window reads
    nothing or the stage saw no call in it (``route`` on the sparse path)."""
    ms, calls = (per_step(spans) or {}).get(stage, (None, 0))
    return ms if calls else None
