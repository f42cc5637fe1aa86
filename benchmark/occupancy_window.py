"""The program's own occupancy account over the profiler-off window, read
after the run from its ``StageClock`` (``pslite_tpu/utils/profiling.py``,
``OCCUPANCY``), as ``stage_window.py`` reads the stages: over the whole
1.07 s slots between the window's first issue and its last wait, by the
steps issued inside them.

The account is the HOST's knowledge of the device: the process is starved
while every op it launched has been waited for, up to the start of the next
launch stage (``starved.prelaunch``) and, counted apart, through that stage
(``starved.launch``); ``ready_at_wait`` counts the ops whose result was
there when first waited for.  It cannot hold the runtime's wake-up (the
spell begins when ``block_until_ready`` has returned), and where a step
issues hundreds of ops before it waits once it reads next to nothing,
whatever the device does: ``idle_gaps`` of a traced run sees those from the
device's side.
"""

import math
from typing import Dict, Optional

from stage_window import MIN_SLOTS


def per_step(spans) -> Optional[Dict[str, float]]:
    """The account's keys, each a step (nanoseconds for ``starved.*``,
    counts for the rest), or None: no spans, a program without the clock or
    from before the account, the no-op clock of ``PS_TELEMETRY=0``, or
    fewer than ``MIN_SLOTS`` whole slots."""
    if not spans:
        return None
    try:
        from pslite_tpu.utils.profiling import stage_clock
    except ImportError:
        return None
    occupancy = getattr(stage_clock(), "occupancy", None)
    if occupancy is None:
        return None
    account, slots, seconds = occupancy(spans[0][0], spans[-1][2])
    if slots < MIN_SLOTS:
        return None
    # The steps issued inside the slots, as ``stage_window.per_step`` counts
    # them: one cut by a border by the part of its issue time inside.
    lo = math.ceil(spans[0][0] * slots / seconds) * seconds / slots
    hi = lo + seconds
    steps = sum(max(0.0, min(t1, hi) - max(t0, lo)) / (t1 - t0)
                for t0, t1, _ in spans if t1 > t0)
    if not steps:
        return None
    return {name: value / steps for name, value in account.items()}
