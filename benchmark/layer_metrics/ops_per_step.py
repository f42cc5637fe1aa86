"""Engine ops a step: calls of the program's stage ``launch`` (one per
``push_pull`` / ``push`` / ``pull`` / sparse call, one for a grouped or
replayed call whatever it holds) over the profiler-off window, from the
program's ``StageClock`` (``stage_window.py``).  Beside ``launches_per_step``
(device programs a step, from the trace) it gives launches per op."""

from stage_window import per_step


def read(ctx):
    stages = per_step(ctx.spans)
    return None if stages is None else stages["launch"][1]
