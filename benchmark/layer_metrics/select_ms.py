"""Host milliseconds a step in the program's stage ``select``, from its
``StageClock`` over the profiler-off window (``stage_window.py``).
Resolving the handle, the zero-copy and ring eligibility checks and the
program-cache lookup (on the sparse path the wait for the table's lock too)."""

from stage_window import stage_ms


def read(ctx):
    return stage_ms(ctx.spans, "select")
