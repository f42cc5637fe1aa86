"""Host milliseconds a step in the program's stage ``launch``, from its
``StageClock`` over the profiler-off window (``stage_window.py``).
The bucket or table lock, first-time optimizer state, the jitted call,
rebinding store and state, slicing the pulled array, the byte counters."""

from stage_window import stage_ms


def read(ctx):
    return stage_ms(ctx.spans, "launch")
