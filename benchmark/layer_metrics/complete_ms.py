"""Host milliseconds a step in the program's stage ``complete.copy``, from its
``StageClock`` over the profiler-off window (``stage_window.py``).
What the ``kv-engine-complete`` thread does once the device is done (the D2H
copy into ``out``, the callback), host work that contends for the GIL with
the issuing thread.  Its blocked wait on the device (``complete.wait``) is not
host work and is left out."""

from stage_window import stage_ms


def read(ctx):
    return stage_ms(ctx.spans, "complete.copy")
