"""Host milliseconds a step inside the jitted calls alone, ``b.prog(...)``
of every engine op: the ``call`` of the program's ``LAUNCH`` notes over the
profiler-off window (``launch_window.py``).  ``launch_ms`` less this is the
program's own Python under the stage ``launch``: the bucket or table lock,
first-time state, rebinding store and state, the cut of the pulled array,
the byte counters.  None on a program without the account."""

from launch_window import summed


def read(ctx):
    return summed(ctx.spans, "call_ms")
