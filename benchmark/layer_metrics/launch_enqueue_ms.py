"""Milliseconds a traced step in the runtime's ``DoEnqueueProgram``, one a
program and device, on whatever thread it runs (a program with a tuple
result is enqueued from a thread of the runtime's own, after the jitted call
has returned): every thread, every device, inside the traced steps.  From
the profile's host plane (``launch_events.py``).  None where nothing was
traced or the trace shows no such event."""

from launch_events import enqueue_ms


def read(ctx):
    return enqueue_ms(ctx)
