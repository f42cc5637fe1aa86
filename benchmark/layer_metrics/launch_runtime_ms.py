"""Milliseconds a traced step inside the runtime's execute call
(``launch_events.RUNTIME``) under jax's ``PjitFunction`` under the program's
``ps.kv.op``, on the issuing thread: what of a launch is the runtime's and
not jax's wrapper around it.  From the profile's host plane, where the
runtime's events lie on a line of their own (``launch_events.py``).  None
where nothing was traced, the trace holds no ``ps.kv.op``
(``Reduction.clock == "lead"``), or the tracer does not show the event."""

from launch_events import RUNTIME, nested_ms


def read(ctx):
    return nested_ms(ctx, RUNTIME)
