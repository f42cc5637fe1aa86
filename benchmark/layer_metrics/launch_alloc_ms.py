"""Milliseconds a traced step the runtime spends allocating the result
buffers of a launch (``launch_events.ALLOC``), inside its execute call under
``PjitFunction`` under ``ps.kv.op``: the part of ``launch_runtime_ms`` that
goes by the fresh arrays a program returns (a donated store costs it
nothing).  From the profile's host plane (``launch_events.py``).  None where
nothing was traced, the trace holds no ``ps.kv.op``, or the tracer does not
show the event."""

from launch_events import ALLOC, nested_ms


def read(ctx):
    return nested_ms(ctx, ALLOC)
