"""Host milliseconds a step with nothing outstanding on the device before the
launch stage that ends the spell begins: from the return of the wait that
left the process with no op launched and not waited for, to the start of the
next op's ``launch``.  All of it is the host's own Python (the end of
``complete.copy``, the caller's loop, the next op's route, select and prep)
and the device is idle through every microsecond of it: the lower end of the
bracket in which a traced run's host-owned idle labels must lie.  From the
program's occupancy account over the profiler-off window
(``occupancy_window.py``)."""

from occupancy_window import per_step


def read(ctx):
    account = per_step(ctx.spans)
    return None if account is None else account["starved.prelaunch"] / 1e6
