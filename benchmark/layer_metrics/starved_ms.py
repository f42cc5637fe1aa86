"""Host milliseconds a step with nothing outstanding on the device, through
the end of the launch stage that ends the spell: ``starved_prelaunch_ms``
plus that op's whole ``launch``.  An upper bound of what the device feels:
it starts somewhere inside that stage (``pull_sparse``'s holds two launches,
and the device runs from 0.14-0.17 ms into the first).  From the program's
occupancy account over the profiler-off window (``occupancy_window.py``)."""

from occupancy_window import per_step


def read(ctx):
    account = per_step(ctx.spans)
    if account is None:
        return None
    return (account["starved.prelaunch"] + account["starved.launch"]) / 1e6
