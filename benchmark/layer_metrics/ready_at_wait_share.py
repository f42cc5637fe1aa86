"""Ops whose result was already there when first waited for (a
``complete.wait`` under the program's ``READY_NS``), as a share of all ops
completed in the profiler-off window.  Near 100% the exchange is host-bound
(the device finishes each op before the host asks); near 0% every wait
blocks on the device.  From the program's occupancy account
(``occupancy_window.py``); None where no op completed."""

from occupancy_window import per_step


def read(ctx):
    account = per_step(ctx.spans)
    if account is None or not account["completed"]:
        return None
    return 100.0 * account["ready_at_wait"] / account["completed"]
