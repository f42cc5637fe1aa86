"""Arrays a step's programs were handed and returned (stores, optimizer
state, gradients, indices, scalars placed on the device, results and
tokens): the ``arrays`` of the program's ``LAUNCH`` notes over the
profiler-off window, a bound op's from its record
(``launch_window.arrays_per_step``).  What the runtime allocates, tracks and
hands back a launch goes by it.  None on a program without the account."""

from launch_window import arrays_per_step


def read(ctx):
    return arrays_per_step(ctx.spans)
