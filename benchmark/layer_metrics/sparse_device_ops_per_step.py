"""Device operations a traced step executes: the events of the ``XLA Ops``
line of a device's plane over the traced section, a step
(``sparse_tables_ops.device_ops_a_step`` over ``ctx.profile``).  A count.
With ``busy_ms`` it gives the mean time an operation: a step over 26 tables
of 2,048 lookups each runs some hundreds of small operations where a step
over one table of 53,248 runs a few dozen, so what an operation costs to
start weighs as much as what it moves.  None where nothing was traced (a CPU
run)."""

from sparse_tables_ops import device_ops_a_step


def read(ctx):
    if ctx.reduction is None:
        return None
    return device_ops_a_step(ctx.profile, ctx.reduction.steps)
