"""Device milliseconds a traced step in LAMB's two kernels,
``%lamb_moments`` and ``%lamb_apply`` (``ops/fused_update.py``; scopes
``ps.update.lamb.moments`` / ``.apply``), from ``ctx.reduction.op_seconds``
by the custom calls' names (``lamb_ops.py``).  None where there is no trace
of a device (a CPU run) or the program has no such kernel."""

from lamb_ops import update_ms


def read(ctx):
    return update_ms(ctx)
