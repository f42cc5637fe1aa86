"""Programs executed on the first device's plane in the traced steps, per
step (the ``XLA Modules`` line).  A count: it has to repeat exactly."""


def read(ctx):
    if ctx.reduction is None:
        return None
    return ctx.reduction.launches_per_step
