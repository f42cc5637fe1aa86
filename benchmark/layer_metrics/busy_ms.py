"""Device time of a step: the union of the intervals in which an operation
ran on a device inside each traced step, mean over steps and devices."""


def read(ctx):
    if ctx.reduction is None:
        return None
    return ctx.reduction.busy_ms_per_step
