"""``jax.monitoring`` compile events (backend compilations and compile-cache
reads) between the profiler-off window's first and last step.  Expected 0:
every shape is warmed in set-up."""


def read(ctx):
    return ctx.compiles_in_window
