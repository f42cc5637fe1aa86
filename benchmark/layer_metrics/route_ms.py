"""Host milliseconds a step in the program's stage ``route``, from its
``StageClock`` over the profiler-off window (``stage_window.py``).
``np.asarray(keys)`` and ``KVWorker._engine_route`` (the dict lookup and
``np.array_equal`` that find the registered bucket).  The sparse calls name
their table and route nothing: no value there."""

from stage_window import stage_ms


def read(ctx):
    return stage_ms(ctx.spans, "route")
