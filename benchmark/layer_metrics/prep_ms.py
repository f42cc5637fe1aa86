"""Host milliseconds a step in the program's stage ``prep``, from its
``StageClock`` over the profiler-off window (``stage_window.py``).
``_prep_grads*`` / ``SparseEngine._prep``, which check shape and sharding and
stage a host-origin gradient onto the device."""

from stage_window import stage_ms


def read(ctx):
    return stage_ms(ctx.spans, "prep")
