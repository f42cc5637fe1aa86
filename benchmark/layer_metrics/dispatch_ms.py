"""Host milliseconds a step in the program's stage ``dispatch``, from its
``StageClock`` over the profiler-off window (``stage_window.py``).
The rest of ``KVWorker._engine_op`` once the engine has returned:
``new_request``, keeping the device result, the completion pool's ``submit``,
``add_wait_hook``."""

from stage_window import stage_ms


def read(ctx):
    return stage_ms(ctx.spans, "dispatch")
