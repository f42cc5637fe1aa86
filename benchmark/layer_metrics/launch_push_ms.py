"""Host milliseconds a step in the stage ``launch`` of the ``sparse.push``
ops (``push`` / ``push_group`` of ``SparseEngine``), from the program's
``LAUNCH`` notes over the profiler-off window (``launch_window.py``); with
``launch_pull_ms`` it adds up to ``launch_ms`` where a step is sparse ops
alone.  None on a program without the account or a window without a push."""

from launch_window import summed


def read(ctx):
    return summed(ctx.spans, "launch_ms", "sparse.push")
