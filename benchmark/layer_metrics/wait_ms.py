"""Host time a step spends waiting: the return of the last issue to the
return of the last ``wait`` (``KVWorker._engine_complete`` joining the
device).  Median over the profiler-off window."""

import statistics


def read(ctx):
    if not ctx.spans:
        return None
    return statistics.median((s[2] - s[1]) * 1e3 for s in ctx.spans)
