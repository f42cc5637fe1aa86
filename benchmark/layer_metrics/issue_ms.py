"""Host time a step spends issuing: the first ``push_pull`` / ``pull_sparse``
call of a step to the return of the last (``KVWorker`` routing, the engine's
host side, the program launch).  Median over the profiler-off window."""

import statistics


def read(ctx):
    if not ctx.spans:
        return None
    return statistics.median((s[1] - s[0]) * 1e3 for s in ctx.spans)
