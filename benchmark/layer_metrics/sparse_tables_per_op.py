"""Tables a grouped sparse op carried: the program's counters
``engine.sparse.group.tables`` over ``engine.sparse.group.ops`` (noted once
an op of ``SparseEngine.pull_group`` / ``push_group`` from the call's own
arguments, no device read) over the profiler-off window, read from the
process's ``StageClock`` (``sparse_tables_ops.grouped_in_window``).  26.0
where a step's rows of 26 tables go in one ``KVWorker`` call; beside
``launches_per_step`` 2.0 and ``ops_per_step`` 2.0 it says the group
engaged.  None on a CPU run of a program without the counter, with no spans,
or where the window holds no grouped op."""

from sparse_tables_ops import grouped_in_window


def read(ctx):
    found = grouped_in_window(ctx.spans)
    return None if found is None else found[0] / found[1]
