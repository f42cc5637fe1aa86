"""Lookups a bag of the pooled sparse ops: the program's counters
``engine.sparse.pool.lookups`` over ``engine.sparse.pool.bags`` (noted once an
op of ``SparseEngine`` under ``pool="sum"`` that carried a bag of more than
one id, from the shapes it was bound at, no device read) over the
profiler-off window, read from the process's ``StageClock``
(``sparse_bags_ops.pooled_in_window``).  8.23 where a step's 106,496 bags
hold 876,544 ids (``multi_hot_sizes`` summed over 26): beside ``ops_per_step``
2.0 it says the bags went through the pooled path, 4,096 x 214 lookups a
step, and were not multiplied out on the job's side.  None on a program
without the counter, with no spans, or where no op pooled."""

from sparse_bags_ops import pooled_in_window


def read(ctx):
    found = pooled_in_window(ctx.spans)
    return None if found is None else found[1] / found[0]
