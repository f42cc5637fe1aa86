"""Device milliseconds a step in the pooled push's update and write, all
tables: in the programs the step's ``sparse.push`` ops launched
(``sparse_bags_ops.program_ops``), the kernels ``row_add`` (a table written by
distinct row) and ``acc_update`` (an accumulator updated in one pass) by name,
any operation whose first result is one device's shard of a table,
``f32[rows_t / W, dim]``, or of its accumulator, ``f32[rows_t / W]`` (XLA's
scatter where a table or an accumulator keeps it: the accumulators of the
tables whose rows are no multiple of 128; a copy of a donated one would show
here first), and every mover whose result is a table's batch of values
``f32[m_t]`` (the accumulator's rows gathered and stepped, the first half of
XLA's pair).  The rows' own step, an elementwise pass, is not in it.  None
where there is no trace of a device (a CPU run) or the programs cannot be put
down to their ops."""

from sparse_bags_ops import write_ms


def read(ctx):
    return write_ms(ctx)
