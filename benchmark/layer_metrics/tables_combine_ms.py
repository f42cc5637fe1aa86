"""Device milliseconds a step in sorting and moving the batches of a step
over many tables, as ``packed_combine_ms`` defines it for one: every
operation of kind ``sort``, the segment sum's kernel (kind ``segment_sum``,
``ops/segment_sum.py``), and every ``fusion``, ``scatter``, ``scatter-add`` or
``gather`` whose result is a batch workspace of ONE table of this cell
(``sparse_tables_ops.py``, m = W * lookups_per_table, from the cell's own
``ctx.config`` and ``ctx.traffic``): gradient rows as pushed ``f32[m, dim]``,
rows placed in a physical row's lanes ``f32[m, pack*dim]``, row ids
``s32[m]``.  Every table's batch has these shapes, so the 26 bodies of the
two group programs are summed; the pull's two movers a table are counted, as
there.  None where there is no trace of a device (a CPU run)."""

from sparse_handle_ops import combine_ms
from sparse_tables_ops import shapes


def read(ctx):
    return combine_ms(ctx, shapes, ("batch_rows", "batch_phys_rows",
                                    "batch_ids"))
