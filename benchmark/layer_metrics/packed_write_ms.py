"""Device milliseconds a step in writing the lane-packed table: what
follows a push's combine, or stands in its place.

From ``ctx.reduction.op_seconds`` (``sparse_handle_ops.py``): the kernel
``row_add`` by name (``ops/row_add.py``: the table written by distinct
physical row), and any operation, whatever its kind, whose first result is
one device's shard of the physical table, ``f32[rows/W/pack, pack*dim]``
(``packed_table_ops.py``, from the cell's own ``ctx.config`` and
``ctx.traffic``: ``f32[27000000,128]``).  Where the program writes a
lane-packed table with XLA's scatter that is the scatter's fusion, which
pays for every slot of the batch.  Nothing else in the push or the pull has
a result of this shape, and a copy of the donated table (13.8 GB: ~35 ms,
and it would not fit) shows here first.  None where there is no trace of a
device (a CPU run)."""

from packed_table_ops import shapes
from sparse_handle_ops import ROW_ADD, ms_a_step


def read(ctx):
    if ctx.reduction is None:
        return None
    table = shapes(ctx.config, ctx.traffic)["table"]
    return ms_a_step(ctx, lambda kind, shape: kind == ROW_ADD
                     or shape == table)
