"""Device milliseconds a step in writing the tables of a step over many:
the kernel ``row_add`` by name (``ops/row_add.py``: a table written by
distinct physical row), and any operation, whatever its kind, whose first
result is one device's shard of one of the configuration's tables,
``f32[rows_t/W/pack, pack*dim]`` (``sparse_tables_ops.py``, from the cell's
own ``ctx.config``): XLA's scatter where the program keeps it for a table,
and a copy of a donated table (2.56 GB: ~6 ms), which shows here first.
None where there is no trace of a device (a CPU run)."""

from sparse_handle_ops import ROW_ADD, ms_a_step
from sparse_tables_ops import shapes


def read(ctx):
    if ctx.reduction is None:
        return None
    tables = frozenset(shapes(ctx.config, ctx.traffic)["tables"])
    return ms_a_step(ctx, lambda kind, shape: kind == ROW_ADD
                     or shape in tables)
