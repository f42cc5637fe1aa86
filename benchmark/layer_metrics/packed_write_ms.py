"""Device milliseconds a step in operations whose result is the whole
lane-packed table: what follows a push's combine, or stands in its place.

Found by result shape alone, whatever the kind (``sparse_handle_ops.py``),
from ``ctx.reduction.op_seconds``: the first result is one device's shard of
the physical table, ``f32[rows/W/pack, pack*dim]`` (``packed_table_ops.py``:
``f32[27000000,128]``).  Where the program writes a lane-packed table with
XLA's scatter that is the scatter's fusion, which pays for every slot of
the batch; where it writes by distinct physical row it is ``%row_add``
(``ops/row_add.py``).  Nothing else in the push or the pull has a result of
this shape, and a copy of the donated table (13.8 GB: ~35 ms, and it would
not fit) shows here first.  None where there is no trace of a device (a CPU
run)."""

from packed_table_ops import cell_shapes
from sparse_handle_ops import ms_a_step


def read(ctx):
    table = cell_shapes()["table"]
    return ms_a_step(ctx, lambda kind, shape: shape == table)
