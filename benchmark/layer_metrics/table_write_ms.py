"""Device milliseconds a step in operations whose result is the whole table
or the whole accumulator, under a stateful sparse handle: the two in-place
scatters of ``_adagrad_sparse`` (the accumulator's ``set``, the store's
``scatter-add``), which run over donated operands.

Found by result shape alone, whatever the kind (``sparse_handle_ops.py``),
from ``ctx.reduction.op_seconds``: the first result is one device's shard of
the table, ``f32[rows/W/pack, pack*dim]``, or of the accumulator,
``f32[rows/W]``.  Nothing else in either sparse program has a result of
these shapes, and a copy of a donated operand (``%copy.<n>`` of 10.24 GB:
~25 ms) shows here first.  None where there is no trace of a device (a CPU
run).
"""

from sparse_handle_ops import cell_shapes, ms_a_step


def read(ctx):
    s = cell_shapes()
    whole = (s["table"], s["accumulator"])
    return ms_a_step(ctx, lambda kind, shape: shape in whole)
