"""Device milliseconds a step in writing what a sparse push touches of the
whole table and, under a stateful handle, of the whole accumulator.

From ``ctx.reduction.op_seconds`` (``sparse_handle_ops.py``; shapes from the
cell's own ``ctx.config`` and ``ctx.traffic``):

- the two kernels by name, whatever their result: ``row_add``
  (``ops/row_add.py``: the table written by distinct row, ``%row_add.1
  f32[rows/W/pack, pack*dim]``) and ``acc_update`` (``ops/acc_update.py``:
  row-wise Adagrad's read-update-write of the touched accumulators, whose
  first result is the accumulator seen as whole 128-lane rows,
  ``f32[rows/W/128, 128]``);
- any operation, whatever its kind, whose first result is one device's
  shard of the table, ``f32[rows/W/pack, pack*dim]``, or of the
  accumulator, ``f32[rows/W]``: XLA's scatters where the program keeps
  them, and a copy of a donated operand (``%copy.<n>`` of 10.24 GB: ~25
  ms), which shows here first.

Nothing else in either sparse program has a result of these shapes.  None
where there is no trace of a device (a CPU run).
"""

from sparse_handle_ops import ACC_UPDATE, ROW_ADD, ms_a_step, shapes


def read(ctx):
    if ctx.reduction is None:
        return None
    s = shapes(ctx.config, ctx.traffic)
    whole = (s["table"], s["accumulator"])
    return ms_a_step(ctx, lambda kind, shape: kind in (ROW_ADD, ACC_UPDATE)
                     or shape in whole)
