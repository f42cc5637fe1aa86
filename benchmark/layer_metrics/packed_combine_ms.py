"""Device milliseconds a step in sorting and moving the batch on a
lane-packed table, as ``combine_ms`` defines it for an unpacked one: every
operation of kind ``sort``, the segment sum's kernel (kind ``segment_sum``,
``ops/segment_sum.py``: the sum by physical row), and every ``fusion``,
``scatter``, ``scatter-add`` or ``gather`` whose result is a workspace of the
gathered batch (``packed_table_ops.py``, m = W * lookups, from the cell's
own ``ctx.config`` and ``ctx.traffic``): gradient rows as pushed, ``f32[m,
dim]``, rows placed in a physical row's lanes, ``f32[m, pack*dim]`` (the
permutation by the sort's order; XLA's scatter-add where the program keeps
it for the segment sum), row ids ``s32[m]`` and flags ``pred[m]``.

The pull program's two movers have these shapes too and are counted, as
``combine_ms`` counts the unpacked pull's gather: the gather of physical
rows, ``f32[m, pack*dim]``, and the selection of each row's slot,
``f32[m, dim]``.  So where the push keeps XLA's scatter with no combine
before it, this reads the pull's two alone (0.748 ms a step on a v5e,
PR 31).  Left out, as there: the cumulative sum over the segment starts,
copies, and elementwise passes XLA names after their operations (the
placement itself is one: ``%compare_select_fusion``).  None where there is
no trace of a device (a CPU run)."""

from packed_table_ops import shapes
from sparse_handle_ops import combine_ms


def read(ctx):
    return combine_ms(ctx, shapes, ("batch_rows", "batch_phys_rows",
                                    "batch_ids", "batch_flags"))
