"""Device milliseconds a step in combining a push's duplicates before the
table is touched (``parallel/sparse.py`` ``_combine_rows``, scope
``ps.sparse.combine``, under the plain sum and under a stateful handle
alike): the sort of the gathered row ids, gradients brought into sorted
order, the segment sum that leaves one G and one row id a distinct row, and
the sort of the segments' ids.

Found by kind and result shape (``sparse_handle_ops.py``; shapes from the
cell's own ``ctx.config`` and ``ctx.traffic``), from
``ctx.reduction.op_seconds``:

- every operation of kind ``sort`` (XLA's TPU compiler keeps a sort an
  operation of its own: the ``argsort``, and the sorts it puts before a
  scatter whose indices it cannot prove sorted, which belong to the price
  of the method);
- the segment sum's kernel, kind ``segment_sum`` (``ops/segment_sum.py``,
  ``%segment_sum.1 f32[W*lookups, dim]``), where the program takes it;
- every operation of kind ``fusion``, ``scatter``, ``scatter-add`` or
  ``gather`` (on the TPU a gather or a scatter shows as ``%fusion.<n>``)
  whose result is a workspace of the gathered batch: gradient rows
  ``f32[W*lookups, dim]`` (the permutation of the gradients; XLA's
  scatter-add where the program keeps it for the segment sum), row ids
  ``s32[W*lookups]`` and ownership ``pred[W*lookups]``.

The pull program's gather of the same batch, ``%fusion f32[W*lookups,
dim]``, has the kind and shape of the last class and cannot be told from
the permutation by name (one name in two programs), so that one gather is
counted too (``PERF.md`` section 5 gives every operation by name from a
trace).

Left out: the cumulative sum over the segment starts, copies, and the
elementwise passes XLA names after their operations
(``%select_negate_fusion``: the step, not the combine); the accumulator's
part is the update's (``table_write_ms``).  None where there is no trace of
a device (a CPU run).
"""

from sparse_handle_ops import combine_ms, shapes


def read(ctx):
    return combine_ms(ctx, shapes, ("batch_rows", "batch_ids", "batch_flags"))
