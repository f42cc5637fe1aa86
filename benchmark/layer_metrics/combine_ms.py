"""Device milliseconds a step in combining duplicates before the table is
touched, under a stateful sparse handle (``parallel/sparse.py``
``_adagrad_sparse``, scope ``ps.sparse.combine``): the sort of the gathered
row ids, ids, ownership and gradients brought into sorted order, and the
segment sum that leaves one G and one row id a distinct row.

Found by kind and result shape (``sparse_handle_ops.py``), from
``ctx.reduction.op_seconds``:

- every operation of kind ``sort`` (XLA's TPU compiler keeps a sort an
  operation of its own: the ``argsort``, and the sorts it puts before a
  scatter whose indices it cannot prove sorted, which belong to the price
  of the method);
- every operation of kind ``fusion``, ``scatter``, ``scatter-add`` or
  ``gather`` (on the TPU a gather or a scatter shows as ``%fusion.<n>``)
  whose result is a workspace of the gathered batch: gradient rows
  ``f32[W*lookups, dim]`` (the permutation of the gradients, the segment
  sum), row ids ``s32[W*lookups]`` and ownership ``pred[W*lookups]`` (both
  permuted by the sort's order; the scatter of each segment's row id).

The pull program's gather of the same batch, ``%fusion f32[W*lookups,
dim]``, has the kind and shape of the first class and cannot be told from
it by name, so that one gather is counted too (``PERF.md`` section 5 gives
every operation by name from a trace; the ``sum`` cell's trace has the
pull's gather alone under this shape).

Left out: the cumulative sum over the segment starts, and the elementwise
passes XLA names after their operations (``%select_negate_fusion``: the
step, not the combine); the accumulator's gather, ``f32[W*lookups]``, is
the update's.  None where there is no trace of a device (a CPU run).
"""

from sparse_handle_ops import cell_shapes, ms_a_step

MOVERS = ("fusion", "scatter", "scatter-add", "gather")


def read(ctx):
    s = cell_shapes()
    batch = (s["batch_rows"], s["batch_ids"], s["batch_flags"])
    return ms_a_step(ctx, lambda kind, shape: kind == "sort" or (
        kind in MOVERS and shape in batch))
