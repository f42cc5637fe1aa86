"""Device milliseconds a step in combining the pooled push's duplicates, all
tables: in the programs the step's ``sparse.push`` ops launched
(``sparse_bags_ops.program_ops``), every operation of kind ``sort``, the
segment sum's kernel (kind ``segment_sum``, ``ops/segment_sum.py``), and every
``fusion``, ``scatter``, ``scatter-add`` or ``gather`` whose result is ONE
table's batch of ``m_t = W * B * h_t`` slots as rows ``f32[m_t, dim]`` or ids
``s32[m_t]`` (from the cell's own ``ctx.config`` and ``ctx.traffic``): the
gather that brings the gradients into sorted order, which is where a slot's
gradient is read through its bag.  None where there is no trace of a device
(a CPU run) or the programs cannot be put down to their ops."""

from sparse_bags_ops import combine_ms


def read(ctx):
    return combine_ms(ctx)
