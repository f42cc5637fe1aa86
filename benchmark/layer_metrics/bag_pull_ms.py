"""Device milliseconds a step in the pooled pull, all tables: every device
operation of the programs the step's ``sparse.pull`` ops launched (told by
the ``op`` of the issuing thread's ``ps.kv.op`` spans, the k-th program the
k-th op's: ``sparse_bags_ops.program_ops``): the rows of every slot of every
bag gathered from the tables, the sum over a bag, and what places the tables'
pooled rows side by side in the group's one result.  None where there is no
trace of a device (a CPU run) or the programs cannot be put down to their
ops."""

from sparse_bags_ops import pull_ms


def read(ctx):
    return pull_ms(ctx)
