"""Device milliseconds a traced step in operations OUTSIDE LAMB's two
kernels whose first result is as large as the tree (at least the
configuration's ``parameters`` elements a device, of any dtype): a
``convert``, ``copy``, ``pad`` or ``slice`` of the gradient or of the
pulled tree, the pass a mixed-precision deployment exists to save
(``mixed_ops.py`` ``tree_sized_ms``).  0 where the kernels take and leave
the job's dtype themselves; None where there is nothing to read."""

from mixed_ops import tree_sized_ms


def read(ctx):
    return tree_sized_ms(ctx)
