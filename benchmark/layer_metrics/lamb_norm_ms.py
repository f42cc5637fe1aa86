"""Device milliseconds a traced step between LAMB's two kernels, in the
operations that make the keys' norms and ratios (scope
``ps.update.lamb.norms``): told by result shape, one or two numbers a key
(``lamb_ops.py`` ``norm_shapes``), whatever the kind but the kernels' own.
None where there is no trace of a device or none is found."""

from lamb_ops import KERNELS, cell_sizes, norm_shapes
from sparse_handle_ops import ms_a_step


def read(ctx):
    if ctx.reduction is None:
        return None
    shapes = norm_shapes(cell_sizes(ctx.config)["keys"])
    return ms_a_step(ctx, lambda kind, shape: kind not in KERNELS
                     and shape in shapes)
