"""Device milliseconds a traced step in LAMB's two kernels where the job's
dtype is narrower than the store's (a bf16 gradient read by
``%lamb_moments``, a bf16 pulled tree written by ``%lamb_apply``): by the
custom calls' names, as ``lamb_update_ms`` (``lamb_ops.py``), in a cell
whose configuration states a ``job_dtype``.  None where there is no trace
of a device, no such kernel, or no job dtype."""

from lamb_ops import update_ms
from mixed_ops import cell_sizes


def read(ctx):
    if cell_sizes(ctx.config) is None:
        return None
    return update_ms(ctx)
