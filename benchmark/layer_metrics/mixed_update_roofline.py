"""The least time the mixed-precision update needs on this chip
(``lamb_mixed_bytes.py`` ``lamb_mixed_update`` over the HBM peak of
``peaks.json``: the gradient once at the job's 2 B, p, m, v read and written
at 4 B, the pulled tree written at 2 B where one shard holds the bucket, a
second pass only over keys larger than VMEM) as a share of
``mixed_update_ms``, the time of the two kernels that make it today."""

from lamb_ops import update_ms
from mixed_ops import cell_sizes


def read(ctx):
    sizes = cell_sizes(ctx.config)
    ms = update_ms(ctx) if sizes is not None else None
    if not ms:
        return None
    least_s = sizes["update_bytes"] / (ctx.peaks["hbm_gb_s"] * 1e9)
    return 100.0 * least_s * 1e3 / ms
