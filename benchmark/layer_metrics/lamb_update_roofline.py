"""The least time LAMB's update needs on this chip (``lamb_bytes.py``
``lamb_update`` over the HBM peak of ``peaks.json``: Adam's bytes, and a
second pass only over keys larger than VMEM) as a share of
``lamb_update_ms``, the time of the two kernels that make it today."""

from lamb_ops import cell_sizes, update_ms


def read(ctx):
    ms = update_ms(ctx)
    if not ms:
        return None
    least_s = (cell_sizes(ctx.config)["update_bytes"]
               / (ctx.peaks["hbm_gb_s"] * 1e9))
    return 100.0 * least_s * 1e3 / ms
