"""The least time the pooled pull needs on this chip as a share of
``bag_pull_ms``: every lookup's row read, every bag's pooled row written,
every id read (``sparse_bags_ops.pooled_pull_bytes``, from the cell's own
``ctx.config`` and ``ctx.traffic``) over the HBM peak of ``peaks.json``.  The
bytes are the work's, whatever implements the pool: a program that writes the
unpooled rows to HBM and reads them back moves about twice as many and reads
under 50% however fast its operations are."""

from sparse_bags_ops import pooled_pull_bytes, pull_ms


def read(ctx):
    ms = pull_ms(ctx)
    if not ms:
        return None
    least_s = (pooled_pull_bytes(ctx.config, ctx.traffic)
               / (ctx.peaks["hbm_gb_s"] * 1e9))
    return 100.0 * least_s * 1e3 / ms
