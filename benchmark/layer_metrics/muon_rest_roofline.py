"""The least time the ``muon`` handle's passes outside Newton-Schulz need
on this chip (``muon_ops.py`` ``rest_bytes``: 24 B a Muon value, 32 B an
AdamW value, over the HBM peak of ``peaks.json``) as a share of
``muon_rest_ms``, the time the program spends in them."""

from muon_ops import cell_sizes, split_ms


def read(ctx):
    ms = split_ms(ctx)
    if ms is None or not ms[1]:
        return None
    least_s = (cell_sizes(ctx.config)["rest_bytes"]
               / (ctx.peaks["hbm_gb_s"] * 1e9))
    return 100.0 * least_s * 1e3 / ms[1]
