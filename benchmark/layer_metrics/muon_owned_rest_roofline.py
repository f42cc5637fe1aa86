"""The least time an owner's passes outside Newton-Schulz need on its chip
(a W-th of ``muon_ops.py`` ``rest_bytes``: 24 B a Muon value, 32 B an AdamW
value, over the HBM peak of ``peaks.json``; a deal of whole keys gives its
fullest owner no less) as a share of ``muon_owned_rest_ms``, the time the
fullest owner spends in them."""

from muon_ops import cell_sizes
from muon_owner_ops import fullest_rest_ms


def read(ctx):
    ms = fullest_rest_ms(ctx)
    if not ms:
        return None
    least_s = (cell_sizes(ctx.config)["rest_bytes"] / int(ctx.config["chips"])
               / (ctx.peaks["hbm_gb_s"] * 1e9))
    return 100.0 * least_s * 1e3 / ms
