"""The least time the fullest owner's Newton-Schulz steps need on this
chip's MXU (``muon_owner_ops.py`` ``fullest_owner_flops``: a W-th of the
tree's least count, ``5 * (3 m^2 n + m^3)`` a matrix, or the heaviest matrix
where that is more, over ``bf16_tflop_s`` of ``peaks.json``) as a share of
``muon_owned_ns_ms``, the time the fullest owner spends in them.  The least
any deal of whole matrices gives its fullest owner, so that no
implementation can read over 100%; a deal that loads one owner above the
others reads lower for it."""

from muon_owner_ops import fullest_ns_ms, fullest_owner_flops


def read(ctx):
    ms = fullest_ns_ms(ctx)
    flops = fullest_owner_flops(ctx.config)
    if not ms or flops is None:
        return None
    least_s = flops / (ctx.peaks["bf16_tflop_s"] * 1e12)
    return 100.0 * least_s * 1e3 / ms
