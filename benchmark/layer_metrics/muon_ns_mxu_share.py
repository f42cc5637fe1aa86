"""The least time the Newton-Schulz steps need on this chip's MXU
(``muon_flops.py`` ``least``: ``5 * (3 m^2 n + m^3)`` a matrix, the two
symmetric products halved, over ``bf16_tflop_s`` of ``peaks.json``) as a
share of ``muon_ns_ms``, the time the program spends in them.  The least
count, so that no implementation can read over 100%: the program's fifteen
full products make 1.45 x as many FLOPs (``published``), and a program that
ran them at the peak would read 69%."""

from muon_ops import cell_sizes, split_ms


def read(ctx):
    ms = split_ms(ctx)
    if ms is None or not ms[0]:
        return None
    least_s = (cell_sizes(ctx.config)["least_flops"]
               / (ctx.peaks["bf16_tflop_s"] * 1e12))
    return 100.0 * least_s * 1e3 / ms[0]
