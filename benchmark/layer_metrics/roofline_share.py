"""The least time a step needs on this chip (``least_bytes.py`` over
``peaks.json``: the larger of least HBM bytes over HBM bandwidth and least
ICI bytes over chip-to-chip bandwidth) as a share of ``busy_ms``."""

from least_bytes import least_seconds


def read(ctx):
    if ctx.reduction is None or ctx.reduction.busy_ms_per_step <= 0:
        return None
    least = least_seconds(ctx.least, ctx.peaks)
    return 100.0 * least["seconds"] * 1e3 / ctx.reduction.busy_ms_per_step
