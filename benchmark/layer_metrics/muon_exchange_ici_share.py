"""The exchange's share of its roofline: the least time one chip's links
need for a step (the driver's own ``least_bytes`` of the step, ``ici``: 3/4
of the tree out for the reduction to the owners and 3/4 in for the gather,
``2 * 4 * N * (W-1)/W`` bytes, at ``peaks.json``'s ``ici_gbit_s``) over
``muon_exchange_ms``, the device time its collectives take.  The count is
what any exchange that shards the state by owner must move and does not
depend on what implements it.  None where ``muon_exchange_ms`` reads
nothing or the step needs no interconnect (one chip)."""

from sparse_route_ops import route_ms


def read(ctx):
    if not str(ctx.config.get("server_handle", "")).startswith("muon"):
        return None
    ms = route_ms(ctx)
    ici = ctx.least.get("ici", 0.0)
    if not ms or not ici:
        return None
    least_ms = ici / (ctx.peaks["ici_gbit_s"] / 8 * 1e9) * 1e3
    return 100.0 * least_ms / ms
