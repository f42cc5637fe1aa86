"""The sparse exchange's share of its roofline: the least time one chip's
links need for a step's rows (the driver's own ``least_bytes`` of the step,
``ici``: the rows a worker pulls from, and the gradients it pushes to, the
other chips, ``2 * lookups * dim * 4 * (W-1)/W`` bytes, at ``peaks.json``'s
``ici_gbit_s``) over ``sparse_route_ms``, the device time its collectives
take.  The count is what any exchange routed by owner must move and does not
depend on what implements it.  None where ``sparse_route_ms`` reads nothing
or the step needs no interconnect (one chip)."""

from sparse_route_ops import route_ms


def read(ctx):
    ms = route_ms(ctx)
    ici = ctx.least.get("ici", 0.0)
    if not ms or not ici:
        return None
    least_ms = ici / (ctx.peaks["ici_gbit_s"] / 8 * 1e9) * 1e3
    return 100.0 * least_ms / ms
