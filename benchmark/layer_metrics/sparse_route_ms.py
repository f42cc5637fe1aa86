"""Device milliseconds a step in the sparse programs' exchange between chips
(``parallel/sparse.py``, scope ``ps.sparse.route``: the index all-gather of
both programs, ``.ids``; the push's gradient all-gather, ``.grads``; the
pull's ``psum_scatter``, ``.rows``): every collective of the traced steps, by
kind (``sparse_route_ops.py``), mean over the devices, from the traced
section's own events (``ctx.profile``).  A sparse cell runs no other
collective.  None where there is no trace of a device (a CPU run) or no
collective in it."""

from sparse_route_ops import route_ms


def read(ctx):
    return route_ms(ctx)
