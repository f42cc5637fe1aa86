"""Device milliseconds a traced step of a bucket sharded by owner spends in
the exchange between chips: the sum over W of the workers' rows to the
owners and the gather of the new parameters back, every collective of the
step told by opcode and never by shape (``sparse_route_ops.py`` ``route_ms``:
the union of the collectives' intervals a chip, the mean over the chips), so
that a program which exchanges otherwise (a reduce-scatter in place of an
all-reduce and a cut) is still read.  None where there is no trace of a
device, the step has no collective (one chip) or the cell is not under
``muon``."""

from sparse_route_ops import route_ms


def read(ctx):
    if not str(ctx.config.get("server_handle", "")).startswith("muon"):
        return None
    return route_ms(ctx)
