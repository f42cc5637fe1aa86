"""Device milliseconds a traced step in Muon's Newton-Schulz steps (scope
``ps.update.muon.ns``): the fifteen batched products a chunk and what lies
between them, told from ``ctx.reduction.op_seconds`` by result shape, a
batch of bfloat16 matrices of one of the configuration's sides
(``muon_ops.py``).  None where there is no trace of a device, the cell is
not under ``muon`` or the program ran no such operation."""

from muon_ops import split_ms


def read(ctx):
    ms = split_ms(ctx)
    return None if ms is None else ms[0]
