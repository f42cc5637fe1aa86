"""Device milliseconds a traced step in everything the ``muon`` handle runs
outside its Newton-Schulz steps (scopes ``ps.update.muon.momentum``,
``.apply``, ``.adamw``): the gradient cut into matrices, momentum,
Nesterov and the cast, decay and step, AdamW, the pulled tree, every
layout change between the flat store and a batch of matrices
(``muon_ops.py``: every device operation that is not told as
Newton-Schulz).  With ``muon_ns_ms`` it adds up to the device's busy time
on one chip.  None where ``muon_ns_ms`` reads nothing."""

from muon_ops import split_ms


def read(ctx):
    ms = split_ms(ctx)
    return None if ms is None else ms[1]
