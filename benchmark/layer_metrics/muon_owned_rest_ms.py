"""Device milliseconds a traced step in everything an owner runs on the
keys it owns outside its Newton-Schulz steps (scopes
``ps.update.muon.momentum``, ``.apply``, ``.adamw``, and the cut of the
summed gradient to the owner's part), on the owner that spends the most
there: ``muon_rest_ms`` of the one-chip cell, an owner's share of it
(``muon_owner_ops.py`` ``step_parts``: between the program's first and last
collective, what is neither a collective nor told as Newton-Schulz).  None
where there is no trace of a device, the cell is not under ``muon`` or the
program has no collective (one shard)."""

from muon_owner_ops import fullest_rest_ms


def read(ctx):
    return fullest_rest_ms(ctx)
