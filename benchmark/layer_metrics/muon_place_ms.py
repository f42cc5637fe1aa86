"""Device milliseconds a traced step in the two placing passes of a bucket
sharded on its keys' borders (scopes ``ps.push.place`` and
``ps.pull.place``): a worker's row laid from key order into the owners'
order before the sum, and the gathered shards laid back into key order for
the pulled tree: what the owners' layout costs beside a bucket cut at any
element.  Told by where they run (``muon_owner_ops.py`` ``step_parts``:
what a chip's program does before its first collective starts and after its
last one ends; a trace hands no scope), the mean over the chips.  None where
there is no trace, the cell is not under ``muon`` or the program has no
collective (one shard)."""

from muon_owner_ops import placing_ms


def read(ctx):
    return placing_ms(ctx)
