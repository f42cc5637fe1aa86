"""Device milliseconds a traced step in Muon's Newton-Schulz steps on the
owner that spends the most there (``muon_owner_ops.py`` ``fullest_ns_ms``:
a chip at a time, the operations ``muon_ops.is_ns`` tells): over several
colocated servers every matrix lies whole on one owner, and the step waits
for the fullest.  None where there is no trace of a device, the cell is not
under ``muon`` or no chip ran such an operation."""

from muon_owner_ops import fullest_ns_ms


def read(ctx):
    return fullest_ns_ms(ctx)
