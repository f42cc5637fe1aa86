"""What the readers of a sparse step's exchange between chips share
(``layer_metrics/sparse_route_ms.py``, ``sparse_route_ici_share.py``): which
device operations are collectives, told by KIND and never by shape, so that a
program which routes its rows otherwise (an all-to-all by owner in place of
an all-gather to every shard) is still read.

An operation's kind is its instruction's name without the number XLA appends
(``sparse_handle_ops.kind_and_shape``).  A collective is one of ``KINDS``:
the five that move data between chips, each also in the ``-start`` /
``-done`` form the compiler gives an asynchronous one.  The TPU compiler may
also wrap a collective in a fusion of its own (a 2x2 compiles the pull's
``psum_scatter`` as ``%fusion.1 = ... fusion(...), kind=kCustom,
calls=%all-reduce-scatter``: an all-reduce and the cut to the device's own
part); the trace prints an operation as its whole HLO line, so such a fusion
is told by the computation it calls.

Read from the traced section's ``ProfileData`` (``ctx.profile``), a device at
a time: the UNION of the collectives' intervals, so that an operation the
trace shows both whole and by its parts is not counted twice; mean over the
devices, a traced step.  Of an asynchronous pair the two ends are counted,
not the time between them in which the transfer overlaps other work: that is
device time the exchange does not add to a step.
"""

from __future__ import annotations

import re
from typing import Optional

from sparse_handle_ops import kind_and_shape
from trace_reduce import DEVICE_PLANE, OPS_LINE, short_name, total, union

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
KINDS = frozenset(kind + form for kind in COLLECTIVES
                  for form in ("", "-start", "-done"))
_CALLS = re.compile(r"calls=%([A-Za-z_\-]+)")


def is_collective(name: str) -> bool:
    """Whether the device operation the trace prints as ``name`` (its whole
    HLO line) moves data between chips."""
    parts = kind_and_shape(short_name(name))
    if parts is not None and parts[0] in KINDS:
        return True
    called = _CALLS.search(name)
    return bool(called) and called.group(1).startswith(COLLECTIVES)


def route_ms(ctx) -> Optional[float]:
    """Device milliseconds a traced step in collectives; None where there is
    no trace of a device or the step has none (one chip)."""
    if ctx.reduction is None or ctx.profile is None \
            or not ctx.reduction.steps:
        return None
    per_device = []
    for plane in ctx.profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        events = [ev for line in plane.lines if line.name == OPS_LINE
                  for ev in line.events]
        if events:
            per_device.append(total(union(
                (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                for ev in events if is_collective(ev.name))))
    if not per_device or not sum(per_device):
        return None
    return sum(per_device) / len(per_device) / ctx.reduction.steps / 1e6
