"""Device-idle milliseconds a traced step that lie under the program's
``ps.kv.op`` span: the first device had nothing to run while the issuing
thread was inside an op's launch (``op.launch``: jax's ``PjitFunction``) or
its route, select, prep and dispatch (``op.other``).  It is the device's
side of what ``issue_ms`` and the stage metrics time from the host's: the
part of the program's issue path that the device does not cover.

From the traced section's idle gaps, once the device's timeline lies on the
host's clock by the program's spans (``trace_reduce.align``).  None where
there is no trace of a device, or the clocks were brought together only by
the first operation (``Reduction.clock == "lead"``: a program without the
spans, launches that do not repeat)."""

from trace_reduce import ISSUE_LABELS


def read(ctx):
    r = ctx.reduction
    if r is None or r.clock != "spans":
        return None
    gaps = dict(map(tuple, r.idle_gaps))
    return sum(gaps.get(label, 0.0) for label in ISSUE_LABELS) \
        * 1e3 / r.steps
