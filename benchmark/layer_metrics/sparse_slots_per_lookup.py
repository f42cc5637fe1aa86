"""Rows of the batch workspace one shard works on for each lookup a worker
sends: the program's counter ``engine.sparse.route.slots`` (what a shard's
push program combines and its pull program gathers in an op, noted once an
op from shapes alone) over the profiler-off window, over the window's sparse
ops and the traffic's ``lookups_per_worker``.  W while every shard is sent
every worker's batch (4.0 on four chips); about 1 for an exchange routed by
owner, where a shard is sent the rows it owns.

Read from the process's ``StageClock`` as ``stage_window.py`` reads the
stages: over the whole 1.07 s slots inside the window.  None with no spans,
on a program without the counter, under the no-op clock of
``PS_TELEMETRY=0``, or where the window holds no whole slot or no sparse op.
"""


def read(ctx):
    if not ctx.spans:
        return None
    try:
        from pslite_tpu.utils.profiling import stage_clock

        routed = stage_clock().routed
    except (ImportError, AttributeError):
        return None
    (slots, ops), whole, _ = routed(ctx.spans[0][0], ctx.spans[-1][2])
    lookups = int(ctx.traffic.get("lookups_per_worker", 0))
    if not whole or not ops or not lookups:
        return None
    return slots / ops / lookups
