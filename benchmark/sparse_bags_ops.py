"""What the readers of a step whose lookups are BAGS share
(``layer_metrics/bag_lookups_per_bag.py``, ``bag_pull_ms.py``,
``bag_pull_roofline.py``, ``bag_combine_ms.py``, ``bag_write_ms.py``) with the
driver ``drivers/sparse_bags_pull_push.py``: the sizes of the cell that is
read, the bytes a pooled pull and a step must move at least, the program's
counter of pooled ops over a window, and a traced step's device operations
told apart by the PROGRAM they ran in and then by kind and result shape.

**Sizes** (:func:`sizes`) come from the cell's own two files, never from a
path: the configuration's ``"tables": [[name, rows], ...]`` of one width
``dim`` with ``"bag_sizes": [h_t, ...]`` in the same order, and the traffic's
``bags_per_table``.

**Operations by program** (:func:`program_ops`).  A step over 26 tables of
26 bag sizes runs some 1,700 operations in two programs, and result shapes
repeat between them: a gathered batch ``f32[B * h, d]`` is the result of the
pull's gather and of the push's read through the bag alike.  (The program's
``jax.named_scope``s would tell them apart, but ``jax.profiler.ProfileData``
hands a device event's three timing stats and no metadata: read on the chip,
PR 54.)  So an operation is first put down to the op that LAUNCHED its
program: the issuing thread's ``ps.kv.op`` spans carry the op's kind
(``sparse.pull``, ``sparse.push``: ``pslite_tpu/utils/profiling.py``
``LAUNCH_OPS``), a device runs a process's programs in the order they were
launched, and where the traced section holds as many programs on the first
device as it holds ops, the k-th program is the k-th op's.  Within a
program an operation is told as the siblings' readers tell it
(``sparse_handle_ops``): by its kind (its instruction's name without the
number XLA appends) and the shape of its first result, worked out from the
sizes: table ``t``'s shard ``f32[rows_t / W, dim]``, its accumulator
``f32[rows_t / W]``, its batch of ``m_t = W * B * h_t`` slots as rows
``f32[m_t, dim]``, ids ``s32[m_t]`` or values ``f32[m_t]``.

- the pull (:func:`pull_ms`): EVERY operation of the programs the step's
  ``sparse.pull`` ops launch: the rows of every slot of every bag gathered,
  the sum over a bag, and what places the tables' pooled rows side by side
  (30 us of 7.3 ms);
- the combine (:func:`combine_ms`), in the ``sparse.push`` programs: every
  ``sort``, the segment sum's kernel, and every mover whose result is a
  table's batch as rows or ids (the gather that brings the gradients into
  sorted order, which is where a slot's is read through its bag);
- the write (:func:`write_ms`), in the ``sparse.push`` programs: the kernels
  ``row_add`` and ``acc_update`` by name, any operation whose first result
  is a table's shard or its accumulator (XLA's scatter where a table or an
  accumulator keeps it; a copy of a donated one would show here first), and
  every mover whose result is a table's batch of values ``f32[m_t]`` (the
  accumulator's rows gathered and stepped: the first half of XLA's pair).

What is in none: in the push the rows' step (``-lr * G / (sqrt(acc) +
eps)``, an elementwise pass over ``f32[m_t, dim]``), ``mean(G ** 2)``, the
borders of the segments, the owner mask.
"""

from __future__ import annotations

import bisect
from typing import Callable, Dict, List, Optional, Tuple

PULL_OP, PUSH_OP = "sparse.pull", "sparse.push"


def sizes(config: dict, traffic: dict) -> Dict[str, object]:
    """``tables`` ``[(name, rows, h), ...]``, ``bags`` a table a worker a
    step (``B``), ``workers``, ``dim``, ``itemsize``, and over all tables and
    workers a step ``all_bags`` and ``all_lookups``."""
    hs = [int(h) for h in config["bag_sizes"]]
    tables = [(str(name), int(rows), h)
              for (name, rows), h in zip(config["tables"], hs)]
    if len(tables) != len(hs) or len(tables) != len(config["tables"]):
        raise ValueError("one bag size a table")
    B, W = int(traffic["bags_per_table"]), int(config["chips"])
    return {"tables": tables, "bags": B, "workers": W,
            "dim": int(config["dim"]), "itemsize": 4,
            "all_bags": W * B * len(tables), "all_lookups": W * B * sum(hs)}


def pooled_pull_bytes(config: dict, traffic: dict) -> float:
    """The least HBM bytes one device moves in a step's pooled pull, whatever
    does the pooling: every lookup's row read (a bag's sum needs each of its
    slots' rows, hot or not; ``lookups x dim x 4``), every bag's pooled row
    written once (``bags x dim x 4``), every id read once (4 B).  Per device:
    a device answers its own worker's bags."""
    s = sizes(config, traffic)
    row = s["dim"] * s["itemsize"]
    return (s["all_lookups"] * row + s["all_bags"] * row
            + s["all_lookups"] * 4) / float(s["workers"])


def step_least_bytes(distinct: List[float], config: dict, traffic: dict
                     ) -> Dict[str, float]:
    """The least one pooled pull then one pooled push under ``row_adagrad``
    must move on one device, the sibling's rule
    (``rowwise_adagrad.pull_push_step_least_bytes``) a table with a bag's
    rows in place of a lookup's: every distinct row touched is read for the
    pull and read and written for the push, its 4-byte accumulator read and
    written (the device's ``1/W`` of them); the ids are read twice; the
    pooled rows are written and the bags' gradients read once, a row a BAG.
    ICI (W > 1): the rows a worker pulls from, and the gradients it pushes
    to, the other devices, a slot each (partial pools on the owners would
    move a bag's).  ``distinct``: a table's distinct rows a step, the mean
    over the pool's batches, all workers'."""
    s = sizes(config, traffic)
    w, row = float(s["workers"]), s["dim"] * s["itemsize"]
    hbm = ici = 0.0
    for (_, _, h), unique in zip(s["tables"], distinct):
        lookups = s["bags"] * h
        hbm += (3 * unique * row + 2 * 4 * unique) / w
        hbm += 2 * lookups * 4 + 2 * s["bags"] * row
        ici += 2 * lookups * row * (w - 1) / w
    return {"hbm": hbm, "ici": ici}


def pooled_in_window(spans) -> Optional[Tuple[int, int, int]]:
    """``(bags, lookups, ops)`` of the pooled sparse ops the program noted
    over the whole 1.07 s slots inside the window of ``spans`` (as
    ``stage_window.py`` reads the stages); None with no spans, on a program
    without the counter, under the no-op clock of ``PS_TELEMETRY=0``, or
    where the window holds no whole slot or no op pooled."""
    if not spans:
        return None
    try:
        from pslite_tpu.utils.profiling import stage_clock

        pooled = stage_clock().pooled
    except (ImportError, AttributeError):
        return None
    (bags, lookups, ops), whole, _ = pooled(spans[0][0], spans[-1][2])
    return (bags, lookups, ops) if whole and ops and bags else None


def shapes(config: dict, traffic: dict) -> Dict[str, frozenset]:
    """The result shapes by which a push program's operations are told:
    ``tables`` and ``accumulators`` (a device's shard of each), and a table's
    batch of ``m_t = W * B * h_t`` slots as ``batch_rows``, ``batch_ids`` and
    ``batch_values``."""
    s = sizes(config, traffic)
    W, dim, B = s["workers"], s["dim"], s["bags"]
    rps = [-(-rows // W) for _, rows, _ in s["tables"]]
    ms = [W * B * h for _, _, h in s["tables"]]
    return {"tables": frozenset(f"f32[{r},{dim}]" for r in rps),
            "accumulators": frozenset(f"f32[{r}]" for r in rps),
            "batch_rows": frozenset(f"f32[{m},{dim}]" for m in ms),
            "batch_ids": frozenset(f"s32[{m}]" for m in ms),
            "batch_values": frozenset(f"f32[{m}]" for m in ms)}


def _stat(ev, key: str):
    return next((v for k, v in getattr(ev, "stats", ()) if k == key), None)


def program_ops(profile) -> Optional[Dict[str, Dict[str, float]]]:
    """``{op kind: {operation's short name: ns}}`` over the traced steps on
    the first device that shows programs: every device operation put down to
    the kind of the op whose launch its program was (see the module's text).
    None where nothing was traced, the program makes no ``ps.kv.op`` spans,
    or the device's programs are not as many as the traced steps' ops (a
    launch that is not a program, a program no op launched)."""
    from trace_reduce import (DEVICE_PLANE, MODULES_LINE, OP, OPS_LINE, STEP,
                              short_name)

    if profile is None:
        return None
    kinds: List[Tuple[float, str]] = []
    device = None
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            if MODULES_LINE in lines and OPS_LINE in lines and (
                    device is None or int(m.group(1)) < device[0]):
                device = (int(m.group(1)), lines)
            continue
        for line in plane.lines:
            events = list(line.events)
            steps = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                     for ev in events if ev.name == STEP]
            if not steps:
                continue
            for ev in events:
                start = float(ev.start_ns)
                if ev.name == OP and any(lo <= start < hi
                                         for lo, hi in steps):
                    kinds.append((start, str(_stat(ev, "op") or "op")))
    if device is None or not kinds:
        return None
    kinds.sort()
    programs = sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                      for ev in device[1][MODULES_LINE].events)
    if len(programs) != len(kinds):
        return None
    starts = [start for start, _ in programs]
    found: Dict[str, Dict[str, float]] = {}
    for ev in device[1][OPS_LINE].events:
        mid = float(ev.start_ns) + float(ev.duration_ns) / 2
        k = bisect.bisect_right(starts, mid) - 1
        if k < 0 or mid > programs[k][1]:
            continue
        of_kind = found.setdefault(kinds[k][1], {})
        name = short_name(ev.name)
        of_kind[name] = of_kind.get(name, 0.0) + float(ev.duration_ns)
    return found


def _ms_a_step(ctx, op: str, pick: Optional[Callable]) -> Optional[float]:
    """Milliseconds a traced step in the operations ``pick(shapes)(kind,
    shape)`` (all of them where ``pick`` is None) of the programs the ops of
    kind ``op`` launched; None where there is no trace of a device, the cell
    that is read holds no bags (a rehearsal hands every reader every cell),
    the programs cannot be put down to their ops, or no such operation is
    found."""
    from sparse_handle_ops import kind_and_shape

    if (ctx.reduction is None or not ctx.reduction.steps
            or "bag_sizes" not in ctx.config):
        return None
    ops = (program_ops(ctx.profile) or {}).get(op)
    if not ops:
        return None
    if pick is not None:
        pick = pick(shapes(ctx.config, ctx.traffic))
    ns, found = 0.0, False
    for name, spent in ops.items():
        parts = kind_and_shape(name)
        if parts is not None and (pick is None or pick(*parts)):
            ns += spent
            found = True
    return ns / ctx.reduction.steps / 1e6 if found else None


def pull_ms(ctx) -> Optional[float]:
    return _ms_a_step(ctx, PULL_OP, None)


def combine_ms(ctx) -> Optional[float]:
    from sparse_handle_ops import MOVERS, SEGMENT_SUM

    def pick(s):
        batch = s["batch_rows"] | s["batch_ids"]
        return lambda kind, shape: (kind in ("sort", SEGMENT_SUM)
                                    or (kind in MOVERS and shape in batch))

    return _ms_a_step(ctx, PUSH_OP, pick)


def write_ms(ctx) -> Optional[float]:
    from sparse_handle_ops import ACC_UPDATE, MOVERS, ROW_ADD

    def pick(s):
        stored = s["tables"] | s["accumulators"]
        return lambda kind, shape: (
            kind in (ROW_ADD, ACC_UPDATE) or shape in stored
            or (kind in MOVERS and shape in s["batch_values"]))

    return _ms_a_step(ctx, PUSH_OP, pick)
