"""The result shapes by which the readers of a step over MANY tables
(``layer_metrics/tables_combine_ms.py``, ``tables_write_ms.py``) tell its
device operations apart, worked out from the sizes of the cell that is read
as ``sparse_handle_ops.shapes`` works one table's out: the configuration's
``"tables": [[name, rows], ...]`` of one width ``dim``, and the traffic's
``lookups_per_table``.

- every table's own shard, ``f32[rows_t/W/pack, pack*dim]`` (``W`` chips,
  rows rounded up to whole physical rows; ``pack`` = 128/dim where ``dim``
  divides 128, else 1: ``SparseEngine.register_sparse``), under ``tables``,
  a tuple in the configuration's order;
- the batch ONE table is sent, ``m = W * lookups_per_table`` entries:
  gradient rows as pushed ``f32[m, dim]``, rows placed in a physical row's
  lanes ``f32[m, pack*dim]`` (``parallel/sparse.py`` ``_place_rows``; the
  packed pull gathers physical rows of the same shape), row ids ``s32[m]``.

And what the two readers that count share: the grouped ops' counter of the
program's ``StageClock`` over a window, and the device operations a traced
step executes.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import packed_table_ops


def shapes(config: dict, traffic: dict) -> Dict[str, object]:
    """One table's shapes by the one-table rule (``packed_table_ops.shapes``
    over ``sparse_handle_ops.shapes``), a table at a time."""
    one = dict(traffic, lookups_per_worker=traffic["lookups_per_table"])
    per = [packed_table_ops.shapes(dict(config, rows=rows), one)
           for _, rows in config["tables"]]
    return dict(((name, per[0][name]) for name in
                 ("batch_rows", "batch_phys_rows", "batch_ids")),
                tables=tuple(s["table"] for s in per))


def grouped_in_window(spans) -> Optional[Tuple[int, int]]:
    """``(tables, ops)`` of the grouped sparse ops the program noted over
    the whole 1.07 s slots inside the window of ``spans`` (as
    ``stage_window.py`` reads the stages); None with no spans, on a program
    without the counter, under the no-op clock of ``PS_TELEMETRY=0``, or
    where the window holds no whole slot or no grouped op."""
    if not spans:
        return None
    try:
        from pslite_tpu.utils.profiling import stage_clock

        grouped = stage_clock().grouped
    except (ImportError, AttributeError):
        return None
    (tables, ops), whole, _ = grouped(spans[0][0], spans[-1][2])
    return (tables, ops) if whole and ops else None


def device_ops_a_step(profile, steps: int) -> Optional[float]:
    """Events on the executed-operations line of a device's plane over the
    traced steps, a step, mean over the devices that show any; None where
    nothing was traced or no device shows an operation."""
    from trace_reduce import DEVICE_PLANE, OPS_LINE

    if profile is None or not steps:
        return None
    # (``ProfileData``'s events are iterated, not measured: no ``len``.)
    counts = [n for n in (
        sum(1 for line in plane.lines if line.name == OPS_LINE
            for _ in line.events)
        for plane in profile.planes if DEVICE_PLANE.match(plane.name)) if n]
    return sum(counts) / len(counts) / steps if counts else None
