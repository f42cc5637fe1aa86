"""Which device operations of a sparse step the readers
``layer_metrics/combine_ms.py`` and ``layer_metrics/table_write_ms.py``
count, worked out from the sizes of the cell that is read (``ctx.config``
and ``ctx.traffic``: the cell's own configuration and traffic files).

A reader is given ``Reduction.op_seconds``: every device operation's seconds
over the traced steps by its short name, ``%<instruction> <first result's
shape>`` (``%sort.3 s32[131072]``, ``%fusion.7 f32[20000000,128]``).  The
**kind** of an operation is its instruction's name without the number XLA
appends (``sort``, ``fusion``, ``copy``, ``scatter-add``); the **shape** is
that of one device's shard, so it follows from the sizes:

- the table, ``f32[rows/W/pack, pack*dim]``, and the accumulator,
  ``f32[rows/W]`` (``W`` chips, rows rounded up; ``pack`` = 128/dim where
  ``dim`` divides 128, else 1: ``SparseEngine.register_sparse``);
- the gathered batch, ``m = W * lookups_per_worker`` entries: gradient
  rows ``f32[m, dim]``, row ids ``s32[m]``, ownership ``pred[m]``.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Tuple

# The sparse path's kernels by the names they give their custom calls:
# ``pslite_tpu/ops/segment_sum.py`` (the combine's segment sum),
# ``pslite_tpu/ops/row_add.py`` (the table written by distinct row) and
# ``pslite_tpu/ops/acc_update.py`` (row-wise Adagrad's accumulator).
KERNELS = SEGMENT_SUM, ROW_ADD, ACC_UPDATE = ("segment_sum", "row_add",
                                              "acc_update")
# On the TPU a gather or a scatter shows as ``%fusion.<n>``.
MOVERS = ("fusion", "scatter", "scatter-add", "gather")

_SHORT = re.compile(r"^%([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)[.\d]* (\w+\[[\d,]*\])$")


def shapes(config: dict, traffic: dict) -> Dict[str, str]:
    """The result shapes by which the operations are told apart."""
    W, dim = int(config["chips"]), int(config["dim"])
    pack = 128 // dim if (dim < 128 and 128 % dim == 0) else 1
    rps = -(-int(config["rows"]) // W)
    rps = -(-rps // pack) * pack
    m = W * int(traffic["lookups_per_worker"])
    return {"table": f"f32[{rps // pack},{pack * dim}]",
            "accumulator": f"f32[{rps}]",
            "batch_rows": f"f32[{m},{dim}]",
            "batch_ids": f"s32[{m}]", "batch_flags": f"pred[{m}]"}


def kind_and_shape(short_name: str) -> Optional[Tuple[str, str]]:
    """``%fusion.7 f32[20000000,128]`` -> ``("fusion", "f32[20000000,128]")``;
    None for a name of another form (a program's, not an operation's)."""
    m = _SHORT.match(short_name)
    return (m.group(1), m.group(2)) if m else None


def ms_a_step(ctx, pick: Callable[[str, str], bool]) -> Optional[float]:
    """Milliseconds a traced step in the operations ``pick(kind, shape)``
    takes; None where there is no trace of a device or none is found."""
    reduction = ctx.reduction
    if reduction is None or not reduction.steps:
        return None
    seconds, found = 0.0, False
    for name, s in reduction.op_seconds.items():
        parts = kind_and_shape(name)
        if parts is not None and pick(*parts):
            seconds += s
            found = True
    return seconds * 1e3 / reduction.steps if found else None


def combine_ms(ctx, shapes_of: Callable[[dict, dict], Dict[str, str]],
               batch: Tuple[str, ...]) -> Optional[float]:
    """What ``combine_ms`` and ``packed_combine_ms`` share: every sort, the
    segment sum's kernel, and every mover whose result is one of the
    workspaces ``batch`` of ``shapes_of(ctx.config, ctx.traffic)``."""
    if ctx.reduction is None:
        return None
    s = shapes_of(ctx.config, ctx.traffic)
    workspaces = tuple(s[name] for name in batch)
    return ms_a_step(ctx, lambda kind, shape: kind in ("sort", SEGMENT_SUM)
                     or (kind in MOVERS and shape in workspaces))
