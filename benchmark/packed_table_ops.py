"""The result shapes by which ``layer_metrics/packed_write_ms.py`` and
``layer_metrics/packed_combine_ms.py`` tell apart the device operations of a
step on a lane-packed table, worked out from the sizes of the cell that is
read as ``sparse_handle_ops.shapes`` works them out, and one more: the
batch's rows once each is placed in its slot's lanes of a physical row,
``f32[m, pack*dim]`` (``parallel/sparse.py`` ``_place_rows``; the packed
pull gathers physical rows of the same shape)."""

from __future__ import annotations

from typing import Dict

import sparse_handle_ops


def shapes(config: dict, traffic: dict) -> Dict[str, str]:
    s = sparse_handle_ops.shapes(config, traffic)
    m = int(config["chips"]) * int(traffic["lookups_per_worker"])
    lanes = s["table"][:-1].rsplit(",", 1)[1]      # pack * dim, as the table's
    return dict(s, batch_phys_rows=f"f32[{m},{lanes}]")
