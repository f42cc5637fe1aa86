"""The result shapes by which ``layer_metrics/packed_write_ms.py`` and
``layer_metrics/packed_combine_ms.py`` tell apart the device operations of a
step on a lane-packed table, worked out from the sizes of the one cell that
reports them (``dlrm-terabyte-emb64.zipf``: its configuration and traffic
files) as ``sparse_handle_ops.shapes`` works them out, and one more: the
batch's rows once each is placed in its slot's lanes of a physical row,
``f32[m, pack*dim]`` (``parallel/sparse.py`` ``_place_rows``; the packed
pull gathers physical rows of the same shape)."""

from __future__ import annotations

import json
import os
from typing import Dict

from sparse_handle_ops import shapes

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "configs", "dlrm-terabyte-emb64.json")
TRAFFIC = os.path.join(HERE, "traffic", "zipf-rows-2048x26.json")


def cell_shapes() -> Dict[str, str]:
    with open(CONFIG) as fh:
        config = json.load(fh)
    with open(TRAFFIC) as fh:
        traffic = json.load(fh)
    s = shapes(config, traffic)
    m = int(config["chips"]) * int(traffic["lookups_per_worker"])
    lanes = s["table"][:-1].rsplit(",", 1)[1]      # pack * dim, as the table's
    return dict(s, batch_phys_rows=f"f32[{m},{lanes}]")
