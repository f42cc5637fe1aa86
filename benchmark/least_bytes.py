"""The least bytes a step has to move: the numerator of ``roofline_share``.

Each function counts only what any correct implementation must move on one
device, so that a share of the roofline cannot pass 100%.  What today's
program moves beyond that (copies, padding, staging, a dense pass where a
sparse one would do) is exactly what the share is there to show.
"""

from __future__ import annotations

from typing import Dict


def dense_adam_step(params: int, workers: int, itemsize: int = 4
                    ) -> Dict[str, float]:
    """One bulk-synchronous push_pull of ``params`` parameters under Adam
    on ``workers`` devices, per device.

    HBM: read the device's own gradient row (``itemsize * N``), read and
    write p, m and v of its shard (``6 * itemsize * N / W``), write the
    gathered parameters it did not own (``itemsize * N * (W-1)/W``).
    ICI: a reduce-scatter then an all-gather each move ``N * (W-1)/W``
    elements in and out of every device.

    Left out: the copy of the device's own shard into the pulled array (a
    zero-copy pull avoids it), bucket padding, reading the step slot, any
    temporary."""
    n, w = float(params), float(workers)
    hbm = itemsize * n + 6 * itemsize * n / w + itemsize * n * (w - 1) / w
    ici = 2 * itemsize * n * (w - 1) / w
    return {"hbm": hbm, "ici": ici}


def sparse_pull_push_step(unique_rows: float, lookups: int, dim: int,
                          workers: int, itemsize: int = 4
                          ) -> Dict[str, float]:
    """One pull then one push of ``lookups`` row ids per worker into a
    row-sharded table of width ``dim``, per device.

    HBM: every distinct row touched is read for the pull and read and
    written for the push (``3 * unique_rows * dim * itemsize``, the
    device's ``1/W`` share of them); the ids are read twice; the
    gradients are read and the pulled rows written once
    (``2 * lookups * dim * itemsize``).
    ICI (W > 1): the rows a worker pulls from, and the gradients it pushes
    to, the other devices: ``2 * lookups * dim * itemsize * (W-1)/W``.

    Left out: duplicates beyond the first touch of a row (a segment sum
    combines them before the table is touched), index exchange, any
    temporary."""
    w = float(workers)
    row = dim * itemsize
    hbm = 3 * unique_rows * row / w + 2 * lookups * 4 + 2 * lookups * row
    ici = 2 * lookups * row * (w - 1) / w
    return {"hbm": hbm, "ici": ici}


def least_seconds(least: Dict[str, float], peaks: Dict[str, float]
                  ) -> Dict[str, object]:
    """The least time the chip could take and which peak bounds it."""
    hbm_s = least["hbm"] / (peaks["hbm_gb_s"] * 1e9)
    ici_s = least["ici"] / (peaks["ici_gbit_s"] / 8 * 1e9)
    if ici_s > hbm_s:
        return {"seconds": ici_s, "bound": "ici"}
    return {"seconds": hbm_s, "bound": "hbm"}
