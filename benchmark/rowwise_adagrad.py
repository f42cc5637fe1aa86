"""Row-wise Adagrad on sparse rows, plainly, and the least bytes of a step
under it: what the driver ``drivers/sparse_handle_pull_push.py`` brings
beside it.  numpy only, float64; it builds on the benchmark's
``RowSumReference`` for what one push sums into a row and imports nothing of
the program.

The recurrence is facebookresearch/dlrm's ``optim/rwsadagrad.py`` (FBGEMM's
``EXACT_ROWWISE_ADAGRAD``), as the configuration's guarantees state it: per
push and touched row, G = the sum of every gradient the push brings to the
row (duplicates within and across workers), ``acc += mean(G**2)`` over the
row's elements, ``row -= lr * G / (sqrt(acc) + eps)``.  Rows and accumulators
start at zero, as a registered table's do.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from least_bytes import sparse_pull_push_step
from reference import Rounding, RowSumReference

KIND = "row_adagrad"
DEFAULTS = (0.01, 1e-8)     # lr, eps where the handle's string leaves one out


def parse_handle(handle: str) -> Tuple[float, float]:
    """``row_adagrad:lr,eps`` -> (lr, eps)."""
    kind, _, rest = handle.partition(":")
    if kind != KIND:
        raise ValueError(f"this reference knows {KIND}, not {handle!r}")
    vals = list(DEFAULTS)
    for i, tok in enumerate(t for t in rest.split(",") if t):
        vals[i] = float(tok)
    return vals[0], vals[1]


class RowwiseAdagradReference(RowSumReference):
    """Follows the watched rows (a dictionary of rows, not the table)
    through every push, in the order issued: unlike the sum, the order
    matters.  ``sums`` holds the rows, so ``pull`` is the parent's.  A push
    that brings a watched row nothing leaves the row and its accumulator as
    they were: its contribution is zero, and so are ``mean(0**2)`` and
    ``lr * 0 / (sqrt(acc) + eps)``."""

    def __init__(self, watch: np.ndarray, dim: int, lr: float, eps: float,
                 rounding: Rounding = None):
        super().__init__(watch, dim, rounding)
        self.lr, self.eps = float(lr), float(eps)
        self.acc = np.zeros(len(self.rows), np.float64)

    def push(self, contribution: np.ndarray, times: int = 1) -> None:
        """Apply one push's ``contribution`` (``RowSumReference
        .contribution``: G of every watched row), ``times`` pushes in a
        row.  The bf16 control rounds what it stores after every push."""
        rd = self.round
        mean_sq = np.mean(contribution ** 2, axis=1)
        for _ in range(times):
            self.acc = rd(self.acc + mean_sq)
            self.sums = rd(self.sums - self.lr * contribution
                           / (np.sqrt(self.acc)[:, None] + self.eps))


def pull_push_step_least_bytes(unique_rows: float, lookups: int, dim: int,
                               workers: int, itemsize: int = 4
                               ) -> Dict[str, float]:
    """The least one pull then one push under the handle must move on one
    device: the ``sum`` step's bytes (``least_bytes.sparse_pull_push_step``:
    every distinct row read for the pull, read and written for the push;
    ids, gradients and pulled rows once) plus one read and one write of
    the 4-byte accumulator of every distinct row (the device's ``1/W`` of
    them).  Left out, as there: duplicates beyond a row's first touch,
    the sort's and the segment sum's workspaces, any temporary."""
    least = dict(sparse_pull_push_step(unique_rows, lookups, dim, workers,
                                       itemsize))
    least["hbm"] += 2 * 4 * unique_rows / float(workers)
    return least
