"""Row-wise Adagrad on sparse rows, plainly: the reference that the
rehearsal's file-only driver (``drivers/row_adagrad_pull_push.py``) brings
beside it.  numpy only; it builds on the benchmark's ``RowSumReference`` for
what one push sums into a row, and imports nothing of the program."""

from typing import Tuple

import numpy as np

from reference import Rounding, RowSumReference


def parse_row_adagrad_handle(handle: str) -> Tuple[float, float]:
    """``row_adagrad:lr,eps`` -> (lr, eps), the handle's defaults where the
    string leaves one out."""
    kind, _, rest = handle.partition(":")
    if kind != "row_adagrad":
        raise ValueError(f"this reference knows row_adagrad, not {handle!r}")
    vals = [0.01, 1e-8]
    for i, tok in enumerate(t for t in rest.split(",") if t):
        vals[i] = float(tok)
    return vals[0], vals[1]


class RowAdagradReference(RowSumReference):
    """The handle as ``SparseEngine.push`` documents it: G is the sum of
    every gradient one push brings to a row (duplicates within and across
    workers), ``acc += mean(G**2)`` over the row's elements, and the row
    steps by ``-lr * G / (sqrt(acc) + eps)``.  Rows and accumulators start
    at zero.  ``sums`` holds the rows, so ``pull`` is the parent's; a push
    that brings a watched row nothing leaves it as it was."""

    def __init__(self, watch: np.ndarray, dim: int, lr: float, eps: float,
                 rounding: Rounding = None):
        super().__init__(watch, dim, rounding)
        self.lr, self.eps = lr, eps
        self.acc = np.zeros(len(self.rows), np.float64)

    def push(self, contribution: np.ndarray, times: int = 1) -> None:
        """Apply one push's ``contribution`` (``RowSumReference
        .contribution``), ``times`` pushes in a row: unlike the sum, the
        order of pushes matters."""
        rd = self.round
        for _ in range(times):
            self.acc = rd(self.acc + np.mean(contribution ** 2, axis=1))
            self.sums = rd(self.sums - self.lr * contribution
                           / (np.sqrt(self.acc)[:, None] + self.eps))
