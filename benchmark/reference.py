"""The plain references that decide ``correct``, and their controls.

numpy only; nothing here imports the program or takes anything the program
made.  The inputs are the benchmark's own (gradients and row ids drawn from
``--seed``), the arithmetic is float64.

Each reference takes a ``rounding`` argument: ``None`` is the reference,
``bf16`` is the control of "How ``correct`` is decided" — the same
recurrence with every stored value rounded to bfloat16, the precision a
later PR would be tempted by for an f32 store.  The control has to come out
as not correct; ``benchmark/readings.py`` reads both on the chip.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

Rounding = Optional[Callable[[np.ndarray], np.ndarray]]


def bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
            ) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def _keep(x: np.ndarray) -> np.ndarray:
    return x


class AdamReference:
    """Adam as the server handle documents it (``adam:lr,b1,b2,eps``,
    ``ops/fused_update.py::adam_update``; Kingma & Ba section 2's
    efficient form)::

        m = b1*m + (1-b1)*g;  v = b2*v + (1-b2)*g*g
        alpha_t = lr * sqrt(1 - b2**t) / (1 - b1**t)
        p = p - alpha_t * m / (sqrt(v) + eps)

    The textbook form with ``v_hat`` under the root differs from it by
    1e-3 relative where gradients are small, so the form is part of the
    configuration's guarantees.  State starts at zero, as a registered
    bucket's does.
    """

    def __init__(self, n: int, lr: float, b1: float, b2: float, eps: float,
                 rounding: Rounding = None):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps
        self.round = rounding or _keep
        self.p = np.zeros(n, np.float64)
        self.m = np.zeros(n, np.float64)
        self.v = np.zeros(n, np.float64)
        self.t = 0

    def step(self, grad_rows: np.ndarray) -> np.ndarray:
        """Apply one step of the workers' gradients ``[W, n]`` (summed
        over W) and return the parameters a pull then reads."""
        rd = self.round
        g = rd(np.asarray(grad_rows, np.float64).sum(axis=0))
        self.t += 1
        self.m = rd(self.b1 * self.m + (1.0 - self.b1) * g)
        self.v = rd(self.b2 * self.v + (1.0 - self.b2) * g * g)
        alpha = (self.lr * np.sqrt(1.0 - self.b2 ** self.t)
                 / (1.0 - self.b1 ** self.t))
        self.p = rd(self.p - alpha * self.m / (np.sqrt(self.v) + self.eps))
        return self.p


def parse_adam_handle(handle: str) -> Dict[str, float]:
    """``adam:lr,b1,b2,eps`` -> its four numbers (the handle's defaults
    where the string leaves one out)."""
    kind, _, rest = handle.partition(":")
    if kind != "adam":
        raise ValueError(f"the dense reference knows adam, not {handle!r}")
    vals = [1e-3, 0.9, 0.999, 1e-8]
    for i, tok in enumerate(t for t in rest.split(",") if t):
        vals[i] = float(tok)
    return dict(zip(("lr", "b1", "b2", "eps"), vals))


class RowSumReference:
    """The default (sum) server handle on sparse rows: every pushed row
    gradient, duplicates within and across workers included, is added
    exactly once.  Only the rows in ``watch`` are followed (a dictionary
    of rows, not the table)."""

    def __init__(self, watch: np.ndarray, dim: int, rounding: Rounding = None):
        self.rows = np.unique(np.asarray(watch).reshape(-1))
        self.round = rounding or _keep
        self.sums = np.zeros((len(self.rows), dim), np.float64)

    def _slots(self, idx: np.ndarray):
        flat = np.asarray(idx).reshape(-1)
        pos = np.searchsorted(self.rows, flat)
        pos = np.minimum(pos, len(self.rows) - 1)
        return pos, self.rows[pos] == flat

    def contribution(self, idx: np.ndarray, grads: np.ndarray) -> np.ndarray:
        """What one push of ``idx`` ``[W, n]`` with ``grads`` ``[W, n, d]``
        adds to each watched row (float64 sums of the f32 gradients)."""
        pos, hit = self._slots(idx)
        g = np.asarray(grads).reshape(len(pos), -1)[hit]
        pos = pos[hit]
        out = np.zeros_like(self.sums)
        # Column by column over the transposed gradients: a float64
        # bincount is a tight loop, where reduceat over rows is not.
        for j, column in enumerate(np.ascontiguousarray(g.T)):
            out[:, j] = np.bincount(pos, weights=column,
                                    minlength=len(self.rows))
        return out

    def push(self, contribution: np.ndarray, times: int = 1) -> None:
        """Add one push's contribution, ``times`` pushes in a row.  The
        bf16 control rounds after every push, as a bf16 table would."""
        if self.round is _keep:
            self.sums += times * contribution
            return
        for _ in range(times):
            self.sums = self.round(self.sums + contribution)

    def pull(self, idx: np.ndarray) -> np.ndarray:
        """Rows for ``idx`` ``[W, n]`` -> ``[W, n, d]``; every id must be
        watched."""
        pos, hit = self._slots(idx)
        if not hit.all():
            raise KeyError("pull of a row the reference does not watch")
        return self.sums[pos].reshape(*np.asarray(idx).shape, -1)


def row_scaled_error(got: np.ndarray, want: np.ndarray, floor: float
                     ) -> float:
    """For rows of sums: the worst ``|got - want|`` in a row over the
    largest ``|want|`` of that row (or ``floor``).  A sum of thousands of
    gradients of both signs is small in some of its 128 elements by
    cancellation; its rounding error is that of the row, not of the
    element, so the element-wise :func:`scaled_error` swings from seed to
    seed by a factor of ten on it (my chip runs, PR 23)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    d = want.shape[-1]
    diff = np.abs(got - want).reshape(-1, d).max(axis=1)
    scale = np.maximum(np.abs(want).reshape(-1, d).max(axis=1), floor)
    return float(np.max(diff / scale, initial=0.0))


def scaled_error(got: np.ndarray, want: np.ndarray, floor: float) -> float:
    """The number each comparison reports: the worst ``|got - want|`` over
    ``max(|want|, floor)``.  ``floor`` is the size below which a value is
    rounding noise for this quantity (one learning rate for Adam's
    parameters, one gradient's magnitude for row sums)."""
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if got.shape != want.shape:
        return float("inf")
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), floor),
                        initial=0.0))
