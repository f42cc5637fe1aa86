"""Pooled lookups (an ``EmbeddingBag`` with ``mode="sum"``) on sparse rows,
plainly: what the driver ``drivers/sparse_bags_pull_push.py`` brings beside
it.  numpy only, float64; it builds on the benchmark's ``RowSumReference``
and ``RowwiseAdagradReference`` for what a push does to a watched row and
imports nothing of the program.

A table has ``R`` rows of ``d`` values and a bag size ``h``.  A worker's ids
are ``I[w, b, j]``, ``B`` bags of ``h`` slots.

- Pooled pull: ``P[w, b] = sum_j T[I[w, b, j]]``.  A row that lies twice in
  a bag is added twice.
- Pooled push of bag gradients ``g[w, b]``: every slot ``(w, b, j)`` brings
  ``g[w, b]`` to its row ``I[w, b, j]``; a row's ``G`` is the sum over every
  slot that names it, within a bag, across bags and across workers, each slot
  exactly once.  With no handle ``T[r] += G[r]``; under ``row_adagrad:lr,eps``
  the sibling's recurrence (``rowwise_adagrad.py``) on ``G``.

Both are written the straightforward way: the bag multiplied out slot by slot
(a slot's gradient is its bag's, by definition), then exactly what the one-row
references do with one id a slot.  ``multiplied_out`` is that form of a whole
batch and ``contribution_by_loop`` the same ``G`` by a plain loop over the
slots, for the tests that hold the vectorised form to them.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from reference import Rounding, RowSumReference
from rowwise_adagrad import RowwiseAdagradReference, parse_handle


def multiplied_out(ids: np.ndarray, grads: np.ndarray):
    """Bags ``[W, B, h]`` and their gradients ``[W, B, d]`` as the slots they
    stand for: ids ``[W, B * h]`` and, slot for slot, ``[W, B * h, d]``."""
    ids, grads = np.asarray(ids), np.asarray(grads)
    W, B, h = ids.shape
    if grads.shape[:2] != (W, B):
        raise ValueError(f"gradients {grads.shape} for bags {ids.shape}: "
                         f"one row a bag")
    return ids.reshape(W, B * h), np.repeat(grads, h, axis=1)


def contribution_by_loop(watch: np.ndarray, ids: np.ndarray,
                         grads: np.ndarray) -> np.ndarray:
    """``G`` of every row of the sorted ``watch`` by a plain loop over the
    slots: slot ``(w, b, j)`` adds ``grads[w, b]`` to row ``ids[w, b, j]``."""
    watch = np.asarray(watch)
    ids, grads = np.asarray(ids), np.asarray(grads, np.float64)
    place = {int(r): k for k, r in enumerate(watch)}
    out = np.zeros((len(watch), grads.shape[-1]), np.float64)
    W, B, h = ids.shape
    for w in range(W):
        for b in range(B):
            for j in range(h):
                k = place.get(int(ids[w, b, j]))
                if k is not None:
                    out[k] += grads[w, b]
    return out


class Touched(NamedTuple):
    """What one pooled push brings to the watched rows: ``G`` of the rows it
    touches alone (``at``: their places among the sorted watched rows).  A
    step of the full-size cell touches a few of a hundred watched rows of a
    4,000,000-row table; a push that brings a watched row nothing leaves the
    row and its accumulator as they were (``G = 0``: ``acc += 0``, ``row -=
    0``), so following the touched rows alone is the same recurrence."""

    at: np.ndarray      # s64[k], ascending
    G: np.ndarray       # f64[k, d]
    rows: int           # the watched rows in all

    def dense(self) -> np.ndarray:
        """``G`` of every watched row, zeros where the push brings none."""
        out = np.zeros((self.rows, self.G.shape[1]), np.float64)
        out[self.at] = self.G
        return out


class _Bags:
    """What the two references below share: a push's contribution through the
    bag, and the pooled pull."""

    def contribution(self, ids: np.ndarray, grads: np.ndarray) -> Touched:
        """What one pooled push of bags ``ids`` ``[W, B, h]`` with bag
        gradients ``grads`` ``[W, B, d]`` brings to the watched rows: slot
        ``(w, b, j)`` brings ``grads[w, b]`` to row ``ids[w, b, j]``.  Only
        the slots that name a watched row are multiplied out (a step of the
        full-size cell holds 409,600 slots of one table, 210 MB of rows, of
        which the watched are a few thousand)."""
        ids, grads = np.asarray(ids), np.asarray(grads)
        W, B, h = ids.shape
        if grads.shape[:2] != (W, B):
            raise ValueError(f"gradients {grads.shape} for bags {ids.shape}: "
                             f"one row a bag")
        flat = ids.reshape(-1)
        slot = np.flatnonzero(self._slots(flat)[1])
        # Slot (w, b, j) lies at w * B * h + b * h + j, its bag at w * B + b.
        G = super().contribution(
            flat[slot][None], grads.reshape(W * B, -1)[slot // h][None])
        at = np.unique(self._slots(flat[slot])[0])
        return Touched(at, G[at], len(self.rows))

    def pull_pooled(self, ids: np.ndarray) -> np.ndarray:
        """Pooled rows for bags ``ids`` ``[W, B, h]`` -> ``[W, B, d]``; every
        id must be watched."""
        return self.pull(ids).sum(axis=-2)


class BagSumReference(_Bags, RowSumReference):
    """The default (sum) server handle under pooled pushes."""

    def push(self, c: Touched, times: int = 1) -> None:
        """``RowSumReference.push`` on the rows the push touches."""
        for _ in range(times):
            self.sums[c.at] = self.round(self.sums[c.at] + c.G)


class BagAdagradReference(_Bags, RowwiseAdagradReference):
    """``row_adagrad:lr,eps`` under pooled pushes: the order of pushes
    matters, so ``push`` follows every one."""

    def push(self, c: Touched, times: int = 1) -> None:
        """``RowwiseAdagradReference.push``'s recurrence on the rows the push
        touches: ``acc += mean(G ** 2)``, ``row -= lr * G / (sqrt(acc) +
        eps)``, what is stored rounded after every push under a control."""
        rd = self.round
        mean_sq = np.mean(c.G ** 2, axis=1)
        for _ in range(times):
            acc = rd(self.acc[c.at] + mean_sq)
            self.acc[c.at] = acc
            self.sums[c.at] = rd(self.sums[c.at] - self.lr * c.G
                                 / (np.sqrt(acc)[:, None] + self.eps))


def bag_reference(watch: np.ndarray, dim: int, handle: Optional[str],
                  rounding: Rounding = None):
    """The reference of a table under ``handle`` (``None`` or ``"sum"``: the
    plain sum; ``row_adagrad:lr,eps``), following the rows ``watch``."""
    if handle in (None, "sum"):
        return BagSumReference(watch, dim, rounding)
    lr, eps = parse_handle(handle)
    return BagAdagradReference(watch, dim, lr, eps, rounding)
