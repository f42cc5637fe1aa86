"""Bounded, scrambled Zipfian row ids (YCSB's ScrambledZipfian).

Rank ``i`` of ``n`` is drawn with probability ``i**-theta / H(n)``, where
``H(n) = sum_{j<=n} j**-theta``, by inverting the CDF: the first
``HEAD`` ranks from an exact table, the tail from the midpoint-rule
closed form of the same sum (its error at ``HEAD`` = 65,536 is under
1e-10 of ``H``).  A table over all 20 M ranks of the Criteo cell would
cost 9 s of every run's set-up.

``models/embedding.skewed_indices`` in the program is not copied:
``rng.zipf(1.2) % rows`` is unbounded Zipf folded over the table, which
puts 18% of all draws on row 0 whatever the table size.
"""

from __future__ import annotations

import numpy as np

HEAD = 1 << 16
# Knuth's multiplicative hash constant (a prime): rank -> row is a
# bijection whenever the row count is coprime to it.
SCRAMBLE = 2654435761
# Rank 0 times anything: the hottest row is row 0 of every table.
HOTTEST_ROW = 0


class BoundedZipf:
    def __init__(self, n: int, theta: float):
        if not (n >= 1 and 0.0 < theta < 1.0):
            raise ValueError(f"need n >= 1 and 0 < theta < 1, got {n}, {theta}")
        self.n = int(n)
        self.theta = float(theta)
        head = min(self.n, HEAD)
        self._head_cdf = np.cumsum(
            np.arange(1, head + 1, dtype=np.float64) ** -self.theta
        )
        self._head = head
        self.harmonic = self._partial(np.float64(self.n))

    def _partial(self, i):
        """``H(i)`` for ``i >= HEAD`` (midpoint rule past the table)."""
        a = 1.0 - self.theta
        return self._head_cdf[-1] + (
            (i + 0.5) ** a - (self._head + 0.5) ** a
        ) / a

    def head_share(self) -> float:
        """Probability of the hottest rank, ``1 / H(n)``."""
        return float(1.0 / self.harmonic)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """0-based ranks for uniform draws ``u`` in [0, 1)."""
        target = np.asarray(u, np.float64) * self.harmonic
        out = np.searchsorted(self._head_cdf, target, side="right")
        tail = out >= self._head
        if tail.any():
            a = 1.0 - self.theta
            base = (self._head + 0.5) ** a
            # Smallest i with H(i) > target, 0-based: ceil(x - 0.5) - 1.
            x = ((target[tail] - self._head_cdf[-1]) * a + base) ** (1.0 / a)
            out[tail] = np.ceil(x - 0.5).astype(np.int64) - 1
        return np.clip(out, 0, self.n - 1)

    def rows(self, u: np.ndarray) -> np.ndarray:
        """Scrambled row ids: hot rows are not neighbours."""
        return (self.ranks(u) * SCRAMBLE) % self.n


def zipf_rows(seed: int, shape, n: int, theta: float) -> np.ndarray:
    """``shape`` int32 row ids in ``[0, n)`` drawn from ``seed``."""
    rng = np.random.default_rng(int(seed))
    return BoundedZipf(n, theta).rows(rng.random(shape)).astype(np.int32)
