"""The plain reference of the ``lamb`` server handle, and its control.

numpy only; nothing here imports the program or takes anything the program
made.  LAMB (You et al. 2020, "Large Batch Optimization for Deep Learning")
as MLPerf Training's BERT benchmark runs it, with Adam's bias correction
(the paper's and NVIDIA ``FusedLAMB``'s default form), one key a tensor::

    m = b1*m + (1-b1)*g;   v = b2*v + (1-b2)*g*g
    mh = m/(1-b1^t);       vh = v/(1-b2^t)
    u = mh/(sqrt(vh)+eps) + wd_k*p        (wd_k = wd, or 0 for NO_DECAY)
    r_k = |p|_2 / |u|_2  if not NO_ADAPT and |p| > 0 and |u| > 0,  else 1
    p = p - lr * r_k * u

Both norms are over every element of key k and nothing else.  The
arithmetic is float64.  ``rounding=`` gives the control, as
``reference.py`` does: every stored value (the summed gradient, m, v, p)
rounded to the named precision after each step.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

Rounding = Optional[Callable[[np.ndarray], np.ndarray]]

# A key's flag word; the values the program documents for a registration.
NO_DECAY = 1
NO_ADAPT = 2


def _keep(x: np.ndarray) -> np.ndarray:
    return x


class LambReference:
    """Whole keys, followed step by step.  ``init[k]`` is key k's stored
    value before the first push, ``flags[k]`` its flag word; the moments
    start at zero, as a registered bucket's do."""

    def __init__(self, init: Sequence[np.ndarray], flags: Sequence[int],
                 lr: float, b1: float, b2: float, eps: float, wd: float,
                 rounding: Rounding = None):
        self.lr, self.b1, self.b2, self.eps, self.wd = lr, b1, b2, eps, wd
        self.round = rounding or _keep
        self.flags = [int(f) for f in flags]
        self.p = [self.round(np.asarray(x, np.float64).reshape(-1))
                  for x in init]
        self.m = [np.zeros_like(x) for x in self.p]
        self.v = [np.zeros_like(x) for x in self.p]
        self.t = 0
        self.ratios: List[float] = [1.0] * len(self.p)

    def keep(self, indices: Sequence[int]) -> None:
        """Go on with these keys alone, in this order: a key's step reads
        no other key."""
        for name in ("p", "m", "v", "flags", "ratios"):
            have = getattr(self, name)
            setattr(self, name, [have[i] for i in indices])

    def step(self, grad_rows: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Apply one step; ``grad_rows[k]`` is key k's gradient ``[W, n_k]``
        (summed over W here) or its sum ``[n_k]``.  Returns the keys'
        parameters as a pull then reads them."""
        rd = self.round
        self.t += 1
        c1 = 1.0 - self.b1 ** self.t
        c2 = 1.0 - self.b2 ** self.t
        for k, rows in enumerate(grad_rows):
            g = np.asarray(rows, np.float64)
            g = rd(g.sum(axis=0) if g.ndim == 2 else g)
            p = self.p[k]
            m = self.m[k] = rd(self.b1 * self.m[k] + (1.0 - self.b1) * g)
            v = self.v[k] = rd(self.b2 * self.v[k] + (1.0 - self.b2) * g * g)
            wd = 0.0 if self.flags[k] & NO_DECAY else self.wd
            u = (m / c1) / (np.sqrt(v / c2) + self.eps) + wd * p
            p_norm = float(np.sqrt(np.sum(p * p)))
            u_norm = float(np.sqrt(np.sum(u * u)))
            adapt = (not self.flags[k] & NO_ADAPT
                     and p_norm > 0.0 and u_norm > 0.0)
            r = p_norm / u_norm if adapt else 1.0
            self.ratios[k] = r
            self.p[k] = rd(p - self.lr * r * u)
        return self.p


def parse_lamb_handle(handle: str) -> Dict[str, float]:
    """``lamb:lr,b1,b2,eps,wd`` -> its five numbers (the handle's defaults
    where the string leaves one out)."""
    kind, _, rest = handle.partition(":")
    if kind != "lamb":
        raise ValueError(f"this reference knows lamb, not {handle!r}")
    vals = [1e-3, 0.9, 0.999, 1e-6, 0.01]
    for i, tok in enumerate(t for t in rest.split(",") if t):
        vals[i] = float(tok)
    return dict(zip(("lr", "b1", "b2", "eps", "wd"), vals))
