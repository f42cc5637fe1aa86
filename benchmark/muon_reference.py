"""The plain reference of the ``muon`` server handle, and its controls.

numpy only; nothing here imports the program or takes anything the program
made.  Muon as Moonshot AI's "Muon is Scalable for LLM Training" (arXiv
2502.16982) and the optimizer the same repository ships
(``MoonshotAI/Moonlight`` ``examples/toy_train.py``, ``class Muon``) run
it, one key a tensor, one matrix at a time with no batching.  For a key
that is a matrix, ``rows x cols``::

    M  = mu*M + G                       buf.mul_(momentum).add_(g)
    Gn = G + mu*M                       g = g.add(buf, alpha=momentum)   (nesterov)
    O  = NS5(Gn)                        zeropower_via_newtonschulz5(g, steps=5)
    W  = W*(1 - lr*wd)                  p.data.mul_(1 - lr * wd)
    W  = W - lr*0.2*sqrt(max(rows, cols)) * O
                                        adjust_lr_for_muon; p.data.add_(u, alpha=-adjusted_lr)

and ``NS5``, line by line::

    X = G.bfloat16()                    X = bf16(Gn)
    if rows > cols: X = X.T
    X = X / (X.norm() + 1e-7)           the norm in f32 over the bf16 values
    5 x:  A = X @ X.T
          B = b*A + c*A @ A             (a, b, c) = (3.4445, -4.7750, 2.0315)
          X = a*X + B @ X
    if rows > cols: X = X.T

For every other key (an embedding, the output head, a gain) AdamW as that
class has it::

    m = b1*m + (1-b1)*g;  v = b2*v + (1-b2)*g*g
    p = p*(1 - lr*wd) - lr * (sqrt(1-b2^t)/(1-b1^t)) * m/(sqrt(v)+eps)

Departures from the published code, each the configuration's to state:

- the 16-bit type is bfloat16, the published one;
- the operands of the fifteen products are bfloat16 and the products
  accumulate wider, as the published ``@`` does on bf16 tensors; here each
  of the recurrence's lines is rounded to bfloat16 ONCE, after its
  epilogue (``A`` as it leaves its product, ``B`` after ``b*A + c*(A A)``,
  ``X`` after ``a*X + (B X)``), where the published code rounds ``b*A``,
  ``A @ A``, ``c*(A A)`` and their sum each: fewer roundings, never more;
- the Frobenius norm's precision is assumed (f32 over the bf16 values,
  the quotient rounded to bfloat16 once); torch's ``norm`` of a bf16 tensor
  accumulates in f32 and rounds the result to bf16;
- AdamW as the class writes it (``g = buf1 / (eps + buf2.sqrt())``, the
  step ``lr / (bias_correction1 / bias_correction2**0.5)``), from memory:
  not confirmable offline.

Outside the products the arithmetic is float64; a product takes its
operands rounded by ``reference.bf16`` and is a float64 ``@`` of them.
Two controls, both of which have to come out as not correct:
``rounding=`` (every stored value, p, M, m, v, rounded to the named
precision after each step) and ``ns_steps=4`` (one Newton-Schulz step
left out).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from reference import bf16

Rounding = Optional[Callable[[np.ndarray], np.ndarray]]

NS_COEFFS = (3.4445, -4.7750, 2.0315)
NS_STEPS = 5
NS_EPS = 1e-7
RMS_MATCH = 0.2


def _keep(x: np.ndarray) -> np.ndarray:
    return x


def newton_schulz(g: np.ndarray, steps: int = NS_STEPS) -> np.ndarray:
    """``zeropower_via_newtonschulz5`` of one matrix ``[rows, cols]``
    (float64 in, the bfloat16 result as float64 out)."""
    a, b, c = NS_COEFFS
    x = bf16(g)
    tall = g.shape[0] > g.shape[1]
    if tall:
        x = x.T
    norm = float(np.float32(np.sqrt(np.sum(x * x))))
    x = bf16(x / (norm + NS_EPS))
    for _ in range(steps):
        xx = bf16(x @ x.T)
        poly = bf16(b * xx + c * (xx @ xx))
        x = bf16(a * x + poly @ x)
    return x.T if tall else x


class MuonReference:
    """Whole keys, followed step by step.  ``init[k]`` is key k's stored
    value before the first push, ``shapes[k]`` its ``(rows, cols)``,
    ``elementwise[k]`` whether it takes AdamW; momentum and moments start
    at zero, as a registered bucket's do."""

    def __init__(self, init: Sequence[np.ndarray], shapes: Sequence,
                 elementwise: Sequence[bool], lr: float, mu: float,
                 wd: float, b1: float, b2: float, eps: float,
                 rounding: Rounding = None, ns_steps: int = NS_STEPS):
        self.lr, self.mu, self.wd = lr, mu, wd
        self.b1, self.b2, self.eps = b1, b2, eps
        self.round = rounding or _keep
        self.ns_steps = ns_steps
        self.shapes = [tuple(int(d) for d in s) for s in shapes]
        self.elementwise = [bool(e) for e in elementwise]
        self.p = [self.round(np.asarray(x, np.float64).reshape(-1))
                  for x in init]
        # M of a Muon key, m of an AdamW key; v of an AdamW key alone.
        self.m = [np.zeros_like(x) for x in self.p]
        self.v = [np.zeros_like(x) if e else None
                  for x, e in zip(self.p, self.elementwise)]
        self.t = 0

    def keep(self, indices: Sequence[int]) -> None:
        """Go on with these keys alone, in this order: a key's step reads
        no other key."""
        for name in ("p", "m", "v", "shapes", "elementwise"):
            have = getattr(self, name)
            setattr(self, name, [have[i] for i in indices])

    def step(self, grad_rows: Sequence[np.ndarray]) -> List[np.ndarray]:
        """Apply one step; ``grad_rows[k]`` is key k's gradient ``[W, n_k]``
        (summed over W here) or its sum ``[n_k]``.  Returns the keys'
        parameters as a pull then reads them."""
        rd = self.round
        self.t += 1
        alpha = (self.lr * np.sqrt(1.0 - self.b2 ** self.t)
                 / (1.0 - self.b1 ** self.t))
        keep = 1.0 - self.lr * self.wd
        for k, rows in enumerate(grad_rows):
            g = np.asarray(rows, np.float64)
            g = g.sum(axis=0) if g.ndim == 2 else g
            p = self.p[k]
            if self.elementwise[k]:
                m = self.m[k] = rd(self.b1 * self.m[k] + (1.0 - self.b1) * g)
                v = self.v[k] = rd(self.b2 * self.v[k]
                                   + (1.0 - self.b2) * g * g)
                self.p[k] = rd(p * keep - alpha * m / (np.sqrt(v) + self.eps))
                continue
            shape = self.shapes[k]
            mom = self.m[k] = rd(self.mu * self.m[k] + g)
            o = newton_schulz((g + self.mu * mom).reshape(shape),
                              self.ns_steps)
            scale = self.lr * RMS_MATCH * np.sqrt(max(shape))
            self.p[k] = rd(p * keep - scale * o.reshape(-1))
        return self.p


def parse_muon_handle(handle: str) -> Dict[str, float]:
    """``muon:lr,mu,wd,b1,b2,eps`` -> its six numbers (the handle's
    defaults where the string leaves one out)."""
    kind, _, rest = handle.partition(":")
    if kind != "muon":
        raise ValueError(f"this reference knows muon, not {handle!r}")
    vals = [1e-3, 0.95, 0.1, 0.9, 0.95, 1e-8]
    for i, tok in enumerate(t for t in rest.split(",") if t):
        vals[i] = float(tok)
    return dict(zip(("lr", "mu", "wd", "b1", "b2", "eps"), vals))
