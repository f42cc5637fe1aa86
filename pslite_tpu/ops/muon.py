"""Muon on a server shard: the first server handle that works on whole
matrices.

Muon (Jordan et al. 2024; as Moonshot AI's "Muon is Scalable for LLM
Training", arXiv 2502.16982, and ``MoonshotAI/Moonlight``
``examples/toy_train.py`` ``class Muon`` run it) updates a weight MATRIX by
its orthogonalised momentum::

    M  = mu*M + G
    Gn = G + mu*M                                   (Nesterov)
    O  = NS5(Gn)
    W  = W*(1 - lr*wd) - lr*0.2*sqrt(max(rows, cols)) * O

``NS5`` is five Newton-Schulz steps in bfloat16: ``X = bf16(Gn)``,
transposed if rows > cols, ``X /= (|X|_F + 1e-7)``, then five times
``A = X X^T; B = b*A + c*A A; X = a*X + B X``.  Keys that are no matrices
to it (embeddings, the output head, gains: ``KEY_ELEMENTWISE``) take AdamW.

What is fixed here and written into the benchmark's reference
(``benchmark/muon_reference.py``): M, W, the decay, the step and the scale
are f32; the operands of the fifteen products are bfloat16 (rounded to
nearest-even), the products accumulate in f32, and each of the recurrence's
three lines is rounded to bfloat16 once, after its epilogue (``A`` as it
leaves its product, ``B`` after ``b*A + c*(A A)``, ``X`` after
``a*X + (B X)``), where the published code rounds every intermediate; the
Frobenius norm is taken in f32 over the bf16 values.

Everything is plain XLA: a batched ``dot_general`` a product, one batch a
:class:`MuonChunk` (keys of one ``(shorter, longer)`` side, tall ones
transposed into it, at most ``MUON_CHUNK_VALUES`` values together, so that
the bf16 temporaries of a chunk, X twice, A and B, stay a few hundred MB
whatever the tree).  The momentum is kept AS THE CHUNKS ARE, one f32 array
``[B, m, n]`` a chunk, so no pass lays it out anew; ``opt_state`` hands it
out, and takes it back, as one vector in the keys' order
(:func:`momentum_vector`, :func:`momentum_chunks`).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import numpy as np

NS_COEFFS = (3.4445, -4.7750, 2.0315)
NS_STEPS = 5
NS_EPS = 1e-7
# 0.2 * sqrt(max(rows, cols)): the report's match of Muon's update RMS to
# AdamW's.
RMS_MATCH = 0.2
# The most values one batched product takes (a layer's 24 expert matrices
# of 1408 x 2048, or three of 2048 x 11264).
MUON_CHUNK_VALUES = 66 * 2 ** 20


class MuonChunk(NamedTuple):
    m: int                      # the shorter side
    n: int                      # the longer side
    keys: Tuple[int, ...]       # the bucket's key indices, in key order
    tall: Tuple[bool, ...]      # rows > cols: transposed into the chunk


class MuonPlan(NamedTuple):
    """How a bucket's keys go through ``muon``: made once a bucket
    (:meth:`CollectiveEngine._muon_plan`)."""

    chunks: Tuple[MuonChunk, ...]
    muon_keys: np.ndarray       # key indices under Muon, in key order
    adamw_keys: np.ndarray      # key indices under AdamW, in key order
    mom_starts: np.ndarray      # where a Muon key lies in the momentum vector
    adamw_starts: np.ndarray    # where an AdamW key lies in m and v
    ns_flops: float             # Newton-Schulz FLOPs a step, as published

    @property
    def matrices(self) -> int:
        return len(self.muon_keys)

    @property
    def muon_len(self) -> int:
        return int(self.mom_starts[-1])

    @property
    def adamw_len(self) -> int:
        return int(self.adamw_starts[-1])

    @property
    def state_bytes(self) -> int:
        """4 B a Muon value + 8 B an AdamW value (the step slot apart)."""
        return 4 * self.muon_len + 8 * self.adamw_len


def ns_flops(rows: int, cols: int) -> float:
    """Five Newton-Schulz steps on one matrix, as published: ``X X^T``
    (2 m^2 n), ``A A`` (2 m^3), ``B X`` (2 m^2 n) a step."""
    m, n = min(rows, cols), max(rows, cols)
    return float(NS_STEPS * (4 * m * m * n + 2 * m ** 3))


def muon_plan(shapes, elementwise, chunk_values: int = MUON_CHUNK_VALUES
              ) -> MuonPlan:
    """``shapes`` ``[K, 2]`` (rows, cols a key), ``elementwise`` ``[K]``
    (the key takes AdamW).  Keys of one ``(shorter, longer)`` side share a
    group whatever their orientation; a group is cut, in key order, into
    chunks of at most ``chunk_values`` values (one key at least)."""
    shapes = np.asarray(shapes, np.int64).reshape(-1, 2)
    elementwise = np.asarray(elementwise, bool)
    lens = shapes[:, 0] * shapes[:, 1]
    muon = np.flatnonzero(~elementwise)
    adamw = np.flatnonzero(elementwise)
    groups: dict = {}
    for k in muon:
        r, c = (int(d) for d in shapes[k])
        groups.setdefault((min(r, c), max(r, c)), []).append(int(k))
    chunks = []
    for (m, n), keys in groups.items():
        per = max(1, chunk_values // (m * n))
        for i in range(0, len(keys), per):
            part = tuple(keys[i:i + per])
            chunks.append(MuonChunk(
                m, n, part,
                tuple(bool(shapes[k, 0] > shapes[k, 1]) for k in part)))
    return MuonPlan(
        chunks=tuple(chunks), muon_keys=muon, adamw_keys=adamw,
        mom_starts=np.concatenate([[0], np.cumsum(lens[muon])]),
        adamw_starts=np.concatenate([[0], np.cumsum(lens[adamw])]),
        ns_flops=float(sum(ns_flops(*shapes[k]) for k in muon)))


def state_shapes(plan: MuonPlan) -> Tuple[Tuple[int, ...], ...]:
    """The state's arrays but for the step slot: a momentum a chunk, then
    AdamW's m and v."""
    return (*((len(c.keys), c.m, c.n) for c in plan.chunks),
            (plan.adamw_len,), (plan.adamw_len,))


def momentum_vector(plan: MuonPlan, chunks, xp):
    """The chunks' momenta as one vector in the keys' order, a key's
    values row-major as its matrix lies in the store (``xp``: numpy or
    ``jax.numpy``)."""
    parts = {}
    for chunk, arr in zip(plan.chunks, chunks):
        for i, (k, tall) in enumerate(zip(chunk.keys, chunk.tall)):
            parts[k] = (arr[i].T if tall else arr[i]).reshape(-1)
    if not parts:
        return xp.zeros((0,), np.float32)
    return xp.concatenate([parts[int(k)] for k in plan.muon_keys])


def momentum_chunks(plan: MuonPlan, vector, xp):
    """The inverse of :func:`momentum_vector`."""
    where = {int(k): i for i, k in enumerate(plan.muon_keys)}
    out = []
    for chunk in plan.chunks:
        rows = []
        for k, tall in zip(chunk.keys, chunk.tall):
            lo = int(plan.mom_starts[where[k]])
            shape = (chunk.n, chunk.m) if tall else (chunk.m, chunk.n)
            mat = vector[lo:lo + chunk.m * chunk.n].reshape(shape)
            rows.append(mat.T if tall else mat)
        out.append(xp.stack(rows))
    return out


def newton_schulz(x, steps: int = NS_STEPS):
    """``NS5`` of a batch ``[B, m, n]`` of bfloat16 matrices, m <= n."""
    import jax.numpy as jnp

    a, b, c = NS_COEFFS
    f32, bf16 = jnp.float32, jnp.bfloat16
    x32 = x.astype(f32)
    norm = jnp.sqrt(jnp.sum(x32 * x32, axis=(1, 2), keepdims=True))
    x = (x32 / (norm + NS_EPS)).astype(bf16)
    for _ in range(steps):
        xx = jnp.einsum("bik,bjk->bij", x, x,
                        preferred_element_type=f32).astype(bf16)
        poly = (b * xx.astype(f32)
                + c * jnp.einsum("bik,bkj->bij", xx, xx,
                                 preferred_element_type=f32)).astype(bf16)
        x = (a * x.astype(f32)
             + jnp.einsum("bij,bjk->bik", poly, x,
                          preferred_element_type=f32)).astype(bf16)
    return x


def muon_update(store, state, agg, starts, shapes, plan: MuonPlan, *,
                lr: float, mu: float, wd: float, b1: float, b2: float,
                eps: float):
    """One step on the one shard that holds the bucket.  ``store`` is the
    flat f32 store, ``state`` as :func:`state_shapes` lays it out with the
    step slot last, ``agg`` the summed gradient as a row ``[1, total]``.
    Returns the new store and state.

    Every key's values are read from and written to the store where they
    lie (a chain of ``dynamic_update_slice`` in place, a chunk at a time:
    the barrier between two chunks keeps the next one's temporaries from
    being made before this one's are let go)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32, bf16 = jnp.float32, jnp.bfloat16
    n_chunks = len(plan.chunks)
    moms, (adam_m, adam_v, step_l) = state[:n_chunks], state[n_chunks:]
    keep = 1.0 - lr * wd

    def put(store, k: int, new_p):
        return lax.dynamic_update_slice(store, new_p, (int(starts[k]),))

    def key_values(vector, k: int):
        return lax.slice(vector, (int(starts[k]),), (int(starts[k + 1]),))

    def key_grad(row, k: int, shape):
        # Cut from the row as it lies: squeezing the row first is a pass
        # over the whole gradient.
        return lax.slice(row, (0, int(starts[k])),
                         (1, int(starts[k + 1]))).reshape(shape)

    new_moms = []
    for chunk, mom in zip(plan.chunks, moms):
        with jax.named_scope("ps.update.muon.momentum"):
            grads = []
            for k, tall in zip(chunk.keys, chunk.tall):
                g = key_grad(agg, k, tuple(int(d) for d in shapes[k]))
                grads.append(g.T if tall else g)
            g = jnp.stack(grads)
            mom = mu * mom + g
            x = (g + mu * mom).astype(bf16)
        with jax.named_scope("ps.update.muon.ns"):
            o = newton_schulz(x)
        with jax.named_scope("ps.update.muon.apply"):
            scale = lr * RMS_MATCH * math.sqrt(chunk.n)
            for i, (k, tall) in enumerate(zip(chunk.keys, chunk.tall)):
                o_k = (o[i].T if tall else o[i]).reshape(-1).astype(f32)
                new_p = key_values(store, k) * keep - scale * o_k
                store = put(store, k, new_p)
        new_moms.append(mom)
        store, agg = lax.optimization_barrier((store, agg))

    with jax.named_scope("ps.update.muon.adamw"):
        t = step_l[0] + 1.0
        c1 = -jnp.expm1(t * math.log(b1))   # 1 - b1^t, as fused_update's
        c2 = -jnp.expm1(t * math.log(b2))
        alpha = (lr * jnp.sqrt(c2) / c1).astype(f32)
        for j, k in enumerate(plan.adamw_keys):
            k = int(k)
            lo = int(plan.adamw_starts[j])
            hi = int(plan.adamw_starts[j + 1])
            g = key_grad(agg, k, (hi - lo,))
            m_k = b1 * lax.slice(adam_m, (lo,), (hi,)) + (1.0 - b1) * g
            v_k = b2 * lax.slice(adam_v, (lo,), (hi,)) + (1.0 - b2) * g * g
            new_p = (key_values(store, k) * keep
                     - alpha * m_k / (jnp.sqrt(v_k) + eps))
            adam_m = lax.dynamic_update_slice(adam_m, m_k, (lo,))
            adam_v = lax.dynamic_update_slice(adam_v, v_k, (lo,))
            store = put(store, k, new_p)

    return store, (*new_moms, adam_m, adam_v, step_l + 1.0)
