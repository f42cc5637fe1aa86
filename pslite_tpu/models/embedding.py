"""Sparse embedding workload (BASELINE config 5): 1M keys, skewed access.

Zipf-distributed row access over a sharded embedding table, replayed as
sparse push (scatter-add aggregation) + pull through the SparseEngine.
"""

from __future__ import annotations

import numpy as np


def skewed_indices(num_rows: int, workers: int, batch: int, seed: int = 0,
                   a: float = 1.2) -> np.ndarray:
    """[workers, batch] Zipf(a)-skewed row ids (hot-key heavy)."""
    rng = np.random.default_rng(seed)
    idx = rng.zipf(a, size=(workers, batch)).astype(np.int64)
    return ((idx - 1) % num_rows).astype(np.int32)


def replay(sparse_engine, num_rows: int = 1 << 20, dim: int = 64,
           batch: int = 4096, steps: int = 1, seed: int = 0):
    """Returns (bytes_moved_per_step, host seconds_per_step)."""
    import time

    name = f"emb_{num_rows}_{dim}"
    if name not in sparse_engine._tables:
        sparse_engine.register_sparse(name, num_rows, dim)
    W = sparse_engine.num_shards
    idx = skewed_indices(num_rows, W, batch, seed=seed)
    grads = np.ones((W, batch, dim), dtype=np.float32)

    sparse_engine.push(name, idx, grads)
    out = sparse_engine.pull(name, idx)
    out.block_until_ready()  # warm the executable cache

    t0 = time.perf_counter()
    for _ in range(steps):
        sparse_engine.push(name, idx, grads)
        out = sparse_engine.pull(name, idx)
    out.block_until_ready()
    sparse_engine.block(name)
    dt = (time.perf_counter() - t0) / max(steps, 1)
    step_bytes = 2 * 4 * W * batch * dim  # push + pull payload
    return step_bytes, dt
