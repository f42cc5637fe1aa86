"""Elastic restart ACROSS FLEET SIZES: checkpoint -> exit 254 -> restore
into a smaller fleet -> continue training.

The reference's keepalive launcher restarts any child that exits 254
(tracker/dmlc_local.py:16-25) but its recovery re-admits the SAME
roster; this framework closes the loop for a fleet whose size changed
across the restart: format-v2 checkpoints save GLOBAL logical state
(checkpoint.save_engine), so an 8-shard save restores into a 4-shard
engine — stores, fused-optimizer state (adam), and sparse tables with
row-Adagrad accumulators all carry over, verified here against a host
recurrence of the full uninterrupted run.

Run (the launcher supplies the keepalive):

    python -m pslite_tpu.tracker.local -n 0 -s 0 -- \
        python examples/elastic_restart.py

First incarnation: 8-shard engine, 2 training steps, save, exit 254.
Second incarnation (checkpoint exists): 4-shard engine on HALF the
devices, restore, 2 more steps, verify, print ELASTIC_RESTART_OK.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

LR, B1, B2, EPS = 1e-2, 0.9, 0.999, 1e-8
SLR, SEPS = 0.1, 1e-8
TOTAL = 100          # 1 key x 100 values: padding differs per fleet size
ROWS, DIM = 13, 4
STEPS = 4            # 2 before the restart, 2 after


def _grads(step: int) -> np.ndarray:
    return np.random.default_rng(100 + step).normal(
        size=TOTAL
    ).astype(np.float32)


def _row_grads(step: int) -> tuple:
    rng = np.random.default_rng(200 + step)
    idx = rng.integers(0, ROWS, size=6).astype(np.int32)
    g = rng.normal(size=(6, DIM)).astype(np.float32)
    return idx, g


def _host_model():
    """The uninterrupted 4-step run as a host recurrence (adam with
    bias correction exactly as ops/quantize's fused handle applies it,
    row-adagrad as parallel/sparse._adagrad_rows)."""
    store = np.zeros(TOTAL, np.float64)
    m = np.zeros(TOTAL, np.float64)
    v = np.zeros(TOTAL, np.float64)
    table = np.zeros((ROWS, DIM), np.float64)
    acc = np.zeros(ROWS, np.float64)
    for step in range(1, STEPS + 1):
        g = _grads(step - 1).astype(np.float64)
        m = B1 * m + (1 - B1) * g
        v = B2 * v + (1 - B2) * g * g
        alpha = LR * np.sqrt(1 - B2 ** step) / (1 - B1 ** step)
        store = store - alpha * m / (np.sqrt(v) + EPS)
        idx, rg = _row_grads(step - 1)
        G = np.zeros((ROWS, DIM), np.float64)
        np.add.at(G, idx, rg.astype(np.float64))
        acc = acc + np.mean(G ** 2, axis=1)
        table = table - SLR * G / (np.sqrt(acc)[:, None] + SEPS)
    return store, table


def _build(mesh):
    from pslite_tpu.parallel.engine import CollectiveEngine
    from pslite_tpu.parallel.sparse import SparseEngine

    eng = CollectiveEngine(mesh=mesh, server_handle=f"adam:{LR}")
    se = SparseEngine(mesh)
    eng.register_dense("w", np.arange(1, dtype=np.uint64), TOTAL)
    se.register_sparse("emb", ROWS, DIM)
    return eng, se


def _train(eng, se, steps) -> None:
    W = eng.num_shards
    for step in steps:
        g = _grads(step)
        eng.push_pull("w", np.tile(g / W, (W, 1)))
        idx, rg = _row_grads(step)
        # Worker 0 carries the batch; the rest push empty rows.
        idxs = np.zeros((W, len(idx)), np.int32)
        gs = np.zeros((W, len(idx), DIM), np.float32)
        idxs[0], gs[0] = idx, rg
        se.push("emb", idxs, gs, handle=f"row_adagrad:{SLR},{SEPS}")
        se.block("emb")


def main() -> int:
    if os.environ.get("DMLC_ROLE", "scheduler") != "scheduler":
        return 0

    import jax

    from pslite_tpu import checkpoint
    from jax.sharding import Mesh

    ckpt = os.environ.get("PS_CKPT", "/tmp/pslite_elastic_restart_ck")
    # Both fleet-portable backends drive the same loop (PS_CKPT_BACKEND
    # = npz | orbax): orbax saves a directory, npz a file.
    backend = os.environ.get("PS_CKPT_BACKEND", "npz")
    if backend == "orbax":
        ck_exists = os.path.isdir(ckpt)
        save = checkpoint.save_engine_orbax
        restore = checkpoint.restore_engine_orbax
    else:
        ck_exists = os.path.exists(ckpt + ".npz")
        save = checkpoint.save_engine
        restore = checkpoint.restore_engine
    devs = jax.devices()
    if not ck_exists:
        # FIRST incarnation: the full 8-shard fleet, half the run.
        eng, se = _build(Mesh(np.array(devs), ("kv",)))
        _train(eng, se, range(0, 2))
        save(eng, ckpt, sparse_engine=se)
        print(f"saved 2-step checkpoint from {eng.num_shards} shards; "
              f"exiting 254 for the keepalive restart", flush=True)
        return 254
    # SECOND incarnation: HALF the fleet (4 shards), restore, finish.
    eng, se = _build(Mesh(np.array(devs[: len(devs) // 2]), ("kv",)))
    restore(eng, ckpt, sparse_engine=se)
    _train(eng, se, range(2, STEPS))
    store, table = _host_model()
    got = np.asarray(eng.pull("w"))
    np.testing.assert_allclose(got, store, rtol=1e-4, atol=1e-4)
    all_rows = np.tile(np.arange(ROWS, dtype=np.int32),
                       (eng.num_shards, 1))
    got_t = np.asarray(se.pull("emb", all_rows))[0]
    np.testing.assert_allclose(got_t, table, rtol=1e-4, atol=1e-4)
    print(f"ELASTIC_RESTART_OK restored onto {eng.num_shards} shards, "
          f"training matches the uninterrupted run", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
