"""``sparse_pull_push``: the closed-loop driver of an embedding table.

A traffic file names its driver (``"driver": "sparse_pull_push"``) and gives
its parameters; the configuration file gives the sizes.  The driver owns the
cell's inputs (made from ``--seed``), one ``step`` that the set-up, the check
and the measured window all call, and the comparison with the plain
reference.  All traffic goes through ``KVWorker``.

One client, closed loop: step t+1 is issued when the pull and the push of
step t are ready.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from driver_base import CHECKED_STEPS, Comparison, _Driver, _jax_key
from least_bytes import sparse_pull_push_step
from reference import RowSumReference, Rounding, row_scaled_error
from zipf import HOTTEST_ROW, zipf_rows


class SparseDriver(_Driver):
    """``zipf-rows``: a step pulls one batch of rows (forward) and pushes
    gradients for the same rows (backward)."""

    TABLE = "emb"

    def __init__(self, cluster, config: dict, traffic: dict, seed: int):
        self.kv = cluster.kv
        self.sparse = cluster.sparse
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.W = int(self.sparse.num_shards)
        self.rows = int(config["rows"])
        self.dim = int(config["dim"])
        self.lookups = int(traffic["lookups_per_worker"])
        self.pool_size = int(traffic["pool_batches"])
        self.limits = config["limits"]
        self.pool: list = []
        self.pool_host: Optional[np.ndarray] = None
        self.grads = None
        self.pulled = None
        self.steps_done = 0
        self._check_pulled: list = []
        self._final = None

    @property
    def payload_bytes_per_step(self) -> int:
        return 2 * self.W * self.lookups * self.dim * 4

    def least_bytes(self) -> Dict[str, float]:
        unique = float(np.mean([len(np.unique(b)) for b in self.pool_host]))
        return sparse_pull_push_step(unique, self.lookups, self.dim, self.W)

    def counters(self) -> Tuple[int, int]:
        return int(self.sparse.push_bytes), int(self.sparse.pull_bytes)

    def expected_counters(self, steps: int) -> Tuple[int, int]:
        half = self.payload_bytes_per_step // 2
        # The comparison's one pull after the window is not a step.
        return half * steps, half * steps

    def setup(self) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axis = self.sparse.mesh, self.sparse.axis
        t0 = time.perf_counter()
        self.sparse.register_sparse(self.TABLE, self.rows, self.dim)
        t1 = time.perf_counter()
        self.pool_host = zipf_rows(
            self.seed, (self.pool_size, self.W, self.lookups), self.rows,
            float(self.traffic["zipf_constant"]))
        idx_sharding = NamedSharding(mesh, P(axis, None))
        self.pool = [jax.device_put(b, idx_sharding) for b in self.pool_host]
        self.grads = jax.jit(
            lambda key: jax.random.normal(
                key, (self.W, self.lookups, self.dim), jnp.float32),
            out_shardings=NamedSharding(mesh, P(axis, None, None)),
        )(_jax_key(self.seed))
        jax.block_until_ready((self.pool, self.grads))
        return {"register": t1 - t0, "inputs": time.perf_counter() - t1}

    def step(self) -> Tuple[float, float, float]:
        kv = self.kv
        idx = self.pool[self.steps_done % self.pool_size]
        t0 = time.perf_counter()
        with self._span("bench_issue"):
            ts_pull = kv.pull_sparse(self.TABLE, idx, out=None)
            self.pulled = kv.get_pulled(ts_pull)
            ts_push = kv.push_sparse(self.TABLE, idx, self.grads)
        t1 = time.perf_counter()
        with self._span("bench_wait"):
            kv.wait(ts_pull)
            kv.wait(ts_push)
        t2 = time.perf_counter()
        self.steps_done += 1
        return t0, t1, t2

    def checked_steps(self) -> None:
        for _ in range(CHECKED_STEPS):
            self.step()
            self._check_pulled.append(self.pulled)

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        lim = self.limits
        kv = self.kv
        if self._final is None:
            # What the table holds after the window's last push, through
            # the same pull call and program as the steps.
            ts = kv.pull_sparse(self.TABLE, self.pool[0], out=None)
            final = kv.get_pulled(ts)
            kv.wait(ts)
            self._final = np.asarray(final)
        grads = np.asarray(self.grads)
        pool = self.pool_host
        # A sample of each compared pull's positions, drawn from the seed:
        # positions, not rows, so hot rows are in it as often as they are
        # pulled (the hottest some hundreds of times).
        rng = np.random.default_rng(self.seed + 1)
        take = min(self.lookups, int(self.traffic.get("compared_lookups",
                                                      8192)))
        where = [np.sort(rng.choice(self.lookups, take, replace=False))
                 for _ in range(CHECKED_STEPS)]
        asked = [pool[s][:, where[s]] for s in range(CHECKED_STEPS)]
        ref = RowSumReference(np.concatenate([a.reshape(-1) for a in asked]),
                              self.dim)
        ctl = (RowSumReference(ref.rows, self.dim, rounding)
               if rounding is not None else None)
        kept: Dict[int, np.ndarray] = {}

        def contribution(k: int) -> np.ndarray:
            if k in kept:
                return kept[k]
            c = ref.contribution(pool[k], grads)
            if ctl is not None or k < CHECKED_STEPS:
                kept[k] = c
            return c

        floor = float(self.traffic.get("gradient_scale", 1.0))
        first3 = 0.0
        for s in range(CHECKED_STEPS):
            # The pull of step s reads the pushes of the steps before it.
            if ctl is None:
                got = np.asarray(self._check_pulled[s])[:, where[s]]
                first3 = max(first3, row_scaled_error(
                    got, ref.pull(asked[s]), floor))
            else:
                first3 = max(first3, row_scaled_error(
                    ctl.sums, ref.sums, floor))
            for r in (ref, ctl):
                if r is not None:
                    r.push(contribution(s % self.pool_size))
        if ctl is None:
            # The sum does not care in which order the window's pushes
            # came: each batch of the pool, times its pushes.
            counts = np.bincount(
                np.arange(CHECKED_STEPS, self.steps_done) % self.pool_size,
                minlength=self.pool_size)
            for k, c in enumerate(counts):
                if c:
                    ref.push(contribution(k), int(c))
            final = row_scaled_error(self._final[:, where[0]],
                                     ref.pull(asked[0]), floor)
        else:
            for s in range(CHECKED_STEPS, self.steps_done):
                c = contribution(s % self.pool_size)
                ref.push(c)
                ctl.push(c)
            final = row_scaled_error(ctl.sums, ref.sums, floor)
        out = [
            ("first3_err", first3, lim["first3_err"]),
            ("final_err", final, lim["final_err"]),
        ]
        if rounding is None:
            # Every copy of the hottest row in the last pull, over all
            # workers' rows, is the one aggregated row.
            hot = self._final[pool[0] == HOTTEST_ROW]
            spread = float(np.max(np.abs(hot - hot[0]))) if len(hot) else 0.0
            out += [
                ("hot_row_copies_spread", spread, 0.0),
                ("hot_row_copies_missing", float(len(hot) == 0), 0.0),
                ("nonfinite_in_pulled_rows",
                 float(np.size(self._final)
                       - np.isfinite(self._final).sum()), 0.0),
            ]
        return out


Driver = SparseDriver
