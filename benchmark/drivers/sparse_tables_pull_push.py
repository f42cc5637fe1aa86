"""``sparse_tables_pull_push``: the closed-loop driver of a job that keeps its
embedding tables as MANY tables, one a categorical feature.

The configuration file lists the tables (``"tables": [[name, rows], ...]``, one
width ``dim``); the traffic file gives the lookups a table a step.  A step is
one ``KVWorker.pull_sparse_group`` of every table's rows (forward), then one
``KVWorker.push_sparse_group`` of gradients for the same rows (backward) with
no handle: two ops, two launches, whatever the number of tables.  One client,
closed loop: step t+1 is issued when the pull and the push of step t are
ready.

The comparison is ``sparse_pull_push``'s, a table at a time: one
``reference.RowSumReference`` a table follows every push, and each number is
the worst over the tables.  A table with no more rows than a step has lookups
for it is compared on every position, so on every row it has, the row-mates
of a lane-packed physical row included.
"""

from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from driver_base import CHECKED_STEPS, Comparison, _Driver, _jax_key
from least_bytes import sparse_pull_push_step
from reference import RowSumReference, Rounding, row_scaled_error
from zipf import HOTTEST_ROW, BoundedZipf


class SparseTablesDriver(_Driver):
    """``zipf-tables``: a step pulls one batch of rows from every table and
    pushes gradients for the same rows, each as ONE grouped op."""

    def __init__(self, cluster, config: dict, traffic: dict, seed: int):
        self.kv = cluster.kv
        # The parent of the PR that brought the grouped calls ends here, in
        # seconds and before any table is registered.
        lacks = [call for call in ("pull_sparse_group", "push_sparse_group")
                 if not callable(getattr(self.kv, call, None))]
        if lacks:
            raise RuntimeError(
                f"this program's KVWorker has no {' / '.join(lacks)}: the "
                f"driver sparse_tables_pull_push issues a step's rows of all "
                f"tables in one call and has no other path")
        self.sparse = cluster.sparse
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.W = int(self.sparse.num_shards)
        self.names = [str(name) for name, _ in config["tables"]]
        self.rows = [int(rows) for _, rows in config["tables"]]
        self.dim = int(config["dim"])
        self.lookups = int(traffic["lookups_per_table"])
        self.pool_size = int(traffic["pool_batches"])
        self.limits = config["limits"]
        self.pool: list = []        # [pool batch][table] on the device
        self.pool_host: list = []   # [table] -> [pool, W, lookups]
        self.grads: list = []       # [table] on the device
        self.pulled = None
        self.steps_done = 0
        self._check_pulled: list = []
        self._final = None

    @property
    def payload_bytes_per_step(self) -> int:
        return 2 * self.W * len(self.names) * self.lookups * self.dim * 4

    def least_bytes(self) -> Dict[str, float]:
        least = {"hbm": 0.0, "ici": 0.0}
        for batches in self.pool_host:
            distinct = float(np.mean([len(np.unique(b)) for b in batches]))
            for kind, value in sparse_pull_push_step(
                    distinct, self.lookups, self.dim, self.W).items():
                least[kind] += value
        return least

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
        for name, rows in zip(self.names, self.rows):
            self.sparse.register_sparse(name, rows, self.dim)
        t1 = time.perf_counter()
        theta = float(self.traffic["zipf_constant"])
        shape = (self.pool_size, self.W, self.lookups)
        # A table's draws from its own stream of the seed.
        self.pool_host = [
            BoundedZipf(rows, theta).rows(
                np.random.default_rng([self.seed, t]).random(shape)
            ).astype(np.int32)
            for t, rows in enumerate(self.rows)]
        idx_sharding = NamedSharding(mesh, P(axis, None))
        self.pool = [[jax.device_put(batches[k], idx_sharding)
                      for batches in self.pool_host]
                     for k in range(self.pool_size)]
        draw = jax.jit(
            lambda key: jax.random.normal(
                key, (self.W, self.lookups, self.dim), jnp.float32),
            out_shardings=NamedSharding(mesh, P(axis, None, None)))
        key = _jax_key(self.seed)
        self.grads = [draw(jax.random.fold_in(key, t))
                      for t in range(len(self.names))]
        jax.block_until_ready((self.pool, self.grads))
        return {"register": t1 - t0, "inputs": time.perf_counter() - t1}

    def step(self) -> Tuple[float, float, float]:
        kv = self.kv
        idx = self.pool[self.steps_done % self.pool_size]
        t0 = time.perf_counter()
        with self._span("bench_issue"):
            ts_pull = kv.pull_sparse_group(self.names, idx)
            self.pulled = kv.get_pulled(ts_pull)
            ts_push = kv.push_sparse_group(self.names, idx, self.grads)
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
            # What the tables hold after the window's last push, through
            # the same call and program as the steps.
            ts = kv.pull_sparse_group(self.names, self.pool[0])
            final = kv.get_pulled(ts)
            kv.wait(ts)
            self._final = [np.asarray(rows) for rows in final]
            self._check_pulled = [[np.asarray(rows) for rows in pulled]
                                  for pulled in self._check_pulled]
        numbers = [self._compare_table(t, rounding)
                   for t in range(len(self.names))]
        first3, final = (max(n[k] for n in numbers) for k in (0, 1))
        out = [
            ("first3_err", first3, lim["first3_err"]),
            ("final_err", final, lim["final_err"]),
        ]
        if rounding is None:
            # Every copy of the hottest row of each table in the last pull,
            # over all workers' rows, is that table's one aggregated row.
            spread = missing = nonfinite = 0.0
            for pool, got in zip(self.pool_host, self._final):
                hot = got[pool[0] == HOTTEST_ROW]
                missing += float(len(hot) == 0)
                if len(hot):
                    spread = max(spread,
                                 float(np.max(np.abs(hot - hot[0]))))
                nonfinite += float(np.size(got) - np.isfinite(got).sum())
            out += [
                ("hot_row_copies_spread", spread, 0.0),
                ("hot_row_copies_missing", missing, 0.0),
                ("nonfinite_in_pulled_rows", nonfinite, 0.0),
            ]
        return out

    def _compare_table(self, t: int, rounding: Rounding
                       ) -> Tuple[float, float]:
        """``(first3_err, final_err)`` of table ``t``: ``sparse_pull_push``'s
        two numbers on this table's pulls, pushes and rows."""
        pool, grads = self.pool_host[t], np.asarray(self.grads[t])
        # A sample of each compared pull's positions, drawn from the seed:
        # positions, not rows, so hot rows are in it as often as they are
        # pulled; every position of a table that has no more rows than
        # lookups, so every row it has.
        rng = np.random.default_rng([self.seed + 1, t])
        take = self.lookups if self.rows[t] <= self.lookups else min(
            self.lookups, int(self.traffic.get("compared_lookups", 1024)))
        where = [np.sort(rng.choice(self.lookups, take, replace=False))
                 for _ in range(CHECKED_STEPS)]
        asked = [pool[s][:, where[s]] for s in range(CHECKED_STEPS)]
        ref = RowSumReference(np.concatenate([a.reshape(-1) for a in asked]),
                              self.dim)
        ctl = (RowSumReference(ref.rows, self.dim, rounding)
               if rounding is not None else None)
        kept: Dict[int, np.ndarray] = {}

        def contribution(k: int) -> np.ndarray:
            if k not in kept:
                kept[k] = ref.contribution(pool[k], grads)
            return kept[k]

        floor = float(self.traffic.get("gradient_scale", 1.0))
        first3 = 0.0
        for s in range(CHECKED_STEPS):
            # The pull of step s reads the pushes of the steps before it.
            if ctl is None:
                got = self._check_pulled[s][t][:, where[s]]
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
            final = row_scaled_error(self._final[t][:, where[0]],
                                     ref.pull(asked[0]), floor)
        else:
            for s in range(CHECKED_STEPS, self.steps_done):
                c = contribution(s % self.pool_size)
                ref.push(c)
                ctl.push(c)
            final = row_scaled_error(ctl.sums, ref.sums, floor)
        return first3, final


Driver = SparseTablesDriver
