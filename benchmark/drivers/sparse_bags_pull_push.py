"""``sparse_bags_pull_push``: the closed-loop driver of a job whose lookups
are BAGS: a sample's lookup into table ``t`` is ``h_t`` ids whose rows the
model takes summed (an ``EmbeddingBag``, pooling sum), and the backward pass
leaves one gradient a bag.

It takes the many-tables driver's class through the harness's own loader and
keeps its counters and checked steps.  The configuration file lists the
tables and, in the same order, their bag sizes (``"bag_sizes": [h_t, ...]``);
the traffic file gives the bags a table a step.  A step is one
``KVWorker.pull_sparse_group(names, ids, pool="sum")`` of every table's
pooled rows (forward), then one ``push_sparse_group(names, ids, grads, handle,
pool="sum")`` of one gradient a bag for the same bags (backward) under the
configuration's ``server_handle``: two ops, two launches.  One client, closed
loop.

A bag's first id is the table's bounded scrambled Zipfian, each table from
its own stream of the seed; its other ids are a fixed function of (seed,
table, first id, slot), uniform over the table's rows (:func:`bag_ids`): a
hot id brings the same bag again, and ids may repeat inside a bag.

The comparison is ``bags_reference.py``'s beside ``drivers/``, a table at a
time, each number the worst over the tables: the pooled pulls of the checked
steps, the pooled pull after the window and the accumulators after it (read
off the engine, row by watched row).  The order of pushes matters under the
handle, so the reference follows every step.  Every row of a table of at most
``bags_per_table`` rows is watched, and every bag of it compared; of a larger
table as many sampled bags a checked step as hold ``compared_lookups`` ids, a
bag of the hottest id among them, and every row they name.
"""

from __future__ import annotations

import inspect
import time
from typing import Dict, List, Tuple

import numpy as np

import harness
from pslite_tpu import KVWorker
from bags_reference import bag_reference
from driver_base import CHECKED_STEPS, Comparison, _jax_key
from reference import Rounding, row_scaled_error
from rowwise_adagrad import parse_handle
from sparse_bags_ops import step_least_bytes
from zipf import HOTTEST_ROW, BoundedZipf

TablesDriver = harness.load_driver(harness.search_dirs(),
                                   "sparse_tables_pull_push")

# A checkout from before the sparse calls took ``pool`` cannot run this
# cell: say so where the driver is loaded, before anything boots.
if "pool" not in inspect.signature(KVWorker.pull_sparse_group).parameters:
    raise RuntimeError(
        "this checkout's KVWorker.pull_sparse_group takes no pool: it cannot "
        "run a cell whose lookups are bags")

_M1, _M2, _M3 = (np.uint64(0x9E3779B97F4A7C15), np.uint64(0xBF58476D1CE4E5B9),
                 np.uint64(0x94D049BB133111EB))


def bag_ids(seed: int, table: int, first: np.ndarray, h: int, rows: int
            ) -> np.ndarray:
    """Bags ``[..., h]`` int32 from their first ids ``first`` ``[...]``: slot
    0 is the first id, slot ``j >= 1`` a 64-bit mix (splitmix64's finaliser)
    of (seed, table, first id, j) modulo the table's rows: uniform over the
    table, the same bag wherever the same first id comes again."""
    first = np.asarray(first)
    if h == 1:
        return first[..., None].astype(np.int32)
    key = np.random.SeedSequence([int(seed), int(table)]).generate_state(
        1, np.uint64)
    x = (first.astype(np.uint64)[..., None] * _M1
         + np.arange(1, h, dtype=np.uint64) * _M2 + key)
    x ^= x >> np.uint64(30)
    x *= _M2
    x ^= x >> np.uint64(27)
    x *= _M3
    x ^= x >> np.uint64(31)
    others = (x % np.uint64(rows)).astype(np.int32)
    return np.concatenate([first[..., None].astype(np.int32), others],
                          axis=-1)


class Driver(TablesDriver):
    """``zipf-bags``: a step pulls the pooled rows of one batch of bags from
    every table and pushes one gradient a bag for the same bags, each as ONE
    grouped op under ``pool="sum"``."""

    POOL = "sum"

    def __init__(self, cluster, config: dict, traffic: dict, seed: int):
        # A table's rows across the API a worker a step are its bags: what
        # the many-tables driver calls ``lookups_per_table`` (its payload,
        # its counters).
        super().__init__(cluster, config,
                         dict(traffic,
                              lookups_per_table=traffic["bags_per_table"]),
                         seed)
        self.traffic = traffic
        self.B = self.lookups
        self.bag_sizes = [int(h) for h in config["bag_sizes"]]
        if len(self.bag_sizes) != len(self.names):
            raise ValueError("the configuration gives one bag size a table")
        self.handle = config["server_handle"]
        self.compared = int(traffic.get("compared_lookups", 1024))
        self._accs: list = []

    def least_bytes(self) -> Dict[str, float]:
        distinct = [float(np.mean([len(np.unique(b)) for b in batches]))
                    for batches in self.pool_host]
        return step_least_bytes(distinct, self.config, self.traffic)

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
        shape = (self.pool_size, self.W, self.B)
        # [table] -> [pool, W, B, h_t]; a table's first ids from its own
        # stream of the seed.
        self.pool_host = [
            bag_ids(self.seed, t, BoundedZipf(rows, theta).rows(
                np.random.default_rng([self.seed, t]).random(shape)), h, rows)
            for t, (rows, h) in enumerate(zip(self.rows, self.bag_sizes))]
        sharding = NamedSharding(mesh, P(axis, None, None))
        self.pool = [[jax.device_put(batches[k], sharding)
                      for batches in self.pool_host]
                     for k in range(self.pool_size)]
        scale = float(self.traffic.get("gradient_scale", 1.0))
        draw = jax.jit(
            lambda key: scale * jax.random.normal(
                key, (self.W, self.B, self.dim), jnp.float32),
            out_shardings=sharding)
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
            ts_pull = kv.pull_sparse_group(self.names, idx, pool=self.POOL)
            self.pulled = kv.get_pulled(ts_pull)
            ts_push = kv.push_sparse_group(self.names, idx, self.grads,
                                           self.handle, pool=self.POOL)
        t1 = time.perf_counter()
        with self._span("bench_wait"):
            kv.wait(ts_pull)
            kv.wait(ts_push)
        t2 = time.perf_counter()
        self.steps_done += 1
        return t0, t1, t2

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        lim = self.limits
        kv = self.kv
        if self._final is None:
            # What the tables hold after the window's last push, through
            # the same call and program as the steps; the accumulators off
            # the engine, in global row order.
            ts = kv.pull_sparse_group(self.names, self.pool[0],
                                      pool=self.POOL)
            final = kv.get_pulled(ts)
            kv.wait(ts)
            self._final = [np.asarray(rows) for rows in final]
            self._check_pulled = [[np.asarray(rows) for rows in pulled]
                                  for pulled in self._check_pulled]
            self._accs = [self.sparse.acc_global_device(name)
                          for name in self.names]
        numbers = [self._compare_table(t, rounding)
                   for t in range(len(self.names))]
        out = [(name, max(n[k] for n in numbers), lim[name])
               for k, name in enumerate(("first3_err", "final_err",
                                         "acc_err"))]
        if rounding is None:
            # A hot first id brings its whole bag again: every bag of the
            # hottest id in the last pull, over all workers, pools to one
            # row, bit for bit.
            spread = missing = nonfinite = 0.0
            for pool, got in zip(self.pool_host, self._final):
                hot = got[pool[0][..., 0] == HOTTEST_ROW]
                missing += float(len(hot) == 0)
                if len(hot):
                    spread = max(spread,
                                 float(np.max(np.abs(hot - hot[0]))))
                nonfinite += float(np.size(got) - np.isfinite(got).sum())
            out += [
                ("hot_bag_copies_spread", spread, 0.0),
                ("hot_bag_copies_missing", missing, 0.0),
                ("nonfinite_in_pulled_rows", nonfinite, 0.0),
            ]
        return out

    def _compared_bags(self, t: int) -> List[np.ndarray]:
        """Which bags of each checked step's batch of table ``t`` are
        compared: all of a table that has no more rows than bags a step, else
        a seeded sample holding ``compared_lookups`` ids, with the first bag
        of the hottest id."""
        h, pool = self.bag_sizes[t], self.pool_host[t]
        if self.rows[t] <= self.B:
            return [np.arange(self.B)] * CHECKED_STEPS
        rng = np.random.default_rng([self.seed + 1, t])
        take = min(self.B, -(-self.compared // h))
        where = []
        for s in range(CHECKED_STEPS):
            picked = rng.choice(self.B, take, replace=False)
            hot = np.flatnonzero(
                (pool[s % self.pool_size][..., 0] == HOTTEST_ROW).any(axis=0))
            where.append(np.unique(np.append(picked, hot[:1])))
        return where

    def _compare_table(self, t: int, rounding: Rounding
                       ) -> Tuple[float, float, float]:
        """``(first3_err, final_err, acc_err)`` of table ``t``."""
        pool, grads = self.pool_host[t], np.asarray(self.grads[t])
        lr, _ = parse_handle(self.handle)
        where = self._compared_bags(t)
        asked = [pool[s % self.pool_size][:, where[s]]
                 for s in range(CHECKED_STEPS)]
        watch = (np.arange(self.rows[t]) if self.rows[t] <= self.B
                 else np.concatenate([a.reshape(-1) for a in asked]))
        ref = bag_reference(watch, self.dim, self.handle)
        # With ``rounding`` the numbers are the control's: the reference in
        # lower precision, put in the program's place.
        ctl = (bag_reference(watch, self.dim, self.handle, rounding)
               if rounding is not None else None)
        pushed = [ref.contribution(batch, grads) for batch in pool]
        # A row is of the size of its steps: one learning rate is the floor.
        first3 = 0.0
        for s in range(self.steps_done):
            if s < CHECKED_STEPS:
                # The pull of step s reads the pushes of the steps before it.
                got = (ctl.pull_pooled(asked[s]) if ctl is not None
                       else self._check_pulled[s][t][:, where[s]])
                first3 = max(first3, row_scaled_error(
                    got, ref.pull_pooled(asked[s]), lr))
            for r in (ref, ctl):
                if r is not None:
                    r.push(pushed[s % self.pool_size])
        got = (ctl.pull_pooled(asked[0]) if ctl is not None
               else self._final[t][:, where[0]])
        final = row_scaled_error(got, ref.pull_pooled(asked[0]), lr)
        # The accumulators of the watched rows, each relative to itself: one
        # bag's gradient a row is of size 1 (``gradient_scale`` squared, the
        # mean over the row's elements), the floor.
        acc = (ctl.acc if ctl is not None
               else np.asarray(self._accs[t][ref.rows], np.float64))
        floor = float(self.traffic.get("gradient_scale", 1.0)) ** 2
        acc_err = float(np.max(np.abs(acc - ref.acc)
                               / np.maximum(ref.acc, floor), initial=0.0))
        if not np.isfinite(acc).all():
            acc_err = float("inf")
        return first3, final, acc_err
