"""A driver that exists only as this file: the sparse driver under a
stateful row-wise server optimizer (``row_adagrad:lr,eps``).

What a later PR's deployment brings, rehearsed: it takes the sparse driver's
class through the harness's own loader, keeps its inputs, set-up, checked
steps, counters and least bytes, and overrides exactly the step (the push
carries the handle) and the comparison (``row_adagrad_reference.py`` beside
``drivers/``: the order of pushes matters, so the reference follows every
step).  ``KVWorker.push_sparse`` passes no handle, so the push goes to the
van's sparse engine itself; the pull stays on ``KVWorker``.
"""

import time
from typing import List

import numpy as np

import harness
from driver_base import CHECKED_STEPS, Comparison
from reference import Rounding, row_scaled_error
from row_adagrad_reference import (RowAdagradReference,
                                   parse_row_adagrad_handle)

SparseDriver = harness.load_driver(harness.search_dirs(), "sparse_pull_push")


class Driver(SparseDriver):
    """``pull_sparse`` then ``push(..., handle)`` of the same rows."""

    def step(self):
        kv = self.kv
        idx = self.pool[self.steps_done % self.pool_size]
        t0 = time.perf_counter()
        with self._span("bench_issue"):
            ts_pull = kv.pull_sparse(self.TABLE, idx, out=None)
            self.pulled = kv.get_pulled(ts_pull)
            token = self.sparse.push(self.TABLE, idx, self.grads,
                                     self.config["server_handle"])
        t1 = time.perf_counter()
        with self._span("bench_wait"):
            kv.wait(ts_pull)
            token.block_until_ready()
        t2 = time.perf_counter()
        self.steps_done += 1
        return t0, t1, t2

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        lim = self.limits
        kv = self.kv
        lr, eps = parse_row_adagrad_handle(self.config["server_handle"])
        if self._final is None:
            ts = kv.pull_sparse(self.TABLE, self.pool[0], out=None)
            final = kv.get_pulled(ts)
            kv.wait(ts)
            self._final = np.asarray(final)
        pool = self.pool_host
        rng = np.random.default_rng(self.seed + 1)
        take = min(self.lookups, int(self.traffic.get("compared_lookups",
                                                      8192)))
        where = [np.sort(rng.choice(self.lookups, take, replace=False))
                 for _ in range(CHECKED_STEPS)]
        asked = [pool[s][:, where[s]] for s in range(CHECKED_STEPS)]
        watch = np.concatenate([a.reshape(-1) for a in asked])
        ref = RowAdagradReference(watch, self.dim, lr, eps)
        # With ``rounding`` the numbers are the control's: the reference in
        # lower precision, put in the program's place.
        ctl = (RowAdagradReference(watch, self.dim, lr, eps, rounding)
               if rounding is not None else None)
        grads = np.asarray(self.grads)
        pushed = [ref.contribution(batch, grads) for batch in pool]
        # A row is of the size of its steps: one learning rate is the floor.
        first3 = 0.0
        for s in range(self.steps_done):
            if s < CHECKED_STEPS:
                # The pull of step s reads the pushes of the steps before it.
                got = (ctl.pull(asked[s]) if ctl is not None else
                       np.asarray(self._check_pulled[s])[:, where[s]])
                first3 = max(first3, row_scaled_error(
                    got, ref.pull(asked[s]), lr))
            for r in (ref, ctl):
                if r is not None:
                    r.push(pushed[s % self.pool_size])
        got = (ctl.pull(asked[0]) if ctl is not None
               else self._final[:, where[0]])
        final = row_scaled_error(got, ref.pull(asked[0]), lr)
        return [("first3_err", first3, lim["first3_err"]),
                ("final_err", final, lim["final_err"])]
