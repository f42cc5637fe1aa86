"""``sparse_handle_pull_push``: the sparse driver under a stateful
server handle (the configuration's ``server_handle``, ``row_adagrad:lr,eps``).

It takes the sparse driver's class through the harness's own loader and
keeps its inputs, set-up, checked steps and counters.  It overrides the step
(the push carries the handle, through ``KVWorker.push_sparse`` like all
traffic), the comparison (``rowwise_adagrad.py`` beside ``drivers/``: the
order of pushes matters, so the reference follows every step on the watched
rows) and the least bytes (the accumulator's are added).
"""

import inspect
import time
from typing import Dict, List

import numpy as np

import harness
from pslite_tpu import KVWorker
from driver_base import CHECKED_STEPS, Comparison
from reference import Rounding, row_scaled_error
from rowwise_adagrad import (RowwiseAdagradReference, parse_handle,
                             pull_push_step_least_bytes)
from zipf import HOTTEST_ROW

SparseDriver = harness.load_driver(harness.search_dirs(), "sparse_pull_push")

# A checkout from before ``push_sparse`` took a handle cannot run this
# cell: say so where the driver is loaded, before anything boots.
if "handle" not in inspect.signature(KVWorker.push_sparse).parameters:
    raise RuntimeError(
        "this checkout's KVWorker.push_sparse takes no server handle: it "
        "cannot run a cell under a stateful sparse handle")


class Driver(SparseDriver):
    """A step is ``pull_sparse`` of one batch, then ``push_sparse`` of
    gradients for the same rows under the handle, then a wait on both."""

    def least_bytes(self) -> Dict[str, float]:
        unique = float(np.mean([len(np.unique(b)) for b in self.pool_host]))
        return pull_push_step_least_bytes(unique, self.lookups, self.dim,
                                          self.W)

    def step(self):
        kv = self.kv
        idx = self.pool[self.steps_done % self.pool_size]
        t0 = time.perf_counter()
        with self._span("bench_issue"):
            ts_pull = kv.pull_sparse(self.TABLE, idx, out=None)
            self.pulled = kv.get_pulled(ts_pull)
            ts_push = kv.push_sparse(self.TABLE, idx, self.grads,
                                     self.config["server_handle"])
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
        lr, eps = parse_handle(self.config["server_handle"])
        if self._final is None:
            # What the table holds after the window's last push, through
            # the same pull call and program as the steps.
            ts = kv.pull_sparse(self.TABLE, self.pool[0], out=None)
            final = kv.get_pulled(ts)
            kv.wait(ts)
            self._final = np.asarray(final)
        pool = self.pool_host
        # A sample of each compared pull's positions, drawn from the seed:
        # positions, not rows, so hot rows are in it as often as they are
        # pulled.
        rng = np.random.default_rng(self.seed + 1)
        take = min(self.lookups, int(self.traffic.get("compared_lookups",
                                                      8192)))
        where = [np.sort(rng.choice(self.lookups, take, replace=False))
                 for _ in range(CHECKED_STEPS)]
        asked = [pool[s][:, where[s]] for s in range(CHECKED_STEPS)]
        watch = np.concatenate([a.reshape(-1) for a in asked])
        ref = RowwiseAdagradReference(watch, self.dim, lr, eps)
        # With ``rounding`` the numbers are the control's: the reference in
        # lower precision, put in the program's place.
        ctl = (RowwiseAdagradReference(watch, self.dim, lr, eps, rounding)
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
        out = [("first3_err", first3, lim["first3_err"]),
               ("final_err", final, lim["final_err"])]
        if rounding is None:
            # Every copy of the hottest row in the last pull, over all
            # workers' rows, is the one updated row.
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
