"""Customer — per-app request/response tracker and receive pump.

Capability parity with the reference's ``include/ps/internal/customer.h`` /
``src/customer.cc``: ``new_request(recver)`` allocates a timestamp and records
how many responses to expect; a dedicated thread pops the receive queue, runs
the app's handle, then counts the response (the count is incremented *after*
the handle runs, which KVWorker's completion logic relies on —
``customer.cc:59-74``).

One extension for the TPU data plane: a timestamp can carry *completion
hooks* (e.g. ``jax.Array.block_until_ready``) so ICI-van requests — which
never produce response messages — still honor ``wait_request`` semantics.

Executor mode (``PS_CUSTOMER_EXECUTOR=N``): handler calls run on N
worker threads fed by a BOUNDED queue, so the pump keeps draining the
receive queue while handlers run — the feed stage of the server's
sharded apply pipeline (docs/apply_shards.md).  ``N=1`` preserves
handler order (one drainer); ``N>1`` is only for order-insensitive
handlers.  Backpressure: a full executor queue blocks the pump instead
of ballooning memory.
"""

from __future__ import annotations

import threading
import traceback
from typing import Callable, Dict, List, Optional

from .message import Message
from .utils.queues import PriorityRecvQueue, ThreadsafeQueue


class Customer:
    def __init__(
        self,
        app_id: int,
        customer_id: int,
        recv_handle: Callable[[Message], None],
        postoffice,
        on_request_error: Optional[
            Callable[[Message, Exception], None]
        ] = None,
        executor_workers: Optional[int] = None,
    ):
        self.app_id = app_id
        self.customer_id = customer_id
        self._recv_handle = recv_handle
        self._po = postoffice
        # Hook: a handler exception on a REQUEST message (the remote
        # side is waiting) — KVServer uses it to send an error-marked
        # response so the waiter fails fast instead of hanging.
        self._on_request_error = on_request_error
        # ts -> [expected, received]; insertion-ordered and pruned of old
        # completed entries (bounded, unlike the reference's ever-growing
        # vector) — see _prune_tracker_locked.
        self._tracker: Dict[int, List[int]] = {}
        self._next_ts = 0
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        # Priority intake (PS_RECV_PRIORITY, same knob as the van's
        # receive queues — docs/chunking.md): a priority op must not
        # wait behind the queued handling of earlier bulk messages
        # (e.g. the codec tier's payload decode, docs/compression.md)
        # any more than it waits behind their frames on the wire.
        # FIFO within a level preserves per-sender arrival order for
        # same-priority traffic — the apply pool's bit-exactness
        # contract; the shutdown sentinel drains LAST, preserving the
        # deliver-queued-traffic-before-retiring contract.
        env = getattr(postoffice, "env", None)
        prio = (env.find_int("PS_RECV_PRIORITY", 1) != 0
                if env is not None else True)
        # Tenant weights (docs/qos.md): bulk intake dequeues weighted-
        # fair across tenants, like the lanes and the van queues —
        # sharing ONE tenant/cost model (vans/chunking.py) so the two
        # intake hops can never diverge.
        from .tenants import table_for
        from .vans.chunking import recv_cost, recv_tenant

        tenant_table = table_for(env)
        self._queue = (
            PriorityRecvQueue(
                self._recv_priority, tenant_fn=recv_tenant,
                cost_fn=recv_cost,
                weights=(tenant_table.weights_by_id()
                         if tenant_table.enabled else None),
            ) if prio else ThreadsafeQueue()
        )
        self._hooks: Dict[int, List[Callable[[], None]]] = {}
        if executor_workers is None:
            env = getattr(postoffice, "env", None)
            executor_workers = (
                env.find_int("PS_CUSTOMER_EXECUTOR", 0)
                if env is not None else 0
            )
        self._exec_workers = max(0, int(executor_workers))
        self._exec_queue: Optional[ThreadsafeQueue] = None
        self._exec_threads: List[threading.Thread] = []
        if self._exec_workers:
            self._exec_queue = ThreadsafeQueue(
                maxsize=4 * self._exec_workers
            )
            for i in range(self._exec_workers):
                t = threading.Thread(
                    target=self._exec_loop,
                    name=f"customer-exec-{app_id}-{customer_id}-{i}",
                    daemon=True,
                )
                t.start()
                self._exec_threads.append(t)
        self._thread = threading.Thread(
            target=self._receiving, name=f"customer-{app_id}-{customer_id}", daemon=True
        )
        self._thread.start()
        postoffice.add_customer(self)

    # -- request tracking ----------------------------------------------------

    def new_request(self, recver: int, num_responses: Optional[int] = None) -> int:
        """Allocate a timestamp expecting one response per addressed node.

        With instance groups, a worker instance only talks to the matching
        server instance in each group, so the expected count is
        ``len(node_ids(recver)) / group_size`` (reference: customer.cc:32-40).
        """
        if num_responses is None:
            ids = self._po.get_node_ids(recver)
            if recver < 8:
                # Group bitmask: one response per matching instance of each
                # group — the scheduler (a singleton) is counted apart so it
                # is not swallowed by the group_size division.
                sched = 1 if any(i == 1 for i in ids) else 0
                num = max(sched + (len(ids) - sched) // self._po.group_size, 1)
            else:  # direct node id
                num = len(ids)
        else:
            num = num_responses
        with self._cv:
            ts = self._next_ts
            self._next_ts += 1
            self._tracker[ts] = [num, 0]
            self._prune_tracker_locked()
            return ts

    _MAX_TRACKER_ENTRIES = 8192

    def _prune_tracker_locked(self) -> None:
        """Bound tracker growth (the reference grows forever,
        customer.cc:32-40): sweep out old COMPLETED entries beyond the
        window; a pruned timestamp reads back as complete.  In-flight
        entries are skipped (never pruned), so one stuck request cannot
        re-unbound the tracker — only genuinely outstanding ones remain."""
        if len(self._tracker) <= self._MAX_TRACKER_ENTRIES:
            return
        keep_recent = self._MAX_TRACKER_ENTRIES // 2
        completed = [
            ts for ts, (exp, got) in self._tracker.items() if got >= exp
        ]
        if len(completed) > keep_recent:
            for ts in completed[: len(completed) - keep_recent]:
                del self._tracker[ts]

    def _entry(self, timestamp: int):
        entry = self._tracker.get(timestamp)
        if entry is not None:
            return entry
        # Only timestamps we actually issued may read back as "pruned =
        # long complete"; a future/bogus ts is a caller bug — fail loud
        # (the pre-bounded tracker raised IndexError here).
        if 0 <= timestamp < self._next_ts:
            return (0, 0)
        raise KeyError(f"unknown timestamp {timestamp}")

    def wait_request(self, timestamp: int, timeout: Optional[float] = None) -> bool:
        if self._hooks:  # unlocked probe: hooks are an ICI-path feature
            for hook in self._take_hooks(timestamp):
                hook()
        with self._cv:
            done = lambda: (  # noqa: E731
                self._entry(timestamp)[0] <= self._entry(timestamp)[1]
            )
            if timeout is None:
                self._cv.wait_for(done)
                return True
            return self._cv.wait_for(done, timeout)

    def num_response(self, timestamp: int) -> int:
        with self._mu:
            return self._entry(timestamp)[1]

    def num_expected(self, timestamp: int) -> int:
        """Responses this timestamp was issued expecting (0 for pruned
        = long-complete entries).  Under elastic routing the per-slice
        fan-out varies per request, so completion checks must read the
        count recorded at issue time, not a global server count."""
        with self._mu:
            return self._entry(timestamp)[0]

    def add_response(self, timestamp: int, num: int = 1) -> None:
        with self._cv:
            if timestamp in self._tracker:
                self._tracker[timestamp][1] += num
            self._cv.notify_all()

    _MAX_HOOK_ENTRIES = 256

    def add_wait_hook(self, timestamp: int, hook: Callable[[], None]) -> None:
        """Attach a device-completion hook run by wait_request (ICI path).

        Hooks must be idempotent (e.g. ``Future.result``): they run on
        *every* wait of the timestamp so concurrent waiters all observe
        completion.  Entries are evicted FIFO beyond a bounded window of
        ``_MAX_HOOK_ENTRIES`` timestamps, so a hook that holds something
        large (a device array) may drop it after its first run, as long
        as later runs still return only once the work is complete."""
        with self._mu:
            self._hooks.setdefault(timestamp, []).append(hook)
            while len(self._hooks) > self._MAX_HOOK_ENTRIES:
                self._hooks.pop(next(iter(self._hooks)))

    def _take_hooks(self, timestamp: int) -> List[Callable[[], None]]:
        with self._mu:
            return list(self._hooks.get(timestamp, ()))

    # -- receive pump --------------------------------------------------------

    @staticmethod
    def _recv_priority(msg: Optional[Message]) -> int:
        """Intake level: None (shutdown sentinel) and TERMINATE drain
        last; data messages use their wire priority."""
        if msg is None:
            return -(1 << 30)
        c = msg.meta.control
        if not c.empty():
            from .message import Command

            if c.cmd == Command.TERMINATE:
                return -(1 << 30)
            return 1 << 20
        return msg.meta.priority

    def accept(self, msg: Message) -> None:
        self._queue.push(msg)

    def _receiving(self) -> None:
        while True:
            msg = self._queue.wait_and_pop()
            if msg is None or msg.meta.control.cmd.name == "TERMINATE":
                break
            if self._exec_queue is not None:
                # Bounded push: blocks when the executor is saturated,
                # so backpressure reaches the van instead of memory.
                self._exec_queue.push(msg)
            else:
                self._handle_msg(msg)
        if self._exec_queue is not None:
            # FIFO sentinels ride behind any queued messages; join so
            # stop() returns only after in-flight handlers finish.
            for _ in self._exec_threads:
                self._exec_queue.push(None)
            for t in self._exec_threads:
                t.join(timeout=5)

    def _exec_loop(self) -> None:
        while True:
            msg = self._exec_queue.wait_and_pop()
            if msg is None:
                return
            self._handle_msg(msg)

    def _handle_msg(self, msg: Message) -> None:
        try:
            self._recv_handle(msg)
        except Exception as exc:
            # A handler bug must not kill the pump: responses still have
            # to be counted or every waiter on this node hangs silently.
            # Log the FULL traceback (a one-line repr buried the actual
            # bug site) and, for requests, let the app fail the remote
            # waiter fast instead of leaving it to hang until timeout.
            from .utils import logging as _log

            _log.warning(
                f"recv handle raised: {exc!r}\n{traceback.format_exc()}"
            )
            if msg.meta.request and self._on_request_error is not None:
                try:
                    self._on_request_error(msg, exc)
                except Exception as hook_exc:
                    _log.warning(
                        f"on_request_error hook failed: {hook_exc!r}"
                    )
        finally:
            # A batched response envelope (docs/batching.md) carries N
            # sub-ops with N distinct timestamps — the app layer counts
            # each sub-op itself; the envelope's own timestamp is just
            # the first op's and must not be double-counted.
            if not msg.meta.request and msg.meta.batch is None:
                self.add_response(msg.meta.timestamp)

    def stop(self) -> None:
        self._queue.push(None)
        self._thread.join(timeout=5)
        self._po.remove_customer(self)
