"""Sharded server apply engine (PS_APPLY_SHARDS) — equivalence,
consistency, error fast-fail, the Customer executor mode, and the
pooled tcp receive path.

The load-bearing claims (docs/apply_shards.md): shard affinity makes
the sharded store match the serial path BIT-FOR-BIT, pulls observe
per-key-consistent snapshots while pushes are in flight, and a handler
exception produces a fast-failing wait instead of a hang.
"""

import threading

import numpy as np
import pytest

from pslite_tpu import (
    KVServer,
    KVServerDefaultHandle,
    KVServerOptimizerHandle,
    KVWorker,
)

from helpers import LoopbackCluster


def _storm_store(shards: int) -> dict:
    """Final server store after a 2-worker concurrent push storm over
    disjoint AND overlapping keys.  Values are small integers, so sums
    are exact in float32 regardless of cross-worker arrival order and
    the serial/sharded comparison can be bit-for-bit."""
    cluster = LoopbackCluster(
        num_workers=2, num_servers=1,
        env_extra={"PS_APPLY_SHARDS": str(shards)},
    )
    cluster.start()
    servers = []
    try:
        handle = KVServerDefaultHandle()
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(handle)
        servers.append(srv)
        assert (srv._apply_pool is not None) == (shards > 0)
        workers = [KVWorker(0, 0, postoffice=po) for po in cluster.workers]

        shared = np.arange(1, 9, dtype=np.uint64)          # overlapping
        k = 64
        errors = []

        def pusher(w: int):
            try:
                own = np.arange(100 + 10 * w, 104 + 10 * w,
                                dtype=np.uint64)           # disjoint
                ts = []
                for i in range(12):
                    ts.append(workers[w].push(
                        shared, np.full(len(shared) * k, 1.0 + w,
                                        np.float32)))
                    ts.append(workers[w].push(
                        own, np.full(len(own) * k, 2.0 + i, np.float32)))
                for t in ts:
                    workers[w].wait(t)
            except Exception as exc:  # surfaced by the main thread
                errors.append(exc)

        threads = [threading.Thread(target=pusher, args=(w,))
                   for w in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors

        # A pull through the same path must agree with the raw store.
        out = np.zeros(len(shared) * k, np.float32)
        workers[0].wait(workers[0].pull(shared, out))
        expected = np.concatenate(
            [handle.store[int(key)] for key in shared])
        np.testing.assert_array_equal(out, expected)
        return {key: arr.copy() for key, arr in handle.store.items()}
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_sharded_matches_serial_bitexact():
    serial = _storm_store(0)
    sharded = _storm_store(4)
    assert sorted(serial) == sorted(sharded)
    for key in serial:
        np.testing.assert_array_equal(serial[key], sharded[key]), key


def test_optimizer_sharded_matches_serial_bitexact():
    """Stateful optimizer (momentum): single worker, sequential pushes
    (deterministic order), so serial vs sharded must agree to the bit."""
    def run(shards):
        cluster = LoopbackCluster(
            num_workers=1, num_servers=1,
            env_extra={"PS_APPLY_SHARDS": str(shards)},
        )
        cluster.start()
        servers = []
        try:
            handle = KVServerOptimizerHandle(kind="sgd_momentum", lr=0.05)
            srv = KVServer(0, postoffice=cluster.servers[0])
            srv.set_request_handle(handle)
            servers.append(srv)
            w = KVWorker(0, 0, postoffice=cluster.workers[0])
            keys = np.arange(1, 8, dtype=np.uint64)
            rng = np.random.default_rng(3)
            for _ in range(6):
                g = rng.normal(size=len(keys) * 16).astype(np.float32)
                w.wait(w.push(keys, g))
            out = np.zeros(len(keys) * 16, np.float32)
            w.wait(w.pull(keys, out))
            return out
        finally:
            for s in servers:
                s.stop()
            cluster.finalize()

    np.testing.assert_array_equal(run(0), run(4))


def test_pull_during_push_consistency():
    """Pulls racing in-place pushes must observe a per-key-consistent
    snapshot: every key's block is uniform (some prefix of the push
    sequence), never a half-applied mix."""
    cluster = LoopbackCluster(
        num_workers=2, num_servers=1,
        env_extra={"PS_APPLY_SHARDS": "4"},
    )
    cluster.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        pusher = KVWorker(0, 0, postoffice=cluster.workers[0])
        puller = KVWorker(0, 0, postoffice=cluster.workers[1])

        keys = np.arange(0, 8, dtype=np.uint64)
        k = 512
        rounds = 16
        # Seed so pulls never race first-touch.
        pusher.wait(pusher.push(keys, np.ones(len(keys) * k, np.float32)))

        def push_storm():
            ts = [pusher.push(keys, np.ones(len(keys) * k, np.float32))
                  for _ in range(rounds)]
            for t in ts:
                pusher.wait(t)

        t = threading.Thread(target=push_storm)
        t.start()
        try:
            for _ in range(20):
                out = np.zeros(len(keys) * k, np.float32)
                puller.wait(puller.pull(keys, out))
                blocks = out.reshape(len(keys), k)
                for i in range(len(keys)):
                    first = blocks[i, 0]
                    assert np.all(blocks[i] == first), \
                        f"torn pull for key {i}: {np.unique(blocks[i])}"
                    assert 1.0 <= first <= rounds + 1
        finally:
            t.join(timeout=60)
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


@pytest.mark.parametrize("shards", [0, 4])
def test_apply_error_fails_fast(shards):
    """A handler exception (pull of an unknown key) must produce an
    error-marked response: wait() raises promptly instead of hanging
    until timeout."""
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1,
        env_extra={"PS_APPLY_SHARDS": str(shards)},
    )
    cluster.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        out = np.zeros(64, np.float32)
        ts = w.pull(np.array([12345], np.uint64), out)  # never pushed
        with pytest.raises(RuntimeError, match="failed server-side"):
            w.wait(ts)
        # The server survives the error: normal traffic still works.
        vals = np.arange(64, dtype=np.float32)
        w.wait(w.push(np.array([7], np.uint64), vals))
        w.wait(w.pull(np.array([7], np.uint64), out))
        np.testing.assert_array_equal(out, vals)
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_global_op_barrier_for_lens_requests():
    """Requests the hash split can't express (variable-length lens) run
    as all-shard barrier ops through the plain handler — same result as
    serial, total order preserved."""
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1,
        env_extra={"PS_APPLY_SHARDS": "4"},
    )
    cluster.start()
    servers = []
    try:
        handle = KVServerDefaultHandle()
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(handle)
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        keys = np.array([2, 5], np.uint64)
        vals = np.arange(8, dtype=np.float32)
        # Fixed-k push first (sharded), then an equal-lens push (global
        # op: lens present), interleaved with more sharded pushes.
        w.wait(w.push(keys, vals))
        w.wait(w.push(keys, vals, lens=np.array([4, 4], np.int32)))
        w.wait(w.push(keys, vals))
        pool = srv._apply_pool
        assert pool is not None
        assert pool.global_requests >= 1
        assert pool.sharded_requests >= 2
        np.testing.assert_array_equal(handle.store[2], 3 * vals[:4])
        np.testing.assert_array_equal(handle.store[5], 3 * vals[4:])
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_registered_buffer_pushes_apply_synchronously():
    """A push that lands in a registered recv buffer aliases SHARED
    memory the pump overwrites on the sender's next push — the pool
    must apply it synchronously (wait=True) so pipelined pushes through
    the same buffer aggregate exactly."""
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1,
        env_extra={"PS_APPLY_SHARDS": "4"},
    )
    cluster.start()
    servers = []
    try:
        handle = KVServerDefaultHandle()
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(handle)
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        worker_id = cluster.workers[0].van.my_node.id
        srv.register_recv_buffer(worker_id, 7,
                                 np.zeros(256, np.float32))
        keys = np.array([7], np.uint64)
        rounds = 8
        # Pipelined (unwaited) pushes: each is copied into the SAME
        # registered buffer by the pump as it arrives.
        ts = [w.push(keys, np.full(256, 1.0, np.float32))
              for _ in range(rounds)]
        for t in ts:
            w.wait(t)
        np.testing.assert_array_equal(
            handle.store[7], np.full(256, float(rounds), np.float32))
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_error_response_suppresses_callback():
    """A completion callback must NOT fire for an error-marked response
    (it would hand the caller a partially-written buffer as if good)."""
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1,
        env_extra={"PS_APPLY_SHARDS": "4"},
    )
    cluster.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        fired = []
        out = np.zeros(64, np.float32)
        ts = w.pull(np.array([999], np.uint64), out,
                    callback=lambda: fired.append(True))
        with pytest.raises(RuntimeError):
            w.wait(ts)
        assert not fired
        # A successful op's callback still fires.
        w.wait(w.push(np.array([1], np.uint64), np.ones(8, np.float32)))
        ok = []
        w.wait(w.pull(np.array([1], np.uint64),
                      np.zeros(8, np.float32),
                      callback=lambda: ok.append(True)))
        assert ok
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_customer_executor_mode():
    """PS_CUSTOMER_EXECUTOR=1: handler calls run on a bounded executor
    thread (the pump keeps draining); end-to-end traffic is unchanged."""
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1,
        env_extra={"PS_CUSTOMER_EXECUTOR": "1",
                   "PS_APPLY_SHARDS": "2"},
    )
    cluster.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        assert srv._customer._exec_threads, "executor mode not active"
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        keys = np.arange(0, 6, dtype=np.uint64)
        vals = np.ones(6 * 32, np.float32)
        for _ in range(4):
            w.wait(w.push(keys, vals))
        out = np.zeros_like(vals)
        w.wait(w.pull(keys, out))
        np.testing.assert_array_equal(out, 4 * vals)
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_recv_pool_reuses_blocks_tcp():
    """The tcp van's pooled receive path: repeat data traffic recycles
    arena blocks (hits > 0) with byte-exact delivery.  PS_NATIVE=0
    forces the pure-Python reader loops the pool lives in."""
    cluster = LoopbackCluster(
        num_workers=1, num_servers=1, van_type="tcp",
        env_extra={"PS_NATIVE": "0"},
    )
    cluster.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=cluster.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=cluster.workers[0])
        keys = np.array([3], np.uint64)
        vals = np.random.default_rng(0).normal(size=32 * 1024).astype(
            np.float32)
        for _ in range(4):
            w.wait(w.push(keys, vals))
        out = np.zeros_like(vals)
        w.wait(w.pull(keys, out))
        np.testing.assert_array_equal(out, 4 * vals)
        server_van = cluster.servers[0].van
        assert server_van._recv_pool is not None
        assert server_van._recv_pool_hits > 0, (
            server_van._recv_pool.hits, server_van._recv_pool.misses)
    finally:
        for s in servers:
            s.stop()
        cluster.finalize()


def test_apply_storm_helper_smoke():
    """``apply_storm_rates`` (docs/apply_shards.md) stays runnable."""
    from pslite_tpu.benchmark import apply_storm_rates

    rate = apply_storm_rates(2, n_workers=2, msgs_per_worker=3,
                             keys_per_msg=4, val_len=256, rounds=1)
    assert rate > 0


def test_priority_queue_fence_blocks_overtaking():
    """PriorityRecvQueue fences (the apply pool's barrier-op guard): a
    fence item pops in priority order among what was queued BEFORE it,
    but nothing pushed AFTER it may overtake it — a sustained stream
    of higher-priority arrivals cannot starve a queued global op (and
    through its all-shard barrier, wedge the sibling shards)."""
    from pslite_tpu.utils.queues import PriorityRecvQueue

    q = PriorityRecvQueue(lambda item: item[0])
    q.push((0, "bulk1"))
    q.push((0, "global"), fence=True)
    q.push((5, "prio-after-1"))
    q.push((5, "prio-after-2"))
    # Pre-fence items still pop by priority; post-fence priority
    # arrivals wait their turn behind the fence.
    assert q.try_pop() == (0, "bulk1")
    assert q.try_pop() == (0, "global")
    # Fence cleared: priority order resumes.
    q.push((0, "bulk2"))
    assert q.try_pop() == (5, "prio-after-1")
    assert q.try_pop() == (5, "prio-after-2")
    assert q.try_pop() == (0, "bulk2")
    assert q.try_pop() is None
    # A higher-priority item queued BEFORE the fence overtakes it.
    q.push((1, "prio-before"))
    q.push((0, "global2"), fence=True)
    q.push((9, "after"))
    assert q.try_pop() == (1, "prio-before")
    assert q.try_pop() == (0, "global2")
    assert q.try_pop() == (9, "after")
