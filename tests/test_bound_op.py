"""A dense bucket's op is bound once (``CollectiveEngine._bind`` ->
``_BoundOp``), routed by its signature, and completed without the
``kv-engine-complete`` thread where it carries nothing to copy and no
callback (``KVWorker._engine_op`` / ``_engine_ready``).
"""

import gc
import threading
import time
import weakref

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker  # noqa: E402
from pslite_tpu.parallel.engine import CollectiveEngine  # noqa: E402
from pslite_tpu.utils import logging as log  # noqa: E402
from pslite_tpu.utils import profiling  # noqa: E402
from pslite_tpu.utils.profiling import StageClock  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("kv",))


def _device_grads(eng, name, rng):
    """``[W, padded]`` on the device, in the bucket's own sharding: what
    passes a bound op's prep as it is."""
    padded = eng.bucket(name).padded_len
    g = rng.normal(size=(eng.num_shards, padded)).astype(np.float32)
    return jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None)))


# -- (a) the bound op against the op that binds ------------------------------


@pytest.mark.parametrize("total", [256, 99])       # 99: no multiple of 4
@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("handle", ["adam:1e-3", "sgd_momentum:0.1,0.9",
                                    "sgd:0.1"])
def test_a_bound_op_is_bit_equal_to_the_op_that_binds(handle, W, total):
    clock = profiling.stage_clock()
    bound = CollectiveEngine(mesh=_mesh(W), server_handle=handle)
    unbound = CollectiveEngine(mesh=_mesh(W), server_handle=handle)
    init = np.linspace(-1.0, 1.0, total).astype(np.float32)
    for eng in (bound, unbound):
        eng.register_dense("b", np.arange(1, dtype=np.uint64), total,
                           init=init)
    assert (bound.bucket("b").padded_len == total) == (W == 1 or total == 256)
    rng = np.random.default_rng(7)
    built = clock.ops_bound
    for step in range(5):
        g = _device_grads(bound, "b", rng)
        unbound._bound.clear()              # every op of this engine binds
        want = unbound.push_pull("b", g)
        got = bound.push_pull("b", g)
        assert got.shape == (total,)        # the slice still cuts the padding
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(bound.store_array("b")),
                                      np.asarray(unbound.store_array("b")))
        if bound.handle_is_stateful:
            kind, state = bound.opt_state("b")
            kind_u, state_u = unbound.opt_state("b")
            assert kind == kind_u == handle.split(":")[0]
            for s, s_u in zip(state, state_u):
                np.testing.assert_array_equal(np.asarray(s), np.asarray(s_u))
        # A push is bound like a push_pull, under a record of its own.
        unbound._bound.clear()
        unbound.push("b", g)
        bound.push("b", g)
    np.testing.assert_array_equal(np.asarray(bound.pull("b")),
                                  np.asarray(unbound.pull("b")))
    assert sorted(k[2] is None for k in bound._bound) == [False, True]
    assert clock.ops_bound - built == 2 + 2 * 5   # bound: 2, unbound: each


def test_a_wrong_row_count_still_raises_from_a_bound_op():
    eng = CollectiveEngine(mesh=_mesh(4))
    eng.register_dense("b", np.arange(1, dtype=np.uint64), 64)
    rng = np.random.default_rng(3)
    eng.push_pull("b", _device_grads(eng, "b", rng))
    assert len(eng._bound) == 1
    two_rows = jax.device_put(np.ones((2, 64), np.float32),
                              NamedSharding(_mesh(2), P("kv", None)))
    with pytest.raises(log.CheckError, match="bad worker dim"):
        eng.push_pull("b", two_rows)
    with pytest.raises(log.CheckError, match="bad worker dim"):
        eng.push_pull("b", np.ones((3, 64), np.float32))   # host-origin


# -- (b) what drops a record -------------------------------------------------


def test_records_are_rebuilt_after_reshard_and_a_new_registration():
    clock = profiling.stage_clock()
    eng = CollectiveEngine(mesh=_mesh(4))
    keys = np.arange(2, dtype=np.uint64)
    eng.register_dense("b", keys, 50)
    eng.register_dense("other", keys + 10, 50)
    g4 = np.ones((4, 100), np.float32)
    built = clock.ops_bound
    for _ in range(3):
        eng.push_pull("b", g4)
        eng.push_pull("other", g4)
    assert clock.ops_bound - built == 2
    first = eng._bound[("b", None, False)]
    assert first.bucket is eng.bucket("b") and first.state_kind is None

    # A second handle on the same bucket gets a record of its own.
    eng.push_pull("b", g4, handle="sgd:0.5")
    eng.push_pull("b", g4, handle="sgd:0.5")
    assert clock.ops_bound - built == 3
    assert eng._bound[("b", "sgd:0.5", False)] is not first
    assert eng._bound[("b", None, False)] is first

    # The same name registered again: its records go, the others stay.
    eng.register_dense("b", keys, 60)
    assert [k[0] for k in eng._bound] == ["other"]
    out = eng.push_pull("b", np.ones((4, 120), np.float32))
    assert out.shape == (120,) and clock.ops_bound - built == 4
    assert eng._bound[("b", None, False)].bucket is eng.bucket("b")
    assert eng.bucket("b").nbytes == 120 * 4

    # reshard drops every record with the programs; padded_len was recut.
    eng.reshard(_mesh(8))
    assert eng._bound == {} and eng._programs == {}
    out = eng.push_pull("b", np.ones((8, 120), np.float32))
    np.testing.assert_allclose(np.asarray(out), 4.0 + 8.0)
    again = eng._bound[("b", None, False)]
    assert again.sharding.mesh.shape["kv"] == 8 and not again.cut
    assert clock.ops_bound - built == 5


# -- through KVWorker ---------------------------------------------------------


@pytest.fixture()
def worker():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.start()
    server = KVServer(0, postoffice=c.servers[0])   # the message path's
    server.set_request_handle(KVServerDefaultHandle())
    yield KVWorker(0, 0, postoffice=c.workers[0])
    c.finalize()


def _counters(worker):
    snap = worker.po.metrics.snapshot()
    return (profiling.stage_clock().ops_bound,
            snap["counters"].get("kv.complete.threaded", 0))


# -- (c) routing --------------------------------------------------------------


def test_routing_by_signature(worker, monkeypatch):
    three = np.array([10, 12, 14], dtype=np.uint64)
    worker.register_dense("three", three, 4)
    worker.register_dense("two", np.array([20, 29], dtype=np.uint64), 4)
    worker.register_dense("one", np.array([30], dtype=np.uint64), 4)
    assert worker._engine_route(three) == "three"
    # The signature (3, 10, 14) is registered, the set is another one.
    other = np.array([10, 11, 14], dtype=np.uint64)
    assert worker._engine_route(other) is None
    vals = np.ones(12, dtype=np.float32)
    assert worker._engine_op(worker.engine.push, (vals,), other) is None
    worker.wait(worker.push(other, vals))            # the message path's
    out = np.zeros_like(vals)
    worker.wait(worker.pull(other, out))
    np.testing.assert_array_equal(out, vals)
    assert worker.engine.push_bytes == worker.engine.pull_bytes == 0
    # Of one or two keys the signature is the set: nothing is compared.
    monkeypatch.setattr(np, "array_equal", None)
    assert worker._engine_route(np.array([20, 29], dtype=np.uint64)) == "two"
    assert worker._engine_route(np.array([30], dtype=np.uint64)) == "one"
    assert worker._engine_route(np.array([20, 28], dtype=np.uint64)) is None
    assert worker._engine_route(np.array([29], dtype=np.uint64)) is None
    assert worker._engine_route(np.array([30], dtype=np.uint64), 1) is None
    monkeypatch.undo()
    # A name registered again under another key: the old key is no route.
    worker.register_dense("one", np.array([31], dtype=np.uint64), 4)
    assert worker._engine_route(np.array([30], dtype=np.uint64)) is None
    assert worker._engine_route(np.array([31], dtype=np.uint64)) == "one"


def test_an_unrouted_op_closes_the_span_it_opened(worker, monkeypatch):
    from pslite_tpu.kv import kv_app

    class Span:
        open = entered = 0

        def __init__(self, *args, **kw):
            pass

        def __enter__(self):
            Span.open += 1
            Span.entered += 1

        def __exit__(self, *exc):
            Span.open -= 1

        def set_metadata(self, **kw):
            pass

    monkeypatch.setattr(kv_app, "tracing", lambda: True)   # a session runs
    monkeypatch.setattr(kv_app, "TraceAnnotation", Span)
    keys = np.array([7777], dtype=np.uint64)                # no bucket
    assert worker._engine_op(worker.engine.push, (np.ones(4),), keys) is None
    assert (Span.entered, Span.open) == (1, 0)
    worker.register_dense("s", keys, 4)
    worker.wait(worker.push(keys, np.ones(4, dtype=np.float32)))
    assert Span.entered >= 2 and Span.open == 0


# -- (d) completion -----------------------------------------------------------


class _Result:
    """Stands for an op's device array: ready when ``done`` is set."""

    def __init__(self):
        self.done = threading.Event()
        self.blocked = 0

    def block_until_ready(self):
        self.blocked += 1
        assert self.done.wait(30)
        return self


def test_wait_on_a_copyless_op_blocks_on_the_array_itself(worker):
    result = _Result()
    ref = weakref.ref(result)
    _, threaded = _counters(worker)
    # Like a sparse call: no keys, the table's name first among the args.
    ts = worker._engine_op(lambda name: result, ("t",))
    assert worker._engine_pool is None               # nothing was submitted
    returned = []
    waiters = [threading.Thread(target=lambda: (worker.wait(ts),
                                                returned.append(time.time())))
               for _ in range(2)]
    for t in waiters:
        t.start()
    time.sleep(0.3)
    assert not returned and all(t.is_alive() for t in waiters)
    result.done.set()
    for t in waiters:
        t.join(30)
    assert len(returned) == 2 and not any(t.is_alive() for t in waiters)
    assert result.blocked == 1                       # the first wait's
    worker.wait(ts)
    worker.wait(ts)                                  # twice in a row
    assert result.blocked == 1
    assert _counters(worker)[1] == threaded
    # The hook is still kept, the array is not.
    assert worker._customer._take_hooks(ts)
    del result
    gc.collect()
    assert ref() is None


def test_a_failed_wait_fails_again(worker):
    class Broken:
        def block_until_ready(self):
            raise RuntimeError("device lost")

    ts = worker._engine_op(lambda name: Broken(), ("t",))
    for _ in range(2):
        with pytest.raises(RuntimeError, match="device lost"):
            worker.wait(ts)


def test_ops_with_out_or_callback_keep_the_completion_thread(worker):
    keys = np.array([5], dtype=np.uint64)
    worker.register_dense("d", keys, 32)
    vals = np.full(32, 2.0, dtype=np.float32)
    W = worker.engine.num_shards             # a host gradient is every row
    _, threaded = _counters(worker)
    fired = threading.Event()
    ts_cb = worker.push(keys, vals, callback=fired.set)
    assert fired.wait(30)                            # with no wait()
    out = np.zeros(32, dtype=np.float32)
    ts_out = worker.push_pull(keys, vals, out)
    worker.wait(ts_out)
    np.testing.assert_allclose(out, W * 4.0)
    assert _counters(worker)[1] == threaded + 2
    # Copy-less, from a host-origin and a device-origin gradient alike.
    ts = worker.push_pull(keys, vals, None)
    pulled = worker.get_pulled(ts)
    worker.wait(ts)
    assert pulled.is_ready()
    np.testing.assert_allclose(np.asarray(pulled), W * 6.0)
    ts = worker.push_pull(keys, _device_grads(worker.engine, "d",
                                              np.random.default_rng(1)), None)
    worker.wait(ts)
    worker.wait(ts_cb)
    assert _counters(worker)[1] == threaded + 2
    assert worker.get_pulled(ts).is_ready()


# -- (e) the clock and the counters over copy-less ops ------------------------


def test_window_over_copyless_ops_counts_each_once(worker):
    clock = profiling.stage_clock()
    width = (1 << StageClock.SLOT_SHIFT) / 1e9
    n_buckets, rounds = 3, 4
    keys = [np.array([100 + i], dtype=np.uint64) for i in range(n_buckets)]
    for i, k in enumerate(keys):
        worker.register_dense(f"w{i}", k, 64)
    grads = [_device_grads(worker.engine, f"w{i}", np.random.default_rng(i))
             for i in range(n_buckets)]
    misses, threaded = _counters(worker)
    for k, g in zip(keys, grads):                    # compile, bind
        worker.wait(worker.push_pull(k, g, None))
    assert _counters(worker) == (misses + n_buckets, threaded)
    # Start in a fresh slot and end after its border.
    time.sleep(width - time.perf_counter() % width + 0.01)
    t_lo = time.perf_counter() - 0.005
    stamps = [worker.push_pull(k, g, None)
              for _ in range(rounds) for k, g in zip(keys, grads)]
    for ts in stamps:
        worker.wait(ts)
        worker.wait(ts)                              # counts nothing more
    time.sleep(max(0.0, 2 * width - (time.perf_counter() - t_lo)) + 0.01)
    stages, slots, _ = clock.window(t_lo - width, time.perf_counter())
    n = n_buckets * rounds
    assert slots >= 2
    assert {s: stages[s][1] for s in profiling.STAGES} \
        == {s: n for s in profiling.STAGES}
    assert stages["complete.wait"][0] > 0 and stages["launch"][0] > 0
    # Nothing was bound inside the window, nothing went through the pool.
    assert _counters(worker) == (misses + n_buckets, threaded)
    assert worker._engine_pool is None


# -- the benchmark's tiny cells: what the counters read over a window ---------


def test_counters_over_the_tiny_cells_of_the_benchmark():
    """``StageClock.ops_bound`` = the buckets registered, none of them
    inside the window, and no op of either driver goes through the pool
    (both pass ``out=None`` and no callback).  In a child: the benchmark's
    rehearsal pins its own devices and imports its modules by bare name."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmark")
    code = (
        "import os, sys, time\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        "os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'\n"
        f"sys.path[:0] = [{bench!r}, {root!r}, {bench + '/tests'!r}]\n"
        "import boot, harness, tiny\n"
        "from pslite_tpu.utils.profiling import stage_clock\n"
        "seen = {}\n"
        "window, shutdown = harness.run_window, boot.Cluster.shutdown\n"
        "def run_window(*a, **kw):\n"
        "    before = stage_clock().ops_bound\n"
        "    out = window(*a, **kw)\n"
        "    seen['in_window'] = stage_clock().ops_bound - before\n"
        "    return out\n"
        "def capture(self):\n"
        "    snap = self.kv.po.metrics.snapshot()\n"
        "    seen['misses'] = stage_clock().ops_bound\n"
        "    seen['threaded'] = snap['counters']['kv.complete.threaded']\n"
        "    seen['buckets'] = len(self.engine._buckets)\n"
        "    shutdown(self)\n"
        "harness.run_window, boot.Cluster.shutdown = run_window, capture\n"
        "for kind in ('dense', 'sparse'):\n"
        "    ok, r = harness.run_cell(tiny.cell(kind), 5, 0.3, False,"
        " time.perf_counter(), require_tpu=False)\n"
        "    assert ok and r['attempted'] >= 1, r\n"
        "    print('COUNTERS', kind, seen['misses'], seen['in_window'],"
        " seen['threaded'], seen['buckets'])\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "PS_LOOPBACK_NS"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = {l.split()[1]: [int(float(x)) for x in l.split()[2:]]
            for l in out.stdout.splitlines() if l.startswith("COUNTERS")}
    misses, in_window, threaded, buckets = rows["dense"]
    assert buckets > 1 and misses == buckets
    assert in_window == 0 and threaded == 0
    # The clock is the process's: the sparse cell after it binds nothing.
    assert rows["sparse"] == [misses, 0, 0, 0]
