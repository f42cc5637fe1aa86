"""Crash consistency of the elastic reshard (VERDICT r03 missing #5).

Semantics under test (documented in vans/ici_van.py reshard_engines):
- a peer dying BEFORE the entry barrier: survivors time out and abort
  with engines untouched (live 2-process kill test);
- a failure DURING the recut (a mid-collective peer death surfaces as
  an exception through jax's collective timeout — injected here
  deterministically at the placement layer): the staged commit aborts
  with the engine fully on the old mesh, stores never torn;
- a peer dying AFTER the recut, before the resume barrier: survivors
  hold committed, consistent new-mesh state and the op raises a
  degraded-cluster error.

Reference analog: recovery tolerates death at any moment
(/root/reference/src/van.cc:266-332); on the collective data plane the
roster is the mesh, so the same tolerance applies to mesh recuts.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from pslite_tpu.parallel import CollectiveEngine, default_mesh
from pslite_tpu.parallel.mesh import make_mesh
from pslite_tpu.parallel.sparse import SparseEngine
from pslite_tpu.utils.logging import CheckError


def _failing_placement(monkeypatch, fail_on_call: int):
    """Patch placement to raise on its Nth call (reshard resolves
    place_host_array from the module at call time)."""
    from pslite_tpu.parallel import placement

    real = placement.place_host_array
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] == fail_on_call:
            raise RuntimeError("injected recut failure (dead peer)")
        return real(*a, **kw)

    monkeypatch.setattr(placement, "place_host_array", flaky)
    return calls


def test_engine_recut_failure_is_atomic(monkeypatch):
    """A failure midway through the recut (bucket 2 of 2, with opt
    state) leaves EVERY bucket on the old mesh — then a clean retry
    succeeds (abort-and-redo)."""
    mesh8 = default_mesh()
    eng = CollectiveEngine(mesh=mesh8, server_handle="adam:0.01")
    keys = np.arange(2, dtype=np.uint64)
    for name in ("a", "b"):
        eng.register_dense(name, keys, 64)
        eng.push_pull(name, np.ones((8, 128), np.float32))
    before = {n: np.asarray(eng.pull(n)) for n in ("a", "b")}
    old_padded = {n: eng.bucket(n).padded_len for n in ("a", "b")}

    mesh4 = make_mesh((4,), ("kv",))
    calls = _failing_placement(monkeypatch, fail_on_call=3)
    with pytest.raises(RuntimeError, match="injected"):
        eng.reshard(mesh4)
    assert calls["n"] >= 3
    # Fully on the old mesh: no field or bucket may have moved.
    assert eng.mesh is mesh8
    assert eng.num_shards == 8
    for n in ("a", "b"):
        assert eng.bucket(n).padded_len == old_padded[n]
        np.testing.assert_allclose(np.asarray(eng.pull(n)), before[n])
        # Optimizer state still live: another step runs.
        eng.push_pull(n, np.ones((8, 128), np.float32))

    # Retry without the fault: the redo completes.
    monkeypatch.undo()
    eng.reshard(mesh4)
    assert eng.num_shards == 4


def test_sparse_recut_failure_is_atomic(monkeypatch):
    """Same staged-commit contract for the sparse tier (tables + fused
    optimizer accumulators)."""
    mesh8 = default_mesh()
    se = SparseEngine(mesh8)
    se.register_sparse("t1", 64, 4)
    se.register_sparse("t2", 32, 4)
    idx = np.tile(np.arange(8, dtype=np.int32)[:, None], (1, 2))
    g = np.ones((8, 2, 4), np.float32)
    se.push("t1", idx, g, handle="row_adagrad:0.1,1e-8")
    se.push("t2", idx, g)
    se.block("t1")
    se.block("t2")
    before1 = np.asarray(se.pull("t1", idx))
    old_shards = se.num_shards

    calls = _failing_placement(monkeypatch, fail_on_call=2)
    with pytest.raises(RuntimeError, match="injected"):
        se.reshard(make_mesh((4,), ("kv",)))
    assert calls["n"] >= 2
    assert se.num_shards == old_shards
    np.testing.assert_allclose(np.asarray(se.pull("t1", idx)), before1)

    monkeypatch.undo()
    se.reshard(make_mesh((4,), ("kv",)))
    assert se.num_shards == 4
    np.testing.assert_allclose(
        np.asarray(se.pull("t1", idx[:4])), before1[:4]
    )


def _barrier_death_cluster(dying_call: int, expect_match: str,
                           expect_new_mesh: bool):
    """Drive KVWorker.reshard with the Nth barrier raising a timeout
    (barrier order: 1=entry, 2=commit, 3=resume)."""
    from tests.helpers import LoopbackCluster

    from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker

    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici_shm")
    c.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=c.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        worker = KVWorker(0, 0, postoffice=c.workers[0])
        eng = worker.engine
        keys = np.arange(2, dtype=np.uint64)
        worker.register_dense("g", keys, 16)
        W = eng.num_shards
        outs = np.zeros(32, np.float32)
        worker.wait(worker.push_pull(keys, np.ones(32, np.float32), outs))

        po = c.workers[0]
        real_barrier = po.barrier
        state = {"n": 0}

        def dying_barrier(*a, **kw):
            state["n"] += 1
            if state["n"] == dying_call:
                raise CheckError("barrier timed out (injected death)")
            return real_barrier(*a, **kw)

        po.barrier = dying_barrier
        new_mesh = make_mesh((W // 2,), ("kv",))
        with pytest.raises(CheckError, match=expect_match):
            worker.reshard(new_mesh)
        po.barrier = real_barrier
        assert eng.num_shards == (W // 2 if expect_new_mesh else W)
        # Stores carried either way.
        out2 = np.zeros(32, np.float32)
        worker.wait(worker.pull(keys, out2))
        np.testing.assert_allclose(out2, outs)
    finally:
        for s in servers:
            s.stop()
        c.finalize()


def test_commit_barrier_death_aborts_together_on_old_mesh():
    """A peer that fails STAGING never joins the commit barrier: the
    survivors' commit-barrier timeout aborts their staged state, so the
    whole cluster stays on the old mesh together."""
    _barrier_death_cluster(2, "aborted together", expect_new_mesh=False)


def test_resume_barrier_death_reports_degraded_committed_state():
    """A peer dying between the commit and the resume barrier: this
    process's recut has COMMITTED (new mesh, consistent stores) and the
    op raises the degraded-cluster error."""
    _barrier_death_cluster(3, "degraded", expect_new_mesh=True)


def _live_crash_cluster(mode: str, rank1_rc: int, timeout0: int):
    """Drive the 2-process crash child in ``mode``; returns
    (worker0_out, worker1_out, rank1_returncode)."""
    from pslite_tpu.utils.network import get_available_port

    port = get_available_port()
    child = os.path.join(os.path.dirname(__file__),
                         "reshard_crash_child.py")
    base_env = dict(
        os.environ,
        DMLC_NUM_WORKER="2",
        DMLC_NUM_SERVER="1",
        DMLC_PS_ROOT_URI="127.0.0.1",
        DMLC_PS_ROOT_PORT=str(port),
        DMLC_NODE_HOST="127.0.0.1",
        PS_VAN_TYPE="ici_tcp",
        PS_ICI_MULTIHOST="1",
        PS_RESHARD_TMO_S="10",
        PS_CRASH_MODE=mode,
    )
    for var in ("JAX_PLATFORMS", "XLA_FLAGS"):
        base_env.pop(var, None)
    roles = [("scheduler", None), ("server", None), ("worker", 0),
             ("worker", 1)]
    procs = []
    for role, rank in roles:
        env = dict(base_env, DMLC_ROLE=role)
        if rank is not None:
            env["DMLC_RANK"] = str(rank)
        procs.append(
            subprocess.Popen(
                [sys.executable, child],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
        )
    # Worker 0 (procs[2]) carries the assertion; scheduler/server stay
    # up by design (the cluster is degraded, never finalized).
    try:
        out0, _ = procs[2].communicate(timeout=timeout0)
        out1, _ = procs[3].communicate(timeout=120)
    finally:
        for p in procs:
            p.kill()
    return out0.decode(), out1.decode(), procs[3].returncode


def test_peer_death_before_entry_barrier():
    """LIVE 2-process cluster: worker 1 dies before calling reshard;
    worker 0 times out at the entry barrier and aborts untouched."""
    out0, out1, rc1 = _live_crash_cluster("exit_before", 42, 420)
    assert rc1 == 42, out1[-800:]
    assert "CRASH_OK rank=0 untouched=True" in out0, out0[-1500:]
    assert "CRASH_FAIL" not in out0, out0[-1500:]


def test_peer_staging_failure_aborts_cluster_together():
    """LIVE 2-process cluster: worker 1's STAGING fails (after the
    collective snapshot legs) and goes silent; worker 0 times out at
    the COMMIT barrier and aborts — both ranks end on the old mesh
    (no cross-process mesh divergence; the failed rank must not
    release the survivor's commit barrier with a stray resume
    request)."""
    out0, out1, rc1 = _live_crash_cluster("stage_fail", 0, 480)
    assert rc1 == 0, out1[-800:]
    assert "CRASH_OK rank=1 untouched=True RuntimeError" in out1, \
        out1[-1500:]
    assert "CRASH_OK rank=0 untouched=True" in out0, out0[-1500:]
    assert "CRASH_FAIL" not in out0 + out1, (out0 + out1)[-1500:]


def test_pair_atomicity_dense_and_sparse(monkeypatch):
    """A failure in the SPARSE staging of a coordinated recut leaves the
    DENSE engine untouched too: both engines stage before either
    commits (reshard_engines' pair contract)."""
    from tests.helpers import LoopbackCluster

    from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker

    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici_shm")
    c.start()
    servers = []
    try:
        srv = KVServer(0, postoffice=c.servers[0])
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        worker = KVWorker(0, 0, postoffice=c.workers[0])
        eng = worker.engine
        se = worker.po.van.sparse_engine
        keys = np.arange(2, dtype=np.uint64)
        worker.register_dense("g", keys, 16)
        W = eng.num_shards
        outs = np.zeros(32, np.float32)
        worker.wait(worker.push_pull(keys, np.ones(32, np.float32), outs))
        se.register_sparse("emb", 16, 4)

        # Dense staging places 1 store; the NEXT placement is the
        # sparse table's — fail there.
        calls = _failing_placement(monkeypatch, fail_on_call=2)
        new_mesh = make_mesh((W // 2,), ("kv",))
        with pytest.raises(RuntimeError, match="injected"):
            worker.reshard(new_mesh)
        assert calls["n"] >= 2
        assert eng.num_shards == W, "dense engine committed alone"
        assert se.num_shards == W, "sparse engine committed alone"
        out2 = np.zeros(32, np.float32)
        worker.wait(worker.pull(keys, out2))
        np.testing.assert_allclose(out2, outs)

        # Redo without the fault: the pair moves together.
        monkeypatch.undo()
        worker.reshard(new_mesh)
        assert eng.num_shards == W // 2 and se.num_shards == W // 2
    finally:
        for s in servers:
            s.stop()
        c.finalize()
